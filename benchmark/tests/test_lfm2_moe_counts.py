"""The lfm2_24b_a2b cell's accounting: the configuration file against the
catalog's row key by key and the cut, the parameter count from the file's
own keys, ``train_flops_per_token``, ``short_conv_work``,
``full_attention_work`` and ``expert_matmul_work`` against counts by
hand, the three new readers on rows made by hand, and the cell's entries
in BENCHMARK.json found BY NAME."""
import json
import os

import pytest

import run as harness

CELL = "lfm2_24b_a2b.train_bf16_b4_s8192"
CONFIG = "lfm2_24b_a2b"
PERIOD = ["conv", "conv", "full_attention", "conv"]
NEW = ("short_conv_ms", "short_conv_op_ms", "short_conv_op_roofline")


def _parts():
    return (harness.load_json("configs", CONFIG),
            harness.load_json("traffic", "train_bf16_b4_s8192"),
            harness.load_module("models", "lfm2_moe"))


def test_the_configuration_states_its_cut_and_nothing_else():
    cfg, mix, _ = _parts()
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types", "num_experts", "vocab_size"]
    assert cfg["published"] == {
        "num_hidden_layers": 40, "num_dense_layers": 2,
        "layer_types": PERIOD * 10, "num_experts": 64, "vocab_size": 65536}
    # published layer 1 (dense, conv), then layers 2 to 7
    assert cfg["layer_types"] == cfg["published"]["layer_types"][1:8]
    assert cfg["layer_types"].count("conv") == 5
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["vocab_size"]) == (7, 1, 8, 8192)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["held_experts"] == {"first": 0, "count": 8, "of": 64}
    # the widths, the heads, the taps and the router are the published
    assert [cfg[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "conv_L_cache", "conv_bias", "intermediate_size",
        "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
        "routed_scaling_factor", "use_expert_bias", "norm_eps",
        "tie_word_embeddings", "max_position_embeddings")] == [
        2048, 32, 8, 64, 3, False, 11776, 1536, 4, True, 1, True, 1e-5,
        True, 128000]
    assert cfg["rope_parameters"] == {"rope_theta": 1000000,
                                      "rope_type": "default"}
    assert (mix["kind"], mix["batch"], mix["seq"], mix["ring"]) == (
        "train_tokens", 4, 8192, 8)
    assert cfg["source"] == ("https://huggingface.co/LiquidAI/LFM2-24B-A2B/"
                             "blob/main/config.json")
    assert cfg["family"] == "lfm2_moe" == cfg["model_type"]
    assert cfg["train_router"] is False and cfg["recompute"] == "per_block"
    for key in ("deployment", "assumed", "source_detail"):
        assert cfg[key]
    for said in ("head_dim", "layer", "short_conv", "attention", "qk_norm",
                 "rope", "router", "gate_epsilon", "router_bias",
                 "balance_loss", "train_router", "tied_head", "partial_sum",
                 "loss", "weight_decay", "rows", "precision", "recompute",
                 "dropout", "init", "the_draw_of_the_architecture"):
        assert cfg["assumed"][said], said
    # every key of the catalog's entry under the same name, but the cut
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):      # the catalog is beside the guide
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        published = [r for r in rows if r["source_url"] == cfg["source"]]
        assert len(published) == 1 and published[0]["name"] == "LFM2-24B-A2B"
        for key, value in published[0]["config"].items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value, key
            else:
                assert cfg[key] == value, key


def test_the_parameter_count_is_a_count_from_the_files_own_keys():
    cfg, _, model = _parts()
    H, D = cfg["hidden_size"], cfg["head_dim"]
    A, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    conv = 3 * H * H + H * H + cfg["conv_L_cache"] * H
    attention = 2 * H * A * D + 2 * H * KV * D + 2 * D
    norms = 2 * H
    expert = 3 * H * cfg["moe_intermediate_size"]
    routed = (H * cfg["published"]["num_experts"]
              + cfg["published"]["num_experts"]
              + cfg["num_experts"] * expert)
    dense = 3 * H * cfg["intermediate_size"]
    assert (conv, attention, norms, expert, dense) == (
        16_783_360, 10_485_888, 4_096, 9_437_184, 72_351_744)
    assert routed == 131_136 + 75_497_472
    mixers = {"conv": conv, "full_attention": attention}
    kinds = cfg["layer_types"]
    total = (mixers[kinds[0]] + norms + dense
             + sum(mixers[k] + norms + routed for k in kinds[1:])
             + cfg["vocab_size"] * H + H)
    assert total == 89_139_200 + 4 * 92_416_064 + 2 * 86_118_592 \
        + 16_777_216 + 2_048
    assert total == cfg["parameters"] == 647_819_904
    # at 16 bytes a parameter: 10.37 GB of state (untied it would be 10.63)
    assert round(total * 16 / 1e9, 2) == 10.37
    assert round((total + cfg["vocab_size"] * H) * 16 / 1e9, 2) == 10.63
    # a whole expert layer's 64 are 9.66 GB: no chip holds two
    assert round(64 * expert * 16 / 1e9, 2) == 9.66
    # every leaf the reference names is a leaf the program has, once
    ref = harness.load_module("reference", "lfm2_moe")
    import math
    shapes = ref.param_shapes(cfg, {})
    assert sum(math.prod(s) for s, _ in shapes.values()) == total
    names = model.param_map(cfg, {})
    import check
    assert sorted(check.key_of(*v) for v in names.values()) == sorted(
        check.expanded_keys(shapes))


def test_flops_a_token_match_a_count_by_hand():
    cfg, mix, model = _parts()
    H, T = 2048, mix["seq"]
    conv_mixer = 2 * 4 * H * H                    # 33.6 M forward
    attn_proj = 2 * (2 * H * 2048 + 2 * H * 512)  # 21 M
    pairs = 2 * 32 * 2 * 64 * (T + 1) / 2         # 33.5 M at 8192, causal
    held = 2 * (4 * 8 / 64) * 3 * H * 1536        # 9.4 M
    router = 2 * H * 64
    dense = 2 * 3 * H * 11776                     # 144.7 M
    head = 2 * H * 8192                           # 33.5 M
    assert [round(v / 1e6, 1) for v in (conv_mixer, attn_proj, pairs, held,
                                        dense, head)] == [
        33.6, 21.0, 33.6, 9.4, 144.7, 33.6]
    forward = (5 * conv_mixer + 2 * (attn_proj + pairs) + dense
               + 6 * (held + router) + head)
    assert model.train_flops_per_token(cfg, T) == pytest.approx(3 * forward)
    assert 0.5e9 < forward < 0.52e9               # "about 0.5 G forward"
    calls = model.attention_calls(cfg, mix["batch"], T)
    assert calls == {"calls": 2, "batch": 4, "heads": 32, "kv_heads": 8,
                     "seq": 8192, "head_dim": 64, "causal": True,
                     "forward_replays": 0}


def test_the_operator_and_the_kernels_need_what_a_hand_count_says():
    cfg, mix, model = _parts()
    tokens = mix["batch"] * mix["seq"]
    assert tokens == 32768
    # one forward call alone, one backward alone: the issue's 537 and 940 MB
    none = {**cfg, "layer_types": ["full_attention"], "num_hidden_layers": 1}
    assert model.short_conv_work(none, mix, 1)[1] \
        == (6144 + 2048) * 2 * tokens == 536_870_912
    one = {**cfg, "layer_types": ["conv"], "num_hidden_layers": 1}
    assert model.short_conv_work(one, mix, 0)[1] \
        == (6144 + 2048 + 6144) * 2 * tokens == 939_524_096
    # a step: ten forward passes with the replay, five backward
    flops, bytes_ = model.short_conv_work(cfg, mix, 10)
    assert bytes_ == 10 * 536_870_912 + 5 * 939_524_096
    assert flops == tokens * 2048 * (10 * 8 + 5 * 23)
    peaks = harness.peak_of("TPU v5 lite")
    need_ms = bytes_ / peaks["hbm_bytes_per_s"] * 1000
    assert 12.0 < need_ms < 12.5                  # "12 ms of need a step"
    assert flops / peaks["bf16_flops_per_s"] < bytes_ / peaks[
        "hbm_bytes_per_s"]
    # the two full layers' kernels: half the square over 32 heads of 64
    flops, bytes_ = model.full_attention_work(cfg, mix, forwards=2)
    pairs = 4 * 32 * 8192 * 8193 / 2
    assert flops == 2 * pairs * 64 * (2 * 2 + 5 * 2)
    row = 4 * 8192 * 64 * 2
    assert bytes_ == 2 * row * (64 + 16) + 2 * row * (128 + 32)
    # a grouped product at the expected load: half an assignment a token
    flops, bytes_ = model.expert_matmul_work(cfg, mix, product_calls=1)
    assert flops == 2 * 4096 * 2048 * 1536
    assert bytes_ == 8 * 2048 * 1536 * 2 + 4096 * (2 * 2048 + 3 * 1536) * 2 / 3


def _row(op_name, ms, mosaic=False):
    return {"instruction": "fusion.1", "op_name": op_name,
            "phase": __import__("scope_reduce").phase_of(op_name),
            "mosaic": mosaic, "ms": ms}


def _rows():
    mixer = "blocks.2:Block/mixer:ShortConv/short_conv"
    return [
        _row(f"jit(step_fn)/jvp(loss)/{mixer}/in_proj:Linear/dot", 3.0),
        _row(f"jit(step_fn)/jvp(loss)/{mixer}/short_conv_op/"
             "short_conv_fwd/pallas_call", 4.0, True),
        _row(f"jit(step_fn)/transpose(jvp(loss))/rematted_computation/"
             f"{mixer}/short_conv_op/short_conv_fwd/pallas_call", 4.0, True),
        _row(f"jit(step_fn)/transpose(jvp(loss))/{mixer}/short_conv_op/"
             "short_conv_bwd/pallas_call", 7.0, True),
        _row(f"jit(step_fn)/transpose(jvp(loss))/{mixer}/short_conv_op/"
             "reduce_sum", 1.0),
        _row("jit(step_fn)/jvp(loss)/blocks.1:Block/moe/dot", 9.0),
    ]


def test_the_new_readers_read_their_scopes():
    cfg, mix, model = _parts()
    logged = []
    ctx = {"scope_rows": _rows(), "log": logged.append, "cfg": cfg,
           "mix": mix, "model": model,
           "peaks": harness.peak_of("TPU v5 lite")}
    read = {n: harness.load_module("layer_metrics", n).read for n in NEW}
    assert read["short_conv_ms"](ctx) == pytest.approx(19.0)
    assert read["short_conv_op_ms"](ctx) == pytest.approx(16.0)
    # the replay has time under the scope: two forward passes a conv layer
    _, bytes_ = model.short_conv_work(cfg, mix, 10)
    need_ms = bytes_ / 819e9 * 1000
    assert read["short_conv_op_roofline"](ctx) == pytest.approx(
        need_ms / 16.0 * 100)
    assert any("10 forward passes" in line for line in logged)
    # without the replay's time: one forward pass a layer
    ctx["scope_rows"] = [r for r in _rows()
                         if "rematted_computation" not in r["op_name"]]
    _, bytes_ = model.short_conv_work(cfg, mix, 5)
    assert read["short_conv_op_roofline"](ctx) == pytest.approx(
        bytes_ / 819e9 * 1000 / 12.0 * 100)


@pytest.mark.parametrize("other", ["granite_4_0_h_micro.train_bf16_b1_s8192",
                                   "trinity_mini.train_bf16_b1_s16384"])
def test_the_new_readers_read_nothing_on_another_cell(other):
    """A step without the scopes (another cell's, or this cell's on a
    program from before them): None, and no raise."""
    cell, cfg, mix = harness.load_cell(other)
    model = harness.load_module("models", cell["model"])
    ctx = {"scope_rows": [_row("jit(step_fn)/jvp(loss)/ssm/ssm_conv/x", 9.0),
                          _row("jit(step_fn)/jvp(loss)/moe/dot", 9.0)],
           "log": [].append, "cfg": cfg, "mix": mix, "model": model,
           "peaks": harness.peak_of("TPU v5 lite")}
    for name in NEW:
        assert harness.load_module("layer_metrics", name).read(ctx) is None
    # nor where no table can be made at all
    ctx["scope_rows"] = None
    for name in NEW:
        assert harness.load_module("layer_metrics", name).read(ctx) is None
    # this cell's files over a step without the scope
    cfg, mix, model = _parts()
    ctx.update(cfg=cfg, mix=mix, model=model, scope_rows=[_row(
        "jit(step_fn)/jvp(loss)/moe/dot", 9.0)])
    for name in NEW:
        assert harness.load_module("layer_metrics", name).read(ctx) is None


def test_the_cell_is_in_the_benchmark_under_its_names():
    """Found by name, wherever a later PR's entries put it in the lists
    (PERF.md 7 (p), (s))."""
    with open(harness.REPO + "/BENCHMARK.json") as f:
        bench = json.load(f)
    config = [c for c in bench["configs"] if c["name"] == CONFIG]
    cells = [w for w in bench["workloads"] if w["config"] == CONFIG]
    assert len(config) == 1 and [w["name"] for w in cells] == [CELL]
    assert cells[0]["chips"] == 1
    assert cells[0]["traffic"] == "train_bf16_b4_s8192"
    cfg = harness.load_json("configs", CONFIG)
    assert config[0]["reduced"] == cfg["reduced"]
    assert config[0]["source"] == cfg["source"]
    assert config[0]["file"] == f"benchmark/configs/{CONFIG}.json"
    for path in (config[0]["file"], f"benchmark/workloads/{CELL}.json",
                 f"benchmark/traffic/{cells[0]['traffic']}.json",
                 "benchmark/models/lfm2_moe.py",
                 "benchmark/reference/lfm2_moe.py",
                 *(f"benchmark/layer_metrics/{n}.py" for n in NEW)):
        assert os.path.isfile(os.path.join(harness.REPO, path)), path
    for entry in (config[0], cells[0]):
        assert len(entry["why"]) <= 200
    reported = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert set(NEW) | {
        "mosaic_kernels_ms", "flash_fwd_ms", "flash_bwd_ms",
        "full_attn_roofline", "opt_state_init_s", "step_python_ms",
        "rms_norm_ms", "rope_ms", "ffn_ms", "moe_ms", "moe_dispatch_ms",
        "moe_experts_roofline", "moe_buffer_live_share",
        "moe_full_buffer_chunks", "moe_load_imbalance"} <= reported
    # not the roofline that divides by all Mosaic time, nor another
    # family's scopes
    assert not reported & {"flash_attn_roofline", "ssm_conv_ms", "ssm_ms",
                           "window_attention_ms", "attn_gate_ms"}
    new = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert len(new) == 3
    for m in new.values():
        assert m["workloads"] == [CELL]
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == "device_trace"
    assert {n: (m["unit"], m["better"], m["layer"])
            for n, m in new.items()} == {
        "short_conv_ms": ("ms", "lower", "model step"),
        "short_conv_op_ms": ("ms", "lower", "model step"),
        "short_conv_op_roofline": ("%", "higher", "kernels")}
    # the cell's own files load by name, and its limits are set
    cell, cfg, mix = harness.load_cell(CELL)
    assert (cell["model"], cell["runner"], cell["dtype"]) == (
        "lfm2_moe", "train_step", "bfloat16")
    assert set(cell["check"]["limits"]) == {
        "loss_gap", "grad_norm_gap", "update_norm_gap", "grad_diff"}
    assert (cell["warm_steps"], cell["trace_steps"]) == (2, 4)
    assert cell["check"]["control"] == "fp8"
    assert cell["check"]["reference_block_rows"] == 1
