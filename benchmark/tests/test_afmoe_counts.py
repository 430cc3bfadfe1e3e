"""The trinity_mini cell's accounting: the configuration file against the
catalog's row key by key and the cut, the parameter count by hand,
``train_flops_per_token``, ``window_attention_work`` and
``full_attention_work`` against counts by hand, the five new readers on
rows and counters made by hand, and the cell's entries in BENCHMARK.json
found BY NAME."""
import json
import os

import pytest

import run as harness

CELL = "trinity_mini.train_bf16_b1_s16384"
CONFIG = "trinity_mini"
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
NEW = ("window_attention_ms", "window_attn_roofline", "full_attn_roofline",
       "attn_gate_ms", "window_blocks_run_share")


def _parts():
    return (harness.load_json("configs", CONFIG),
            harness.load_json("traffic", "train_bf16_b1_s16384"),
            harness.load_module("models", "afmoe"))


def test_the_configuration_states_its_cut_and_nothing_else():
    cfg, mix, _ = _parts()
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types", "num_experts", "vocab_size"]
    assert cfg["published"] == {
        "num_hidden_layers": 32, "num_dense_layers": 2,
        "layer_types": PERIOD * 8, "num_experts": 128, "vocab_size": 200192}
    # published layer 1 (dense, sliding), then one whole period, 4 to 7
    assert cfg["layer_types"] == ["sliding_attention"] + PERIOD
    assert cfg["layer_types"][1:] == cfg["published"]["layer_types"][4:8]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["vocab_size"]) == (5, 1, 16, 25024)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["held_experts"] == {"first": 0, "count": 16, "of": 128}
    # the widths, the heads, the window and the router are the published
    assert [cfg[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size", "moe_intermediate_size",
        "num_experts_per_tok", "num_shared_experts", "sliding_window",
        "rope_theta", "route_norm", "route_scale", "score_func",
        "mup_enabled", "rms_norm_eps", "tie_word_embeddings",
        "max_position_embeddings")] == [
        2048, 32, 4, 128, 6144, 1024, 8, 1, 2048, 10000, True, 2.826,
        "sigmoid", True, 1e-5, False, 131072]
    assert (mix["kind"], mix["batch"], mix["seq"], mix["ring"]) == (
        "train_tokens", 1, 16384, 8)
    assert cfg["source"] == ("https://huggingface.co/arcee-ai/Trinity-Mini/"
                             "blob/main/config.json")
    assert cfg["family"] == "afmoe" == cfg["model_type"]
    assert cfg["train_router"] is False and cfg["recompute"] == "per_block"
    for key in ("deployment", "assumed", "source_detail"):
        assert cfg[key]
    for said in ("layer", "mup", "attention", "qk_norm", "rope", "window",
                 "gate", "router", "router_bias", "balance_loss",
                 "train_router", "shared_expert", "partial_sum", "loss",
                 "weight_decay", "rows", "precision", "recompute", "init"):
        assert cfg["assumed"][said], said
    # every key of the catalog's entry under the same name, but the cut
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):      # the catalog is beside the guide
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        published = [r for r in rows if r["source_url"] == cfg["source"]]
        assert len(published) == 1 and published[0]["name"] == "Trinity-Mini"
        for key, value in published[0]["config"].items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value, key
            else:
                assert cfg[key] == value, key


def test_the_parameter_count_is_a_count_by_hand():
    cfg, _, model = _parts()
    H, D = 2048, 128
    attention = 3 * H * 32 * D + 2 * H * 4 * D + 2 * D      # q, gate, o; k, v
    assert attention == 27_263_232
    norms = 4 * H
    dense = attention + norms + 3 * H * 6144
    assert dense == 65_020_160
    expert = 3 * H * 1024
    layer = attention + norms + H * 128 + 128 + expert + 16 * expert
    assert layer == 134_488_448
    total = dense + 4 * layer + 2 * 25024 * H + H
    assert total == cfg["parameters"] == 705_474_304
    # and the reference's leaves add up to it
    ref = harness.load_module("reference", cfg["family"])
    got = 0
    for shape, _ in ref.param_shapes(cfg, {}).values():
        n = 1
        for d in shape:
            n *= d
        got += n
    assert got == total
    assert sorted(harness.load_module("models", "afmoe").param_map(cfg, {})
                  .values()) == sorted(
        (leaf.partition("[")[0], int(leaf[:-1].rpartition("[")[2])
         if leaf.endswith("]") else None)
        for leaf in __import__("check").expanded_keys(
            ref.param_shapes(cfg, {})))


def test_flops_a_token_match_a_count_by_hand():
    cfg, mix, model = _parts()
    T, H, W = mix["seq"], 2048, 2048
    attention = 27_262_976                     # the five projections' weights
    routed = 8 * 16 / 128 * 3 * H * 1024       # ONE assignment a token here
    expert_ff = H * 128 + 3 * H * 1024 + routed
    weights = (5 * attention + 3 * H * 6144 + 4 * expert_ff
               + H * 25024)
    band = W * T - W * (W - 1) / 2
    assert band == model.pairs_a_head(cfg, T, "sliding_attention") \
        == 31_458_304
    triangle = T * (T + 1) / 2
    assert triangle == model.pairs_a_head(cfg, T, "full_attention") \
        == 134_225_920
    # a window no shorter than the row is the triangle
    assert model.pairs_a_head(cfg, 2048, "sliding_attention") \
        == 2048 * 2049 / 2
    pairs = (4 * band + triangle) / T                      # a token, a head
    want = 6 * weights + 3 * 2 * 32 * 2 * 128 * pairs
    assert model.train_flops_per_token(cfg, T) == pytest.approx(want)
    # the issue's 2.44 GFLOP a token, attention a third of it
    assert 2.40e9 < want < 2.48e9
    assert 0.30 < 3 * 2 * 32 * 2 * 128 * pairs / want < 0.34
    # run as full triangles the window layers would need 4 times the full
    # layer's pairs; as bands, less than it
    assert 4 * band < triangle < 5 * band


def test_the_attention_kernels_need_what_a_hand_count_says():
    cfg, mix, model = _parts()
    T, A, KV, D = mix["seq"], 32, 4, 128
    band, triangle = 31_458_304, 134_225_920
    row = T * D * 2
    for work, pairs, layers in ((model.window_attention_work, band, 4),
                                (model.full_attention_work, triangle, 1)):
        # a forward call a layer (the replay keeps out and lse): 2 products
        # a forward and 5 a backward, 2 FLOPs a multiply-add
        flops, bytes_ = work(cfg, mix, layers)
        assert flops == 2 * A * pairs * D * 7 * layers
        assert bytes_ == layers * row * ((2 * A + 2 * KV)
                                         + (4 * A + 4 * KV))
        # a replay that ran the forward kernel again: two more products
        again, _ = work(cfg, mix, 2 * layers)
        assert again - flops == 2 * A * pairs * D * 2 * layers
    # the grouped products at the expected load: 16,384 rows a call
    flops, bytes_ = model.expert_matmul_work(cfg, mix, 36)
    assert flops == 36 * 2 * 16384 * 2048 * 1024
    assert bytes_ == 36 * (16 * 2048 * 1024 * 2
                           + 16384 * (2 * 2048 + 3 * 1024) * 2 / 3)


def _row(op_name, ms, mosaic=False):
    return {"instruction": "custom-call.1" if mosaic else "fusion.1",
            "op_name": op_name, "phase": "forward", "mosaic": mosaic,
            "ms": ms}


def _rows():
    attn = ("jit(step_fn)/jvp(loss)/blocks.1:Block/attn:"
            "GroupedQueryAttention")
    sdpa = attn + "/scaled_dot_product_attention"
    back = sdpa.replace("jvp(loss)", "transpose(jvp(loss))")
    return [
        _row(sdpa + "/window_attention/flash_fwd/pallas_call", 6.0, True),
        _row(back + "/window_attention/flash_bwd_dkv/pallas_call", 12.0,
             True),
        _row(sdpa + "/window_attention/transpose", 1.5),
        _row(sdpa + "/flash_fwd/pallas_call", 20.0, True),
        _row(back + "/flash_bwd_dkv/pallas_call", 40.0, True),
        _row(sdpa + "/transpose", 2.0),
        _row(attn + "/attn_gate/mul", 0.75),
        _row(attn.replace("jvp(loss)", "rematted_computation")
             + "/attn_gate/logistic", 0.25),
        _row(attn + "/rope/mul", 3.0)]


def test_the_new_readers_read_their_scopes_and_counters(monkeypatch):
    cfg, mix, model = _parts()
    peaks = harness.peak_of("TPU v5 lite")
    logged = []
    ctx = {"scope_rows": _rows(), "log": logged.append, "cfg": cfg,
           "mix": mix, "model": model, "peaks": peaks}
    read = {n: harness.load_module("layer_metrics", n).read for n in NEW}
    assert read["window_attention_ms"](ctx) == 19.5
    assert read["attn_gate_ms"](ctx) == 1.0
    # one forward call counted each; the kernels' time alone
    flops, _ = model.window_attention_work(cfg, mix, 1)
    assert read["window_attn_roofline"](ctx) == pytest.approx(
        flops / peaks["bf16_flops_per_s"] * 1000 / 18.0 * 100)
    flops, _ = model.full_attention_work(cfg, mix, 1)
    assert read["full_attn_roofline"](ctx) == pytest.approx(
        flops / peaks["bf16_flops_per_s"] * 1000 / 60.0 * 100)
    assert any("1 forward kernel calls" in line for line in logged)
    # the counters: 150 of a triangle's 528 blocks a kernel traced
    import scope_reduce
    counters = {"pallas.flash.window_blocks_full": 90,
                "pallas.flash.window_blocks_masked": 60,
                "pallas.flash.window_blocks_skipped": 378}
    monkeypatch.setattr(scope_reduce, "program_counter", counters.get)
    assert read["window_blocks_run_share"](ctx) == pytest.approx(
        150 / 528 * 100)


def test_the_new_readers_read_nothing_where_there_is_nothing(monkeypatch):
    """A step without the scopes or a program without the counters (the
    parent's): None, and no raise."""
    cfg, mix, model = _parts()
    import scope_reduce
    monkeypatch.setattr(scope_reduce, "program_counter", {}.get)
    ctx = {"scope_rows": [_row("jit(step_fn)/jvp(loss)/moe/dot", 9.0)],
           "log": [].append, "cfg": cfg, "mix": mix, "model": model,
           "peaks": harness.peak_of("TPU v5 lite")}
    for name in NEW:
        assert harness.load_module("layer_metrics", name).read(ctx) is None
    # nor where no table can be made at all
    ctx["scope_rows"] = None
    for name in NEW[:4]:
        assert harness.load_module("layer_metrics", name).read(ctx) is None
    # a model file of another family has no full_attention_work
    other = dict(ctx, scope_rows=_rows(),
                 model=harness.load_module("models", "gpt"))
    assert harness.load_module("layer_metrics",
                               "full_attn_roofline").read(other) is None


def test_the_cell_is_in_the_benchmark_under_its_names():
    """Found by name, wherever a later PR's entries put it in the lists
    (PERF.md 7 (p), (s))."""
    with open(harness.REPO + "/BENCHMARK.json") as f:
        bench = json.load(f)
    config = [c for c in bench["configs"] if c["name"] == CONFIG]
    cells = [w for w in bench["workloads"] if w["config"] == CONFIG]
    assert len(config) == 1 and [w["name"] for w in cells] == [CELL]
    assert cells[0]["chips"] == 1
    assert cells[0]["traffic"] == "train_bf16_b1_s16384"
    cfg = harness.load_json("configs", CONFIG)
    assert config[0]["reduced"] == cfg["reduced"]
    assert config[0]["source"] == cfg["source"]
    assert config[0]["file"] == f"benchmark/configs/{CONFIG}.json"
    for path in (config[0]["file"], f"benchmark/workloads/{CELL}.json",
                 f"benchmark/traffic/{cells[0]['traffic']}.json",
                 "benchmark/models/afmoe.py", "benchmark/reference/afmoe.py",
                 *(f"benchmark/layer_metrics/{n}.py" for n in NEW)):
        assert os.path.isfile(os.path.join(harness.REPO, path)), path
    for entry in (config[0], cells[0]):
        assert len(entry["why"]) <= 200
    assert all(w["chips"] == 1 for w in bench["workloads"])
    reported = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert set(NEW) | {
        "mosaic_kernels_ms", "flash_fwd_ms", "flash_bwd_ms",
        "opt_state_init_s", "step_python_ms", "rms_norm_ms", "rope_ms",
        "moe_ms", "moe_dispatch_ms", "moe_experts_roofline",
        "moe_buffer_live_share", "moe_full_buffer_chunks",
        "moe_load_imbalance", "ffn_ms"} <= reported
    # not the roofline that counts half the square for every call
    assert not reported & {"flash_attn_roofline", "mla_attention_ms",
                           "sparse_attention_ms", "ssm_ms"}
    new = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert len(new) == 5
    for m in new.values():
        assert m["workloads"] == [CELL]
        assert m["moves"] == "tokens_per_s_per_chip"
    assert {n: (m["unit"], m["source"]) for n, m in new.items()} == {
        "window_attention_ms": ("ms", "device_trace"),
        "window_attn_roofline": ("%", "device_trace"),
        "full_attn_roofline": ("%", "device_trace"),
        "attn_gate_ms": ("ms", "device_trace"),
        "window_blocks_run_share": ("%", "program_counter")}
    # the cell's own files load by name, and its limits are set
    cell, cfg, mix = harness.load_cell(CELL)
    assert (cell["model"], cell["runner"], cell["dtype"]) == (
        "afmoe", "train_step", "bfloat16")
    assert set(cell["check"]["limits"]) == {
        "loss_gap", "grad_norm_gap", "update_norm_gap", "grad_diff"}
    assert (cell["warm_steps"], cell["trace_steps"]) == (2, 4)
