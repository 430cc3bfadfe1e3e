"""The granite_4_0_h_micro cell's accounting: the configuration file
against the published config and the cut, the parameter count by the
file's own arithmetic, ``train_flops_per_token`` and ``ssm_scan_work``
against counts by hand, the derived pattern against ``layer_types``, the
two new readers on rows made by hand, and the cell's files loading by
name."""
import json
import math
import os

import pytest

import run as harness

CELL = "granite_4_0_h_micro.train_bf16_b1_s8192"
CONFIG = "granite_4_0_h_micro"
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


def _parts():
    return (harness.load_json("configs", CONFIG),
            harness.load_json("traffic", "train_bf16_b1_s8192"),
            harness.load_module("models", "granite_hybrid"))


def test_the_configuration_states_its_cut_and_nothing_else():
    cfg, mix, _ = _parts()
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "layer_types": PERIOD * 4,
                                "vocab_size": 100352}
    assert cfg["layer_types"] == PERIOD == cfg["published"]["layer_types"][:10]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (10, 12544)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    # the widths, the heads and the four multipliers are the published ones
    assert [cfg[k] for k in (
        "hidden_size", "mamba_n_heads", "mamba_d_head", "mamba_n_groups",
        "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_chunk_size",
        "shared_intermediate_size", "intermediate_size",
        "num_attention_heads", "num_key_value_heads", "attention_multiplier",
        "embedding_multiplier", "residual_multiplier", "logits_scaling",
        "num_local_experts", "num_experts_per_tok", "rms_norm_eps",
        "tie_word_embeddings", "position_embedding_type")] == [
        2048, 64, 64, 1, 128, 4, 2, 256, 8192, 8192, 32, 8, 1 / 64, 12, 0.22,
        8, 0, 0, 1e-5, True, "nope"]
    assert (mix["batch"], mix["seq"], mix["ring"]) == (1, 8192, 8)
    assert cfg["source"] == ("https://huggingface.co/ibm-granite/"
                             "granite-4.0-h-micro/blob/main/config.json")
    assert cfg["family"] == "granitemoehybrid" == cfg["model_type"]
    for key in ("deployment", "assumed", "parameter_arithmetic", "derived"):
        assert cfg[key]
    for said in ("head_dim", "layout", "gate_norm", "time_step", "rope",
                 "multipliers", "chunk", "weight_decay", "rows", "loss",
                 "precision", "recompute", "init"):
        assert cfg["assumed"][said], said
    # every number of the catalog's entry under the same key, but the cut
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):      # the catalog is beside the guide
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        published = [r for r in rows if r["source_url"] == cfg["source"]]
        assert len(published) == 1
        for key, value in published[0]["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
        assert published[0]["config"]["layer_types"] == \
            cfg["published"]["layer_types"]


def test_the_derived_pattern_is_layer_types():
    """``ssm_scan_roofline`` counts the state-space layers by
    ``hybrid_override_pattern``, a key of another family's config: here it
    is derived from ``layer_types``, and the program walks chunks of
    ``scan_chunk``, not the published schedule's."""
    cfg, _, model = _parts()
    letters = {"mamba": "M", "attention": "*"}
    assert cfg["hybrid_override_pattern"] == "".join(
        letters[t] for t in cfg["layer_types"]) == "MMMMM*MMMM"
    assert cfg["hybrid_override_pattern"].count("M") == \
        model._kinds(cfg).count("m") == 9
    assert set(cfg["derived"]) == {"hybrid_override_pattern", "scan_chunk"}
    assert cfg["scan_chunk"] == 128 and cfg["mamba_chunk_size"] == 256
    rehearsal = harness.load_cell(CELL, rehearse=True)[1]
    assert rehearsal["hybrid_override_pattern"] == "".join(
        letters[t] for t in rehearsal["layer_types"])
    # the rehearsal keeps ONE group of more heads than a kernel step takes
    assert rehearsal["mamba_n_groups"] == 1
    assert 3 * rehearsal["mamba_n_heads"] > 128


def test_the_parameter_count_is_the_files_arithmetic():
    cfg, _, model = _parts()
    ref = harness.load_module("reference", "granitemoehybrid")
    shapes = ref.param_shapes(cfg, {})
    n = sum(math.prod(s) for s, _ in shapes.values())
    mixer = (2048 * 8512 + 4 * 4352 + 4352 + 3 * 64 + 4096 + 4096 * 2048)
    assert mixer == 25_847_232
    assert 4096 + 4352 + 64 == 8512 and 4096 + 2 * 128 == 4352
    attention = 2048 * (2048 + 512 + 512) + 2048 * 2048
    assert attention == 10_485_760
    ffn = 2048 * 16384 + 8192 * 2048
    assert ffn == 50_331_648
    m, a = mixer + ffn + 4096, attention + ffn + 4096
    assert (m, a) == (76_182_976, 60_821_504)
    assert 9 * m + a == 746_468_288
    assert n == 9 * m + a + 12544 * 2048 + 2048
    assert n == cfg["parameters"] == 772_160_448
    for number in ("25,847,232", "10,485,760", "50,331,648", "76,182,976",
                   "60,821,504", "746,468,288", "25,690,112", "772,160,448",
                   "12.35 GB"):
        assert number in cfg["parameter_arithmetic"], number
    # the whole vocabulary would leave no room for a step
    assert n + 7 * 12544 * 2048 == 951_991_232
    assert ref.layers_of(cfg) == [("m", i) for i in range(5)] + [("a", 0)] \
        + [("m", i) for i in range(5, 9)]
    # every program parameter is one reference leaf, the stacked ones
    # layer by layer of their kind, and nothing is left over; ONE matrix
    # is the embedding and the head
    stacks = {"m": 9, "a": 1}
    names = model.param_map(cfg, {})
    assert len(set(names.values())) == len(names)
    assert set(names.values()) == {
        (leaf, i) for leaf in shapes if leaf.startswith("layers.")
        for i in range(stacks[leaf.split(".")[1]])} | {
        (leaf, None) for leaf in shapes if not leaf.startswith("layers.")}
    assert "head.w" not in shapes and shapes["tok"][0] == (12544, 2048)


def test_flops_a_token_match_a_count_by_hand():
    cfg, mix, model = _parts()
    m = 2048 * 8512 + 4096 * 2048 + 3 * 2048 * 8192
    a = 2048 * (2048 + 512 + 512) + 2048 * 2048 + 3 * 2048 * 8192
    weights = 9 * m + a + 2048 * 12544
    assert weights == pytest.approx(771.9e6, rel=2e-4)
    pairs = 3 * 2 * 32 * (64 + 64) * 8193 / 2
    assert pairs == pytest.approx(0.1007e9, rel=1e-3)
    # a token, forward: C B^T ONCE for the group's 64 heads; a head: inside
    # the chunk, into the state, out of it
    scan = 2 * 128 * 128 + 64 * (2 * 128 * 64 + 2 * 128 * 64 + 2 * 128 * 64)
    assert scan == 32768 + 64 * 49152
    assert 9 * 3 * scan == pytest.approx(0.0858e9, rel=1e-3)
    want = 6 * weights + pairs + 9 * 3 * scan
    assert model.train_flops_per_token(cfg, 8192) == pytest.approx(want)
    assert want == pytest.approx(4.818e9, rel=1e-3)
    assert want * mix["batch"] * mix["seq"] == pytest.approx(39.5e12,
                                                             rel=2e-3)


def test_the_scan_and_the_attention_need_what_a_hand_count_says():
    cfg, mix, model = _parts()
    tokens = 8192
    per_token = 32768 + 64 * 49152
    # nine forwards, nine replays, nine backwards of twice a forward
    flops, bytes_ = model.ssm_scan_work(cfg, mix, forward_calls=18)
    assert flops == tokens * per_token * (18 + 2 * 9)
    # x [4096] and B, C [128] ONE group wide in bfloat16, dt [64] float32
    inputs = tokens * (4096 * 2 + 2 * 128 * 2 + 64 * 4)
    y = tokens * 4096 * 2
    assert bytes_ == 18 * (inputs + y) + 9 * (2 * inputs + y)
    assert bytes_ == pytest.approx(4.454e9, rel=1e-3)
    peaks = harness.peak_of("TPU v5 lite")
    assert max(flops / peaks["bf16_flops_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"]) * 1000 == pytest.approx(
        5.44, rel=2e-3)                      # memory-bound
    # without a replay: the forward calls are the layers
    assert model.ssm_scan_work(cfg, mix, 9)[0] == tokens * per_token * 27
    calls = model.attention_calls(cfg, 1, 8192)
    assert calls == {"calls": 1, "batch": 1, "heads": 32, "kv_heads": 8,
                     "seq": 8192, "head_dim": 64, "causal": True,
                     "forward_replays": 0}


def _row(op_name, ms, phase):
    return {"instruction": "fusion.1", "op_name": op_name, "phase": phase,
            "mosaic": False, "ms": ms}


def test_the_new_readers_read_their_scopes_and_nothing_where_there_are_none():
    layer = "jit(step_fn)/jvp(loss)/blocks.0:Layer"
    mixer = layer + "/mixer:Mamba2Mixer/ssm"
    rows = [_row(layer + "/ffn:GatedFFN/ffn/in_proj:Linear/dot_general", 7.0,
                 "forward"),
            _row(layer + "/ffn:GatedFFN/ffn/mul", 0.5, "forward"),
            _row(layer.replace("jvp(loss)", "rematted_computation")
                 + "/ffn:GatedFFN/ffn/out_proj:Linear/dot_general", 3.0,
                 "recompute"),
            _row(mixer + "/ssm_conv/add", 1.25, "forward"),
            _row(mixer + "/ssm_conv/mul", 0.75, "backward"),
            _row(mixer + "/ssm_scan/dot_general", 3.0, "forward"),
            _row(layer + "/norm2:RMSNorm/rms_norm/mul", 2.0, "forward")]
    ctx = {"scope_rows": rows, "log": [].append}
    read = {n: harness.load_module("layer_metrics", n).read
            for n in ("ffn_ms", "ssm_conv_ms")}
    assert read["ffn_ms"](ctx) == 10.5
    assert read["ssm_conv_ms"](ctx) == 2.0
    # a step without the scopes (the parent's program has no ``ffn``, a
    # cell without state-space layers no ``ssm_conv``): nothing, no raise
    none = {"scope_rows": [_row("jit(step_fn)/jvp(loss)/moe/dot", 9.0,
                                "forward")], "log": [].append}
    assert [r(none) for r in read.values()] == [None, None]


def test_the_cell_is_in_the_benchmark_under_its_names():
    with open(harness.REPO + "/BENCHMARK.json") as f:
        bench = json.load(f)
    config = [c for c in bench["configs"] if c["name"] == CONFIG]
    cells = [w for w in bench["workloads"] if w["config"] == CONFIG]
    assert len(config) == 1 and [w["name"] for w in cells] == [CELL]
    assert cells[0]["chips"] == 1
    assert cells[0]["traffic"] == "train_bf16_b1_s8192"
    cfg = harness.load_json("configs", CONFIG)
    assert config[0]["reduced"] == cfg["reduced"]
    assert config[0]["source"] == cfg["source"]
    assert config[0]["file"] == f"benchmark/configs/{CONFIG}.json"
    # the entries name files that exist
    for path in (config[0]["file"], f"benchmark/workloads/{CELL}.json",
                 f"benchmark/traffic/{cells[0]['traffic']}.json",
                 "benchmark/models/granite_hybrid.py",
                 "benchmark/reference/granitemoehybrid.py",
                 "benchmark/layer_metrics/ffn_ms.py",
                 "benchmark/layer_metrics/ssm_conv_ms.py"):
        assert os.path.isfile(os.path.join(harness.REPO, path)), path
    for entry in (config[0], cells[0]):
        assert len(entry["why"]) <= 200
    reported = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert {"ffn_ms", "ssm_conv_ms", "ssm_ms", "ssm_scan_ms",
            "ssm_scan_roofline", "ssm_state_share", "mosaic_kernels_ms",
            "flash_fwd_ms", "flash_bwd_ms", "rms_norm_ms",
            "opt_state_init_s", "step_python_ms"} <= reported
    # not the roofline that divides by ALL Mosaic time, nor anything of
    # expert layers, RoPE or another family's attention
    assert not reported & {"flash_attn_roofline", "moe_ms", "rope_ms",
                           "mla_attention_ms", "eva_attention_ms"}
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(
            harness.REPO, "benchmark", "layer_metrics", m["name"] + ".py"))
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"] in ("ffn_ms", "ssm_conv_ms")}
    assert len(new) == 2
    assert new["ffn_ms"]["workloads"] == [CELL]
    assert new["ssm_conv_ms"]["workloads"] == [
        "nemotron_3_nano_30b_a3b.train_bf16_b2_s8192", CELL]
    assert all(m["moves"] == "tokens_per_s_per_chip" and m["unit"] == "ms"
               for m in new.values())
    cell, cfg, mix = harness.load_cell(CELL)
    assert (cell["model"], cfg["family"], cell["runner"]) == (
        "granite_hybrid", "granitemoehybrid", "train_step")
    assert "mosaic_is" not in cell
    assert cell["check"]["control"] == "fp8"
    assert (cell["warm_steps"], cell["trace_steps"]) == (2, 4)
    assert set(cell["check"]["limits"]) == set(cell["rehearsal"]["limits"]) \
        == {"loss_gap", "grad_norm_gap", "update_norm_gap", "grad_diff"}
