"""The nemotron_3_nano_30b_a3b cell's accounting: the configuration file
against the published config and the cut, the parameter count,
``train_flops_per_token``, ``expert_matmul_work`` and ``ssm_scan_work``
against counts by hand, the new readers on rows made by hand, and the
cell's files loading by name."""
import json
import math

import pytest

import run as harness

CELL = "nemotron_3_nano_30b_a3b.train_bf16_b2_s8192"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def _parts():
    return (harness.load_json("configs", "nemotron_3_nano_30b_a3b"),
            harness.load_json("traffic", "train_bf16_b2_s8192"),
            harness.load_module("models", "nemotron_h"))


def test_the_configuration_states_its_cut_and_nothing_else():
    cfg, mix, _ = _parts()
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                              "n_routed_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 52,
                                "hybrid_override_pattern": PATTERN,
                                "n_routed_experts": 128,
                                "vocab_size": 131072}
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*"),
            len(PATTERN)) == (23, 23, 6, 52)
    assert cfg["hybrid_override_pattern"] == PATTERN[:9] == "MEMEM*EME"
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (9, 8, 16384)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["held_experts"] == {"first": 0, "count": 8, "of": 128}
    # the widths, the router and the heads are the published ones
    assert [cfg[k] for k in (
        "hidden_size", "mamba_num_heads", "mamba_head_dim", "n_groups",
        "ssm_state_size", "conv_kernel", "chunk_size",
        "moe_intermediate_size", "moe_shared_expert_intermediate_size",
        "num_experts_per_tok", "n_shared_experts", "routed_scaling_factor",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "mlp_hidden_act", "layer_norm_epsilon", "expand")] == [
        2688, 64, 64, 8, 128, 4, 128, 1856, 3712, 6, 1, 2.5, 32, 2, 128,
        "relu2", 1e-5, 2]
    assert (mix["batch"], mix["seq"], mix["ring"]) == (2, 8192, 8)
    assert cfg["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
        "blob/main/config.json")
    for key in ("deployment", "assumed"):
        assert cfg[key]
    for said in ("mamba_inner", "layout", "gate_norm", "time_step", "rope",
                 "router", "router_bias", "train_router", "weight_decay",
                 "rows", "precision", "init"):
        assert cfg["assumed"][said], said
    # every number of the catalog's entry under the same key, but the cut
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = [r for r in rows if r["source_url"] == cfg["source"]]
    if published:                    # the catalog is beside the guide
        for key, value in published[0]["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key


def test_the_parameter_count_is_the_one_on_record():
    # ISSUE 39: 4 x 38,744,896 (M) + 4 x 100,125,440 (E) + 23,399,040 (*)
    # + 2 x 44,040,192 + 2,688 = 666,963,456
    cfg, _, model = _parts()
    ref = harness.load_module("reference", "nemotron_h")
    shapes = ref.param_shapes(cfg, {})
    n = sum(math.prod(s) for s, _ in shapes.values())
    m = (2688 * 10304 + 4 * 6144 + 6144 + 3 * 64 + 4096 + 4096 * 2688
         + 2688)
    assert m == 38_744_896
    expert = 2 * 2688 * 1856
    assert expert == 9_977_856
    e = 2688 * 128 + 128 + 2 * 2688 * 3712 + 8 * expert + 2688
    assert e == 100_125_440 and e - 8 * expert == 20_302_592
    a = 2688 * 4096 * 2 + 2688 * 256 * 2 + 2688
    assert a == 23_399_040
    assert n == 4 * m + 4 * e + a + 2 * 16384 * 2688 + 2688
    assert n == cfg["parameters"] == 666_963_456
    assert ref.held_ids(cfg) == tuple(range(8))
    assert ref.blocks_of(cfg) == [("m", 0), ("e", 0), ("m", 1), ("e", 1),
                                  ("m", 2), ("a", 0), ("e", 2), ("m", 3),
                                  ("e", 3)]
    # every program parameter is one reference leaf, the stacked ones
    # block by block of their kind, and nothing is left over
    stacks = {"m": 4, "e": 4, "a": 1}
    assert set(model.param_map(cfg, {}).values()) == {
        (leaf, i) for leaf in shapes if leaf.startswith("layers.")
        for i in range(stacks[leaf.split(".")[1]])} | {
        (leaf, None) for leaf in shapes if not leaf.startswith("layers.")}
    # the 7-layer fallback of the issue would have been MEMEM*E
    assert PATTERN[:7] == "MEMEM*E"


def test_flops_a_token_match_a_count_by_hand():
    # ISSUE 39: 6 x 318.4 M + 0.20 G (attention at 8192) + 0.04 G (the
    # scan's products) = 2.15 GFLOP a token, 35 TFLOP a step
    cfg, mix, model = _parts()
    m = 2688 * 10304 + 4096 * 2688
    e = 2688 * 128 + 2 * 2688 * 3712 + 6 * 8 / 128 * 2 * 2688 * 1856
    a = 2688 * (4096 + 256 + 256) + 4096 * 2688
    weights = 4 * m + 4 * e + a + 2688 * 16384
    assert weights == pytest.approx(318.4e6, rel=2e-4)
    pairs = 3 * 2 * 32 * (128 + 128) * 8193 / 2
    assert pairs == pytest.approx(0.20e9, rel=0.01)
    # a token and head, forward: C B^T shared by 8 heads, inside the chunk,
    # into the state, out of it
    scan = 64 * (2 * 128 * 128 / 8 + 2 * 128 * 64 + 2 * 128 * 64
                 + 2 * 128 * 64)
    assert 4 * 3 * scan == pytest.approx(0.04e9, rel=0.03)
    want = 6 * weights + pairs + 4 * 3 * scan
    assert model.train_flops_per_token(cfg, 8192) == pytest.approx(want)
    assert want == pytest.approx(2.15e9, rel=2e-3)
    assert want * mix["batch"] * mix["seq"] == pytest.approx(35e12, rel=0.01)


def test_the_scan_and_the_expert_products_need_what_a_hand_count_says():
    cfg, mix, model = _parts()
    tokens = 2 * 8192
    per_token = 64 * (4096 + 3 * 16384)
    # four forwards, four replays, four backwards of twice a forward
    flops, bytes_ = model.ssm_scan_work(cfg, mix, forward_calls=8)
    assert flops == tokens * per_token * (8 + 2 * 4)
    inputs = tokens * (4096 * 2 + 2 * 1024 * 2 + 64 * 4)
    y = tokens * 4096 * 2
    assert bytes_ == 8 * (inputs + y) + 4 * (2 * inputs + y)
    # without a replay: the forward calls are the layers
    assert model.ssm_scan_work(cfg, mix, 4)[0] == tokens * per_token * 12
    # a grouped product: 3,072 expected rows a sequence, 2688 x 1856, the
    # eight held experts' weights once; TWO products an expert
    flops, bytes_ = model.expert_matmul_work(cfg, mix, product_calls=1)
    rows = 8192 * 6 * 8 / 128
    assert rows == 3072
    assert flops == 2 * rows * 2688 * 1856
    assert bytes_ == 8 * 2688 * 1856 * 2 + rows * (2688 + 1856) * 2
    calls = model.attention_calls(cfg, 2, 8192)
    assert (calls["calls"], calls["heads"], calls["kv_heads"],
            calls["head_dim"], calls["forward_replays"]) == (1, 32, 2, 128, 0)


def _ctx(rows, log):
    return {"scope_rows": rows, "log": log.append,
            "cfg": harness.load_json("configs", "nemotron_3_nano_30b_a3b"),
            "mix": harness.load_json("traffic", "train_bf16_b2_s8192"),
            "model": harness.load_module("models", "nemotron_h"),
            "peaks": harness.peak_of("TPU v5 lite")}


def _row(op_name, ms, phase):
    return {"instruction": "fusion.1", "op_name": op_name, "phase": phase,
            "mosaic": False, "ms": ms}


def test_the_readers_read_the_scopes_and_nothing_where_there_are_none():
    base = "jit(step_fn)/jvp(loss)/blocks.0:Block/mixer:Mamba2Mixer/ssm"
    rows = [_row(base + "/in_proj:Linear/dot_general", 5.0, "forward"),
            _row(base + "/ssm_conv/add", 1.0, "forward"),
            _row(base + "/ssm_scan/dot_general", 3.0, "forward"),
            _row(base + "/ssm_scan/exp", 2.0, "backward"),
            _row("jit(step_fn)/jvp(loss)/moe/moe_router/dot", 9.0, "forward")]
    log = []
    ctx = _ctx(rows, log)
    read = {n: harness.load_module("layer_metrics", n).read
            for n in ("ssm_ms", "ssm_scan_ms", "ssm_scan_roofline")}
    assert read["ssm_ms"](ctx) == 11.0
    assert read["ssm_scan_ms"](ctx) == 5.0
    # no time under the scope in the replay: four forward passes
    flops, bytes_ = ctx["model"].ssm_scan_work(ctx["cfg"], ctx["mix"], 4)
    need_ms = max(flops / 197e12, bytes_ / 819e9) * 1000
    assert read["ssm_scan_roofline"](ctx) == pytest.approx(need_ms / 5 * 100)
    assert "4 forward passes" in log[-1]
    rows.append(_row(base.replace("jvp(loss)", "rematted_computation")
                     + "/ssm_scan/dot_general", 3.0, "recompute"))
    flops, bytes_ = ctx["model"].ssm_scan_work(ctx["cfg"], ctx["mix"], 8)
    need_ms = max(flops / 197e12, bytes_ / 819e9) * 1000
    assert read["ssm_scan_roofline"](ctx) == pytest.approx(need_ms / 8 * 100)
    assert "8 forward passes" in log[-1]
    # a step without the scopes (the parent's program): nothing, no raise
    none = _ctx([_row("jit(step_fn)/jvp(loss)/moe/dot", 9.0, "forward")], [])
    assert [r(none) for r in read.values()] == [None, None, None]


def test_the_state_share_reader_reads_the_registry(monkeypatch):
    import moe_counters
    reader = harness.load_module("layer_metrics", "ssm_state_share")
    stats = {"ssm.state_share.steps": 4, "ssm.mean_decay.steps": 4}
    for call, (share, decay) in enumerate([(2.0, 3.2), (3.2, 3.4)]):
        stats[f"ssm.state_share.total.{call}"] = share
        stats[f"ssm.state_share.last.{call}"] = share / 4
        stats[f"ssm.mean_decay.total.{call}"] = decay
        stats[f"ssm.mean_decay.last.{call}"] = decay / 4
    monkeypatch.setattr(moe_counters, "registry", lambda log, who: stats)
    log = []
    assert reader.read({"log": log.append}) == pytest.approx(0.65)
    assert "0.5000, 0.8000" in log[-1] and "0.8000, 0.8500" in log[-1]
    monkeypatch.setattr(moe_counters, "registry", lambda log, who: {})
    assert reader.read({"log": log.append}) is None
    monkeypatch.setattr(moe_counters, "registry", lambda log, who: None)
    assert reader.read({"log": log.append}) is None


def test_the_cell_is_in_the_benchmark_under_its_names():
    with open(harness.REPO + "/BENCHMARK.json") as f:
        bench = json.load(f)
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "nemotron_3_nano_30b_a3b"
    assert bench["configs"][-1]["reduced"] == harness.load_json(
        "configs", "nemotron_3_nano_30b_a3b")["reduced"]
    reported = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert {"ssm_ms", "ssm_scan_ms", "ssm_scan_roofline", "ssm_state_share",
            "mosaic_kernels_ms", "flash_fwd_ms", "flash_bwd_ms",
            "rms_norm_ms", "moe_ms", "moe_dispatch_ms",
            "moe_experts_roofline", "moe_buffer_live_share",
            "moe_full_buffer_chunks", "moe_load_imbalance",
            "opt_state_init_s", "step_python_ms"} <= reported
    assert [m["name"] for m in bench["per_layer"][-4:]] == [
        "ssm_ms", "ssm_scan_ms", "ssm_scan_roofline", "ssm_state_share"]
    cell, cfg, mix = harness.load_cell(CELL)
    assert (cell["model"], cfg["family"], cell["runner"]) == (
        "nemotron_h", "nemotron_h", "train_step")
    assert "mosaic_is" not in cell
    assert cell["check"]["limits"] and cell["rehearsal"]["limits"]
