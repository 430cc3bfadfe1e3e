"""The ouro_2_6b cell's accounting: the configuration file against the
published config and the cut, the parameter count, ``train_flops_per_
token`` against a count by hand, ``attention_calls``, the two new readers
on rows and counters made by hand, the cell's files loading by name, and
a rehearsal of the cell."""
import json
import math
import os

import pytest

import run as harness
from paddle_tpu import observability
from paddle_tpu.utils import monitor

CELL = "ouro_2_6b.train_bf16_b2_s4096"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _parts():
    return (harness.load_json("configs", "ouro_2_6b"),
            harness.load_json("traffic", "train_bf16_b2_s4096"),
            harness.load_module("models", "ouro"))


def test_the_configuration_states_its_cut_and_nothing_else():
    cfg, mix, _ = _parts()
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert cfg["num_hidden_layers"] == 8
    # no width, head count, vocabulary row or pass is cut
    assert [cfg[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size", "vocab_size", "total_ut_steps",
        "early_exit_threshold", "rope_theta", "rms_norm_eps",
        "max_position_embeddings", "tie_word_embeddings", "hidden_act",
        "model_type")] == [2048, 16, 16, 128, 5632, 49152, 4, 1, 1000000,
                           1e-6, 65536, False, "silu", "ouro"]
    assert (mix["batch"], mix["seq"], mix["ring"]) == (2, 4096, 8)
    assert cfg["source"] == ("https://huggingface.co/ByteDance/Ouro-2.6B/"
                             "blob/main/config.json")
    assert cfg["parameters"] == 612438017 and "6 pipeline stages" in \
        cfg["deployment"]
    for item in ("loop", "sandwich_norms", "biases", "rope", "exit_gate",
                 "exit_distribution", "objective", "early_exit_threshold",
                 "rows"):
        assert cfg["assumed"][item]


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalogs_config_is_held_under_its_name():
    cfg, _, _ = _parts()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, "-") != v]
    assert differs == cfg["reduced"]


def test_the_parameter_count_is_the_one_on_record():
    # ISSUE 37: embedding and head 100,663,296 each, a block 51,388,416
    # (attention 16,777,216, SwiGLU 34,603,008, four gains 8,192), the
    # final norm 2,048, the gate 2,049
    cfg, _, model = _parts()
    ref = harness.load_module("reference", "ouro")
    shapes = ref.param_shapes(cfg, {})
    n = sum(math.prod(s) for s, _ in shapes.values())
    block = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert block == 51388416
    assert n == 2 * 49152 * 2048 + 8 * block + 2048 + 2049
    assert n == 612438017 == cfg["parameters"]
    # every program parameter is one reference leaf, the stacked ones
    # block by block, and nothing is left over: the weights exist once
    assert set(model.param_map(cfg, {}).values()) == {
        (leaf, i) for leaf in shapes if leaf.startswith("layers.")
        for i in range(8)} | {(leaf, None) for leaf in shapes
                              if not leaf.startswith("layers.")}


def test_flops_a_token_are_counted_per_application():
    # ISSUE 37: 13.89 GFLOP a token at 4096, 113.8 TFLOP a step; counted
    # per parameter the utilization would read a quarter
    cfg, mix, model = _parts()
    block = 4 * 2048 * 2048 + 3 * 2048 * 5632
    want = (6 * (4 * 8 * block + 4 * 2048 * 49152)
            + 6 * 4 * 8 * 4096 * 2048)
    assert model.train_flops_per_token(cfg, 4096) == want
    assert want == pytest.approx(13.89e9, rel=5e-4)
    assert want * mix["batch"] * mix["seq"] == pytest.approx(113.8e12,
                                                             rel=5e-4)
    # the head's four passes are about a fifth of it
    assert 6 * 4 * 2048 * 49152 / want == pytest.approx(0.174, abs=0.002)
    per_parameter = 6 * (8 * block + 2048 * 49152) + 6 * 8 * 4096 * 2048
    assert want / per_parameter == pytest.approx(4.0, rel=1e-9)
    # one pass is a quarter of everything
    assert model.train_flops_per_token({**cfg, "total_ut_steps": 1},
                                       4096) * 4 == want


def test_attention_calls_are_a_call_a_block_application():
    cfg, mix, model = _parts()
    monitor.stat_reset()
    calls = model.attention_calls(cfg, mix["batch"], mix["seq"])
    assert calls == dict(calls=32, batch=2, heads=16, seq=4096, head_dim=128,
                         causal=True, forward_replays=0)
    # what the replay runs is read from the program's own count of what
    # it kept: a program that kept nothing replays every forward kernel
    monitor.stat_set("recompute.kept.attn_out", 0)
    monitor.stat_set("recompute.kept.attn_lse", 0)
    assert model.attention_calls(cfg, 2, 4096)["forward_replays"] == 1
    monitor.stat_set("recompute.kept.attn_out", 32)
    monitor.stat_set("recompute.kept.attn_lse", 32)
    assert model.attention_calls(cfg, 2, 4096)["forward_replays"] == 0
    monitor.stat_reset()
    roof = harness.load_module("layer_metrics", "flash_attn_roofline")
    seconds, bound = roof.roofline_seconds(calls, harness.peak_of(
        "TPU v5 lite"))
    # 32 calls x 7 products x 2 * 2 * 16 * 4096^2 * 128 / 2 FLOPs
    assert bound == "compute"
    assert seconds == pytest.approx(
        32 * 7 * 2 * 2 * 16 * 4096 * 4096 * 128 / 2 / 197e12, rel=1e-9)


# ------------------------------------------------------- the two readers --
def _row(op_name, ms, mosaic=False):
    return {"op_name": op_name, "ms": ms, "mosaic": mosaic,
            "phase": "forward", "instruction": "fusion.1"}


def test_loop_exit_ms_sums_its_scope_and_reads_nothing_without_it():
    reader = harness.load_module("layer_metrics", "loop_exit_ms")
    pre = "jit(step_fn)/jvp(loss)/"
    rows = [_row(pre + "exit_gate:LoopExitGate/loop_exit/reduce_sum", 1.0),
            _row("jit(step_fn)/transpose(jvp(loss))/loop_exit/mul", 2.0),
            _row(pre + "loop_exit/exp", 4.0),
            # the head and the stack are not the exit's
            _row(pre + "linear_cross_entropy/dot_general", 8.0),
            _row(pre + "stack:LoopedStack/loop_stack/blocks.0:Block/"
                       "rms_norm/mul", 16.0)]
    log = lambda m: None  # noqa: E731
    assert reader.read({"scope_rows": rows, "log": log}) == 7.0
    assert reader.read({"scope_rows": rows[3:], "log": log}) is None
    assert reader.read({"scope_rows": None, "log": log}) is None


@pytest.fixture
def registry(monkeypatch):
    """A program whose read publishes nothing new; the test fills the
    registry itself."""
    monitor.stat_reset()
    monkeypatch.setattr(observability, "read_device_counters", dict,
                        raising=False)
    yield monitor
    monitor.stat_reset()


def test_loop_mean_exit_step_is_the_mean_of_the_steps_shares(registry):
    reader = harness.load_module("layer_metrics", "loop_mean_exit_step")
    logged = []
    # no step counted: nothing read, and the reason logged
    assert reader.read({"log": logged.append}) is None
    assert "nothing read" in logged[-1]
    # three steps whose shares sum to 0.3 / 0.6 / 0.9 / 1.2
    monitor.stat_set("loop.exit_share.steps", 3)
    monitor.stat_set("loop.exit_entropy.steps", 3)
    for t, total in enumerate((0.3, 0.6, 0.9, 1.2)):
        monitor.stat_set(f"loop.exit_share.total.0.{t}", total)
        monitor.stat_set(f"loop.exit_share.last.0.{t}", total / 3)
    monitor.stat_set("loop.exit_entropy.total.0", 3.9)
    monitor.stat_set("loop.exit_entropy.last.0", 1.3)
    got = reader.read({"log": logged.append})
    assert got == pytest.approx(1 * 0.1 + 2 * 0.2 + 3 * 0.3 + 4 * 0.4)
    assert "3 steps" in logged[-1] and "1.30000 nats" in logged[-1]


def test_loop_mean_exit_step_reads_nothing_without_the_reader(monkeypatch):
    """A program from before device counters."""
    reader = harness.load_module("layer_metrics", "loop_mean_exit_step")
    monkeypatch.delattr(observability, "read_device_counters")
    logged = []
    assert reader.read({"log": logged.append}) is None
    assert "no device counters" in logged[-1]


# ------------------------------------------------------------- the cell --
def test_the_cell_loads_by_name_and_is_in_BENCHMARK_json():
    cell, cfg, mix, model, ref, runner = harness.load_parts(CELL)
    assert (mix["batch"], mix["seq"], cell["chips"]) == (2, 4096, 1)
    assert cell["optimizer"] == {
        "name": "adamw", "lr": 2e-4, "weight_decay": 0.01,
        "clip_global_norm": 1.0, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8}
    assert (cell["warm_steps"], cell["trace_steps"]) == (2, 4)
    assert cell["mosaic_is"] == "flash_attention"
    assert (cell["check"]["steps"], cell["check"]["control"]) == (2, "fp8")
    assert set(cell["check"]["limits"]) == {
        "loss_gap", "grad_norm_gap", "update_norm_gap", "grad_diff"}
    names = {m["name"] for m in harness.metric_entries("per_layer", CELL)}
    assert {"loop_exit_ms", "loop_mean_exit_step", "flash_attn_roofline",
            "flash_fwd_ms", "flash_bwd_ms", "mosaic_kernels_ms",
            "rms_norm_ms", "rope_ms", "head_loss_ms", "model_flops_util",
            "unscoped_share", "opt_state_init_s", "step_python_ms"} <= names
    assert not {"moe_ms", "mtp_ms", "mla_attention_ms",
                "eva_attention_ms"} & names
    assert {m["name"] for m in harness.metric_entries("end_to_end", CELL)} \
        == {"tokens_per_s_per_chip", "setup_s"}
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["configs"][-1]["name"] == "ouro_2_6b"
    assert bench["configs"][-1]["reduced"] == cfg["reduced"]
    assert bench["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in bench["per_layer"][-2:]] == [
        "loop_exit_ms", "loop_mean_exit_step"]


def test_the_cell_rehearses_and_counts_its_exits(capsys):
    """``run.py --rehearse``: the cell's own code at its rehearsal widths
    on the CPU, correct under the rehearsal limits; the step's device
    counters reach the registry and the reader."""
    monitor.stat_reset()
    args = harness.argparse.Namespace(workload=CELL, seed=37, seconds=0.5,
                                      trace=0, keep_trace=None)
    result = harness.run_cell(args, rehearse=True)
    assert result["correct"] is True and result["attempted"] >= 1
    out = capsys.readouterr().out
    assert "loss_gap" in out and "OVER" not in out
    stats = monitor.all_stats()
    assert (stats["loop.steps"], stats["loop.block_calls"]) == (4, 12)
    assert stats["linear_cross_entropy.calls"] >= 1
    logged = []
    mean = harness.load_module("layer_metrics", "loop_mean_exit_step").read(
        {"log": logged.append})
    assert 1.0 < mean < 4.0
    assert "sum 1.0000" in logged[-1]
