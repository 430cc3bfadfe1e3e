"""The reduction from a trace to busy time, per-name sums and gaps: on a
trace made by hand, and on a cut-down piece of a recorded one."""
import gzip
import json
import os

import pytest

import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "gpt3_large_two_steps.json.gz")


def _trace(ops, modules=(), host=()):
    return {"devices": {"/device:TPU:0": {
        "ops": [(n, s, d, {}) for n, s, d in ops],
        "modules": [(n, s, d, {}) for n, s, d in modules]}},
        "host": list(host)}


def test_overlapping_intervals_give_the_union_not_the_sum():
    out = tr.reduce_planes(_trace([
        ("%a = f32[8]{0} fusion(f32[8]{0} %x)", 0, 100),
        ("%b = f32[8]{0} fusion(f32[8]{0} %y)", 50, 100),
        ("%c = f32[8]{0} copy(f32[8]{0} %z)", 300, 100)]))
    assert out["busy_s"] == pytest.approx(250e-9)       # not 300
    assert out["window_s"] == pytest.approx(400e-9)
    assert out["idle_gaps"] == [("outside", pytest.approx(150e-9))]
    assert tr.union([(0, 100), (50, 150), (300, 400)]) == [(0, 150),
                                                           (300, 400)]


def test_nested_ops_count_once_in_the_sums():
    out = tr.reduce_planes(_trace([
        ("%loop = (s32[]) while((s32[]) %t), body=%b", 0, 1000),
        ("%body.1 = f32[8]{0} fusion(f32[8]{0} %x)", 100, 300),
        ("%body.1 = f32[8]{0} fusion(f32[8]{0} %x)", 500, 300)]))
    assert out["busy_s"] == pytest.approx(1000e-9)
    assert out["op_seconds"]["loop"] == pytest.approx(400e-9)
    assert out["op_seconds"]["body.1 f32[8]"] == pytest.approx(600e-9)
    assert sum(out["op_seconds"].values()) == pytest.approx(out["busy_s"])


def test_the_window_is_the_named_executable_and_gaps_name_the_host_span():
    trace = _trace(
        ops=[("%warm = f32[8]{0} fusion()", 0, 50),
             ("%k = f32[8]{0} custom-call(f32[8]{0} %q), "
              'custom_call_target="tpu_custom_call"', 1000, 400),
             ("%f = f32[8]{0} fusion()", 1500, 400),
             ("%k = f32[8]{0} custom-call(f32[8]{0} %q), "
              'custom_call_target="tpu_custom_call"', 2100, 400),
             ("%f = f32[8]{0} fusion()", 2600, 400)],
        modules=[("jit_other(1)", 0, 50), ("jit_step_fn(77)", 1000, 1000),
                 ("jit_step_fn(77)", 2100, 900)],
        host=[("wait", 900, 1200), ("feed", 1850, 300)])
    out = tr.reduce_planes(trace, module="jit_step_fn")
    assert out["module_runs"] == 2
    assert out["window_s"] == pytest.approx(2000e-9)
    assert out["busy_s"] == pytest.approx(1600e-9)
    # longest first; the gap at 1900..2100 lies inside "feed"
    assert out["idle_gaps"][0] == ("feed", pytest.approx(200e-9))
    assert "k f32[8] tpu_custom_call" in out["op_seconds"]
    assert "warm f32[8]" not in out["op_seconds"]
    mosaic = sum(d for t, _, d, *_ in out["events"] if tr.MOSAIC in t)
    assert mosaic == 800


def test_a_trace_without_device_operations_is_an_error():
    with pytest.raises(ValueError, match="no device operation"):
        tr.reduce_planes({"devices": {}, "host": []})


def test_the_recorded_fixture_reduces_to_fixed_numbers():
    with gzip.open(FIXTURE, "rt") as f:
        raw = json.load(f)
    trace = {"devices": {p: {k: [tuple(e) for e in v] for k, v in d.items()}
                         for p, d in raw["devices"].items()},
             "host": [tuple(e) for e in raw["host"]]}
    out = tr.reduce_planes(trace, module=raw["module"])
    want = raw["expected"]
    assert out["module_runs"] == want["module_runs"]
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert out["busy_s"] / out["window_s"] == pytest.approx(
        want["busy_share"], rel=1e-9)
    top = tr.breakdown(out)
    assert [n for n, _ in top["device_ops"][:3]] == want["top_ops"]
    for name, secs in want["op_seconds"].items():
        assert out["op_seconds"][name] == pytest.approx(secs, rel=1e-9)
    assert [[n, pytest.approx(s, rel=1e-9)]
            for n, s in top["idle_gaps"]] == want["idle_gaps"]
