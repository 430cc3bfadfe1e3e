"""The five metrics of the set-up timeline (``before_import_s``,
``program_trace_s``, ``program_lower_s``, ``program_load_s``,
``first_call_s``) over a registry and a report made by hand: the
arithmetic and the log lines, and nothing read, without raising, on a
program from before the timeline."""
import json
import os
import re

import pytest

import run as harness
import setup_timeline
from paddle_tpu import observability
from paddle_tpu.utils import monitor

METRICS = ("before_import_s", "program_trace_s", "program_lower_s",
           "program_load_s", "first_call_s")


def _fn(count, seconds, self_s):
    return {"count": count, "seconds": seconds, "self_s": self_s}


def _owner(phases, **span):
    return dict({"phases": {p: {"functions": fns}
                            for p, fns in phases.items()}}, **span)


REPORT = {
    "events": 40,
    "owners": {
        "outside": _owner({"trace": {"block_grad": _fn(1, 9.0, 9.0),
                                     "add": _fn(20, 0.5, 0.5)}}),
        "train_step.build": _owner(
            {"trace": {"step_fn": _fn(1, 6.0, 2.5), "add": _fn(9, 0.5, 0.5),
                       "_flash_fwd": _fn(2, 2.0, 2.0),
                       "_ssd_fwd": _fn(3, 1.0, 1.0)}}),
        "train_step.first_call": _owner(
            {"lower": {"step_fn": _fn(1, 2.0, 2.0)},
             "load": {"step_fn": _fn(1, 1.5, 1.5)}},
            inclusive={"trace": 6.0, "lower": 2.0, "load": 1.5,
                       "compile": 0.25, "other_s": 0.75}),
        "setup.param_init": _owner(
            {"trace": {"_normal": _fn(2, 0.25, 0.25)},
             "compile": {"_normal": _fn(2, 0.5, 0.5)}}, wall_s=1.0),
        "setup.amp_decorate": _owner({}, wall_s=0.25),
    },
    "stamps": {"process_start": 100.0, "import_start": 108.5,
               "import_end": 109.0, "ready": {"train_step": 140.0}},
    "cache": {
        "outside": {"loads": 7, "compiles": 0, "written": 0,
                    "retrieval_s": 3.0, "saved_s": 90.0},
        "train_step.first_call": {"loads": 1, "compiles": 0, "written": 0,
                                  "retrieval_s": 1.25, "saved_s": 40.0},
        "setup.param_init": {"loads": 0, "compiles": 2, "written": 2,
                             "retrieval_s": 0.0, "saved_s": 0.0},
    },
}
GAUGES = {"setup.before_import_s": 8.5, "setup.program.trace_s": 6.25,
          "setup.program.lower_s": 2.0, "setup.program.load_s": 1.5,
          "setup.program.compile_s": 0.5, "setup.first_call_s": 10.5}


@pytest.fixture
def program(monkeypatch):
    """A program with the timeline: the gauges and the report above."""
    monitor.stat_reset()
    for name, value in GAUGES.items():
        monitor.stat_set(name, value)
    monkeypatch.setattr(observability, "setup_report", lambda: REPORT,
                        raising=False)
    yield
    monitor.stat_reset()


@pytest.fixture
def old_program(monkeypatch):
    """A program from before the timeline: no report, no gauge."""
    monitor.stat_reset()
    monkeypatch.delattr(observability, "setup_report", raising=False)
    yield
    monitor.stat_reset()


def _read(name, logged=None):
    logged = [] if logged is None else logged
    return harness.load_module("layer_metrics", name).read(
        {"log": logged.append})


@pytest.mark.parametrize("name, value", [
    ("before_import_s", 8.5), ("program_trace_s", 6.25),
    ("program_lower_s", 2.0), ("program_load_s", 2.0),
    ("first_call_s", 10.5)])
def test_each_metric_reads_its_gauges(program, name, value):
    assert _read(name) == value


@pytest.mark.parametrize("name", METRICS)
def test_nothing_is_read_from_a_program_without_the_timeline(
        old_program, name):
    logged = []
    assert _read(name, logged) is None
    assert logged == []


@pytest.mark.parametrize("name", METRICS)
def test_a_gauge_without_a_report_reads_nothing_or_the_gauge(
        old_program, name):
    """Half a program (the registry filled by something else): the plain
    readers give the gauge, the ones with a log line nothing."""
    for gauge, value in GAUGES.items():
        monitor.stat_set(gauge, value)
    want = {"before_import_s": 8.5, "program_lower_s": 2.0}.get(name)
    assert _read(name) == want


def test_the_trace_line_names_the_programs_functions_by_self_seconds(
        program):
    logged = []
    _read("program_trace_s", logged)
    line, inclusive = logged
    assert inclusive == (
        "[program_trace_s] most seconds with what they trace inside: "
        "step_fn 6.000 s x1, _flash_fwd 2.000 s x2, _ssd_fwd 1.000 s x3, "
        "add 0.500 s x9, _normal 0.250 s x2")
    assert line.startswith("[program_trace_s] 6.250 s in 17 traces of 40 "
                           "events in all")
    names = [part.split()[0] for part in
             line.split("most self seconds: ")[1].split(", ")]
    # the reference's block_grad is not the program's; ``add`` sums the
    # program's nine, not the caller's twenty
    assert names == ["step_fn", "_flash_fwd", "_ssd_fwd", "add", "_normal"]
    assert "step_fn 2.500 s x1" in line and "add 0.500 s x9" in line


def test_at_most_eight_functions_are_named(program, monkeypatch):
    many = {f"launcher_{i}": _fn(1, 1.0, 1.0 + i) for i in range(12)}
    rep = dict(REPORT, owners={"train_step.build": _owner({"trace": many})})
    monkeypatch.setattr(observability, "setup_report", lambda: rep)
    logged = []
    _read("program_trace_s", logged)
    named = logged[0].split("most self seconds: ")[1].split(", ")
    assert len(named) == 8 and named[0].startswith("launcher_11 12.000 s")


def test_the_load_line_leaves_the_callers_cache_traffic_out(program):
    logged = []
    assert _read("program_load_s", logged) == 2.0
    assert logged == [
        "[program_load_s] loading 1.500 s in 1 loads, compiling 0.500 s in "
        "2 fresh compiles; the cache's retrieval 1.250 s, "
        "compile_time_saved_sec 40.000"]


def test_the_first_calls_parts_add_up_to_it(program):
    logged = []
    assert _read("first_call_s", logged) == 10.5
    assert logged == [
        "[first_call_s] first call = trace 6.000 + lower 2.000 + load 1.750 "
        "+ other 0.750 = 10.500 s",
        "[first_call_s] before it, seconds of wall: amp_decorate 0.250, "
        "param_init 1.000; after the process's start: import from 8.500 to "
        "9.000, ready train_step 40.000"]
    assert sum(setup_timeline.first_call_parts(REPORT)) == 10.5


def test_every_entry_points_first_call_is_in_the_parts():
    rep = {"owners": {
        name: {"inclusive": {"trace": 1.0, "lower": 2.0, "load": 3.0,
                             "compile": 4.0, "other_s": 5.0}}
        for name in setup_timeline.FIRST_CALLS + ("setup.param_init",)}}
    assert setup_timeline.first_call_parts(rep) == [3.0, 6.0, 21.0, 15.0]


def test_the_real_programs_report_feeds_all_five(monkeypatch):
    """The live program's own report, after one compiled step: every
    metric reads a number, the first call equals its four parts, and the
    program's sums stay under the whole process's."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.observability import compiles

    monitor.stat_reset()
    compiles.reset_compiles()
    monitor.stat_set("setup.before_import_s", 1.0)      # set at import
    paddle.seed(0)
    net = nn.Linear(4, 3)
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=net.parameters())
    step = TrainStep(net, lambda o, y: ((o - y) ** 2).mean(), opt)
    step(jnp.ones((2, 4)), jnp.ones((2, 3)))
    logged = []
    got = {name: _read(name, logged) for name in METRICS}
    assert all(v is not None for v in got.values()), got
    trace_lower = harness.load_module("layer_metrics", "trace_lower_s").read(
        {"log": logged.append})
    assert got["program_trace_s"] + got["program_lower_s"] <= trace_lower
    line = next(x for x in logged if x.startswith("[first_call_s]"))
    *parts, whole = map(float, re.findall(r"\d+\.\d+", line))
    assert len(parts) == 4 and sum(parts) == pytest.approx(whole, abs=2e-3)
    assert whole == pytest.approx(got["first_call_s"], rel=0.01)
    compiles.reset_compiles()
    monitor.stat_reset()


def test_the_five_are_in_the_benchmark_as_the_issue_words_them():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in METRICS}
    assert sorted(mine) == sorted(METRICS)
    for m in mine.values():
        assert m == {"name": m["name"], "unit": "s", "better": "lower",
                     "source": "program_span", "layer": "entry and compile",
                     "moves": "setup_s"}
    for cell in bench["workloads"]:
        reported = {m["name"]
                    for m in harness.metric_entries("per_layer",
                                                    cell["name"])}
        assert set(METRICS) <= reported
