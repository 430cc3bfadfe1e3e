"""The set-up timeline (``observability.setup_report``): every trace,
lowering, cache load and compile jax makes is kept with its function's
name, its self seconds and its owner: the innermost program set-up span
open on its thread, the entry point that recompiled, or ``outside``."""
import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, observability, optimizer
from paddle_tpu.jit import TrainStep
from paddle_tpu.observability import compiles
from paddle_tpu.utils import monitor

REPO = os.path.dirname(os.path.dirname(paddle.__file__))
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


@pytest.fixture
def timeline():
    """Sums from nothing; the registry is left as it was found."""
    compiles.reset_compiles()
    yield observability.setup_report
    compiles.reset_compiles()


def _functions(rep, owner, phase):
    return rep["owners"].get(owner, {}).get("phases", {}).get(
        phase, {}).get("functions", {})


def _train_step():
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=net.parameters())
    return TrainStep(net, lambda o, y: ((o - y) ** 2).mean(), opt)


def _fresh(name):
    """A function jax has never seen, under ``name``."""
    salt = time.perf_counter_ns() % 1000003

    def f(x):
        return jnp.sin(x) * salt + 1
    f.__name__ = f.__qualname__ = name
    return f


# ------------------------------------------------------------- owners --
def test_a_function_jitted_outside_every_span_is_owned_by_outside(timeline):
    jax.jit(_fresh("mine_alone"))(jnp.ones(3))
    rep = timeline()
    for phase in ("trace", "lower", "compile"):
        assert _functions(rep, "outside", phase)["mine_alone"]["count"] == 1
    assert "spans" not in rep["owners"]["outside"]


def test_a_train_steps_first_call_owns_its_step(timeline):
    step = _train_step()
    step(jnp.ones((2, 4)), jnp.ones((2, 2)))
    rep = timeline()
    # the trace happens under the build (``_carry_counters`` traces the
    # step once), lowering and compile in the first compiled call
    assert _functions(rep, "train_step.build", "trace")["step_fn"][
        "self_s"] > 0
    for phase in ("lower", "compile"):
        got = _functions(rep, "train_step.first_call", phase)["step_fn"]
        assert got["count"] == 1 and got["self_s"] > 0
    for owner in ("outside", "train_step.call"):
        for phase in ("trace", "lower", "compile"):
            assert "step_fn" not in _functions(rep, owner, phase)
    first = rep["owners"]["train_step.first_call"]
    assert first["spans"] == 1
    assert first["wall_s"] >= rep["owners"]["train_step.build"]["wall_s"]
    assert "train_step" in rep["stamps"]["ready"]


@pytest.mark.parametrize("owner", ["setup.param_init",
                                   "setup.opt_state_init"])
def test_building_a_step_runs_under_its_set_up_spans(timeline, owner):
    step = _train_step()
    step(jnp.ones((2, 4)), jnp.ones((2, 2)))
    got = timeline()["owners"][owner]
    assert got["spans"] == (4 if owner == "setup.param_init" else 1)
    # (what it ran eagerly may have been compiled by an earlier test)
    assert 0 <= got["self_s"] <= got["wall_s"] and got["wall_s"] > 0


def test_amp_decorate_has_a_span_and_a_counter(timeline):
    before = monitor.get_stat("setup.amp_decorate_s")
    net = nn.Linear(4, 8)
    paddle.amp.decorate(net, level="O2", dtype="bfloat16")
    got = timeline()["owners"]["setup.amp_decorate"]
    assert got["spans"] == 1 and got["count"] > 0
    grew = monitor.get_stat("setup.amp_decorate_s") - before
    assert 0 < grew and abs(grew - got["wall_s"]) < 0.05


def test_a_new_batch_shape_after_ready_is_the_entry_points_recompile(
        timeline):
    step = _train_step()
    for _ in range(2):
        step(jnp.ones((2, 4)), jnp.ones((2, 2)))
    x, y = jnp.ones((3, 4)), jnp.ones((3, 2))   # the caller's own work
    before = timeline()
    assert "train_step.call" not in before["owners"]
    step(x, y)
    rep = timeline()
    for phase in ("trace", "lower", "compile"):
        got = _functions(rep, "train_step.call", phase)["step_fn"]
        assert got["count"] == 1, phase
    # what the step traced inside is the recompile's too, not the caller's
    assert len(_functions(rep, "train_step.call", "trace")) > 1
    assert _functions(rep, "outside", "trace") == _functions(
        before, "outside", "trace")
    assert rep["owners"]["train_step.first_call"]["spans"] == 1
    assert "spans" not in rep["owners"]["train_step.call"]
    # the recompile is the program's: in the published sums
    assert rep["program"]["compile"] > before["program"]["compile"]


def test_eval_step_has_a_first_call_of_its_own(timeline):
    step = _train_step()
    step.eval_step(jnp.ones((2, 4)), jnp.ones((2, 2)))
    step.eval_step(jnp.ones((2, 4)), jnp.ones((2, 2)))
    rep = timeline()
    assert rep["owners"]["eval_step.first_call"]["spans"] == 1
    assert _functions(rep, "eval_step.first_call", "compile")["eval_fn"][
        "count"] == 1
    assert "eval_step" in rep["stamps"]["ready"]
    step.eval_step(jnp.ones((5, 4)), jnp.ones((5, 2)))
    assert _functions(timeline(), "eval_step.call", "compile")["eval_fn"][
        "count"] == 1


def test_the_executors_first_run_of_a_program_owns_its_step(timeline):
    from paddle_tpu import static
    paddle.enable_static()
    try:
        main = static.Program()
        with static.program_guard(main, static.Program()):
            x = static.data("x", [4, 8], "float32")
            out = (x * 2.0).sum()
        exe = static.Executor()
        feed = {"x": np.ones((4, 8), np.float32)}
        exe.run(main, feed=feed, fetch_list=[out])
        exe.run(main, feed=feed, fetch_list=[out])
    finally:
        paddle.disable_static()
        static.reset_default_programs()
    rep = timeline()
    first = rep["owners"]["executor.first_run"]
    assert first["spans"] == 1
    assert rep["owners"]["executor.build"]["spans"] == 1
    assert first["inclusive"]["compile"] + first["inclusive"]["load"] > 0
    assert "executor" in rep["stamps"]["ready"]
    assert exe._first_run is None


def test_another_threads_compile_is_not_the_open_spans(timeline):
    f = jax.jit(_fresh("on_a_thread"))
    with compiles.setup_span("test.owner"):
        t = threading.Thread(target=f, args=(jnp.ones(3),))
        t.start()
        t.join()
        jax.jit(_fresh("on_this_thread"))(jnp.ones(3))
    rep = timeline()
    assert "on_a_thread" in _functions(rep, "outside", "compile")
    assert "on_this_thread" in _functions(rep, "test.owner", "compile")
    threads = {t["fun_name"]: t["thread"] for t in rep["timeline"]}
    assert threads["on_a_thread"] != threads["on_this_thread"]


def test_reports_while_many_threads_note_intervals_lose_nothing(timeline):
    """More threads than cores close intervals while a report sums their
    notes from outside: every interval is counted once, with its self
    seconds (a lost or doubled note would break both sums)."""
    workers, each = 16, 1500
    before, stop = sys.getswitchinterval(), threading.Event()

    def close_many(k):
        for i in range(each):
            t = 1000.0 * k + i
            compiles._on_close(TRACE, t + 0.25, t + 0.5, fun_name="inner")
            compiles._on_close(TRACE, t, t + 1.0, fun_name="outer")

    def report_often():
        while not stop.is_set():
            timeline()

    sys.setswitchinterval(1e-5)
    try:
        reader = threading.Thread(target=report_often)
        threads = [threading.Thread(target=close_many, args=(k,))
                   for k in range(1, workers + 1)]
        reader.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        stop.set()
        reader.join(timeout=120)
        assert not reader.is_alive() and not any(
            t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(before)
    fns = _functions(timeline(), "outside", "trace")
    assert fns["inner"]["count"] == fns["outer"]["count"] == workers * each
    assert fns["inner"]["self_s"] == pytest.approx(0.25 * workers * each)
    assert fns["outer"]["self_s"] == pytest.approx(0.75 * workers * each)


# --------------------------------------------------------- self times --
def test_an_inlined_launchers_trace_is_taken_out_of_its_callers(timeline):
    def launcher(x):
        time.sleep(0.05)
        return jnp.cos(x)
    launch = jax.jit(launcher, inline=True)

    def caller(x):
        time.sleep(0.02)
        return launch(x) + launch(x * 2)

    with compiles.setup_span("test.owner"):
        time.sleep(0.03)
        jax.jit(caller)(jnp.ones(7))
    rep = timeline()
    traces = _functions(rep, "test.owner", "trace")
    inner, outer = traces["launcher"], traces["caller"]
    # (the second call finds the first's jaxpr: an event of no length)
    assert inner["count"] == 2 and 0.05 <= inner["seconds"] < 0.2
    assert outer["seconds"] >= 0.07                 # holds the launcher
    assert 0.02 <= outer["self_s"] <= outer["seconds"] - inner["seconds"]
    # exclusive seconds add up: the span's wall is its intervals' selves
    # and the python between them
    own = rep["owners"]["test.owner"]
    selves = sum(f["self_s"] for ph in own["phases"].values()
                 for f in ph["functions"].values())
    assert selves == pytest.approx(own["self_s"])
    assert selves + own["other_s"] == pytest.approx(own["span_self_s"])
    assert own["other_s"] >= 0.03
    inc = own["inclusive"]
    assert (inc["trace"] + inc["lower"] + inc["load"] + inc["compile"]
            + inc["other_s"]) == pytest.approx(own["wall_s"])
    # the whole-process counters count the launcher in its caller too
    assert sum(ph["seconds"] for ph in own["phases"].values()) > selves


def test_a_nested_span_is_taken_out_of_the_span_around_it(timeline):
    with compiles.setup_span("test.outer"):
        jax.jit(_fresh("in_outer"))(jnp.ones(3))
        with compiles.setup_span("test.inner"):
            jax.jit(_fresh("in_inner"))(jnp.ones(3))
    rep = timeline()
    outer, inner = rep["owners"]["test.outer"], rep["owners"]["test.inner"]
    assert "in_inner" in _functions(rep, "test.inner", "compile")
    assert "in_inner" not in _functions(rep, "test.outer", "compile")
    assert outer["span_self_s"] == pytest.approx(
        outer["wall_s"] - inner["wall_s"])
    assert outer["inclusive"]["compile"] == pytest.approx(
        outer["phases"]["compile"]["self_s"]
        + inner["phases"]["compile"]["self_s"])


# -------------------------------------------- what is kept, and where --
def _interval(name, secs, event=TRACE, start=100.0):
    compiles._on_close(event, start, start + secs, fun_name=name)


def test_the_ring_holds_the_newest_4096_and_the_sums_everything(timeline):
    for i in range(5000):
        _interval("long", 0.002, start=float(i))
    for i in range(300):
        _interval("ufunc", 2e-5, start=6000.0 + i)
    rep = timeline()
    assert len(rep["timeline"]) == 4096 == compiles._MAX_TIMELINE
    assert {t["fun_name"] for t in rep["timeline"]} == {"long"}
    assert rep["timeline"][-1]["start"] == 4999.0
    fns = _functions(rep, "outside", "trace")
    assert fns["long"]["count"] == 5000 and fns["ufunc"]["count"] == 300
    assert rep["events"] == 5300


def test_children_close_before_their_parents_and_leave_their_selves(
        timeline):
    """jax's order: ``sin`` and ``add`` inside ``launcher`` inside
    ``step_fn``, with an earlier sibling that is nobody's child."""
    _interval("before", 1.0, start=0.0)
    _interval("sin", 0.25, start=10.5)
    _interval("add", 0.25, start=11.0)
    _interval("launcher", 1.5, start=10.25)
    _interval("tail", 0.5, start=12.0)
    _interval("step_fn", 4.0, start=10.0)
    fns = _functions(timeline(), "outside", "trace")
    assert {k: (v["seconds"], v["self_s"]) for k, v in fns.items()} == {
        "before": (1.0, 1.0), "sin": (0.25, 0.25), "add": (0.25, 0.25),
        "launcher": (1.5, 1.0), "tail": (0.5, 0.5), "step_fn": (4.0, 2.0)}
    assert sum(v["self_s"] for v in fns.values()) == 5.0    # the two walls


def test_a_lowering_and_a_compile_go_by_the_traces_name(timeline):
    _interval("f", 0.01)
    _interval("jit(f)", 0.02, LOWER, start=101.0)
    _interval("jit(f)", 0.03, BACKEND, start=102.0)
    rep = timeline()
    for phase in ("trace", "lower", "compile"):
        assert list(_functions(rep, "outside", phase)) == ["f"], phase


def test_the_caches_answer_makes_a_compile_a_load(timeline):
    with compiles.setup_span("test.owner"):
        compiles._on_cache_event("/jax/compilation_cache/cache_hits")
        compiles._on_cache_seconds(
            "/jax/compilation_cache/compile_time_saved_sec", 4.5)
        compiles._on_cache_seconds(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
        compiles._on_close(BACKEND, 1.0, 1.5, fun_name="jit(f)")
        _interval("jit(g)", 2.0, BACKEND, start=2.0)
    rep = timeline()
    assert _functions(rep, "test.owner", "load")["f"]["seconds"] == 0.5
    assert _functions(rep, "test.owner", "compile")["g"]["seconds"] == 2.0
    assert rep["cache"]["test.owner"] == {
        "loads": 1, "written": 0, "retrieval_s": 0.5, "saved_s": 4.5,
        "compiles": 1}
    assert rep["program"]["load"] == 0.5 and rep["program"]["compile"] == 2.0
    assert monitor.get_stat("setup.program.load_s") == 0.5


def test_the_caches_durations_are_listened_to_while_a_load_is_in_flight(
        timeline):
    """jax calls a duration listener for every trace and lowering too,
    so that one is registered from a hit to its compile's close only."""
    listeners = jax.monitoring.get_event_duration_listeners \
        if hasattr(jax.monitoring, "get_event_duration_listeners") \
        else jax._src.monitoring.get_event_duration_listeners
    assert compiles._on_cache_seconds not in listeners()
    compiles._on_cache_event("/jax/compilation_cache/cache_hits")
    assert listeners().count(compiles._on_cache_seconds) == 1
    _interval("jit(f)", 0.5, BACKEND)
    assert compiles._on_cache_seconds not in listeners()
    assert compiles._loads_in_flight == 0
    # a write (no hit) registers nothing
    compiles._on_cache_event("/jax/compilation_cache/cache_misses")
    assert compiles._on_cache_seconds not in listeners()
    _interval("jit(g)", 0.5, BACKEND, start=200.0)
    rep = timeline()
    assert rep["cache"]["outside"]["loads"] == 1
    assert rep["cache"]["outside"]["written"] == 1


def test_a_persistent_cache_answers_the_second_build():
    """First build ``compile``, the same build after
    ``jax.clear_caches()`` ``load``: in a fresh interpreter, because
    tier-1 keeps the persistent cache off."""
    script = (
        "import json, sys, tempfile\n"
        "import jax, jax.numpy as jnp\n"
        "jax.config.update('jax_compilation_cache_dir',"
        " tempfile.mkdtemp())\n"
        "jax.config.update("
        "'jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.config.update("
        "'jax_persistent_cache_min_entry_size_bytes', 0)\n"
        "import paddle_tpu\n"
        "from paddle_tpu import observability\n"
        "from paddle_tpu.observability import compiles\n"
        "def cached_twice(x): return jnp.tanh(x) * 3 + x\n"
        "out = []\n"
        "for name in ('test.first', 'test.second'):\n"
        "    with compiles.setup_span(name):\n"
        "        jax.jit(cached_twice)(jnp.ones(5))\n"
        "    jax.clear_caches()\n"
        "rep = observability.setup_report()\n"
        "print(json.dumps({k: {p: sorted(v['functions']) for p, v in"
        " rep['owners'][k]['phases'].items()}"
        " for k in ('test.first', 'test.second')}))\n"
        "print(json.dumps(rep['cache']))\n")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=300, cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    phases, cache = map(json.loads, out.stdout.strip().splitlines()[-2:])
    assert "cached_twice" in phases["test.first"]["compile"]
    assert "cached_twice" not in phases["test.first"].get("load", [])
    assert "cached_twice" in phases["test.second"]["load"]
    assert "cached_twice" not in phases["test.second"].get("compile", [])
    assert cache["test.second"]["loads"] >= 1
    assert cache["test.first"]["written"] >= 1


# ---------------------------------------------------- before the import --
def test_the_age_of_the_process_at_import_is_published():
    """``setup.before_import_s`` and the stamps, from a fresh
    interpreter: the caller's own second before the import is in it."""
    script = (
        "import json, time\n"
        "t0 = time.time(); time.sleep(1.0)\n"
        "import paddle_tpu\n"
        "from paddle_tpu import observability\n"
        "from paddle_tpu.utils import monitor\n"
        "rep = observability.setup_report()\n"
        "print(json.dumps([t0, monitor.all_stats(), rep['stamps'],"
        " rep['owners']['setup.import']['wall_s']]))\n")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=300, cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    t0, stats, stamps, wall = json.loads(out.stdout.strip().splitlines()[-1])
    assert 1.0 <= stats["setup.before_import_s"] < 60
    assert stamps["process_start"] <= t0 + 0.011        # a 10 ms grain
    assert stamps["import_start"] >= t0 + 1.0
    assert stamps["import_start"] - stamps["process_start"] == \
        pytest.approx(stats["setup.before_import_s"])
    assert stamps["import_end"] - stamps["import_start"] == \
        pytest.approx(stats["setup.import_s"], abs=0.05)
    assert wall == pytest.approx(stats["setup.import_s"], abs=0.05)
    assert stamps["backend_made_before_import"] is False


def test_without_proc_the_age_is_absent(timeline, monkeypatch, tmp_path):
    assert compiles.process_age(str(tmp_path / "no" / "stat")) is None
    (tmp_path / "stat").write_text("1 (python) S 0")     # not Linux's
    assert compiles.process_age(str(tmp_path / "stat")) is None
    assert compiles.process_age() > 0
    stamps, had = dict(compiles._stamps), monitor.all_stats()
    monkeypatch.setattr(compiles, "process_age", lambda: None)
    monitor.stat_reset("setup.before_import_s")
    compiles._stamps.clear()
    try:
        compiles.import_done(time.time() - 0.5, True)
        assert "setup.before_import_s" not in monitor.all_stats()
        got = timeline()["stamps"]
        assert "process_start" not in got
        assert got["backend_made_before_import"] is True
        assert got["import_end"] - got["import_start"] == pytest.approx(
            0.5, abs=0.1)
    finally:
        compiles._stamps.clear()
        compiles._stamps.update(stamps)
        if "setup.before_import_s" in had:
            monitor.stat_set("setup.before_import_s",
                             had["setup.before_import_s"])


def test_a_backend_made_by_the_program_has_a_span(timeline, monkeypatch):
    from paddle_tpu import device
    from paddle_tpu.core import xla_env
    assert device.device_count() >= 1           # made long ago: no span
    assert "setup.backend_init" not in timeline()["owners"]
    monkeypatch.setattr(xla_env, "_backend_initialized", lambda: False)
    assert device.get_device().startswith("cpu")
    assert timeline()["owners"]["setup.backend_init"]["spans"] == 1


# ------------------------------------------------------ the steady path --
def test_a_steady_call_opens_three_spans_and_adds_no_counter(
        timeline, monkeypatch):
    from paddle_tpu.jit import train_step
    step = _train_step()
    x, y = jnp.ones((2, 4)), jnp.ones((2, 2))
    for _ in range(2):
        step(x, y)
    opened, setups = [], []
    real_span, real_begin = train_step.span, compiles.begin_setup
    monkeypatch.setattr(train_step, "span",
                        lambda name: opened.append(name) or real_span(name))
    monkeypatch.setattr(compiles, "begin_setup",
                        lambda name: setups.append(name) or real_begin(name))
    names, events = set(monitor.all_stats()), timeline()["events"]
    step(x, y)
    assert opened == ["train_step.prepare", "train_step.execute",
                      "train_step.writeback"]
    assert setups == []
    assert set(monitor.all_stats()) == names
    assert timeline()["events"] == events


def test_the_whole_process_counters_still_grow(timeline):
    """``setup.trace_s`` / ``setup.lower_s`` (``trace_lower_s`` reads
    them) are fed by the one listener, nested traces in their callers
    too; the program's own sums leave the caller's functions out."""
    before = monitor.all_stats()
    jax.jit(_fresh("the_callers"))(jnp.ones(3))
    step = _train_step()
    step(jnp.ones((2, 4)), jnp.ones((2, 2)))
    rep, after = timeline(), monitor.all_stats()

    def grew(name):
        return after.get(name, 0) - before.get(name, 0)

    for phase in ("trace", "lower"):
        whole, mine = grew(f"setup.{phase}_s"), rep["program"][phase]
        assert 0 < mine < whole, phase
        assert after[f"setup.program.{phase}_s"] == mine
    assert rep["program"]["compile"] > 0
    assert after["setup.first_call_s"] - before.get(
        "setup.first_call_s", 0) == pytest.approx(
            rep["owners"]["train_step.first_call"]["wall_s"])
