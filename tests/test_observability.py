"""Unified observability (ISSUE 5): structured tracer, recompile
attribution, Prometheus/JSON metrics export, crash flight recorder —
plus the satellite contracts (disabled-path overhead, histogram
quantile interpolation, RecordEvent robustness)."""
import json
import os
import re
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import nn, observability as obs, optimizer, profiler
from paddle_tpu.core import dispatch, obs_hook
from paddle_tpu.testing import fault
from paddle_tpu.utils import monitor


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.uninstall_flight_recorder()
    obs.disable_perf()
    obs.uninstall_slo_monitor()
    yield
    obs.uninstall_flight_recorder()
    obs.uninstall_slo_monitor()
    obs.disable_perf()
    obs.disable()


def _static_mlp(seed=7, in_dim=8):
    paddle.seed(seed)
    main = paddle.static.Program()
    with paddle.static.program_guard(main):
        x = paddle.static.data("x", [None, in_dim], "float32")
        y = paddle.static.data("y", [None, 1], "float32")
        h = paddle.static.nn.fc(x, 16, activation="relu")
        pred = paddle.static.nn.fc(h, 1)
        loss = F.mse_loss(pred, y)
        optimizer.SGD(learning_rate=0.01).minimize(loss)
    return main, loss


def _feed(n, in_dim=8, seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(n, in_dim).astype(np.float32),
            "y": rng.randn(n, 1).astype(np.float32)}


# ---------------------------------------------------------------- tracer --
def test_disabled_path_contract():
    """The tier-1 overhead contract: off means ONE module-attribute
    check, and the monitor hot paths never grew an observability hook."""
    assert obs_hook.current() is None
    assert not obs.enabled()
    # the hook read is a bare module-global load — nothing else
    assert obs_hook.current.__code__.co_names == ("_tracer",)
    # instrumented hot paths read obs_hook._tracer directly and never
    # import the observability package per call
    assert "obs_hook" in dispatch.apply.__code__.co_names
    assert "observability" not in dispatch.apply.__code__.co_names
    # stat_add / stat_observe hot paths are untouched (no tracer refs)
    for fn in (monitor.stat_add, monitor.stat_observe,
               monitor.StatRegistry.add, monitor.StatRegistry.observe,
               monitor._Histogram.observe):
        names = fn.__code__.co_names
        assert not any(n in ("obs_hook", "_tracer", "observability",
                             "tracer", "emit") for n in names), \
            f"{fn.__qualname__} grew an observability reference: {names}"
    # module-level helpers are no-ops while disabled
    obs.emit("instant", "nope")
    obs.counter("nope", 1)
    obs.set_step(3)
    with obs.span("nope"):
        pass


def test_tracer_records_ops_and_spans_with_nesting():
    t = obs.enable(capacity=256)
    x = paddle.to_tensor(np.ones((2, 3), np.float32))
    _ = (x * 2.0).sum()
    with profiler.RecordEvent("outer"):
        with profiler.RecordEvent("inner"):
            pass
    evs = t.events()
    kinds = {e["kind"] for e in evs}
    assert "op" in kinds and "span" in kinds
    spans = {e["name"]: e for e in evs if e["kind"] == "span"}
    assert spans["inner"]["parent"] == spans["outer"]["id"]
    assert spans["outer"].get("parent") is None
    ops = [e for e in evs if e["kind"] == "op"]
    assert all(e["dur"] >= 0 for e in ops)


def test_tracer_ring_buffer_bounds_memory():
    t = obs.enable(capacity=16)
    for i in range(100):
        t.emit("instant", f"e{i}")
    evs = t.events()
    assert len(evs) == 16
    assert evs[-1]["name"] == "e99"     # newest kept
    assert t.emitted == 100


def test_chrome_trace_schema_and_jsonl(tmp_path):
    t = obs.enable(capacity=256)
    with t.span("phase", detail=1):
        t.counter("c", 2)
        t.emit("instant", "marker")
    trace = t.chrome_trace()
    assert trace["traceEvents"]
    phs = set()
    for ev in trace["traceEvents"]:
        assert isinstance(ev["name"], str)
        assert ev["ph"] in {"X", "i", "C", "B", "E", "M"}
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int)
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
        phs.add(ev["ph"])
    assert {"X", "i", "C"} <= phs
    p = tmp_path / "trace.json"
    t.export_chrome_trace(str(p))
    json.load(open(p))                          # parses
    jsonl = t.export_jsonl(str(tmp_path / "t.jsonl"))
    rows = [json.loads(ln) for ln in jsonl.splitlines()]
    assert rows and all("kind" in r and "time" in r for r in rows)


def test_step_correlation_from_executor():
    t = obs.enable()
    paddle.enable_static()
    try:
        main, loss = _static_mlp()
        exe = paddle.static.Executor()
        for _ in range(3):
            exe.run(main, feed=_feed(8), fetch_list=[loss])
        exe.close()
    finally:
        paddle.disable_static()
        paddle.static.reset_default_programs()
    runs = [e for e in t.events()
            if e["kind"] == "span" and e["name"] == "executor.run"]
    assert [e["step"] for e in runs] == [1, 2, 3]


# ------------------------------------------------- RecordEvent satellite --
def test_record_event_end_without_begin_is_noop():
    r = profiler.RecordEvent("never")
    r.end()                     # was: TypeError on perf_counter() - None
    r.end()                     # idempotent too


def test_record_event_exception_safe_and_nested_under_tracer():
    t = obs.enable()
    with pytest.raises(ValueError):
        with profiler.RecordEvent("outer"):
            with profiler.RecordEvent("inner"):
                raise ValueError("boom")
    spans = {e["name"]: e for e in t.events() if e["kind"] == "span"}
    # both spans closed despite the raise, nesting preserved
    assert spans["inner"]["parent"] == spans["outer"]["id"]
    ev = profiler.RecordEvent("twice").begin()
    ev.end()
    ev.end()                    # second end is a no-op
    assert len([e for e in t.events() if e["name"] == "twice"]) == 1


# ------------------------------------------------- quantile satellite ----
def test_quantile_linear_interpolation_exact_at_bucket_edges():
    monitor.stat_reset("q.edge")
    # 4 samples in the [1, 10^(1/8)) bucket and 4 in [1000, 10^3.125)
    # (at 1200, so the max-clamp stays out of the way)
    for _ in range(4):
        monitor.stat_observe("q.edge", 1.0)
    for _ in range(4):
        monitor.stat_observe("q.edge", 1200.0)
    # rank at the lower bucket's LAST sample reads its upper edge exactly
    assert monitor.quantile("q.edge", 0.5) == pytest.approx(
        10.0 ** (1.0 / 8.0))
    # a rank just inside the upper bucket reads its lower edge (1000)
    assert monitor.quantile("q.edge", 0.5001) == pytest.approx(
        1000.0, rel=1e-3)
    # one sample deep into a 4-sample bucket: lo + (hi-lo)/4 by rank
    lo, hi = 1000.0, 10.0 ** 3.125
    assert monitor.quantile("q.edge", 5.0 / 8.0) == pytest.approx(
        lo + (hi - lo) * 0.25)
    monitor.stat_reset("q.edge")


def test_quantile_single_valued_bucket_is_exact():
    monitor.stat_reset("q.single")
    for _ in range(10):
        monitor.stat_observe("q.single", 3.7)
    # min==max clamp: every interior quantile is exactly the value
    for q in (0.1, 0.25, 0.5, 0.9, 0.99):
        assert monitor.quantile("q.single", q) == 3.7
    monitor.stat_reset("q.single")


def test_quantile_interpolates_by_rank_within_bucket():
    monitor.stat_reset("q.lin")
    # 8 samples in one bucket [10, 10^(9/8)): rank q*8 moves linearly
    # from lo to hi across the bucket
    for _ in range(8):
        monitor.stat_observe("q.lin", 10.5)
    lo, hi = 10.0, 10.0 ** (9.0 / 8.0)
    est = lo + (hi - lo) * 0.5
    # min/max clamp to the single observed value wins here
    assert monitor.quantile("q.lin", 0.5) == 10.5
    monitor.stat_reset("q.lin")
    # mixed values spread inside the same bucket: interpolation lands
    # between them, clamped within [vmin, vmax]
    for v in (10.1, 10.4, 10.8, 12.0):
        monitor.stat_observe("q.lin", v)
    q50 = monitor.quantile("q.lin", 0.5)
    assert 10.1 <= q50 <= 12.0
    assert q50 == pytest.approx(lo + (hi - lo) * (2.0 / 4.0))
    assert est  # silence linters: est documents the formula
    monitor.stat_reset("q.lin")


def test_quantile_extremes_and_empty_unchanged():
    monitor.stat_reset("q.ext")
    for v in (0.5, 2.0, 7.0):
        monitor.stat_observe("q.ext", v)
    assert monitor.quantile("q.ext", 0.0) == 0.5
    assert monitor.quantile("q.ext", 1.0) == 7.0
    monitor.stat_reset("q.ext")
    assert monitor.quantile("q.ext", 0.5) == 0.0


# ---------------------------------------------- recompile attribution ----
def test_executor_compile_attribution_causes():
    obs.reset_compiles()
    paddle.enable_static()
    try:
        main, loss = _static_mlp()
        exe = paddle.static.Executor()
        exe.run(main, feed=_feed(8), fetch_list=[loss])
        exe.run(main, feed=_feed(8, seed=1), fetch_list=[loss])  # cached
        exe.run(main, feed=_feed(4), fetch_list=[loss])
        # edit the program: another op bumps the version
        with paddle.static.program_guard(main):
            _ = paddle.static.nn.fc(main.feed_vars["x"], 4)
        exe.run(main, feed=_feed(4), fetch_list=[loss])
        exe.close()
    finally:
        paddle.disable_static()
        paddle.static.reset_default_programs()
    rep = obs.explain_compiles("executor")
    causes = [r["cause"] for r in rep["records"]]
    assert causes == ["first_compile", "new_feed_signature",
                      "new_program_version"]
    assert rep["unexplained"] == 0
    # the diff names what changed, old -> new
    sig_change = rep["records"][1]["changed"]
    assert "feed_signature" in sig_change
    assert monitor.get_stat("compiles.executor.new_feed_signature") >= 1


def test_predictor_compile_attribution_new_bucket(tmp_path):
    from paddle_tpu import inference, jit
    from paddle_tpu.jit import InputSpec

    obs.reset_compiles()
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(4, 2))
    prefix = str(tmp_path / "m")
    jit.save(model, prefix, input_spec=[InputSpec([None, 4], "float32")])
    pred = inference.create_predictor(inference.Config(prefix))
    for n in (1, 2, 3, 5):
        pred.run([np.zeros((n, 4), np.float32)])
    rep = obs.explain_compiles("predictor")
    causes = [r["cause"] for r in rep["records"]]
    assert causes[0] == "first_compile"
    assert set(causes[1:]) == {"new_bucket"}
    assert len(rep["records"]) == pred.num_compiled_variants()
    assert rep["unexplained"] == 0


def test_jit_compile_attribution():
    from paddle_tpu.jit import to_static

    obs.reset_compiles()

    @to_static
    def f(a, scale):
        return a * scale

    x = paddle.to_tensor(np.ones((2, 2), np.float32))
    f(x, 2.0)
    f(x, 2.0)               # cache hit: no new record
    f(x, 3.0)               # new static-leaf value
    rep = obs.explain_compiles("jit")
    causes = [r["cause"] for r in rep["records"]]
    assert causes == ["first_compile", "new_input_structure"]
    assert rep["unexplained"] == 0


# ------------------------------------------------------ metrics export ---
PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.eE+\-naif]+$")


def test_prometheus_text_parses_and_covers_registry():
    monitor.stat_reset()
    monitor.stat_add("obs.test.counter", 5)
    monitor.stat_observe("obs.test.lat", 2.5)
    text = obs.prometheus_text({"extra_gauge": 1.25})
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert PROM_LINE.match(line), f"bad exposition line: {line!r}"
    assert "paddle_tpu_obs_test_counter 5" in text
    assert 'paddle_tpu_obs_test_lat{quantile="0.5"} 2.5' in text
    assert "paddle_tpu_obs_test_lat_count 1" in text
    assert "paddle_tpu_extra_gauge 1.25" in text
    monitor.stat_reset()


def test_prometheus_name_collision_between_stat_and_histogram():
    monitor.stat_reset()
    monitor.stat_add("clash", 1)
    monitor.stat_observe("clash", 2.0)
    text = obs.prometheus_text()
    # the gauge renames rather than colliding with the summary family
    assert "paddle_tpu_clash_stat 1" in text
    assert "paddle_tpu_clash_count 1" in text
    monitor.stat_reset()


def test_metrics_snapshot_and_jsonl_dump(tmp_path):
    monitor.stat_add("snap.c", 2)
    snap = obs.metrics_snapshot()
    assert snap["stats"]["snap.c"] >= 2 and "histograms" in snap
    p = str(tmp_path / "metrics.jsonl")
    obs.dump_metrics(p, extra={"tag": "t1"})
    obs.dump_metrics(p, extra={"tag": "t2"})
    rows = [json.loads(ln) for ln in open(p).read().splitlines()]
    assert [r["tag"] for r in rows] == ["t1", "t2"]
    assert all("stats" in r for r in rows)
    with pytest.raises(ValueError):
        obs.dump_metrics()      # no path, no flag


def test_metrics_dump_rotation_bounds_file_growth(tmp_path):
    """Satellite (ISSUE 20): a long-lived replica's JSONL flight file
    rotates at FLAGS_metrics_dump_max_mb into .1..N, never one
    unbounded file — and the live file is the rename's LAST step."""
    old = paddle.get_flags(["metrics_dump_max_mb", "metrics_dump_keep"])
    # threshold of ~100 bytes: every dump line (several KB) trips it
    paddle.set_flags({"metrics_dump_max_mb": 1e-4,
                      "metrics_dump_keep": 2})
    p = str(tmp_path / "metrics.jsonl")
    try:
        for _ in range(4):
            obs.dump_metrics(p)
        assert os.path.exists(p)
        assert os.path.exists(p + ".1") and os.path.exists(p + ".2")
        assert not os.path.exists(p + ".3")     # keep=2 drops the rest
        # every generation is intact JSONL, one snapshot per line
        for path in (p, p + ".1", p + ".2"):
            rows = [json.loads(ln)
                    for ln in open(path).read().splitlines()]
            assert rows and all("stats" in r for r in rows)
        # the live file holds only the newest line
        assert len(open(p).read().splitlines()) == 1
    finally:
        paddle.set_flags(old)


def test_metrics_dump_no_rotation_when_flag_unset(tmp_path):
    p = str(tmp_path / "metrics.jsonl")
    for _ in range(3):
        obs.dump_metrics(p)
    assert len(open(p).read().splitlines()) == 3
    assert not os.path.exists(p + ".1")


def test_build_info_gauge_in_snapshot_and_prometheus():
    """Satellite (ISSUE 20): every process exports its version/backend
    identity — the fleet aggregator diffs it across replicas."""
    info = obs.build_info()
    assert info["jax"] and info["jaxlib"] and info["framework"]
    assert info["backend"] == "cpu"
    assert obs.metrics_snapshot()["build"] == info
    text = obs.prometheus_text()
    line = next(ln for ln in text.splitlines()
                if ln.startswith("paddle_tpu_build_info{"))
    assert PROM_LINE.match(line) and line.endswith(" 1")
    assert f'jax="{info["jax"]}"' in line
    assert f'backend="{info["backend"]}"' in line


def test_metrics_dump_callback(tmp_path):
    from paddle_tpu.hapi.callbacks import MetricsDump
    p = str(tmp_path / "fit_metrics.jsonl")
    cb = MetricsDump(path=p, save_freq=2)
    cb.on_epoch_end(0)
    cb.on_epoch_end(1)          # (1+1) % 2 == 0 -> dumps
    cb.on_train_end()
    rows = [json.loads(ln) for ln in open(p).read().splitlines()]
    assert [r["tag"] for r in rows] == ["epoch_end", "train_end"]
    assert rows[0]["epoch"] == 1


def test_http_metrics_content_negotiation(tmp_path):
    from paddle_tpu import inference, jit, serving
    from paddle_tpu.jit import InputSpec
    from paddle_tpu.serving.http import Client, ServingServer

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(4, 2))
    prefix = str(tmp_path / "m")
    jit.save(model, prefix, input_spec=[InputSpec([None, 4], "float32")])
    pred = inference.create_predictor(inference.Config(prefix))
    engine = serving.InferenceEngine(pred, max_batch_size=4,
                                     batch_timeout_ms=1.0)
    engine.warmup()
    engine.infer_sync([np.zeros((1, 4), np.float32)], timeout=30)
    with ServingServer(engine, port=0) as srv:
        client = Client(srv.url)
        js = client.metrics()           # default stays JSON
        assert js["counters"]["responses"] >= 1
        text = client.metrics_text()    # Accept: text/plain -> Prometheus
        assert text.startswith("# TYPE")
        assert "paddle_tpu_serving_engine_queue_depth" in text
        for line in text.splitlines():
            if line and not line.startswith("#"):
                assert PROM_LINE.match(line), line
    engine.close()


# --------------------------------------------------- flight recorder -----
def test_flight_recorder_on_executor_crash(tmp_path):
    t = obs.enable()
    flight = str(tmp_path / "flight.json")
    obs.install_flight_recorder(path=flight)
    paddle.enable_static()
    try:
        main, loss = _static_mlp()
        exe = paddle.static.Executor()
        exe.run(main, feed=_feed(8), fetch_list=[loss])
        with fault.inject("executor.run:count=1"):
            with pytest.raises(fault.FaultInjected):
                exe.run(main, feed=_feed(8), fetch_list=[loss])
        exe.close()
    finally:
        paddle.disable_static()
        paddle.static.reset_default_programs()
    box = json.load(open(flight))
    assert box["exception"]["type"] == "FaultInjected"
    assert "executor.run" in box["reason"]
    kinds = {e["kind"] for e in box["events"]}
    assert "fault" in kinds             # the injected fault is on tape
    assert "compile" in kinds
    assert box["stats"] and "histograms" in box
    assert box["compiles"]["total"] >= 1
    assert t.events()                   # tracer survived the dump


def test_flight_recorder_on_enforce_error(tmp_path):
    from paddle_tpu.core.enforce import InvalidArgumentError, enforce
    flight = str(tmp_path / "flight.json")
    obs.install_flight_recorder(path=flight)
    with pytest.raises(InvalidArgumentError):
        enforce(False, "observability test failure")
    box = json.load(open(flight))
    assert box["reason"].startswith("enforce.")
    assert box["exception"]["type"] == "InvalidArgumentError"
    assert "observability test failure" in box["exception"]["message"]


def test_flight_recorder_same_exception_dumps_once(tmp_path):
    flight = str(tmp_path / "flight.json")
    obs.install_flight_recorder(path=flight)
    monitor.stat_reset("flight.dumps")
    from paddle_tpu.core.enforce import InvalidArgumentError
    paddle.enable_static()
    try:
        main, loss = _static_mlp()
        exe = paddle.static.Executor()
        with fault.inject(
                "executor.run:count=1,exc=FaultInjected"):
            with pytest.raises(fault.FaultInjected):
                exe.run(main, feed=_feed(8), fetch_list=[loss])
        exe.close()
    finally:
        paddle.disable_static()
        paddle.static.reset_default_programs()
    assert monitor.get_stat("flight.dumps") == 1
    assert InvalidArgumentError  # imported for error-class visibility


def test_flight_recorder_distinct_exceptions_each_dump(tmp_path):
    # dedup must be per live OBJECT: a freed exception's recycled id
    # must not swallow dumps for later, distinct errors
    from paddle_tpu.core.enforce import InvalidArgumentError
    flight = str(tmp_path / "flight.json")
    obs.install_flight_recorder(path=flight)
    monitor.stat_reset("flight.dumps")
    for i in range(5):
        InvalidArgumentError(f"err {i}")     # constructed, then freed
    assert monitor.get_stat("flight.dumps") == 5
    box = json.load(open(flight))
    assert "err 4" in box["exception"]["message"]   # the LATEST error


def test_flight_recorder_traceback_upgrades_dump(tmp_path):
    # EnforceError dumps at construction (no stack yet); the re-report
    # from the raise boundary carries the traceback and must overwrite
    from paddle_tpu.core.enforce import NotFoundError
    flight = str(tmp_path / "flight.json")
    obs.install_flight_recorder(path=flight)
    monitor.stat_reset("flight.dumps")

    def deep():
        raise NotFoundError("lost thing")

    try:
        deep()
    except NotFoundError as e:
        obs_hook.crash_handler()(e, "executor.run(test)")
        # a third report of the same traceback'd object stays deduped
        obs_hook.crash_handler()(e, "executor.run(test)")
    assert monitor.get_stat("flight.dumps") == 2
    box = json.load(open(flight))
    tb = "".join(box["exception"]["traceback"])
    assert "deep" in tb                     # stack frames present


def test_end_span_with_foreign_id_does_not_drain_stack():
    t = obs.enable()
    outer = t.begin_span("outer")
    inner = t.begin_span("inner")
    t.end_span(inner)
    t.end_span(inner)       # double end: ignored
    t.end_span(99999)       # never-begun id: ignored
    assert not [e for e in t.events() if e["name"] == "outer"]
    t.end_span(outer)
    spans = {e["name"]: e for e in t.events() if e["kind"] == "span"}
    assert spans["inner"]["parent"] == spans["outer"]["id"]
    assert len([e for e in t.events() if e["name"] == "inner"]) == 1


def test_flight_recorder_uninstall_restores_hooks(tmp_path):
    prev_hook = sys.excepthook
    obs.install_flight_recorder(path=str(tmp_path / "f.json"))
    assert sys.excepthook is not prev_hook
    assert obs_hook.crash_handler() is not None
    assert obs.flight_recorder_path() == str(tmp_path / "f.json")
    obs.uninstall_flight_recorder()
    assert sys.excepthook is prev_hook
    assert obs_hook.crash_handler() is None
    assert obs.flight_recorder_path() is None


def test_manual_dump_flight(tmp_path):
    obs.enable()
    obs.emit("instant", "before_dump")
    p = str(tmp_path / "manual.json")
    out = obs.dump_flight(path=p, reason="manual-test")
    assert out == p
    box = json.load(open(p))
    assert box["reason"] == "manual-test"
    assert box["exception"] is None
    assert any(e["name"] == "before_dump" for e in box["events"])


# ----------------------------------------------------- serving events ----
def test_serving_events_carry_request_ids(tmp_path):
    from paddle_tpu import inference, jit, serving
    from paddle_tpu.jit import InputSpec

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(4, 2))
    prefix = str(tmp_path / "m")
    jit.save(model, prefix, input_spec=[InputSpec([None, 4], "float32")])
    pred = inference.create_predictor(inference.Config(prefix))
    engine = serving.InferenceEngine(pred, max_batch_size=4,
                                     batch_timeout_ms=1.0)
    engine.warmup()
    t = obs.enable()
    engine.infer_sync([np.zeros((2, 4), np.float32)], timeout=30)
    engine.drain(timeout=10)
    engine.close()
    sv = [e for e in t.events() if e["kind"] == "serving"]
    enq = [e for e in sv if e["name"] == "enqueue"]
    disp = [e for e in sv if e["name"] == "dispatch"]
    assert enq and disp
    rid = enq[0]["args"]["rid"]
    assert rid in disp[0]["args"]["rids"]       # request correlation
    assert disp[0]["args"]["ok"] is True
    assert disp[0]["dur"] >= 0


# -------------------------------------------- perf observatory (ISSUE 9) --
def test_perf_disabled_path_contract():
    """Every observatory emitting site pays one obs_hook attribute
    check when off — no observability import on any hot path.  The
    co_names assertions live in tools/obs_smoke.py (the CI gate);
    calling them here keeps the two from silently diverging."""
    assert obs_hook.current_perf() is None
    assert not obs.perf_enabled()
    assert obs_hook.current_perf.__code__.co_names == ("_perf",)
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import obs_smoke
    failures = []
    obs_smoke._check_disabled_contract(failures)
    assert failures == []
    assert obs.perf_report() == {"enabled": False}
    assert "disabled" in obs.render_perf_report()


def test_tracer_ring_drop_accounting(tmp_path):
    t = obs.enable(capacity=16)
    for i in range(100):
        t.emit("instant", f"e{i}")
    assert t.emitted == 100
    assert t.dropped == 84              # 100 emitted, 16 buffered
    assert t.high_watermark == 16
    rs = t.ring_stats()
    assert rs == {"events_emitted": 100, "events_dropped": 84,
                  "ring_capacity": 16, "ring_high_watermark": 16}
    # mirrored into monitor for the Prometheus exposition
    assert monitor.get_stat("obs.events_dropped") == 84
    assert monitor.get_stat("obs.ring_high_watermark") == 16
    text = obs.prometheus_text()
    assert "paddle_tpu_obs_events_dropped 84" in text
    # flight dumps carry the accounting so a truncated tape says so
    box = json.load(open(obs.dump_flight(
        path=str(tmp_path / "f.json"), reason="drop-test")))
    # the dump's own crash event lands in the full ring too: >= 84
    assert box["obs"]["events_dropped"] >= 84
    # an unwrapped ring reports a sub-capacity high watermark
    t2 = obs.enable(capacity=64)
    for i in range(5):
        t2.emit("instant", f"x{i}")
    assert t2.dropped == 0 and t2.high_watermark == 5


def test_perf_step_anatomy_and_memory_from_executor():
    t = obs.enable(capacity=512)
    obs.enable_perf(sample_every=2)
    monitor.stat_reset("perf.fences")
    paddle.enable_static()
    try:
        main, loss = _static_mlp()
        exe = paddle.static.Executor()
        for _ in range(5):
            exe.run(main, feed=_feed(8), fetch_list=[loss])
        exe.close()
    finally:
        paddle.disable_static()
        paddle.static.reset_default_programs()
    rep = obs.perf_report()
    assert rep["enabled"] and rep["sample_every"] == 2
    idents = [r for r in rep["identities"]
              if r["component"] == "executor"]
    assert len(idents) == 1
    r0 = idents[0]
    # the compiling run is excluded (its wall is compile time):
    # 5 runs -> 4 measured steps, fenced on steps 2 and 4
    assert r0["steps"] == 4 and r0["sampled"] == 2
    assert r0["host_ms_mean"] > 0
    assert r0["measured"]["step_ms_p50"] > 0
    assert r0["measured"]["peak_bytes"] > 0
    assert r0["predicted"]["peak_bytes"] > 0
    assert np.isfinite(r0["drift"]["step_time_pct"])
    assert np.isfinite(r0["drift"]["peak_bytes_pct"])
    # histograms: host lane every step, device lane on fences only
    assert monitor.histogram_summary("step.host_ms")["count"] >= 4
    assert monitor.histogram_summary("step.device_ms")["count"] >= 2
    assert monitor.get_stat("perf.fences") == 2
    assert monitor.get_stat("mem.live_bytes_total") > 0
    # tracer lanes: host feed/dispatch + device events, truthful
    # intervals (feed and dispatch are separated by cache-lookup work)
    perf_evs = [e for e in t.events() if e["kind"] == "perf"]
    names = {e["name"] for e in perf_evs}
    assert {"step.host.feed", "step.host.dispatch",
            "step.device"} <= names
    dev = [e for e in perf_evs if e["name"] == "step.device"]
    assert all(e["dur"] > 0 for e in dev)
    # the rendered report names the identity
    assert "executor#" in obs.render_perf_report()


def test_drift_math_hand_computed():
    from paddle_tpu.observability.perf import (_IdentityPerf,
                                               _predicted_step_s)
    idp = _IdentityPerf("executor", 7)
    idp.steps = 10
    idp.sampled = 3
    idp.host_sum_s = 0.05               # 5 ms/step mean
    idp.device_s.extend([0.002, 0.004, 0.003])
    idp.peak_bytes = 1500
    idp.predicted = {"predicted_step_s": 0.002, "peak_bytes": 1000}
    d = idp.drift()
    assert d["host_ms_mean"] == pytest.approx(5.0)
    assert d["measured"]["step_ms_p50"] == pytest.approx(3.0)
    assert d["measured"]["step_ms_min"] == pytest.approx(2.0)
    assert d["measured"]["step_ms_max"] == pytest.approx(4.0)
    # (3 ms measured - 2 ms predicted) / 2 ms = +50%
    assert d["drift"]["step_time_pct"] == pytest.approx(50.0)
    # (1500 - 1000) / 1000 = +50%
    assert d["drift"]["peak_bytes_pct"] == pytest.approx(50.0)
    # a sharded prediction compares per-shard, not per-fleet
    idp.predicted = {"predicted_step_s": 0.002, "peak_bytes": 4000,
                     "peak_bytes_per_shard": 750}
    d = idp.drift()
    assert d["drift"]["peak_bytes_pct"] == pytest.approx(100.0)
    # no prediction -> drift axes absent, never fabricated
    idp.predicted = None
    assert idp.drift()["drift"] == {}
    # predicted step re-derived from the roofline when the record
    # carries only FLOPs/traffic
    from paddle_tpu.static.analysis.cost import CHIP_SPECS
    spec = CHIP_SPECS["cpu"]
    est = _predicted_step_s({"flops": spec.peak_flops,
                             "min_traffic_bytes": 0})
    assert est == pytest.approx(1.0)    # exactly one peak-FLOPs second


def test_quantile_from_counts_windowed_delta():
    monitor.stat_reset("q.win")
    for _ in range(10):
        monitor.stat_observe("q.win", 1.0)
    base = monitor.histogram_raw("q.win")
    for _ in range(10):
        monitor.stat_observe("q.win", 1200.0)
    cur = monitor.histogram_raw("q.win")
    counts = [a - b for a, b in zip(cur["counts"], base["counts"])]
    n = cur["count"] - base["count"]
    assert n == 10
    # the window sees ONLY the second batch: its p50 sits in the
    # [1000, 10^3.125) bucket, rank-interpolated to the bucket middle
    lo, hi = 1000.0, 10.0 ** 3.125
    q50 = monitor.quantile_from_counts(counts, n, 0.5)
    assert q50 == pytest.approx(lo + (hi - lo) * 0.5)
    # whereas the cumulative histogram's p50 still reads batch A
    assert monitor.quantile("q.win", 0.4) < 100.0
    assert monitor.quantile_from_counts(counts, 0, 0.5) == 0.0
    monitor.stat_reset("q.win")


# --------------------------------------------------- SLO monitors --------
def test_slo_rule_validation():
    with pytest.raises(ValueError):
        obs.SLORule("m", objective=0.0)
    with pytest.raises(ValueError):
        obs.SLORule("m", 1.0, window=0.0)
    with pytest.raises(ValueError):
        obs.SLORule("m", 1.0, burn_rate=0.0)
    with pytest.raises(ValueError):
        obs.SLORule("m", 1.0, quantile=1.0)
    with pytest.raises(ValueError):
        obs.SLOMonitor([])
    with pytest.raises(ValueError):
        obs.SLOMonitor([obs.SLORule("a", 1.0, name="dup"),
                        obs.SLORule("b", 1.0, name="dup")])
    rules = obs.standard_serving_rules(p99_latency_ms=50.0,
                                       shed_ratio=0.01)
    assert [r.name for r in rules] == ["serving_p99_latency_ms",
                                       "serving_shed_ratio"]


def test_slo_quantile_window_breach_and_recovery():
    t = obs.enable(capacity=128)
    monitor.stat_reset("slo.t.lat")
    monitor.stat_reset("slo.breaches")
    m = obs.install_slo_monitor([obs.SLORule(
        "slo.t.lat", 10.0, window=5.0, quantile=0.5, name="lat")])
    # first poll: no base snapshot -> the whole cumulative history is
    # NOT evaluated as a window; no data = healthy
    st = m.poll(now=100.0)
    assert st["status"] == "ok"
    assert st["rules"][0]["measured"] is None
    for _ in range(4):
        monitor.stat_observe("slo.t.lat", 100.0)
    st = m.poll(now=101.0)
    assert st["status"] == "degraded" and st["breached"] == ["lat"]
    assert st["rules"][0]["measured"] > 10.0
    assert st["rules"][0]["burn"] > 1.0
    assert st["reasons"] and "lat" in st["reasons"][0]
    assert monitor.get_stat("slo.breaches") == 1
    assert monitor.get_stat("slo.lat.breached") == 1
    assert monitor.get_stat("slo.degraded") == 1
    # still breached while the burst stays inside the 5 s window
    st = m.poll(now=103.0)
    assert st["status"] == "degraded"
    assert monitor.get_stat("slo.breaches") == 1    # no re-fire
    # once every base candidate postdates the burst: no data -> recover
    st = m.poll(now=109.0)
    assert st["status"] == "ok"
    assert monitor.get_stat("slo.lat.breached") == 0
    evs = [e for e in t.events() if e["kind"] == "slo"]
    assert [e["name"] for e in evs] == ["breach", "recover"]
    assert evs[0]["args"]["rule"] == "lat"
    # status() replays the last poll without re-snapshotting
    assert m.status()["status"] == "ok"
    assert obs.slo_status(poll=False)["status"] == "ok"
    monitor.stat_reset("slo.t.lat")


def test_slo_burn_rate_threshold():
    monitor.stat_reset("slo.t.burn")
    m = obs.install_slo_monitor([obs.SLORule(
        "slo.t.burn", 10.0, window=5.0, quantile=0.5, burn_rate=2.0,
        name="fast_burn")])
    m.poll(now=10.0)
    for _ in range(4):
        monitor.stat_observe("slo.t.burn", 15.0)    # burn ~1.5x
    st = m.poll(now=11.0)
    r = st["rules"][0]
    assert r["measured"] > 10.0                     # over objective...
    assert 1.0 < r["burn"] < 2.0
    assert not r["breached"]                        # ...but a slow burn
    assert st["status"] == "ok"
    for _ in range(16):
        monitor.stat_observe("slo.t.burn", 100.0)   # now a fast burn
    st = m.poll(now=12.0)
    assert st["rules"][0]["breached"]
    monitor.stat_reset("slo.t.burn")


def test_slo_ratio_and_rate_rules():
    monitor.stat_reset("slo.t.shed")
    monitor.stat_reset("slo.t.reqs")
    monitor.stat_reset("slo.t.evts")
    m = obs.install_slo_monitor([
        obs.SLORule("slo.t.shed", 0.10, window=60.0, per="slo.t.reqs",
                    name="shed_ratio"),
        obs.SLORule("slo.t.evts", 1.0, window=60.0, name="evt_rate"),
    ])
    monitor.stat_add("slo.t.reqs", 100)     # predates the base snapshot
    m.poll(now=0.0)
    monitor.stat_add("slo.t.shed", 5)
    monitor.stat_add("slo.t.reqs", 40)      # windowed ratio: 5/40
    monitor.stat_add("slo.t.evts", 10)      # windowed rate: 10/2s = 5/s
    st = m.poll(now=2.0)
    ratio, rate = st["rules"]
    assert ratio["kind"] == "ratio"
    assert ratio["measured"] == pytest.approx(0.125)
    assert ratio["breached"]
    assert rate["kind"] == "rate"
    assert rate["measured"] == pytest.approx(5.0)
    assert rate["breached"]
    # shed events against ZERO denominator traffic burn unambiguously:
    # take a clean base past the earlier traffic, then shed with no
    # requests inside the evaluated window
    m.poll(now=4.0)
    monitor.stat_add("slo.t.shed", 3)
    st = m.poll(now=70.0)               # base = the now-4.0 snapshot
    # non-finite measurements serialize as the JSON-safe string "inf"
    # (the status dict lands verbatim in /perf bodies and JSONL lines)
    assert st["rules"][0]["measured"] == "inf"
    assert st["rules"][0]["breached"]
    json.dumps(st)      # the whole status stays strict-JSON-parseable
    # an idle window (no deltas at all) is healthy, not unknown
    st = m.poll(now=200.0)
    assert st["status"] == "ok"
    assert st["rules"][0]["measured"] is None
    for n in ("slo.t.shed", "slo.t.reqs", "slo.t.evts"):
        monitor.stat_reset(n)


def test_slo_status_without_monitor_is_ok():
    assert obs.get_slo_monitor() is None
    st = obs.slo_status()
    assert st == {"installed": False, "status": "ok", "rules": [],
                  "breached": [], "reasons": []}


def test_healthz_slo_degradation_and_recovery(tmp_path):
    import time as _time

    from paddle_tpu import inference, jit, serving
    from paddle_tpu.jit import InputSpec
    from paddle_tpu.serving.http import Client, ServingServer

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(4, 2))
    prefix = str(tmp_path / "m")
    jit.save(model, prefix, input_spec=[InputSpec([None, 4], "float32")])
    pred = inference.create_predictor(inference.Config(prefix))
    engine = serving.InferenceEngine(pred, max_batch_size=4,
                                     batch_timeout_ms=1.0, name="h")
    engine.warmup()
    monitor.stat_reset("slo.h.lat")
    obs.install_slo_monitor([obs.SLORule(
        "slo.h.lat", 10.0, window=0.5, quantile=0.5, name="h_lat")])
    obs.slo_status()                    # base snapshot
    with ServingServer(engine, port=0) as srv:
        client = Client(srv.url)
        h = client.healthz()
        assert h["status"] == "running" and h["slo"] == "ok"
        for _ in range(4):
            monitor.stat_observe("slo.h.lat", 500.0)
        h = client.healthz()            # probe polls -> degraded 503
        assert h["status"] == "degraded"
        assert h["engine_state"] == "running"   # liveness unaffected
        assert h["slo"]["breached"] == ["h_lat"]
        assert any("h_lat" in r for r in h["slo"]["reasons"])
        # the breach ages out of the 0.5 s window -> 200 again
        deadline = _time.monotonic() + 5.0
        while _time.monotonic() < deadline:
            _time.sleep(0.2)
            h = client.healthz()
            if h["status"] == "running":
                break
        assert h["status"] == "running" and h["slo"] == "ok"
        # /perf endpoint: report disabled, SLO block present
        p = client.perf()
        assert p["perf"] == {"enabled": False}
        assert p["slo"]["installed"] is True
    engine.close()
    monitor.stat_reset("slo.h.lat")


# ------------------------------------------- per-engine serving labels ----
def test_engine_name_mirrors_stats_and_labels_gauges(tmp_path):
    from paddle_tpu import inference, jit, serving
    from paddle_tpu.jit import InputSpec
    from paddle_tpu.serving.http import Client, ServingServer

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(4, 2))
    prefix = str(tmp_path / "m")
    jit.save(model, prefix, input_spec=[InputSpec([None, 4], "float32")])
    pred = inference.create_predictor(inference.Config(prefix))
    monitor.stat_reset("serving.engine.bert.requests")
    engine = serving.InferenceEngine(pred, max_batch_size=4,
                                     batch_timeout_ms=1.0, name="bert")
    engine.warmup()
    engine.infer_sync([np.zeros((2, 4), np.float32)], timeout=30)
    assert engine.stats()["engine"] == "bert"
    # named engines mirror their counters under serving.engine.<name>.*
    assert monitor.get_stat("serving.engine.bert.requests") == 1
    assert monitor.get_stat("serving.engine.bert.batches") == 1
    assert monitor.histogram_summary(
        "serving.engine.bert.latency_ms")["count"] == 1
    with ServingServer(engine, port=0) as srv:
        text = Client(srv.url).metrics_text()
        assert ('paddle_tpu_serving_engine_queue_depth{engine="bert"}'
                in text)
        assert "paddle_tpu_serving_engine_bert_requests 1" in text
        for line in text.splitlines():
            if line and not line.startswith("#"):
                assert PROM_LINE.match(line), line
    engine.close()
    # an unnamed engine keeps the unprefixed layout (no mirror)
    e2 = serving.InferenceEngine(pred, max_batch_size=4,
                                 batch_timeout_ms=1.0)
    assert e2.name is None and e2.stats()["engine"] is None
    e2.close()


def test_metrics_snapshot_carries_slo_perf_and_drop_blocks():
    t = obs.enable(capacity=32)
    obs.enable_perf(sample_every=0)     # host anatomy only, no fences
    monitor.stat_reset("slo.t.snap")
    m = obs.install_slo_monitor([obs.SLORule(
        "slo.t.snap", 1.0, window=5.0, name="snap_rate")])
    m.poll(now=1.0)
    t.emit("instant", "x")
    snap = obs.metrics_snapshot()
    # one JSONL line is a complete offline record: distributions AND
    # objective state, not just counters
    assert "histograms" in snap and "stats" in snap
    assert snap["obs"]["ring_capacity"] == 32
    assert snap["slo"]["installed"] is True
    assert snap["slo"]["rules"][0]["name"] == "snap_rate"
    assert snap["perf"]["enabled"] is True
    monitor.stat_reset("slo.t.snap")


def test_prometheus_extra_gauges_join_families_one_type_line():
    monitor.stat_reset("promfam.reqs")
    monitor.stat_add("promfam.reqs", 3)
    try:
        text = obs.prometheus_text({
            'promfam_reqs{engine="a"}': 1,
            'promfam_reqs{engine="b"}': 2,
            "promfam_reqs": 9,          # duplicate of the registry stat
        })
    finally:
        monitor.stat_reset("promfam.reqs")
    lines = text.splitlines()
    fam = [i for i, ln in enumerate(lines)
           if ln.startswith("paddle_tpu_promfam_reqs")
           or ln == "# TYPE paddle_tpu_promfam_reqs gauge"]
    # exactly one TYPE line, and the whole family is contiguous —
    # strict scrapers reject repeated or split metric families
    assert sum(ln.startswith("# TYPE paddle_tpu_promfam_reqs")
               for ln in lines) == 1
    assert fam == list(range(fam[0], fam[0] + len(fam)))
    assert 'paddle_tpu_promfam_reqs{engine="a"} 1' in lines
    assert 'paddle_tpu_promfam_reqs{engine="b"} 2' in lines
    # the unlabeled extra duplicates the registry series: skipped, the
    # registry's value wins
    assert "paddle_tpu_promfam_reqs 3" in lines
    assert "paddle_tpu_promfam_reqs 9" not in lines


def test_slo_explicit_per_wins_over_histogram_metric():
    # quantile= and per= contradict each other: rejected up front
    with pytest.raises(ValueError):
        obs.SLORule("m", 1.0, quantile=0.99, per="n")
    monitor.stat_reset("slo.t.hist_ms")
    monitor.stat_reset("slo.t.den")
    m = obs.install_slo_monitor([obs.SLORule(
        "slo.t.hist_ms", 0.5, window=60.0, per="slo.t.den",
        name="hist_ratio")])
    m.poll(now=0.0)
    for _ in range(4):                  # 4 windowed observations...
        monitor.stat_observe("slo.t.hist_ms", 100.0)
    monitor.stat_add("slo.t.den", 16)   # ...per 16 denominator events
    st = m.poll(now=1.0)
    r = st["rules"][0]
    # the explicit denominator makes this a ratio of observation
    # counts (4/16), NOT a p99 of the 100 ms samples
    assert r["kind"] == "ratio"
    assert r["measured"] == pytest.approx(0.25)
    assert not r["breached"]
    for n in ("slo.t.hist_ms", "slo.t.den"):
        monitor.stat_reset(n)


def test_slo_uninstall_clears_rule_gauges():
    monitor.stat_reset("slo.t.stale")
    m = obs.install_slo_monitor([obs.SLORule(
        "slo.t.stale", 1.0, window=60.0, name="stale_rate")])
    m.poll(now=0.0)
    monitor.stat_add("slo.t.stale", 1000)
    st = m.poll(now=1.0)
    assert st["rules"][0]["breached"]
    assert monitor.get_stat("slo.stale_rate.breached") == 1
    # a dashboard must not keep seeing the breach after the monitor
    # that produced it is gone
    obs.uninstall_slo_monitor()
    assert monitor.get_stat("slo.stale_rate.breached") == 0
    assert monitor.get_stat("slo.stale_rate.burn") == 0
    assert monitor.get_stat("slo.degraded") == 0
    monitor.stat_reset("slo.t.stale")


def test_perf_identity_split_per_feed_signature():
    # two feed shapes of ONE program are two executables with two
    # predictions — their step times must not mix in one rolling
    # window, or drift compares shape A's measurement against shape
    # B's prediction
    obs.enable_perf(sample_every=0)
    paddle.enable_static()
    try:
        main, loss = _static_mlp()
        exe = paddle.static.Executor()
        for n in (4, 16):
            for _ in range(3):
                exe.run(main, feed=_feed(n), fetch_list=[loss])
        exe.close()
    finally:
        paddle.disable_static()
        paddle.static.reset_default_programs()
    idents = [r for r in obs.perf_report()["identities"]
              if r["component"] == "executor"]
    assert len(idents) == 2
    assert all(r["steps"] == 2 for r in idents)     # compile excluded
    names = {str(r["identity"]) for r in idents}
    assert any("[4x8;4x1]" in n for n in names), names
    assert any("[16x8;16x1]" in n for n in names), names


def test_slo_min_count_gates_quantile_windows():
    with pytest.raises(ValueError):
        obs.SLORule("m", 1.0, min_count=0)
    monitor.stat_reset("slo.t.mc_ms")
    m = obs.install_slo_monitor([obs.SLORule(
        "slo.t.mc_ms", 1.0, window=60.0, quantile=0.99,
        min_count=5, name="mc")])
    m.poll(now=0.0)
    for _ in range(4):
        monitor.stat_observe("slo.t.mc_ms", 100.0)
    st = m.poll(now=1.0)
    # 4 observations < min_count: no data, healthy — a fresh monitor
    # can't degrade /healthz off a handful of samples
    assert st["rules"][0]["measured"] is None
    assert st["status"] == "ok"
    monitor.stat_observe("slo.t.mc_ms", 100.0)
    st = m.poll(now=2.0)
    assert st["rules"][0]["measured"] is not None
    assert st["rules"][0]["breached"]
    assert monitor.get_stat("slo.mc.measured") > 0
    # window goes idle: the measured gauge is dropped, not frozen at
    # the breach-level value
    st = m.poll(now=200.0)
    assert st["rules"][0]["measured"] is None
    assert monitor.get_stat("slo.mc.measured") == 0
    monitor.stat_reset("slo.t.mc_ms")


def test_resolve_perf_chip_rejects_unknown_flag():
    from paddle_tpu.core.flags import get_flag, set_flags
    from paddle_tpu.static.analysis.cost import resolve_perf_chip
    old = get_flag("perf_chip")
    try:
        set_flags({"perf_chip": "v5"})      # typo for v5p
        with pytest.raises(ValueError, match="perf_chip"):
            resolve_perf_chip()
        set_flags({"perf_chip": ""})
        assert resolve_perf_chip() == "cpu"     # from the device
    finally:
        set_flags({"perf_chip": old})


@pytest.mark.parametrize("kind,want", [("TPU v5 lite", "v5e"),
                                       ("TPU v4", "v4"), ("TPU v99", None)])
def test_resolve_perf_chip_from_device_kind(monkeypatch, kind, want):
    """A TPU is priced by its device_kind; a kind with no spec is an
    error, never some other chip's roofline."""
    import types

    import jax
    from paddle_tpu.static.analysis.cost import resolve_perf_chip
    monkeypatch.setattr(jax, "devices", lambda *a: [types.SimpleNamespace(
        platform="tpu", device_kind=kind)])
    if want is None:
        with pytest.raises(ValueError, match="TPU v99"):
            resolve_perf_chip()
    else:
        assert resolve_perf_chip() == want


def test_engine_label_escapes_prometheus_value():
    from paddle_tpu.serving.http import _engine_label
    assert _engine_label(None) == "" and _engine_label("") == ""
    assert _engine_label("bert") == '{engine="bert"}'
    assert _engine_label('a"b\\c\nd') == r'{engine="a\"b\\c\nd"}'


def test_perf_report_cli_multiline_jsonl_and_flight(tmp_path, capsys):
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import perf_report as cli

    obs.enable(capacity=64)
    obs.enable_perf(sample_every=0)
    monitor.stat_reset("slo.t.cli")
    m = obs.install_slo_monitor([obs.SLORule(
        "slo.t.cli", 1.0, window=60.0, per="slo.t.cli_den",
        name="cli_ratio")])
    m.poll(now=0.0)
    jsonl = str(tmp_path / "metrics.jsonl")
    obs.dump_metrics(jsonl)
    # breach with zero denominator: measured serializes as "inf"
    monitor.stat_add("slo.t.cli", 3)
    m.poll(now=1.0)
    # gauges peg at a finite sentinel instead of going stale (a
    # dashboard must not show a healthy burn while breached=1)
    assert monitor.get_stat("slo.cli_ratio.burn") == 1e12
    assert monitor.get_stat("slo.cli_ratio.measured") == 1e12
    obs.dump_metrics(jsonl)             # line 2: every line is JSON-{
    rc = cli.main([jsonl])              # regression: multi-line JSONL
    out = capsys.readouterr().out       # was misread as ONE document
    assert rc == 1                      # breached in the embedded eval
    assert "perf observatory" in out
    assert "measured inf" in out and "BREACHED" in out
    # a flight dump renders through the same loader, and stays strict
    # JSON even with the inf breach in flight — the breach tracer
    # event and the embedded status must never serialize the bare
    # Infinity token (jq / JSON.parse / chrome trace viewer reject it)
    flight = str(tmp_path / "box.json")
    obs.dump_flight(flight, reason="test")
    raw = open(flight).read()
    assert "Infinity" not in raw
    assert "Infinity" not in json.dumps(obs_hook._tracer.chrome_trace())
    assert cli.main([flight]) == 1
    assert "perf observatory" in capsys.readouterr().out
    # a source whose observatory was off is "no report" for the exit
    # contract — a CI gate must not pass with the observatory disabled
    disabled = str(tmp_path / "disabled.json")
    with open(disabled, "w") as f:
        json.dump({"perf": {"enabled": False}}, f)
    assert cli.main([disabled]) == 1
    capsys.readouterr()
    for n in ("slo.t.cli", "slo.t.cli_den"):
        monitor.stat_reset(n)


def test_perf_identity_lru_cap():
    from paddle_tpu.observability import perf as perf_mod
    p = obs.enable_perf(sample_every=0)     # host anatomy only
    for i in range(perf_mod._MAX_IDENTITIES + 10):
        p.step("executor", f"id{i}", 0.0, 0.0, 0.0, 0.0, None)
    t = p.report()["totals"]
    # stale identities are LRU-evicted, not retained forever (the
    # Executor drops stale-version cache entries; their perf state
    # must not accumulate across a long-lived process)
    assert t["identities"] == perf_mod._MAX_IDENTITIES
    assert t["identities_evicted"] == 10


def test_serving_step_histogram_mirrors_per_engine():
    p = obs.enable_perf(sample_every=0)
    for n in ("perf.serving.dispatch_ms", "perf.serving.bert.dispatch_ms"):
        monitor.stat_reset(n)
    p.serving_step("bert", "dispatch", 0.01)
    p.serving_step(None, "dispatch", 0.02)          # unnamed: no mirror
    assert monitor.histogram_summary(
        "perf.serving.dispatch_ms")["count"] == 2
    assert monitor.histogram_summary(
        "perf.serving.bert.dispatch_ms")["count"] == 1
    for n in ("perf.serving.dispatch_ms", "perf.serving.bert.dispatch_ms"):
        monitor.stat_reset(n)


# ------------------------------------------------------------ CI gate ----
def test_obs_smoke_in_process():
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import obs_smoke
    failures = obs_smoke.run_checks()
    assert failures == []
