#!/usr/bin/env python
"""CI smoke gate for unified observability (sibling of bench_smoke.py /
chaos_smoke.py / serve_smoke.py).

Drives a short train + serve loop on CPU with tracing ON and exits
non-zero when the observability contract regresses:

1. **flight recorder** — an injected crash (``fault`` rule on
   ``executor.run``) must leave a readable flight-recorder dump that
   contains the injected fault event, the exception, and a full
   metrics snapshot.
2. **recompile attribution** — ``explain_compiles()`` must report ZERO
   unexplained compiles across the run; the executor's second feed
   signature must be attributed to ``new_feed_signature``; every
   Predictor compile in the serve loop must carry a named cause and
   their count must equal ``num_compiled_variants()`` (100%
   attribution).
3. **metrics export** — the HTTP ``/metrics`` endpoint must serve the
   Prometheus text exposition under an Accept: text/plain header
   (every line must parse) while keeping the JSON stats for default
   clients; the JSONL metrics dump must append parseable lines.
4. **trace integrity** — the chrome-trace export must satisfy the
   trace-event schema (name/ph/ts/pid/tid per event, dur on complete
   events) and carry span, op, compile and serving events.
5. **closed perf loop** — with the runtime performance observatory on
   (``observability.enable_perf``), the bench-MLP train loop must
   yield fenced device-time samples, a finite measured-vs-predicted
   drift per compile identity, and nonzero device-memory gauges.
6. **SLO burn-rate alerting** — a serving run with injected predictor
   latency must breach the declared p99 objective: ``/healthz``
   degrades to 503 with the breach reasons, the breach event and the
   degraded SLO block land in a flight-recorder dump (with the ring's
   drop accounting), the engine-labelled Prometheus gauges carry
   ``{engine="..."}``, and the endpoint recovers to 200 once the
   rolling window clears.
7. **disabled-path contract** — every new emitting site (Executor.run,
   the serving dispatch/decode steps) reaches the observatory through
   ``core.obs_hook`` module attributes only — no per-call
   ``observability`` import anywhere in the hot path; the fleet
   exporter tick rides the same contract (``obs_hook._export``
   None-check in Executor._run, InferenceEngine._execute and
   GenerationEngine._decode_step).
8. **fleet gate** — ``chaos_smoke --scenario fleet`` in a subprocess:
   a supervised generation replica spooling telemetry hard-crashes
   mid-traffic; the merged chrome-trace must carry aligned lanes for
   the parent and BOTH child incarnations plus the restart reason, and
   a pinned ``/generate`` trace must assemble into one connected span
   tree across the process hop.

Usage:  python tools/obs_smoke.py [--verbose]
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# metric_name{labels} value  — the text exposition grammar subset we emit
PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.eE+\-naif]+$")

_CHROME_PH = {"X", "i", "C", "B", "E", "M"}


def _check_chrome_schema(trace: dict, failures: list) -> None:
    evs = trace.get("traceEvents")
    if not isinstance(evs, list) or not evs:
        failures.append("chrome trace has no traceEvents")
        return
    for ev in evs:
        probs = []
        if not isinstance(ev.get("name"), str):
            probs.append("name")
        if ev.get("ph") not in _CHROME_PH:
            probs.append("ph")
        if not isinstance(ev.get("ts"), (int, float)) or ev["ts"] < 0:
            probs.append("ts")
        if not isinstance(ev.get("pid"), int):
            probs.append("pid")
        if not isinstance(ev.get("tid"), int):
            probs.append("tid")
        if ev.get("ph") == "X" and not (
                isinstance(ev.get("dur"), (int, float)) and ev["dur"] >= 0):
            probs.append("dur")
        if probs:
            failures.append(f"trace event violates schema ({probs}): "
                            f"{ev}")
            return


def _check_disabled_contract(failures: list) -> None:
    """Every new emitting site pays one obs_hook attribute check when
    the observatory is off — never a per-call observability import."""
    from paddle_tpu.serving.engine import InferenceEngine
    from paddle_tpu.serving.generation import GenerationEngine
    from paddle_tpu.static.executor import Executor
    for fn in (Executor.run, InferenceEngine._execute,
               GenerationEngine._decode_step):
        names = fn.__code__.co_names
        if "obs_hook" not in names:
            failures.append(f"{fn.__qualname__} lost its obs_hook "
                            f"disabled-path check")
        if "observability" in names:
            failures.append(f"{fn.__qualname__} imports observability "
                            f"on the hot path: {names}")
    # the fleet exporter tick is a hot-path site too: one _export
    # attribute None-check per dispatch/decode step when not spooling
    for fn in (InferenceEngine._execute, GenerationEngine._decode_step):
        if "_export" not in fn.__code__.co_names:
            failures.append(f"{fn.__qualname__} lost its obs_hook."
                            f"_export disabled-path check")
    # the perf anatomy lives in Executor._run (run is a thin span
    # wrapper) — it must reach the observatory through the obs_hook
    # attribute, not an import.  _run legitimately imports
    # observability on the COMPILE-ONLY path (record_compile), so the
    # per-call-import assertion above can't apply; the _perf attribute
    # access is the contract co_names CAN see.
    run_names = Executor._run.__code__.co_names
    if "obs_hook" not in run_names or "_perf" not in run_names:
        failures.append("Executor._run lost its obs_hook._perf "
                        "disabled-path check")
    # supervised-training heartbeat rides the same contract: one
    # module-attribute check per step, nothing more, when unsupervised
    if "_heartbeat" not in run_names:
        failures.append("Executor._run lost its obs_hook._heartbeat "
                        "disabled-path check")
    # ... and so does the fleet exporter's per-step tick
    if "_export" not in run_names:
        failures.append("Executor._run lost its obs_hook._export "
                        "disabled-path check")


def run_checks(verbose: bool = False) -> list:
    """Returns a list of failure strings (empty = healthy)."""
    import math
    import time

    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import inference, jit, observability as obs
    from paddle_tpu import optimizer, serving
    from paddle_tpu.jit import InputSpec
    from paddle_tpu.serving.http import Client, ServingServer
    from paddle_tpu.testing import fault
    from paddle_tpu.testing.chaos import make_dyadic_model
    from paddle_tpu.utils import monitor

    failures: list = []
    workdir = tempfile.mkdtemp(prefix="obs_smoke_")
    obs.reset_compiles()
    tracer = obs.enable(capacity=8192)
    # runtime performance observatory: fence every 2nd step so the
    # short smoke loop still yields device-time samples + memory gauges
    obs.enable_perf(sample_every=2)
    flight = os.path.join(workdir, "flight_record.json")
    obs.install_flight_recorder(path=flight)
    try:
        # -- short static train loop (two feed signatures) ----------------
        paddle.enable_static()
        try:
            paddle.seed(7)
            main = paddle.static.Program()
            with paddle.static.program_guard(main):
                x = paddle.static.data("x", [None, 8], "float32")
                y = paddle.static.data("y", [None, 1], "float32")
                h = paddle.static.nn.fc(x, 16, activation="relu")
                pred = paddle.static.nn.fc(h, 1)
                loss = F.mse_loss(pred, y)
                optimizer.SGD(learning_rate=0.01).minimize(loss)
            exe = paddle.static.Executor()
            rng = np.random.RandomState(0)

            def feed(n):
                return {"x": rng.randn(n, 8).astype(np.float32),
                        "y": rng.randn(n, 1).astype(np.float32)}

            for _ in range(4):
                exe.run(main, feed=feed(8), fetch_list=[loss])
            exe.run(main, feed=feed(4), fetch_list=[loss])

            # -- injected crash must leave a black box --------------------
            crashed = False
            with fault.inject("executor.run:count=1"):
                try:
                    exe.run(main, feed=feed(8), fetch_list=[loss])
                except fault.FaultInjected:
                    crashed = True
            if not crashed:
                failures.append("injected executor.run fault never fired")
            if not os.path.exists(flight):
                failures.append("no flight-recorder dump after the "
                                "injected crash")
            else:
                box = json.load(open(flight))
                kinds = {e.get("kind") for e in box.get("events", [])}
                if "fault" not in kinds:
                    failures.append(f"flight dump lacks the injected "
                                    f"fault event (kinds: {kinds})")
                if (box.get("exception") or {}).get("type") \
                        != "FaultInjected":
                    failures.append("flight dump lacks the exception")
                if not box.get("stats") or "histograms" not in box:
                    failures.append("flight dump lacks the metrics "
                                    "snapshot")
            exe.close()
        finally:
            paddle.disable_static()
            paddle.static.reset_default_programs()

        rep = obs.explain_compiles("executor")
        causes = [r["cause"] for r in rep["records"]]
        if "new_feed_signature" not in causes:
            failures.append(f"feed-signature recompile not attributed "
                            f"(causes: {causes})")

        # -- closed perf loop: drift per identity + memory gauges ---------
        perf_rep = obs.perf_report()
        idents = [r for r in perf_rep.get("identities", [])
                  if r["component"] == "executor" and r["sampled"]]
        if not idents:
            failures.append("perf observatory recorded no fenced "
                            "executor samples on the MLP run")
        else:
            r0 = idents[0]
            m, d = r0["measured"], r0["drift"]
            p50 = m.get("step_ms_p50")
            # sane-bounds gate: the measured step exists and is a
            # plausible wall time (1 us .. 10 s), and both drift axes
            # are computed and finite against the compile record's
            # prediction — the closed loop the ISSUE demands
            if not p50 or not 1e-3 <= p50 <= 1e4:
                failures.append(f"measured device step implausible: "
                                f"{p50} ms")
            for axis in ("step_time_pct", "peak_bytes_pct"):
                v = d.get(axis)
                if v is None or not math.isfinite(v):
                    failures.append(f"drift axis {axis} not computed "
                                    f"vs the prediction: {d}")
                elif v <= -99.9:
                    failures.append(f"{axis} drift {v:.1f}% — measured "
                                    f"~0 vs prediction (clock bug?)")
        if not monitor.get_stat("mem.live_bytes_total"):
            failures.append("device-memory gauges are zero after the "
                            "fenced samples")

        # -- serve loop: every compile must carry a named cause -----------
        paddle.seed(5)
        model = make_dyadic_model()
        prefix = os.path.join(workdir, "m")
        jit.save(model, prefix,
                 input_spec=[InputSpec([None, 8], "float32")])
        pred = inference.create_predictor(inference.Config(prefix))
        engine = serving.InferenceEngine(pred, max_batch_size=8,
                                         batch_timeout_ms=5.0,
                                         max_queue=64)
        engine.warmup()
        reqs = [(rng.randint(-8, 9, (int(rng.randint(1, 5)), 8)) / 4.0)
                .astype(np.float32) for _ in range(24)]
        futures = [engine.infer([r]) for r in reqs]
        for f in futures:
            f.result(60)

        prep = obs.explain_compiles("predictor")
        n_attr = len([r for r in prep["records"]
                      if r["cause"] != "unexplained"])
        if n_attr != pred.num_compiled_variants():
            failures.append(
                f"predictor compiles not 100% attributed: "
                f"{n_attr} records vs {pred.num_compiled_variants()} "
                f"variants")
        total = obs.explain_compiles()
        if total["unexplained"] != 0:
            failures.append(f"{total['unexplained']} unexplained "
                            f"compile(s): {total['by_cause']}")
        if total["total"] == 0:
            failures.append("no compiles recorded at all")

        # -- /metrics content negotiation + Prometheus grammar ------------
        srv = ServingServer(engine, port=0).start()
        try:
            client = Client(srv.url)
            js = client.metrics()
            if "counters" not in js or "latency_ms" not in js:
                failures.append("JSON /metrics lost the engine stats")
            text = client.metrics_text()
            bad = [ln for ln in text.splitlines()
                   if ln and not ln.startswith("#")
                   and not PROM_LINE.match(ln)]
            if bad:
                failures.append(f"unparseable Prometheus lines: "
                                f"{bad[:3]}")
            if "paddle_tpu_serving_latency_ms" not in text:
                failures.append("Prometheus output lacks the serving "
                                "latency summary")
            if "paddle_tpu_serving_engine_queue_depth" not in text:
                failures.append("Prometheus output lacks the engine "
                                "gauges")
        finally:
            srv.close()
            engine.close()

        # -- SLO breach under injected latency + /healthz degradation -----
        eng2 = serving.InferenceEngine(pred, max_batch_size=8,
                                       batch_timeout_ms=1.0,
                                       max_queue=64, name="slo")
        eng2.warmup()
        obs.install_slo_monitor([obs.SLORule(
            "serving.latency_ms", 60.0, window=1.5, quantile=0.99,
            name="p99_latency_ms")])
        obs.slo_status()                    # base window snapshot
        srv2 = ServingServer(eng2, port=0).start()
        try:
            client2 = Client(srv2.url)
            h = client2.healthz()
            if h.get("status") != "running" or h.get("slo") != "ok":
                failures.append(f"healthy probe should be running+slo "
                                f"ok, got {h}")
            # inject latency at the predictor: every dispatch now blows
            # the 60 ms objective
            orig_run = pred.run
            pred.run = lambda feeds: (time.sleep(0.15),
                                      orig_run(feeds))[1]
            try:
                for f in [eng2.infer([r]) for r in reqs[:5]]:
                    f.result(60)
            finally:
                pred.run = orig_run
            h = client2.healthz()
            if h.get("status") != "degraded":
                failures.append(f"/healthz did not degrade under the "
                                f"injected latency: {h}")
            elif "p99_latency_ms" not in h["slo"]["breached"]:
                failures.append(f"degraded /healthz lacks the breached "
                                f"rule: {h}")
            # the breach must land in the black box, with the ring's
            # drop accounting riding along
            slo_flight = os.path.join(workdir, "slo_flight.json")
            obs.dump_flight(slo_flight, reason="slo_breach")
            box2 = json.load(open(slo_flight))
            if (box2.get("slo") or {}).get("status") != "degraded":
                failures.append("flight dump lacks the degraded SLO "
                                "status block")
            if "events_dropped" not in (box2.get("obs") or {}):
                failures.append("flight dump lacks the tracer ring "
                                "drop accounting")
            if "slo" not in {e.get("kind") for e in tracer.events()}:
                failures.append("no slo breach event on the tracer")
            # recovery: fast traffic until the rolling window clears
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                for f in [eng2.infer([r]) for r in reqs[:2]]:
                    f.result(60)
                h = client2.healthz()
                if h.get("status") == "running":
                    break
                time.sleep(0.3)
            if h.get("status") != "running":
                failures.append(f"/healthz never recovered after the "
                                f"window cleared: {h}")
            # per-engine labelled gauges on the Prometheus exposition
            text2 = client2.metrics_text()
            if ('paddle_tpu_serving_engine_queue_depth{engine="slo"}'
                    not in text2):
                failures.append("Prometheus output lacks the "
                                "engine-labelled gauges")
            if "paddle_tpu_serving_engine_slo_requests" not in text2:
                failures.append("per-engine mirrored stats "
                                "(serving.engine.slo.*) missing")
        finally:
            srv2.close()
            eng2.close()
            obs.uninstall_slo_monitor()

        # -- JSONL metrics dump -------------------------------------------
        dump_path = os.path.join(workdir, "metrics.jsonl")
        obs.dump_metrics(dump_path)
        obs.dump_metrics(dump_path)
        lines = open(dump_path).read().splitlines()
        if len(lines) != 2 or not all(
                "stats" in json.loads(ln) for ln in lines):
            failures.append("metrics JSONL dump is malformed")

        # -- trace integrity ----------------------------------------------
        trace = tracer.chrome_trace()
        _check_chrome_schema(trace, failures)
        kinds = {e.get("kind") for e in tracer.events()}
        for want in ("span", "op", "compile", "serving", "fault", "perf"):
            if want not in kinds:
                failures.append(f"tracer recorded no '{want}' events "
                                f"(kinds: {kinds})")
        _check_disabled_contract(failures)

        # -- fleet gate: cross-process spool + trace, own interpreter -----
        # (the drill supervises real child processes and stages obs
        # flags into their env, so it gets a subprocess of its own
        # rather than fighting this process's live tracer)
        import subprocess
        fleet = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "chaos_smoke.py"),
             "--scenario", "fleet"],
            capture_output=True, text=True, timeout=600)
        if fleet.returncode != 0:
            tail = (fleet.stdout + fleet.stderr).strip().splitlines()
            failures.append(f"fleet observability gate failed: "
                            f"{tail[-6:]}")
        if verbose:
            print(f"events={len(tracer.events())} kinds={sorted(kinds)} "
                  f"compiles={total['by_cause']} "
                  f"flight={os.path.exists(flight)}")
        _ = monitor.get_stat("flight.dumps")
    finally:
        obs.uninstall_flight_recorder()
        obs.uninstall_slo_monitor()
        obs.disable_perf()
        obs.disable()
        shutil.rmtree(workdir, ignore_errors=True)
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[1])
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    failures = run_checks(verbose=args.verbose)
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print("obs_smoke: observability healthy (crash black box written, "
          "100% of compiles attributed, Prometheus + JSON /metrics "
          "served, trace schema valid, drift loop closed, SLO breach "
          "degraded + recovered /healthz, disabled path one-check, "
          "fleet spool + cross-process trace gate green)")
    return 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
