"""paddle_tpu.observability — unified tracing, metrics and post-mortems.

The reproduction's four telemetry islands (profiler host spans,
``utils.monitor`` gauges/histograms, the serving ``/metrics`` endpoint,
``fault.fired.*`` counters) correlate here:

- :func:`span` / :func:`begin_span` are the program's one host-span
  primitive: always a ``jax.profiler.TraceAnnotation("pt:<name>")`` (on
  the device trace's clock in any profiler capture), and an event in the
  ring when that is enabled.  ``profiler.RecordEvent`` wraps it.
  :mod:`.scopes` holds the names the program puts *inside* its
  executables (``jax.named_scope``, kernel names).
- :func:`enable` installs a process-wide :class:`Tracer` — a ring
  buffer of typed events (spans, eager op dispatches, compiles, worker
  restarts, checkpoint save/restore/fallback, serving dispatches,
  fault fires) with step/request correlation ids, exportable as
  chrome-trace JSON or JSONL.  Disabled (the default), every
  instrumented hot path pays one module-attribute None-check
  (``core.obs_hook``, same pattern as ``core.profiler_hook``).
- :func:`device_counter` is the one way out for a value inside a compiled
  step (an expert's load, the branch a ``cond`` took): ``jit.TrainStep``
  keeps what a step emitted in its device-resident carry, and
  :func:`read_device_counters` moves it into ``utils.monitor`` when
  somebody asks (:mod:`.device_counters`).  Host counters are
  ``utils.monitor``'s from the start; both are read from there.
- :func:`explain_compiles` attributes every XLA compile the static
  Executor, the jit layer and the inference Predictor performed to a
  named cause (new program version, new feed signature, new bucket,
  ...) with a diff against the previous signature — always on, counted
  per-cause in ``monitor``.
- :func:`setup_report` is the set-up timeline: every trace, lowering,
  cache load and compile jax made, with its function's name, self
  seconds and owner (the program's set-up spans ``setup.import``,
  ``setup.param_init``, ``train_step.first_call`` ..., or ``outside``),
  on the clock a caller stamps its own start with — always on.
- :func:`prometheus_text` / :func:`metrics_snapshot` /
  :func:`dump_metrics` export the whole monitor registry as Prometheus
  text exposition or JSON (``serving/http.py`` content-negotiates
  ``/metrics``; ``hapi.callbacks.MetricsDump`` +
  ``FLAGS_metrics_dump_path`` append JSONL from training).
- :func:`install_flight_recorder` arms the crash flight recorder:
  EnforceError / executor exceptions / SIGTERM / sys.excepthook dump
  the last N events + full metrics snapshot atomically for post-mortem.
- :func:`enable_perf` installs the runtime performance observatory
  (:mod:`.perf`): sampled step-time anatomy (host vs device lanes),
  per-device live/peak memory gauges, and a rolling
  predicted-vs-measured drift tracker surfaced by :func:`perf_report`.
- :func:`install_slo_monitor` (:mod:`.slo`) evaluates declarative
  :class:`SLORule` rolling-window burn-rate rules over the monitor
  registry; :func:`slo_status` drives ``/healthz`` degradation and the
  ``paddle_tpu_slo_*`` Prometheus gauges.
- :func:`install_exporter` (:mod:`.export`) spools this process's
  metrics + trace segments under ``FLAGS_obs_spool_dir`` for the fleet
  aggregator (:mod:`.fleet`): :func:`fleet_snapshot`,
  :func:`fleet_prometheus_text` (one exposition with ``proc`` labels),
  :func:`merged_chrome_trace` (one timeline, a lane per process),
  :func:`assemble_trace` (one distributed request's span tree) and
  :class:`FleetView` behind ``GET /admin/fleet``.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax

from ..core import obs_hook
from .compiles import (annotate_compile, explain_compiles,
                       record_compile, reset_compiles, setup_report)
from .device_counters import (collecting, device_counter,
                              read_device_counters)
from .export import (TelemetryExporter, get_exporter, install_exporter,
                     uninstall_exporter)
from .fleet import (FleetView, assemble_trace, collect_fleet_bundle,
                    fleet_prometheus_text, fleet_snapshot,
                    merged_chrome_trace, read_spool)
from .flight import (dump_flight, flight_recorder_path,
                     install_flight_recorder, uninstall_flight_recorder)
from .metrics import (build_info, dump_metrics, metrics_snapshot,
                      prometheus_text)
from .perf import (PerfObservatory, device_memory, disable_perf,
                   enable_perf, get_perf, perf_enabled, perf_report,
                   render_perf_report)
from .slo import (SLOMonitor, SLORule, get_slo_monitor,
                  install_slo_monitor, slo_status,
                  standard_serving_rules, uninstall_slo_monitor)
from .tracer import EVENT_KINDS, Tracer

__all__ = [
    "Tracer", "EVENT_KINDS", "enable", "disable", "enabled",
    "get_tracer", "emit", "span", "begin_span", "end_span", "counter",
    "set_step", "device_counter", "collecting", "read_device_counters",
    "record_compile", "explain_compiles", "reset_compiles",
    "annotate_compile", "setup_report",
    "prometheus_text", "metrics_snapshot", "dump_metrics", "build_info",
    "install_flight_recorder", "uninstall_flight_recorder",
    "dump_flight", "flight_recorder_path",
    "TelemetryExporter", "install_exporter", "uninstall_exporter",
    "get_exporter",
    "FleetView", "read_spool", "fleet_snapshot", "fleet_prometheus_text",
    "merged_chrome_trace", "assemble_trace", "collect_fleet_bundle",
    "PerfObservatory", "enable_perf", "disable_perf", "perf_enabled",
    "get_perf", "perf_report", "render_perf_report", "device_memory",
    "SLORule", "SLOMonitor", "install_slo_monitor",
    "uninstall_slo_monitor", "get_slo_monitor", "slo_status",
    "standard_serving_rules",
]


def enable(capacity: int = 8192, trace_ops: bool = True) -> Tracer:
    """Install (and return) a fresh process-wide tracer."""
    t = Tracer(capacity=capacity, trace_ops=trace_ops)
    obs_hook.set_tracer(t)
    return t


def disable() -> None:
    """Remove the tracer; instrumented sites return to the one
    None-check disabled path."""
    obs_hook.set_tracer(None)


def enabled() -> bool:
    return obs_hook.current() is not None


def get_tracer() -> Optional[Tracer]:
    return obs_hook.current()


def emit(kind: str, name: str, **args) -> None:
    """Emit one event on the active tracer; no-op when disabled."""
    t = obs_hook._tracer
    if t is not None:
        t.emit(kind, name, args=args or None)


def counter(name: str, delta=1, value=None) -> None:
    """Emit a counter-delta event; no-op when disabled."""
    t = obs_hook._tracer
    if t is not None:
        t.counter(name, delta, value=value)


def set_step(step: int) -> None:
    """Set the step correlation id on the active tracer (no-op when
    disabled)."""
    t = obs_hook._tracer
    if t is not None:
        t.set_step(step)


def begin_span(name: str, **args):
    """Open the program's one kind of host span; returns the token
    :func:`end_span` takes.  The span always enters
    ``jax.profiler.TraceAnnotation("pt:" + name)``, so it is in any
    profiler trace, on the device trace's clock, whether or not the
    ring is on (outside a capture that costs about a microsecond); with
    the ring enabled it is also recorded there, with its parent."""
    ann = jax.profiler.TraceAnnotation("pt:" + name)
    ann.__enter__()
    t = obs_hook._tracer
    return ann, t, (t.begin_span(name, **args) if t is not None else None)


def end_span(token) -> None:
    ann, t, sid = token
    if sid is not None:
        t.end_span(sid)
    ann.__exit__(None, None, None)


@contextlib.contextmanager
def span(name: str, **args):
    """:func:`begin_span` / :func:`end_span` as a context manager;
    yields the ring's span id (None with the ring off)."""
    token = begin_span(name, **args)
    try:
        yield token[2]
    finally:
        end_span(token)
