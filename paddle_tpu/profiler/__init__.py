"""paddle.profiler — tracing and host-op profiling.

Reference: paddle/fluid/platform/profiler.h:127 (RecordEvent),
:210-213 (EnableProfiler/DisableProfiler), python/paddle/profiler/
profiler.py (the 2.x Profiler class), tools/timeline.py:131 (chrome
trace export).

TPU-native design: device-side timing belongs to XLA — ``Profiler``
drives ``jax.profiler`` traces (viewable in TensorBoard/Perfetto, the
timeline.py analog), and :class:`RecordEvent` spans go through
``observability.begin_span`` so framework phases appear as named
``pt:`` spans on the host track of the same trace.  Host-side per-op timing for
eager mode hooks the single dispatch point (core/dispatch.apply) — the
analog of the reference's RecordEvent inside Tracer::TraceOp — and
``summary()`` prints the top-k table the reference prints on
DisableProfiler.
"""
from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from enum import Enum
from typing import Dict, List, Optional, Tuple

import jax

from ..core import profiler_hook
from ..observability import begin_span, end_span

__all__ = [
    "Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
    "export_chrome_tracing", "load_profiler_result", "start_profiler",
    "stop_profiler", "profiler_guard",
]


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1   # accepted for parity
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class RecordEvent:
    """Named span (reference: platform/profiler.h:127 RecordEvent).

    Context manager or ``begin()``/``end()`` pair: a thin wrapper over
    the program's one span primitive (``observability.begin_span``: a
    ``pt:<name>`` annotation on the trace's host track, and a nested
    span in the observability ring when that is enabled) that also
    accumulates host time under ``name`` when a Profiler is active.

    Robustness contract: ``end()`` without a prior ``begin()`` is a
    no-op (not a TypeError), ``end()`` is idempotent, and the context
    manager closes the span even when the body raises."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._span = None
        self._t0 = None

    def begin(self):
        self._span = begin_span(self.name)
        self._t0 = time.perf_counter()
        return self

    def end(self):
        t0, self._t0 = self._t0, None
        if t0 is None:      # begin() never ran, or end() ran already
            return
        dt = time.perf_counter() - t0
        span, self._span = self._span, None
        end_span(span)
        prof = profiler_hook.current()
        if prof is not None:
            prof._record(self.name, dt, kind="span")

    __enter__ = begin

    def __exit__(self, *exc):
        self.end()
        return False


class Profiler:
    """reference: python/paddle/profiler/profiler.py Profiler.

    ``start()``/``stop()`` bracket a profiling session; ``step()`` marks
    iteration boundaries (a RecordEvent span per step).  When
    ``trace_dir`` is set (or ``on_trace_ready=export_chrome_tracing(d)``)
    a jax profiler trace is captured for the session — the device-side
    timeline.  ``summary()`` prints host-side op/span tables.

    Timing semantics: jax dispatch is asynchronous, so by DEFAULT each
    recorded op time covers only the host-side dispatch (Python + trace
    + enqueue) — the device work is still in flight when the timer
    stops.  That is the right view for finding host-bound eager loops,
    but it under-reports device-heavy ops.  Pass ``sync_ops=True`` (or
    set ``FLAGS_profiler_sync_ops``) to block on each op's outputs
    before recording, making the span cover the device work too; this
    serializes the host/device pipeline, so the *sum* becomes accurate
    per-op attribution while the *total* no longer reflects pipelined
    wall-clock.  For true device timelines use ``trace_dir`` (XLA's own
    profiler owns device-side timing)."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only: bool = False, trace_dir: Optional[str] = None,
                 sync_ops: Optional[bool] = None):
        from ..core.flags import get_flag
        self.targets = targets
        self._sync_ops = (get_flag("profiler_sync_ops") if sync_ops is None
                          else bool(sync_ops))
        self._on_trace_ready = on_trace_ready
        self._trace_dir = trace_dir or getattr(on_trace_ready, "_dir", None)
        self._timer_only = timer_only
        self._op_stats: Dict[str, List[float]] = defaultdict(
            lambda: [0, 0.0])      # name -> [count, total_s]
        self._span_stats: Dict[str, List[float]] = defaultdict(
            lambda: [0, 0.0])
        self._step_ann = None
        self._step_count = 0
        self._tracing = False

    # -- hook sink ---------------------------------------------------------
    def _record(self, name: str, dt: float, kind: str = "op"):
        table = self._op_stats if kind == "op" else self._span_stats
        ent = table[name]
        ent[0] += 1
        ent[1] += dt

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        profiler_hook.set_active(self)
        if self._trace_dir and not self._timer_only:
            jax.profiler.start_trace(self._trace_dir)
            self._tracing = True
        return self

    def stop(self):
        if self._step_ann is not None:
            self._step_ann.end()
            self._step_ann = None
        if self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False
        if profiler_hook.current() is self:  # don't clobber another one
            profiler_hook.set_active(None)
        if self._on_trace_ready is not None:
            self._on_trace_ready(self)
        return self

    def step(self, num_samples: Optional[int] = None):
        if self._step_ann is not None:
            self._step_ann.end()
        self._step_count += 1
        self._step_ann = RecordEvent(
            f"ProfileStep#{self._step_count}").begin()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- reporting ---------------------------------------------------------
    def key_averages(self) -> List[Tuple[str, int, float]]:
        """[(op_name, calls, total_ms)] sorted by total host time."""
        rows = [(n, int(c), 1000.0 * t)
                for n, (c, t) in self._op_stats.items()]
        return sorted(rows, key=lambda r: -r[2])

    def summary(self, sorted_by="total", op_detail=True, top_k: int = 20,
                thread_sep=False, time_unit="ms") -> str:
        """Top-k host-time table (the reference's DisableProfiler print,
        platform/profiler.cc PrintProfiler)."""
        lines = []
        if self._span_stats:
            lines.append(f"{'span':<32}{'calls':>8}{'total_ms':>12}"
                         f"{'avg_ms':>10}")
            for n, (c, t) in sorted(self._span_stats.items(),
                                    key=lambda kv: -kv[1][1])[:top_k]:
                lines.append(f"{n:<32}{c:>8}{1000 * t:>12.3f}"
                             f"{1000 * t / max(c, 1):>10.3f}")
            lines.append("")
        lines.append(f"{'op (eager host dispatch)':<32}{'calls':>8}"
                     f"{'total_ms':>12}{'avg_ms':>10}")
        for n, c, tms in self.key_averages()[:top_k]:
            lines.append(f"{n:<32}{c:>8}{tms:>12.3f}"
                         f"{tms / max(c, 1):>10.3f}")
        out = "\n".join(lines)
        print(out)
        return out


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready factory (reference: profiler.py
    export_chrome_tracing; tools/timeline.py).  The jax trace is already
    chrome/perfetto-compatible — this just points the Profiler at a
    directory."""
    def handler(prof):
        return None

    handler._dir = dir_name
    return handler


def load_profiler_result(path: str):
    """Parity shim: jax traces are read with TensorBoard/Perfetto."""
    raise NotImplementedError(
        "load the trace directory with TensorBoard's profile plugin or "
        "ui.perfetto.dev (jax traces are perfetto-format)")


# -- fluid-era API (reference: python/paddle/fluid/profiler.py) -------------

_legacy: Optional[Profiler] = None


def start_profiler(state="All", tracer_option="Default"):
    global _legacy
    _legacy = Profiler()
    _legacy.start()


def stop_profiler(sorted_key="total", profile_path=None):
    global _legacy
    if _legacy is not None:
        _legacy.stop()
        text = _legacy.summary(sorted_by=sorted_key)
        if profile_path:
            with open(profile_path, "w") as f:
                f.write(text)
        _legacy = None


@contextlib.contextmanager
def profiler_guard(state="All", sorted_key="total", profile_path=None):
    """fluid.profiler.profiler context (reference: fluid/profiler.py:35)."""
    start_profiler(state)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)
