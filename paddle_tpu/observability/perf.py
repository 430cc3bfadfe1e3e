"""Runtime performance observatory: step anatomy, memory, drift.

PR-5's tracer records that things happened and the static cost model
(static/analysis/cost.py) predicts what *should* happen; this module
closes the loop at runtime:

- **Step-time anatomy** — the static Executor (and the serving
  engines) report per-step host time (feed conversion + dispatch
  submit) on every step, and *device* time on a sampled subset: every
  ``sample_every``-th step per compile identity is fenced with
  ``jax.block_until_ready`` so the wall from dispatch to results-ready
  is measured.  Unsampled steps stay fully asynchronous — sampling is
  what keeps the donated async pipeline intact while still yielding a
  device-time distribution (``step.host_ms`` / ``step.device_ms``
  monitor histograms + ``perf`` tracer lanes).
- **Device-memory telemetry** — on each fenced sample the live jax
  buffers are sized per device (per-shard via ``addressable_shards``
  when a mesh is live), exported as ``mem.device.<id>.live_bytes`` /
  ``.peak_live_bytes`` gauges and compared against the compile
  record's predicted peak.
- **Drift tracker** — per compile identity, a rolling window of
  measured step times / peak bytes is compared to the cost model's
  prediction (the ``predicted`` dict ``record_compile`` carries);
  :func:`perf_report` renders totals, per-identity drift %% and the
  worst offenders (``tools/perf_report.py`` is the CLI).
- **Exposed-vs-hidden comm split** — when the compile record predicts
  gradient-collective seconds (grad_comm under a sharding plan), each
  fenced step is split into comm that hid behind backward and comm
  that extended the step (``comm.exposed_ms`` / ``comm.hidden_ms``
  histograms + a per-identity ``comm`` block in the report).  Under
  ``overlap='none'`` the split is structural (hidden == 0 — the
  lowering barriers comm after backward); on overlapping paths the
  exposed share is learned from the fence, so a *scheduling*
  regression (collectives sliding out from behind backward) moves
  drift even when every kernel is as fast as ever.

Disabled-path contract (the PR-5 rule): when the observatory is off,
every instrumented site pays ONE module-attribute None-check
(``core.obs_hook._perf``) — no imports, no calls, no timestamps.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Dict, List, Optional

from ..core import flags, obs_hook
from ..utils import monitor

__all__ = ["PerfObservatory", "enable_perf", "disable_perf",
           "perf_enabled", "get_perf", "perf_report",
           "render_perf_report", "device_memory"]

_DEVICE_SAMPLES = 128       # rolling window of fenced samples kept
_MAX_IDENTITIES = 256       # LRU cap on tracked compile identities —
                            # the Executor evicts stale-version cache
                            # entries but their identities would
                            # otherwise accumulate here forever


def device_memory() -> Dict[str, dict]:
    """Live jax buffer bytes per device, sized shard-wise.

    Walks ``jax.live_arrays()`` and attributes each addressable shard's
    bytes to the device that holds it — under a mesh every chip is
    charged only for the shards it actually stores, not the global
    array.  Returns ``{device_label: {"live_bytes", "arrays"}}``.
    """
    import jax
    per: Dict[str, dict] = {}
    for a in jax.live_arrays():
        try:
            shards = a.addressable_shards
        except Exception:           # deleted/donated buffer mid-walk
            continue
        for sh in shards:
            try:
                d = sh.device
                nbytes = sh.data.nbytes
            except Exception:
                continue
            key = f"{getattr(d, 'platform', 'dev')}:{getattr(d, 'id', 0)}"
            slot = per.setdefault(key, {"live_bytes": 0, "arrays": 0})
            slot["live_bytes"] += int(nbytes)
            slot["arrays"] += 1
    return per


def _comm_split_s(predicted: Optional[dict], measured_s: Optional[float]
                  ) -> Optional[dict]:
    """Exposed-vs-hidden comm split of one step, in seconds, from the
    compile record's overlap prediction plus (when available) a fenced
    measurement.

    ``overlap == 'none'`` is structural: the lowering barriers the comm
    stage after backward, so exposed == total and hidden == 0 by
    construction, never by measurement.  On an overlapping path the
    exposed share is *learned* from the fence: whatever the measured
    step ran beyond the compute-only prediction is attributed to
    exposed comm, clamped to [0, total comm] — an upper bound (queue
    backlog and model error land in it too, which is exactly what
    drift tracking wants to catch: a scheduling regression shows up as
    exposed comm growing toward total).  Without a measurement the
    predicted split is reported."""
    if not predicted:
        return None
    comm_s = predicted.get("predicted_comm_s")
    if not comm_s:
        return None
    path = predicted.get("comm_overlap", "none")
    exposed_pred = predicted.get("predicted_exposed_comm_s", comm_s)
    if path == "none":
        exposed = comm_s
    elif measured_s is not None:
        compute_s = max(0.0, (predicted.get("predicted_step_s") or 0.0)
                        - exposed_pred)
        exposed = min(comm_s, max(0.0, measured_s - compute_s))
    else:
        exposed = min(comm_s, exposed_pred)
    return {"comm_s": comm_s, "exposed_s": exposed,
            "hidden_s": comm_s - exposed, "overlap": path,
            "predicted_exposed_s": exposed_pred}


def _predicted_step_s(predicted: Optional[dict]) -> Optional[float]:
    """Predicted step seconds for a compile record's ``predicted``
    dict: taken verbatim when the record carries ``predicted_step_s``,
    re-derived from FLOPs / min traffic against the roofline chip spec
    (``FLAGS_perf_chip``, auto-detected backend by default) otherwise."""
    if not predicted:
        return None
    if predicted.get("predicted_step_s"):
        return float(predicted["predicted_step_s"])
    flops = predicted.get("flops")
    traffic = predicted.get("min_traffic_bytes")
    if not flops and not traffic:
        return None
    from ..static.analysis.cost import CHIP_SPECS, resolve_perf_chip
    spec = CHIP_SPECS[resolve_perf_chip()]
    return max((flops or 0) / spec.peak_flops,
               (traffic or 0) / spec.hbm_bw)


class _IdentityPerf:
    """Rolling measured-vs-predicted state for one compile identity."""

    __slots__ = ("component", "identity", "steps", "sampled",
                 "host_sum_s", "device_s", "peak_bytes", "predicted")

    def __init__(self, component: str, identity):
        self.component = component
        self.identity = identity
        self.steps = 0
        self.sampled = 0
        self.host_sum_s = 0.0
        self.device_s: collections.deque = collections.deque(
            maxlen=_DEVICE_SAMPLES)
        self.peak_bytes = 0
        self.predicted: Optional[dict] = None

    def drift(self) -> dict:
        """Measured vs predicted, as the report shows it.  Drift %% is
        ``(measured - predicted) / predicted * 100`` — positive =
        slower / bigger than the model predicted.  ``peak_bytes`` is
        the max per-device live bytes observed at THIS identity's
        fences — ``jax.live_arrays()`` is process-wide, so with several
        programs or engines resident the number is an upper bound on
        this identity's own footprint, not an attribution."""
        out: dict = {
            "component": self.component,
            "identity": self.identity,
            "steps": self.steps,
            "sampled": self.sampled,
            "host_ms_mean": (self.host_sum_s / self.steps * 1e3
                             if self.steps else None),
        }
        measured: dict = {}
        if self.device_s:
            srt = sorted(self.device_s)
            measured["step_ms_p50"] = srt[len(srt) // 2] * 1e3
            measured["step_ms_min"] = srt[0] * 1e3
            measured["step_ms_max"] = srt[-1] * 1e3
        if self.peak_bytes:
            measured["peak_bytes"] = self.peak_bytes
        out["measured"] = measured
        out["predicted"] = dict(self.predicted) if self.predicted else None
        drift: dict = {}
        pstep = _predicted_step_s(self.predicted)
        if pstep and measured.get("step_ms_p50"):
            drift["step_time_pct"] = (
                (measured["step_ms_p50"] / 1e3 - pstep) / pstep * 100.0)
            out["predicted_step_ms"] = pstep * 1e3
        ppeak = (self.predicted or {}).get("peak_bytes_per_shard") \
            or (self.predicted or {}).get("peak_bytes")
        if ppeak and self.peak_bytes:
            drift["peak_bytes_pct"] = (
                (self.peak_bytes - ppeak) / ppeak * 100.0)
        out["drift"] = drift
        split = _comm_split_s(
            self.predicted,
            (measured["step_ms_p50"] / 1e3
             if measured.get("step_ms_p50") is not None else None))
        if split is not None:
            out["comm"] = {
                "overlap": split["overlap"],
                "comm_ms": split["comm_s"] * 1e3,
                "exposed_ms": split["exposed_s"] * 1e3,
                "hidden_ms": split["hidden_s"] * 1e3,
                "predicted_exposed_ms":
                    split["predicted_exposed_s"] * 1e3,
            }
        return out


class PerfObservatory:
    """Process-wide runtime performance observatory.

    Install with :func:`enable_perf`; instrumented sites reach it
    through ``core.obs_hook._perf`` (one None-check when off).

    Args:
        sample_every: fence + memory-sample every Nth step per compile
            identity (default ``FLAGS_perf_sample_every``).  ``<= 0``
            disables fencing — host anatomy only.
        memory: take device-memory samples on fenced steps.
    """

    def __init__(self, sample_every: Optional[int] = None,
                 memory: bool = True):
        self.sample_every = int(
            flags.get_flag("perf_sample_every") if sample_every is None
            else sample_every)
        self.memory = bool(memory)
        # reentrant: dump_flight embeds report() from the SIGTERM
        # handler, which can interrupt the SAME thread mid-step()
        # inside this lock — a plain Lock would self-deadlock the
        # crash path whose whole purpose is reliability at preemption
        self._lock = threading.RLock()
        self._ids: "collections.OrderedDict[tuple, _IdentityPerf]" = \
            collections.OrderedDict()
        self._ids_evicted = 0
        self._dev_peak: Dict[str, int] = {}
        self._serving_steps: Dict[str, int] = {}

    # -- executor step anatomy --------------------------------------------
    def step(self, component: str, identity, t_feed0: float,
             host_feed_s: float, t_disp0: float, dispatch_s: float,
             fetches, predicted: Optional[dict] = None) -> None:
        """One executor step.  ``t_feed0``/``t_disp0`` are the
        perf_counter stamps at feed-conversion and dispatch start;
        ``fetches`` is the async result to fence on sampled steps."""
        with self._lock:
            key = (component, identity)
            idp = self._ids.get(key)
            if idp is None:
                idp = self._ids[key] = _IdentityPerf(component, identity)
                if len(self._ids) > _MAX_IDENTITIES:
                    self._ids.popitem(last=False)   # least recent
                    self._ids_evicted += 1
            else:
                self._ids.move_to_end(key)
            idp.steps += 1
            n = idp.steps
            host_s = host_feed_s + dispatch_s
            idp.host_sum_s += host_s
            if predicted is not None:
                idp.predicted = predicted
            fence = self.sample_every > 0 and n % self.sample_every == 0
            if fence:
                idp.sampled += 1
        monitor.stat_observe("step.host_ms", host_s * 1e3)
        trc = obs_hook._tracer
        if trc is not None:
            # host lanes as two truthful intervals: feed conversion
            # and dispatch submit are separated by cache-lookup/state
            # work, so one span of their summed duration would end
            # mid-gap and never overlap the device span it pairs with
            trc.emit("perf", "step.host.feed", ts=t_feed0,
                     dur=host_feed_s, args={"identity": str(identity)})
            trc.emit("perf", "step.host.dispatch", ts=t_disp0,
                     dur=dispatch_s, args={"identity": str(identity)})
        if not fence:
            return
        import jax
        jax.block_until_ready(fetches)
        device_s = time.perf_counter() - t_disp0
        with self._lock:
            idp.device_s.append(device_s)
        monitor.stat_observe("step.device_ms", device_s * 1e3)
        monitor.stat_add("perf.fences")
        # exposed-vs-hidden comm split per fenced step: when the compile
        # record predicted gradient-collective seconds, attribute this
        # step's wall beyond the compute-only prediction to exposed comm
        # (structurally all-exposed under overlap='none')
        split = _comm_split_s(idp.predicted, device_s)
        if split is not None:
            monitor.stat_observe("comm.exposed_ms",
                                 split["exposed_s"] * 1e3)
            monitor.stat_observe("comm.hidden_ms",
                                 split["hidden_s"] * 1e3)
        if trc is not None:
            # device lane: dispatch start -> results ready.  Includes
            # any queue backlog the async pipeline had built — the
            # number answers "how long until this step's results
            # exist", which is what drift is measured against.
            trc.emit("perf", "step.device", ts=t_disp0, dur=device_s,
                     args={"identity": str(identity), "step": n})
        if self.memory:
            self._sample_memory(idp)

    # -- serving anatomy ---------------------------------------------------
    def serving_step(self, engine: Optional[str], kind: str,
                     dur_s: float) -> None:
        """One serving dispatch / decode step (already host-synced by
        the engine).  ``engine`` is the engine's ``name`` — None when
        unnamed, never a sentinel string, so an engine literally named
        ``"default"`` still gets its mirror.  Feeds the process-wide
        step histogram — mirrored per named engine
        (``perf.serving.<engine>.<kind>_ms``), so a multi-model
        process can tell a slow engine from a fast one — and the
        memory sampler on the observatory cadence."""
        monitor.stat_observe(f"perf.serving.{kind}_ms", dur_s * 1e3)
        if engine:
            monitor.stat_observe(f"perf.serving.{engine}.{kind}_ms",
                                 dur_s * 1e3)
        with self._lock:
            # cadence per (engine, kind): an unnamed InferenceEngine
            # and unnamed GenerationEngine both pass engine=None and
            # would otherwise share one counter, sampling memory at
            # ~2x the configured rate off the interleaved count
            ck = (engine, kind)
            n = self._serving_steps.get(ck, 0) + 1
            self._serving_steps[ck] = n
        if self.memory and self.sample_every > 0 \
                and n % self.sample_every == 0:
            self._sample_memory(None)

    # -- device memory -----------------------------------------------------
    def _sample_memory(self, idp: Optional[_IdentityPerf]) -> None:
        per = device_memory()
        total = 0
        peak_dev = 0
        with self._lock:
            for key, slot in per.items():
                b = slot["live_bytes"]
                total += b
                peak_dev = max(peak_dev, b)
                prev = self._dev_peak.get(key, 0)
                if b > prev:
                    self._dev_peak[key] = b
                monitor.stat_set(f"mem.device.{key}.live_bytes", b)
                monitor.stat_set(f"mem.device.{key}.peak_live_bytes",
                                 max(b, prev))
            if idp is not None and peak_dev > idp.peak_bytes:
                idp.peak_bytes = peak_dev
        monitor.stat_set("mem.live_bytes_total", total)
        trc = obs_hook._tracer
        if trc is not None:
            trc.counter("mem.live_bytes_total", 0, value=total)

    def memory_snapshot(self) -> dict:
        """Current + peak live bytes per device label."""
        per = device_memory()
        with self._lock:
            peaks = dict(self._dev_peak)
        return {key: {"live_bytes": slot["live_bytes"],
                      "arrays": slot["arrays"],
                      "peak_live_bytes": max(peaks.get(key, 0),
                                             slot["live_bytes"])}
                for key, slot in per.items()}

    # -- reporting ---------------------------------------------------------
    def report(self) -> dict:
        """The drift report: totals, per-identity measured-vs-predicted
        drift %%, worst offenders first (``explain_compiles``-style)."""
        with self._lock:
            ids = [idp.drift() for idp in self._ids.values()]
            peaks = dict(self._dev_peak)
        ids.sort(key=lambda r: abs(r["drift"].get("step_time_pct", 0.0)),
                 reverse=True)
        return {
            "enabled": True,
            "sample_every": self.sample_every,
            "totals": {
                "identities": len(ids),
                "identities_evicted": self._ids_evicted,
                "steps": sum(r["steps"] for r in ids),
                "sampled": sum(r["sampled"] for r in ids),
            },
            "identities": ids,
            "worst": [f"{r['component']}#{r['identity']}" for r in ids
                      if r["drift"].get("step_time_pct") is not None][:5],
            "devices": {k: {"peak_live_bytes": v}
                        for k, v in peaks.items()},
        }


def enable_perf(sample_every: Optional[int] = None,
                memory: bool = True) -> PerfObservatory:
    """Install (and return) a fresh process-wide observatory."""
    p = PerfObservatory(sample_every=sample_every, memory=memory)
    obs_hook.set_perf(p)
    return p


def disable_perf() -> None:
    """Remove the observatory; instrumented sites return to the one
    None-check disabled path."""
    obs_hook.set_perf(None)


def perf_enabled() -> bool:
    return obs_hook._perf is not None


def get_perf() -> Optional[PerfObservatory]:
    return obs_hook._perf


def perf_report() -> dict:
    """The installed observatory's drift report (``{"enabled": False}``
    when the observatory is off)."""
    p = obs_hook._perf
    if p is None:
        return {"enabled": False}
    return p.report()


def _fmt_pct(v) -> str:
    return "n/a" if v is None else f"{v:+.1f}%"


def render_perf_report(rep: Optional[dict] = None) -> str:
    """Human-readable drift report (the CLI's output)."""
    rep = perf_report() if rep is None else rep
    if not rep.get("enabled"):
        return "perf observatory: disabled (observability.enable_perf())"
    t = rep["totals"]
    lines = [
        f"perf observatory: {t['identities']} compile identities, "
        f"{t['steps']} steps, {t['sampled']} fenced samples "
        f"(every {rep['sample_every']})"]
    for r in rep["identities"]:
        m = r["measured"]
        d = r["drift"]
        lines.append(
            f"  {r['component']}#{r['identity']}: steps={r['steps']} "
            f"host {r['host_ms_mean']:.3f} ms/step" if r["host_ms_mean"]
            is not None else
            f"  {r['component']}#{r['identity']}: steps={r['steps']}")
        if m.get("step_ms_p50") is not None:
            pred = (f", predicted {r['predicted_step_ms']:.3f} ms "
                    f"(drift {_fmt_pct(d.get('step_time_pct'))})"
                    if r.get("predicted_step_ms") else "")
            lines.append(
                f"    device p50 {m['step_ms_p50']:.3f} ms "
                f"[{m['step_ms_min']:.3f}, {m['step_ms_max']:.3f}]{pred}")
        if m.get("peak_bytes"):
            p = r.get("predicted") or {}
            ppeak = p.get("peak_bytes_per_shard") or p.get("peak_bytes")
            pred = (f", predicted {ppeak} "
                    f"(drift {_fmt_pct(d.get('peak_bytes_pct'))})"
                    if ppeak else "")
            lines.append(f"    peak live bytes {m['peak_bytes']}{pred}")
        c = r.get("comm")
        if c is not None:
            lines.append(
                f"    comm {c['comm_ms']:.3f} ms "
                f"(exposed {c['exposed_ms']:.3f} / hidden "
                f"{c['hidden_ms']:.3f}, overlap={c['overlap']}, "
                f"predicted exposed {c['predicted_exposed_ms']:.3f})")
    for dev, slot in sorted(rep.get("devices", {}).items()):
        lines.append(f"  device {dev}: peak live "
                     f"{slot['peak_live_bytes']} bytes")
    if rep.get("worst"):
        lines.append(f"  worst step-time drift: "
                     f"{', '.join(rep['worst'])}")
    return "\n".join(lines)
