"""Recompile attribution: every XLA compile gets a named cause.

The three compiling layers — the static Executor, the jit
(to_static) cache, and the inference Predictor — report each compile
here with a structured *signature* (an ordered dict of the cache-key
components that could have forced it).  Attribution is central: the
previous signature for the same (component, identity) is diffed against
the new one, the first changed field (in the caller's significance
order) names the cause — ``new_program_version``, ``new_feed_signature``,
``new_bucket``, ... — and the diff itself is kept so
:func:`explain_compiles` can show *what* changed, not just that
something did.  A compile whose signature matches its predecessor
exactly is ``unexplained`` — the smoke gate (tools/obs_smoke.py)
asserts that count stays 0.

Always on: compiles are rare and cost seconds, so attribution is not
gated behind ``observability.enable()`` — only the tracer *event* per
compile is.  Each record also counts ``compiles.<component>.<cause>``
and ``compiles.total`` in monitor, so bench/CI trajectories explain
perf deltas per cause.

Also always on, for the same reason: what set-up spends before the
backend compile.  jax reports each trace and each lowering
(``jax.monitoring``); the listener registered here when the package is
imported sums them into ``setup.trace_s`` and ``setup.lower_s`` in
monitor, beside the program's own ``setup.import_s``,
``setup.param_init_s`` / ``setup.param_init_count`` and
``setup.opt_state_init_s`` (counted where that work happens).
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax.monitoring

from ..core import obs_hook
from ..utils import monitor

__all__ = ["record_compile", "explain_compiles", "reset_compiles",
           "annotate_compile"]

_SETUP_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "setup.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "setup.lower_s",
}


def _on_jax_duration(event: str, secs: float, **_kw) -> None:
    stat = _SETUP_EVENTS.get(event)
    if stat is not None:
        monitor.stat_add(stat, secs)


jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)

_MAX_RECORDS = 2048          # ring of full records; totals never drop

_lock = threading.Lock()
_records: collections.deque = collections.deque(maxlen=_MAX_RECORDS)
_prev: Dict[Tuple[str, object], dict] = {}
_totals: collections.Counter = collections.Counter()


def _freeze(v):
    """Signature values must be hashable/comparable; stringify the rest."""
    if isinstance(v, (int, float, bool, str, bytes, type(None))):
        return v
    if isinstance(v, (tuple, list)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, (set, frozenset)):
        return tuple(sorted(_freeze(x) for x in v))
    return repr(v)


def record_compile(component: str, identity, signature: Dict[str, object],
                   note: str = "", predicted: Optional[dict] = None,
                   kernels: Optional[List[str]] = None,
                   comm: Optional[dict] = None,
                   cache: Optional[str] = None) -> dict:
    """Report one compile.

    ``component``: "executor" | "jit" | "predictor" | ... .
    ``identity``: what recompiles are diffed against — the program
    serial, the StaticFunction instance serial, the Predictor serial.
    ``signature``: ordered cache-key components, most significant
    first; the first field that differs from the previous compile of
    the same identity names the cause (``new_<field>``).
    ``predicted``: the static cost model's numbers for the compiled
    step (FLOPs, peak bytes — static/analysis/cost.compile_summary);
    kept on the record but deliberately OUT of the signature, so a
    cost-model change can never masquerade as a recompile cause.
    ``explain_compiles`` surfaces it next to the attribution, which is
    where predicted-vs-measured drift shows up.
    ``kernels``: the Pallas-tier kernels this executable selected
    (realized fusion-candidate epilogues, fused Adam) — like
    ``predicted``, on the record but OUT of the signature: flipping
    the tier recompiles via its own cache-key field, never as an
    attribution mystery, and the perf observatory can attribute a
    step-time delta to kernel on/off by reading the record.
    ``comm``: the grad-comm bucket schedule this executable lowered
    (per-bucket size/algorithm/wire/issue point + the resolved overlap
    path) — on the record, OUT of the signature (knob flips recompile
    through the plan fingerprint's ``sharding`` field), so overlap
    decisions are auditable from ``explain_compiles()``.
    ``cache``: persistent-compile-cache provenance — ``"loaded"`` (the
    executable was deserialized from ``FLAGS_compile_cache_dir``,
    no XLA compile happened), ``"compiled"`` (fresh compile, stored for
    next time), or ``"rejected:<why>"`` (a cache entry existed but its
    version/topology stamp or device fingerprint mismatched; fresh
    compile).  OUT of the signature for the same reason as the others:
    cache state must never masquerade as a recompile cause.
    """
    sig = {k: _freeze(v) for k, v in signature.items()}
    now = time.time()
    with _lock:
        prev = _prev.get((component, identity))
        if prev is None:
            cause = "first_compile"
            changed: Dict[str, tuple] = {}
        else:
            changed = {k: (prev.get(k), v) for k, v in sig.items()
                       if prev.get(k) != v}
            if changed:
                cause = "new_" + next(k for k in sig if k in changed)
            else:
                cause = "unexplained"
        _prev[(component, identity)] = sig
        rec = {
            "time": now,
            "component": component,
            "identity": identity,
            "cause": cause,
            "changed": changed,
            "signature": sig,
        }
        if note:
            rec["note"] = note
        if predicted:
            rec["predicted"] = dict(predicted)
        if kernels:
            rec["kernels"] = list(kernels)
        if comm:
            rec["comm"] = dict(comm)
        if cache:
            rec["cache"] = str(cache)
        _records.append(rec)
        _totals[(component, cause)] += 1
    monitor.stat_add(f"compiles.{component}.{cause}")
    monitor.stat_add("compiles.total")
    trc = obs_hook._tracer
    if trc is not None:
        trc.emit("compile", f"{component}.compile",
                 args={"cause": cause, "identity": str(identity),
                       "changed": sorted(changed)})
    return rec


def annotate_compile(component: str, identity, cache: str) -> bool:
    """Attach cache provenance to the NEWEST record of ``(component,
    identity)`` after the fact.  The lazily-compiling Executor records
    its compile when the cache key misses but only learns loaded-vs-
    compiled at the first dispatch — this closes that gap so
    ``explain_compiles()`` shows provenance for every site.  Returns
    False when no record matches (nothing to annotate)."""
    with _lock:
        for rec in reversed(_records):
            if (rec["component"] == component
                    and rec["identity"] == identity):
                rec["cache"] = str(cache)
                return True
    return False


def explain_compiles(component: Optional[str] = None) -> dict:
    """Why did every compile happen?

    Returns ``{"total", "unexplained", "by_cause": {"component.cause":
    n}, "records": [...]}`` — ``records`` keeps the newest
    ``_MAX_RECORDS`` full entries (cause + field-level diff), the
    totals cover the whole process lifetime.  ``component`` filters
    both."""
    with _lock:
        recs = [dict(r) for r in _records
                if component is None or r["component"] == component]
        totals = {f"{c}.{cause}": n for (c, cause), n in _totals.items()
                  if component is None or c == component}
    total = sum(totals.values())
    unexplained = sum(n for k, n in totals.items()
                      if k.endswith(".unexplained"))
    return {"total": total, "unexplained": unexplained,
            "by_cause": dict(sorted(totals.items())), "records": recs}


def reset_compiles() -> None:
    """Drop attribution history (tests / fresh smoke runs)."""
    with _lock:
        _records.clear()
        _prev.clear()
        _totals.clear()
