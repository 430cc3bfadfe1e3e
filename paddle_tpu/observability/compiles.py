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

Also always on, for the same reason: the set-up timeline, where the
seconds before an entry point's first compiled call go.  jax reports the
close of every trace, lowering and backend compile with its start, its
end and the function's name, on ``time.time()`` (``jax.monitoring``); ONE
registration here (:func:`_register`: the closes and the persistent
cache's answers) keeps, for each closed interval, its phase
(``trace``, ``lower``, ``load`` where the cache answered, ``compile``
where it did not), ``fun_name``, thread, and **owner**: the innermost
program set-up span (:func:`setup_span`) open on its thread, else the
entry point whose traced body claimed it after its first call
(:func:`claim`: ``train_step.call``, ``eval_step.call``,
``executor.run``), else ``outside`` (the caller's own jax work).  Self
seconds are a length less the intervals that closed inside it on the
same thread; a span's ``other`` is its wall less its intervals.  :func:`setup_report` reads it; ``setup.trace_s`` /
``setup.lower_s`` (whole process, nested traces counted in their
callers too) are fed as before, beside ``setup.import_s``,
``setup.param_init_s`` / ``setup.param_init_count``,
``setup.opt_state_init_s``, ``setup.amp_decorate_s`` and
``setup.first_call_s`` (counted where that work happens), and the
program's own share is published as ``setup.program.trace_s`` /
``.lower_s`` / ``.load_s`` / ``.compile_s`` (self seconds of every
owner but ``outside``) and ``setup.before_import_s``.
"""
from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax.monitoring

from ..core import obs_hook
from ..utils import monitor

__all__ = ["record_compile", "explain_compiles", "reset_compiles",
           "annotate_compile", "setup_report"]

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
    """Drop attribution history and the set-up timeline's sums (tests /
    fresh smoke runs); the stamps of the process and of its import stay."""
    with _lock:
        _records.clear()
        _prev.clear()
        _totals.clear()
        _span_totals.clear()
        _ready.clear()
        for st in _threads:
            st.agg.clear()
            st.cache.clear()
            st.closed.clear()
            del st.pending[:]
            st.claim = None
            st.program = [0.0, 0.0, 0.0, 0.0]
    _ring.clear()


# ------------------------------------------------ the set-up timeline --
_TRACE, _LOWER, _LOAD, _COMPILE = range(4)
_PHASES = ("trace", "lower", "load", "compile")
_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": (_TRACE, "setup.trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        (_LOWER, "setup.lower_s"),
    "/jax/core/compile/backend_compile_duration": (_COMPILE, None),
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_SECONDS = {"/jax/compilation_cache/cache_retrieval_time_sec": 2,
                  "/jax/compilation_cache/compile_time_saved_sec": 3}
OUTSIDE = "outside"
_MAX_TIMELINE = 4096         # newest raw intervals; the sums never drop
_TIMELINE_FROM_S = 1e-3      # a ufunc's microsecond trace is in the sums only
_MAX_PENDING = 8192          # noted intervals a thread holds before it sums


class _Thread:
    """One thread's side of the timeline; only that thread writes it, so
    the callback takes no lock."""
    __slots__ = ("ident", "pending", "summing", "closed", "spans", "agg",
                 "cache", "hit", "program", "claim")

    def __init__(self):
        self.ident = threading.get_ident()
        self.pending = []   # closed intervals ``_flush`` has not summed
        self.summing = threading.Lock()     # a report may sum them too
        self.closed = []    # (start, seconds) of summed intervals that no
                            # later close has taken for its children yet
        self.spans = []     # open set-up spans, innermost last
        self.agg = {}       # (owner, phase, fun_name) -> [n, s, self s]
        self.cache = {}     # owner -> [hits, misses, retrieval s, saved s]
        self.hit = None     # the cache's answer to the compile in flight
        self.program = [0.0, 0.0, 0.0, 0.0]   # self s a phase, not OUTSIDE
        self.claim = None   # [owner, when, its trace's end, fun_name]


class _Span:
    """An open set-up span: an owner.  ``phases`` sums the self seconds
    of what jax closed under it, the spans nested in it included."""
    __slots__ = ("name", "start", "nested", "token", "phases")

    def __init__(self, name: str):
        self.name = name
        self.start = time.time()
        self.nested = 0.0       # wall seconds of the spans closed inside
        self.token = None       # ``observability.begin_span``'s
        self.phases = [0.0, 0.0, 0.0, 0.0]


_tls = threading.local()
_threads: List[_Thread] = []
_ring: collections.deque = collections.deque(maxlen=_MAX_TIMELINE)
_span_totals: Dict[str, list] = {}  # name -> [n, wall, nested, phases]
_stamps: Dict[str, object] = {}
_ready: Dict[str, float] = {}
_loads_in_flight = 0        # cache hits whose two durations are awaited


def _state() -> _Thread:
    try:
        return _tls.st
    except AttributeError:
        st = _tls.st = _Thread()
        with _lock:
            _threads.append(st)
        return st


def _on_close(event: str, start: float, end: float, fun_name: str = "?",
              **_kw) -> None:
    """jax closed a trace, a lowering or a backend compile.  This runs
    tens of thousands of times a start (every ``jnp`` ufunc inside a
    trace is a jitted function of its own), so it only notes the
    interval with what has to be read now, its owner; ``_flush`` sums."""
    known = _EVENTS.get(event)
    if known is None:
        return
    phase, feed = known
    if feed is not None:
        monitor.stat_add(feed, end - start)
    try:
        st = _tls.st
    except AttributeError:
        st = _state()
    answer = None
    if phase == _COMPILE and st.hit is not None:
        answer, st.hit = st.hit, None
        if answer[0]:
            phase = _LOAD
            _load_done()
    if st.spans:
        owner = st.spans[-1]
    elif st.claim is None:
        owner = OUTSIDE
    else:
        owner = _claimed(st, phase, start, end, fun_name)
        if st.claim is None and owner is not OUTSIDE:
            # an entry point's recompile is through: into the sums
            st.pending.append((phase, fun_name, start, end, owner, answer))
            _flush(st)
            _publish()
            return
    st.pending.append((phase, fun_name, start, end, owner, answer))
    if len(st.pending) >= _MAX_PENDING:
        _flush(st)


def _flush(st: _Thread) -> None:
    """Sum what ``st`` noted since the last flush.  Events came at their
    close, children before parents: what closed on the thread since an
    interval's start lay inside it and is taken out of its self seconds,
    each interval popped once.  Called by the thread itself (a set-up
    span's close, a recompile's end, a report), and for another thread by
    a report, which may then miss an interval that closes meanwhile until
    the next one."""
    pending, closed, agg = st.pending, st.closed, st.agg
    with st.summing:
        done = 0
        while done < len(pending):
            phase, fun_name, start, end, owner, answer = pending[done]
            done += 1
            secs = own = end - start
            while closed and closed[-1][0] >= start:
                own -= closed.pop()[1]
            closed.append((start, secs))
            if phase and fun_name[:4] == "jit(":    # a trace is plain ``f``
                fun_name = fun_name[4:-1]
            if owner.__class__ is _Span:
                owner.phases[phase] += own
                owner = owner.name
            elif owner is not OUTSIDE:          # an entry point's recompile
                st.program[phase] += own
            if answer is not None:
                tally = st.cache.get(owner)
                if tally is None:
                    st.cache[owner] = answer
                else:
                    for i, v in enumerate(answer):
                        tally[i] += v
            key = (owner, phase, fun_name)
            rec = agg.get(key)
            if rec is None:
                agg[key] = [1, secs, own]
            else:
                rec[0] += 1
                rec[1] += secs
                rec[2] += own
            if secs >= _TIMELINE_FROM_S:
                _ring.append((_PHASES[phase], fun_name, owner, start, end,
                              st.ident, own))
        del pending[:done]


def _load_done() -> None:
    global _loads_in_flight
    with _lock:
        _loads_in_flight -= 1
        if not _loads_in_flight:
            jax.monitoring.unregister_event_duration_listener(
                _on_cache_seconds)


def _claimed(st: _Thread, phase: int, start: float, end: float,
             fun_name: str) -> str:
    """The owner of an interval that closes under no set-up span while an
    entry point's claim stands (``claim``): the trace that holds the
    claim, what was traced inside it, and the lowering and the compile of
    the same function that follow are the entry point's; anything else
    ends the claim."""
    owner, when, trace_end, claimed_fun = st.claim
    if trace_end is None:
        if start >= when:
            return owner
        if phase == _TRACE:     # the innermost trace round the claim
            st.claim[2:] = end, f"jit({fun_name})"
            return owner
        return OUTSIDE
    if start >= trace_end and phase and fun_name == claimed_fun:
        if phase >= _LOAD:
            st.claim = None
        return owner
    st.claim = None
    return OUTSIDE


def _on_cache_event(event: str, **_kw) -> None:
    """The persistent cache said something of the compile in flight on
    this thread.  After a hit it gives two durations: the listener for
    those is registered only while a load is in flight, because jax
    calls a duration listener for every trace and lowering too."""
    global _loads_in_flight
    if event == _CACHE_HIT:
        _answer()[0] += 1
        with _lock:
            _loads_in_flight += 1
            if _loads_in_flight == 1:
                jax.monitoring.register_event_duration_secs_listener(
                    _on_cache_seconds)
    elif event == _CACHE_MISS:
        _answer()[1] += 1


def _on_cache_seconds(event: str, secs: float, **_kw) -> None:
    i = _CACHE_SECONDS.get(event)
    if i is not None:
        _answer()[i] += secs


def _answer() -> list:
    st = _state()
    if st.hit is None:
        st.hit = [0, 0, 0.0, 0.0]
    return st.hit


def _register() -> None:
    """The one registration: jax's close of every trace, lowering and
    backend compile (start, end, ``fun_name``), and what the persistent
    cache said of the compile in flight (a hit, a write; after a hit,
    ``_on_cache_seconds`` for its two durations)."""
    jax.monitoring.register_event_time_span_listener(_on_close)
    jax.monitoring.register_event_listener(_on_cache_event)


_register()


def _publish() -> Dict[str, float]:
    """The program's own self seconds a phase (every owner but
    ``outside``), as summed so far: into the ``setup.program.*`` gauges."""
    totals = [0.0, 0.0, 0.0, 0.0]
    for st in list(_threads):
        for i, v in enumerate(st.program):
            totals[i] += v
    for name, v in zip(_PHASES, totals):
        monitor.stat_set(f"setup.program.{name}_s", v)
    return dict(zip(_PHASES, totals))


# -- the program's set-up spans: the owners ------------------------------
def begin_setup(name: str) -> _Span:
    from . import begin_span
    span = _Span(name)
    st = _state()
    st.claim = None
    st.spans.append(span)
    span.token = begin_span(name)
    return span


def end_setup(span: _Span) -> float:
    """Close the span; returns its wall seconds."""
    from . import end_span
    end_span(span.token)
    wall = time.time() - span.start
    st = _state()
    _flush(st)
    spans = st.spans
    for i in range(len(spans) - 1, -1, -1):
        if spans[i] is span:
            del spans[i:]       # and a span left open inside it
            break
    outer = spans[-1] if spans else None
    if outer is not None:
        outer.nested += wall
    with _lock:
        tot = _span_totals.get(span.name)
        if tot is None:
            tot = _span_totals[span.name] = [0, 0.0, 0.0, [0.0] * 4]
        tot[0] += 1
        tot[1] += wall
        tot[2] += span.nested
        # its phases go to its name's sums, and on to the span round it
        # or, from an outermost span, to the program's
        for sums in (tot[3], st.program if outer is None else outer.phases):
            for i, v in enumerate(span.phases):
                sums[i] += v
    if outer is None:
        _publish()
    return wall


@contextlib.contextmanager
def setup_span(name: str):
    """A program set-up span: ``observability.span(name)`` (so
    ``pt:<name>`` in any profiler trace) that also OWNS what jax traces,
    lowers, loads and compiles on this thread while it is open.  Not for
    a steady path: it reads the clock and keeps sums."""
    span = begin_setup(name)
    try:
        yield span
    finally:
        end_setup(span)


def claim(owner: str) -> None:
    """From the top of an entry point's traced body: with no set-up span
    open (its first call is behind it), the trace in flight, what it
    traces inside, and the lowering and compile that follow are
    ``owner``'s (``_claimed``): a recompile, told by ``fun_name`` which
    and of what.  Runs at trace time only."""
    st = _state()
    if not st.spans:
        st.claim = [owner, time.time(), None, None]


def first_call_done(entry: str, span: _Span) -> None:
    """The return of an entry point's first compiled call: close its
    first-call span (``begin_setup``'s), count its wall into
    ``setup.first_call_s`` and stamp the entry point ``ready``."""
    monitor.stat_add("setup.first_call_s", end_setup(span))
    _ready.setdefault(entry, time.time())
    st = _state()
    if not st.spans:            # an entry point is called under no trace:
        st.closed.clear()       # nothing waits for a parent any more


def process_age(stat: str = "/proc/self/stat") -> Optional[float]:
    """Seconds since this process was started (Linux: its start time in
    ``stat`` against ``CLOCK_BOOTTIME``, a grain of 10 ms); None where
    that cannot be read."""
    try:
        with open(stat, "rb") as f:
            ticks = int(f.read().rsplit(b")", 1)[1].split()[19])
        return (time.clock_gettime(time.CLOCK_BOOTTIME)
                - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def import_done(start: float, backend_was_made: bool) -> None:
    """The last line of ``paddle_tpu/__init__.py``: ``setup.import`` was
    stamped at the first (``start``, ``time.time()``) and is entered
    here, the span primitive not being imported before.  What jax did on
    this thread meanwhile had no span to close under and is the import's.
    The age of the process at ``start`` is ``setup.before_import_s``:
    the interpreter, and ``import jax`` and the backend where the caller
    made them first (``backend_was_made``)."""
    span = begin_setup("setup.import")
    span.start = start
    st = _state()
    _flush(st)
    for (owner, phase, fun), got in list(st.agg.items()):
        if owner == OUTSIDE:
            del st.agg[(owner, phase, fun)]
            st.agg[("setup.import", phase, fun)] = got
            span.phases[phase] += got[2]
    if OUTSIDE in st.cache:
        st.cache["setup.import"] = st.cache.pop(OUTSIDE)
    for i, t in enumerate(_ring):
        if t[2] == OUTSIDE and t[5] == st.ident:
            _ring[i] = t[:2] + ("setup.import",) + t[3:]
    _stamps["import_start"] = start
    _stamps["backend_made_before_import"] = bool(backend_was_made)
    age = process_age()
    if age is not None:
        _stamps["process_start"] = time.time() - age
        monitor.stat_set("setup.before_import_s",
                         start - _stamps["process_start"])
    _stamps["import_end"] = start + end_setup(span)


def setup_report() -> dict:
    """Where set-up went, on ``time.time()``.

    ``owners``: owner -> ``{"count", "seconds", "self_s", "phases":
    {phase: {"count", "seconds", "self_s", "functions": {fun_name:
    {...}}}}}`` over every interval jax closed (sums of the whole
    process; ``seconds`` counts a nested trace in its callers too,
    ``self_s`` does not), and for an owner that is a set-up span also
    ``spans`` (how many), ``wall_s``, ``span_self_s`` (less the spans
    nested in it), ``other_s`` (``span_self_s`` less its intervals'
    ``self_s``: python between jax's events) and ``inclusive``: the
    four phases' self seconds with the nested spans' in them, and the
    ``other_s`` they leave of ``wall_s``.
    ``cache``: owner -> loads, fresh compiles, entries written, seconds
    of retrieval and of compile time saved.  ``stamps``: process start,
    import start and end, whether jax's backend was made before the
    import, and each entry point's ``ready`` (the return of its first
    compiled call).  ``program``: self seconds a phase over every owner
    but ``outside`` (the ``setup.program.*`` gauges).  ``events``: how
    many intervals were counted.  ``timeline``: the newest 4,096
    intervals of a millisecond or more."""
    owners: Dict[str, dict] = {}
    cache: Dict[str, dict] = {}
    events = 0
    for st in list(_threads):
        _flush(st)
    program = _publish()

    def sums(**more):
        return dict({"count": 0, "seconds": 0.0, "self_s": 0.0}, **more)

    for st in list(_threads):
        for (owner, phase, fun), (n, secs, own) in list(st.agg.items()):
            events += n
            o = owners.setdefault(owner, sums(phases={}))
            ph = o["phases"].setdefault(_PHASES[phase], sums(functions={}))
            fn = ph["functions"].setdefault(fun, sums())
            for d in (o, ph, fn):
                d["count"] += n
                d["seconds"] += secs
                d["self_s"] += own
        for owner, (hits, misses, retrieval, saved) in list(
                st.cache.items()):
            c = cache.setdefault(owner, {"loads": 0, "written": 0,
                                         "retrieval_s": 0.0, "saved_s": 0.0})
            c["loads"] += hits
            c["written"] += misses
            c["retrieval_s"] += retrieval
            c["saved_s"] += saved
    with _lock:
        spans = {k: (*v[:3], list(v[3])) for k, v in _span_totals.items()}
    for name, (n, wall, nested, phases) in spans.items():
        o = owners.setdefault(name, sums(phases={}))
        o["spans"] = n
        o["wall_s"] = wall
        o["span_self_s"] = wall - nested
        o["other_s"] = wall - nested - o["self_s"]
        o["inclusive"] = dict(zip(_PHASES, phases),
                              other_s=wall - sum(phases))
    for owner, c in cache.items():
        compiled = owners.get(owner, {}).get("phases", {}).get("compile")
        c["compiles"] = compiled["count"] if compiled else 0
    return {"owners": owners, "cache": cache, "program": program,
            "stamps": dict(_stamps, ready=dict(_ready)),
            "events": events,
            "timeline": [dict(zip(("phase", "fun_name", "owner", "start",
                                   "end", "thread", "self_s"), t))
                         for t in list(_ring)]}
