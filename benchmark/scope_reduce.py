"""From the device trace's instruction names to the program's scopes:
which phase and which component of the step each device second belongs
to.

The trace names a device op by its HLO instruction (``%fusion.3338``);
the program's ``jax.named_scope`` names live in the ``op_name``
metadata of the executable's optimized HLO
(``jit(step_fn)/jvp(loss)/blocks.3:Block/q:Linear/dot_general``).  The
two are joined by instruction name: the live executable is found by the
runner's ``EXECUTABLE`` as ``run.py::executable_footprint`` finds it.  A
fusion takes its own instruction's ``op_name``.  Times are self times
per traced step (``trace["op_seconds"]`` over ``module_runs``), so the
phases and the unscoped rest add up to the device's busy time.

The names below are the yardstick's copy of the program's vocabulary
(``paddle_tpu/observability/scopes.py``), not an import of it: a rename
in the program reads as "no scopes here", never as a silent change of
what a metric measures.  An executable that carries none of them (a
program without scopes, or one that jax's persistent cache answered
with an executable compiled before the scopes were there) gives
``None`` for every scope metric, and a log line saying why.

``split`` and ``phase_of`` work on plain dicts and strings, so a test
can hand them a trace and an HLO text made by hand.
"""
import re

LOSS = "loss"
OPTIMIZER_PHASES = ("optimizer", "grad_clip", "unscale", "scaler")
ATTENTION = "scaled_dot_product_attention"
HEAD_LOSS = "linear_cross_entropy"
FLASH_FWD = ("flash_fwd",)
FLASH_BWD = ("flash_bwd_dq", "flash_bwd_dkv")
REMAT = "rematted_computation"
MOSAIC = "tpu_custom_call"
PHASES = ("forward", "backward", "recompute", "optimizer", "unscoped")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=(){}]+)\s*=\s")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s=(){}]+)\s*\(.*->.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,(){}]+)")


def op_names(hlo_text):
    """instruction name -> ``op_name`` ("" where it has none), for every
    instruction of every computation (instruction names are unique in a
    module).  A fusion takes its own instruction's ``op_name``; one the
    compiler left without (a copy it made itself) takes that of the
    computation it calls: its root's, or the last instruction's that has
    one."""
    out, last_in, bare, computation = {}, {}, [], None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            header = _COMPUTATION.match(line)
            if header is not None:
                computation = header.group(1)
            continue
        found = _OP_NAME.search(line, m.end())
        if found is not None:
            out[m.group(1)] = last_in[computation] = found.group(1)
        else:
            out[m.group(1)] = ""
            callee = _CALLS.search(line, m.end())
            if callee is not None:
                bare.append((m.group(1), callee.group(1)))
    for instruction, callee in bare:
        out[instruction] = last_in.get(callee, "")
    return out


def _program_scope(seg):
    """A component (``q:Linear``), a phase or a functional of the
    program's vocabulary, bare or inside jax's ``jvp(..)``; jax's own
    wrappers (``jit(..)``, ``checkpoint``, ``while``) and primitive names
    are not."""
    if seg.startswith("jvp(") and seg.endswith(")"):
        seg = seg[4:-1]
    return (":" in seg or seg == LOSS or seg in OPTIMIZER_PHASES
            or seg in (ATTENTION, HEAD_LOSS))


def phase_of(op_name):
    """The phase rule, for an ``op_name`` ("" or None: none).  In this
    order: under ``optimizer``, ``grad_clip``, ``unscale`` or ``scaler``
    -> optimizer; under jax's ``rematted_computation`` -> recompute; under
    ``transpose(`` -> backward; under ``jvp(`` or ``loss`` with a scope of
    the program (``jvp(loss)`` is both) -> forward; anything else, an
    instruction without ``op_name`` included -> unscoped."""
    segs = op_name.split("/") if op_name else []
    if any(s in OPTIMIZER_PHASES for s in segs):
        return "optimizer"
    if REMAT in segs:
        return "recompute"
    if any(s.startswith("transpose(") for s in segs):
        return "backward"
    if (any(s.startswith("jvp(") or s == LOSS for s in segs)
            and any(_program_scope(s) for s in segs)):
        return "forward"
    return "unscoped"


def has_vocabulary(names):
    """Whether any ``op_name`` carries a phase of the program's
    vocabulary: ``jvp(loss)``, ``loss`` or ``optimizer``."""
    marks = (f"({LOSS})", f"/{LOSS}/", f"/{OPTIMIZER_PHASES[0]}/")
    return any(m in n for n in names.values() for m in marks)


def split(op_seconds, runs, names):
    """``op_seconds``: the trace reduction's per-name self times, keyed
    ``<instruction> <type> [<custom-call target>]``.  -> (rows, how many
    of the trace's instructions ``names`` holds, named or not); a row is
    {"instruction", "op_name", "phase", "mosaic", "ms"}, ms per traced
    step."""
    rows, matched = [], 0
    for key, seconds in op_seconds.items():
        instruction = key.split(" ", 1)[0]
        op_name = names.get(instruction, "")
        matched += instruction in names
        rows.append({"instruction": instruction, "op_name": op_name,
                     "phase": phase_of(op_name), "mosaic": MOSAIC in key,
                     "ms": seconds / runs * 1000})
    return rows, matched


def under(row, scopes):
    """Whether the row's ``op_name`` has one of ``scopes`` as a whole
    path segment."""
    return any(s in scopes for s in row["op_name"].split("/"))


def table(ctx):
    """The rows of ``split`` for the traced window of ``ctx``, joined
    against the live executable; cached in ``ctx``.  None, with a log
    line, where nothing can be read: no traced execution, no live
    executable of the runner's name, instruction names that do not match
    the trace, or an executable without the program's scopes."""
    if "scope_rows" in ctx:
        return ctx["scope_rows"]
    ctx["scope_rows"] = rows = _table(ctx)
    if rows is not None:
        _log_summary(ctx["log"], rows)
    return rows


def _table(ctx):
    log, trace = ctx["log"], ctx["trace"]
    module = ctx["runner"].EXECUTABLE
    if not trace["module_runs"]:
        log(f"[scopes] no traced execution of {module}: nothing to read")
        return None
    import jax
    texts = [ex.hlo_modules()[0].to_string()
             for ex in jax.devices()[0].client.live_executables()
             if ex.hlo_modules()[0].name == module]
    if not texts:
        log(f"[scopes] no live executable named {module}: nothing to read")
        return None
    best = None
    for text in texts:
        names = op_names(text)
        rows, matched = split(trace["op_seconds"], trace["module_runs"],
                              names)
        if best is None or matched > best[1]:
            best = (rows, matched, names, len(text))
    rows, matched, names, size = best
    named_ms = sum(r["ms"] for r in rows if r["op_name"])
    log(f"[scopes] {module}: optimized HLO of {size} characters, "
        f"{len(names)} instructions, {sum(map(bool, names.values()))} "
        f"with op_name; {matched} of {len(rows)} traced instruction names "
        f"are in it, {named_ms:.3f} ms a step under an op_name")
    if matched * 2 < len(rows):
        log("[scopes] under half of the trace's instruction names are in "
            "the executable's HLO: not the traced program, nothing read")
        return None
    if not has_vocabulary(names):
        log("[scopes] the executable's op_names carry none of the "
            "program's scopes (no 'loss', no 'optimizer'): a program "
            "without scopes, or an executable that the persistent "
            "compile cache answered from before they were added (its key "
            "leaves metadata out); nothing read, clear the cache")
        return None
    return rows


def _log_summary(log, rows):
    by_phase = {p: 0.0 for p in PHASES}
    for r in rows:
        by_phase[r["phase"]] += r["ms"]
    log("[scopes] ms a step by phase: " + ", ".join(
        f"{p} {v:.3f}" for p, v in by_phase.items())
        + f"; sum {sum(by_phase.values()):.3f}")
    kernels = {}
    for r in rows:
        if r["mosaic"]:
            segs = r["op_name"].split("/")
            name = segs[-2] if len(segs) > 1 else r["instruction"]
            ms, n = kernels.get(name, (0.0, 0))
            kernels[name] = (ms + r["ms"], n + 1)
    if kernels:
        log("[scopes] Mosaic kernels, ms a step (instructions): "
            + ", ".join(f"{k} {ms:.3f} ({n})"
                        for k, (ms, n) in sorted(kernels.items())))
    loose = {}
    for r in rows:
        if r["phase"] == "unscoped":
            kind = (r["instruction"].rsplit(".", 1)[0],
                    r["op_name"] or "no op_name")
            ms, n = loose.get(kind, (0.0, 0))
            loose[kind] = (ms + r["ms"], n + 1)
    top = sorted(loose.items(), key=lambda kv: -kv[1][0])[:8]
    log("[scopes] the unscoped time by instruction kind, ms a step "
        "(instructions): " + ", ".join(
            f"{kind} [{name}] {ms:.3f} ({n})"
            for (kind, name), (ms, n) in top))


def phase_ms(ctx, phase):
    rows = table(ctx)
    if rows is None:
        return None
    return sum(r["ms"] for r in rows if r["phase"] == phase)


def component_ms(ctx, scopes, mosaic_only=False):
    """Device ms a step under any of ``scopes``, all phases."""
    rows = table(ctx)
    if rows is None:
        return None
    return sum(r["ms"] for r in rows if under(r, scopes)
               and (r["mosaic"] or not mosaic_only))


def program_counter(name):
    """A counter of the program's always-on registry
    (``paddle_tpu.utils.monitor``); None where the program has no such
    counter (a program from before it was added)."""
    from paddle_tpu.utils import monitor
    return monitor.all_stats().get(name)
