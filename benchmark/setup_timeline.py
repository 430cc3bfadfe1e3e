"""What the five set-up metrics share: the program's set-up timeline
(``paddle_tpu.observability.setup_report``: every trace, lowering, cache
load and compile jax made, with its function's name, self seconds and
owner) for their log lines.  The values themselves are gauges of the
program's always-on registry, read by ``scope_reduce.program_counter``;
a program from before the timeline has neither, and every reader here
then returns nothing."""

OUTSIDE = "outside"     # the owner of the caller's own jax work: the
                        # plain reference's functions and the harness's
FIRST_CALLS = ("train_step.first_call", "eval_step.first_call",
               "executor.first_run")


def report():
    """The program's report, or None where it has none."""
    from paddle_tpu import observability
    make = getattr(observability, "setup_report", None)
    return make() if make is not None else None


def program_functions(rep, phase):
    """function name -> [count, self seconds, seconds with what it holds]
    of one phase over every owner but ``outside``."""
    out = {}
    for owner, o in rep["owners"].items():
        if owner == OUTSIDE:
            continue
        fns = o["phases"].get(phase, {}).get("functions", {})
        for name, f in fns.items():
            got = out.setdefault(name, [0, 0.0, 0.0])
            got[0] += f["count"]
            got[1] += f["self_s"]
            got[2] += f["seconds"]
    return out


def program_cache(rep):
    """Loads, fresh compiles, retrieval seconds and compile seconds saved
    over every owner but ``outside``."""
    keys = ("loads", "compiles", "retrieval_s", "saved_s")
    mine = [c for owner, c in rep["cache"].items() if owner != OUTSIDE]
    return {k: sum(c[k] for c in mine) for k in keys}


def first_call_parts(rep):
    """(trace, lower, load or compile, other) seconds of the entry
    points' first calls, the spans nested in them included; they add up
    to the spans' wall."""
    parts = [0.0, 0.0, 0.0, 0.0]
    for name in FIRST_CALLS:
        inc = rep["owners"].get(name, {}).get("inclusive")
        if inc is not None:
            for i, v in enumerate((inc["trace"], inc["lower"],
                                   inc["load"] + inc["compile"],
                                   inc["other_s"])):
                parts[i] += v
    return parts
