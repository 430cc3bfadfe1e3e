"""Seconds from an entry point's ``compiled is None`` branch through the
return of its first compiled call (``TrainStep``'s
``train_step.first_call``, ``eval_step``'s and the ``Executor``'s): the
program's ``setup.first_call_s`` counter, the part of ``setup_s`` that a
kernel's form moves.  The log lines split it by phase, the spans nested
in it included, and give what the timeline has of the build before it
(the spans' wall seconds) and its stamps, for reconciling ``setup_s``."""
import scope_reduce
import setup_timeline


def read(ctx):
    rep = setup_timeline.report()
    value = scope_reduce.program_counter("setup.first_call_s")
    if rep is None or value is None:
        return None
    trace, lower, load, other = setup_timeline.first_call_parts(rep)
    ctx["log"](f"[first_call_s] first call = trace {trace:.3f} + lower "
               f"{lower:.3f} + load {load:.3f} + other {other:.3f} "
               f"= {trace + lower + load + other:.3f} s")
    walls = {name.split(".", 1)[1]: o["wall_s"]
             for name, o in rep["owners"].items()
             if name.startswith("setup.") and "wall_s" in o}
    stamps = rep["stamps"]
    since = stamps.get("process_start", stamps.get("import_start"))
    ctx["log"]("[first_call_s] before it, seconds of wall: "
               + ", ".join(f"{k} {v:.3f}" for k, v in sorted(walls.items()))
               + "; after the process's start: import from "
               f"{stamps['import_start'] - since:.3f} to "
               f"{stamps['import_end'] - since:.3f}, ready "
               + ", ".join(f"{k} {t - since:.3f}"
                           for k, t in sorted(stamps["ready"].items())))
    return value
