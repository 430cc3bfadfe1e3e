"""Seconds jax spent loading the PROGRAM's executables from the
persistent cache or, where it had none, compiling them: the gauges
``setup.program.load_s`` + ``setup.program.compile_s`` (every owner of
the set-up timeline but ``outside``; ``compile_s`` counts the plain
reference's too).  The log line gives loads, fresh compiles and what the
cache said of its own time."""
import scope_reduce
import setup_timeline


def read(ctx):
    rep = setup_timeline.report()
    load = scope_reduce.program_counter("setup.program.load_s")
    fresh = scope_reduce.program_counter("setup.program.compile_s")
    if rep is None or (load is None and fresh is None):
        return None
    c = setup_timeline.program_cache(rep)
    ctx["log"](f"[program_load_s] loading {load or 0:.3f} s in "
               f"{c['loads']} loads, compiling {fresh or 0:.3f} s in "
               f"{c['compiles']} fresh compiles; the cache's retrieval "
               f"{c['retrieval_s']:.3f} s, compile_time_saved_sec "
               f"{c['saved_s']:.3f}")
    return (load or 0) + (fresh or 0)
