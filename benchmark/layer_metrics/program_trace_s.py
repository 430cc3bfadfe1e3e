"""Seconds jax spent tracing the PROGRAM's functions to jaxprs: the
``setup.program.trace_s`` gauge, self seconds (a launcher's trace is
taken out of its caller's) over every owner of the set-up timeline but
``outside``, so the plain reference's functions are not in it as they
are in ``trace_lower_s``.  The log lines name the eight functions with
most self seconds, and the eight with most seconds when what they trace
inside is counted in: the kernel launchers by name (jax calls the body of
every ``pallas_call`` ``wrapped``, so by self seconds the kernels are one
row)."""
import scope_reduce
import setup_timeline


def read(ctx):
    rep = setup_timeline.report()
    value = scope_reduce.program_counter("setup.program.trace_s")
    if rep is None or value is None:
        return None
    fns = setup_timeline.program_functions(rep, "trace")
    top = sorted(fns.items(), key=lambda kv: -kv[1][1])[:8]
    ctx["log"](f"[program_trace_s] {value:.3f} s in "
               f"{sum(f[0] for f in fns.values())} traces of "
               f"{rep['events']} events in all; most self seconds: "
               + ", ".join(f"{name} {own:.3f} s x{n}"
                           for name, (n, own, _) in top))
    top = sorted(fns.items(), key=lambda kv: -kv[1][2])[:8]
    ctx["log"]("[program_trace_s] most seconds with what they trace "
               "inside: " + ", ".join(f"{name} {secs:.3f} s x{n}"
                                      for name, (n, _, secs) in top))
    return value
