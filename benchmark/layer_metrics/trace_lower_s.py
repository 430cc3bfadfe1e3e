"""Seconds jax spent tracing functions to jaxprs and lowering them to
MLIR, before any backend compile or cache look-up: the program's
``setup.trace_s`` and ``setup.lower_s`` counters (sums of jax.monitoring's
``jaxpr_trace_duration`` and ``jaxpr_to_mlir_module_duration``) together.
Like ``compile_s`` it covers the whole process, the plain reference's
functions included."""
import scope_reduce


def read(ctx):
    trace = scope_reduce.program_counter("setup.trace_s")
    lower = scope_reduce.program_counter("setup.lower_s")
    if trace is None and lower is None:
        return None
    ctx["log"](f"[trace_lower_s] tracing {trace or 0:.3f} s, lowering "
               f"{lower or 0:.3f} s")
    return (trace or 0) + (lower or 0)
