"""Seconds jax spent lowering the PROGRAM's jaxprs to MLIR modules: the
``setup.program.lower_s`` gauge (every owner of the set-up timeline but
``outside``; ``trace_lower_s`` counts the plain reference's too)."""
import scope_reduce


def read(ctx):
    return scope_reduce.program_counter("setup.program.lower_s")
