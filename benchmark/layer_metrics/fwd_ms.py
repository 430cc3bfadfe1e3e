"""Device time a step in the forward pass: instructions whose ``op_name``
is under ``jvp(`` with a scope of the program, outside the replayed
forward and the backward (``scope_reduce.phase_of``)."""
import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "forward")
