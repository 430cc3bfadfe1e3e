"""Device time a step in the backward pass: instructions whose
``op_name`` is under jax's ``transpose(``, the replayed forward left out
(``scope_reduce.phase_of``)."""
import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "backward")
