"""Device time a step in forward work replayed during the backward
pass: instructions under jax's ``rematted_computation`` (per-block
``recompute``, and the chunked head's own checkpoint)."""
import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "recompute")
