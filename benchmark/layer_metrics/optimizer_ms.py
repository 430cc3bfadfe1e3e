"""Device time a step after the gradients exist: instructions under the
program's ``optimizer``, ``grad_clip``, ``unscale`` or ``scaler``
scopes."""
import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "optimizer")
