"""Seconds jax spent compiling or loading executables during set-up: the
sum of ``/jax/core/compile/backend_compile_duration`` (jax.monitoring)."""


def read(ctx):
    return ctx["compile"]["seconds"]
