"""Seconds from the first to the last line of ``paddle_tpu/__init__.py``
(jax's own import included when it happens there): the program's
``setup.import_s`` counter."""
import scope_reduce


def read(ctx):
    return scope_reduce.program_counter("setup.import_s")
