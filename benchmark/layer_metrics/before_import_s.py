"""Seconds the process was old at the first line of
``paddle_tpu/__init__.py``: the interpreter's start and whatever the
caller imported and made before the package (here ``import jax`` and the
TPU client, which ``has_chips`` makes first): the program's
``setup.before_import_s`` gauge (Linux; absent elsewhere)."""
import scope_reduce


def read(ctx):
    return scope_reduce.program_counter("setup.before_import_s")
