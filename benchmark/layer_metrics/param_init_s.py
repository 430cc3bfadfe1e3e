"""Seconds the process spent inside parameter creation and its eager
initialisers (``Layer.create_parameter``): the program's
``setup.param_init_s`` counter; ``setup.param_init_count`` is logged
beside it."""
import scope_reduce


def read(ctx):
    value = scope_reduce.program_counter("setup.param_init_s")
    if value is not None:
        count = scope_reduce.program_counter("setup.param_init_count")
        ctx["log"](f"[param_init_s] {value:.3f} s in {count} parameters")
    return value
