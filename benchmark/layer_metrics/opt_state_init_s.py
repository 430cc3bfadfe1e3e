"""Seconds inside ``Optimizer.functional_init`` (the optimizer state's
eager initialisation, a leaf at a time): the program's
``setup.opt_state_init_s`` counter."""
import scope_reduce


def read(ctx):
    return scope_reduce.program_counter("setup.opt_state_init_s")
