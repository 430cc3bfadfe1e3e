"""Mean host time of one ``TrainStep.__call__`` outside its compiled call
(argument gathering before, write-back after): the program's
``train_step.python_ns`` over ``train_step.calls``, every call of the
process counted."""
import scope_reduce


def read(ctx):
    ns = scope_reduce.program_counter("train_step.python_ns")
    calls = scope_reduce.program_counter("train_step.calls")
    if not ns or not calls:
        return None
    return ns / calls / 1e6
