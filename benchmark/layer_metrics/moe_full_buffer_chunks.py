"""Chunks (rows of the batch, one expert layer each) a step whose held
assignments outgrew the expert layer's small buffer and took the full
one (``moe.full_buffer_chunks``, counted by the compiled step: the other
branch of ``ops/moe.py::_by_load``), mean over every step of the process.
A step's time follows it: a cell whose routers drift onto the held
experts reads it rise before its rate falls.  Nothing to read on a
program without device counters or a step without an expert layer."""
import moe_counters


def read(ctx):
    got = moe_counters.loads(ctx, "moe_full_buffer_chunks")
    if got is None:
        return None
    return sum(got["full"]) / got["steps"]
