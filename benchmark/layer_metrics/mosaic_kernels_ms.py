"""Summed device time of Mosaic custom calls (``tpu_custom_call``: the
program's Pallas kernels) per traced step.  From the device trace alone;
nothing to read where the step holds no such call."""
import trace_reduce


def read(ctx):
    if not ctx["trace"]["module_runs"]:
        return None
    s = trace_reduce.mosaic_seconds_per_run(ctx["trace"])
    return None if s is None else s * 1000
