"""Useful over attempted in the expert layers' two gathers, in percent:
the held assignments the chunks had (``moe.chunk_assignments``, counted
by the compiled step) over the buffer rows their gathers walked
(``moe.small_buffer_rows`` a chunk that took the small buffer,
``moe.full_buffer_rows`` a chunk that took the full one, by
``moe.full_buffer_chunks``), every step of the process.  The gathers cost
by the buffer's rows, live or not, so the rest is what a dispatch over
live rows only would save.  Nothing to read on a program without device
counters or a step without an expert layer."""
import moe_counters


def read(ctx):
    got = moe_counters.loads(ctx, "moe_buffer_live_share")
    if got is None:
        return None
    live = sum(map(sum, got["assigned"]))
    chunks = sum(map(len, got["assigned"])) * got["steps"]
    full = sum(got["full"])
    walked = (chunks - full) * got["small_rows"] + full * got["full_rows"]
    ctx["log"](f"[moe_buffer_live_share] {live} held assignments over "
               f"{walked} buffer rows ({chunks - full} small buffers, "
               f"{full} full ones)")
    return live / walked * 100
