"""Blocks the sliding-window flash calls run over the blocks of the causal
triangle, in percent, from the program's trace-time counters
(``pallas.flash.window_blocks_full`` + ``.window_blocks_masked`` over
those plus ``.window_blocks_skipped``: block pairs a (batch, head) of
every windowed kernel traced, forward and backward walk).  The pairs a
window needs are ``W L - W (W - 1) / 2`` of ``L (L + 1) / 2``; the blocks
run are more, by the blocks that straddle the window's lower edge.
Nothing to read on a program without the counters or a step without a
window call."""
import scope_reduce

PREFIX = "pallas.flash.window_blocks_"


def read(ctx):
    full, masked, skipped = (scope_reduce.program_counter(PREFIX + part)
                             for part in ("full", "masked", "skipped"))
    if None in (full, masked, skipped) or not full + masked + skipped:
        ctx["log"]("[window_blocks_run_share] no windowed flash call was "
                   "traced in this process: nothing read")
        return None
    ctx["log"](f"[window_blocks_run_share] {full} blocks without a mask "
               f"and {masked} with one run, {skipped} of the causal "
               "triangle skipped")
    return (full + masked) / (full + masked + skipped) * 100
