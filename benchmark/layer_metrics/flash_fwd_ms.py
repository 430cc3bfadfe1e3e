"""Device time a step in the ``flash_fwd`` Mosaic kernel: the forward
pass's calls and the ones recompute replays.  Nothing to read where the
step holds no such kernel."""
import scope_reduce


def read(ctx):
    return scope_reduce.component_ms(ctx, scope_reduce.FLASH_FWD,
                                     mosaic_only=True) or None
