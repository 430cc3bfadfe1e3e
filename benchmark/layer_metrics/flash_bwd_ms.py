"""Device time a step in the ``flash_bwd_dq`` and ``flash_bwd_dkv``
Mosaic kernels together.  Nothing to read where the step holds no such
kernel."""
import scope_reduce


def read(ctx):
    return scope_reduce.component_ms(ctx, scope_reduce.FLASH_BWD,
                                     mosaic_only=True) or None
