"""Device time a step under the program's ``window_attention`` scope, all
phases: the sliding-window calls of ``scaled_dot_product_attention``,
their kernels (``flash_fwd``, ``flash_bwd_dkv`` over the blocks the
window leaves), the layout copies around them, ``delta`` and the sum of a
group's dK and dV.  Nothing to read where the step holds no such
scope."""
import scope_reduce


def read(ctx):
    return scope_reduce.component_ms(ctx, ("window_attention",)) or None
