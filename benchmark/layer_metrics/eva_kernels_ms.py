"""Device time a step in the Mosaic kernels under the program's
``eva_attention`` scope (``eva_fwd``, ``eva_bwd_dq`` and the flash dk/dv
kernel run over folded windows), all phases, the forward calls that
recompute replays included.  Nothing to read where the step holds no
such kernel."""
import scope_reduce

EVA_ATTENTION = ("eva_attention",)


def read(ctx):
    return scope_reduce.component_ms(ctx, EVA_ATTENTION,
                                     mosaic_only=True) or None
