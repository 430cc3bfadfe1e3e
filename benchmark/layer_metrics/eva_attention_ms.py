"""Device time a step under the program's ``eva_attention`` scope, all
phases: the Mosaic kernels, the chunk pooling and its backward (XLA
fusions under ``eva_pool``) and the layout changes around them.  The
projections and the rotary embedding are the layers', not its.  Nothing
to read where the step holds no such scope."""
import scope_reduce

EVA_ATTENTION = ("eva_attention",)


def read(ctx):
    return scope_reduce.component_ms(ctx, EVA_ATTENTION) or None
