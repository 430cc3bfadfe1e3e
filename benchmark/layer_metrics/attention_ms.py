"""Device time a step under the program's
``scaled_dot_product_attention`` scope, all phases, its kernels
included (the projections around it are the layers', not its)."""
import scope_reduce


def read(ctx):
    return scope_reduce.component_ms(ctx, (scope_reduce.ATTENTION,))
