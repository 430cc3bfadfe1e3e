"""Device time a step under the program's ``sparse_attention`` scope,
all phases: the Mosaic kernels and the layout work inside the scope.
Nothing to read where the step holds no such scope."""
import scope_reduce


def read(ctx):
    return scope_reduce.component_ms(ctx, ("sparse_attention",)) or None
