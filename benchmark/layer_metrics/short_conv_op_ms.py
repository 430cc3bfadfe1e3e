"""Device time a step under the program's ``short_conv_op`` scope, all
phases: the gated short convolution alone, ``C * conv(B * z)`` between a
mixer's two projections (the kernels ``short_conv_fwd`` /
``short_conv_bwd``, or XLA's slices, products and shifted multiply-adds)
with its replay and its backward.  Nothing to read where the step holds
no such scope."""
import scope_reduce


def read(ctx):
    return scope_reduce.component_ms(ctx, ("short_conv_op",)) or None
