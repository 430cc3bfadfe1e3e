"""Device time a step under the program's ``ssm_conv`` scope, all phases:
a state-space mixer's depthwise causal convolution (the taps' shifted
multiply-adds in float32 over the x, B and C channels, the bias, silu)
with its replay and its backward.  Nothing to read where the step holds
no such scope."""
import scope_reduce


def read(ctx):
    return scope_reduce.component_ms(ctx, ("ssm_conv",)) or None
