"""Device time a step under the program's ``short_conv`` scope, all
phases: the gated short-convolution mixers whole (the in-projection to
[B ; C ; z], the two gates round the taps, the out-projection) with
their replay and their backward.  Nothing to read where the step holds
no such scope."""
import scope_reduce


def read(ctx):
    return scope_reduce.component_ms(ctx, ("short_conv",)) or None
