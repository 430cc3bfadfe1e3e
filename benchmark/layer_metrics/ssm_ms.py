"""Device time a step under the program's ``ssm`` scope, all phases: the
state-space mixers whole (the in- and out-projections, the causal
convolution, the scan, the gated group norm) with their replay and their
backward.  Nothing to read where the step holds no such scope."""
import scope_reduce


def read(ctx):
    return scope_reduce.component_ms(ctx, ("ssm",)) or None
