"""Device time a step under the program's ``ssm_scan`` scope, all phases:
the softplus, the decays, the chunked scan with its state pass and
``D x``, their replay and the hand-written backward (XLA fusions or
kernels, whatever implements them).  Nothing to read where the step
holds no such scope."""
import scope_reduce


def read(ctx):
    return scope_reduce.component_ms(ctx, ("ssm_scan",)) or None
