"""Device time a step under the program's ``mtp`` scope, all phases: the
multi-token-prediction module's two norms and projection, its block
(attention, router, experts, shared expert), and its pass through the
shared head and the chunked cross-entropy.  Nothing to read where the
step holds no such scope."""
import scope_reduce


def read(ctx):
    return scope_reduce.component_ms(ctx, ("mtp",)) or None
