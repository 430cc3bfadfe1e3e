"""Device time a step under the program's ``moe_router`` and
``moe_dispatch`` scopes, all phases: what routing costs beside the
experts' matmuls (float32 router and top-k, the sort of the assignments,
the gather to the experts' rows and the gate-weighted gather back, and
their transposes).  Nothing to read where the step holds no such
scope."""
import scope_reduce


def read(ctx):
    return scope_reduce.component_ms(
        ctx, ("moe_router", "moe_dispatch")) or None
