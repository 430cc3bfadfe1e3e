"""Device time a step under the program's ``moe`` scope, all phases: the
router, the sort and the two gathers, the grouped products over the held
experts and their backward (the keye_vl2 cell's four expert layers),
with XLA's own ``ragged-dot`` kernels, which carry no scope.  Nothing to
read where the step holds no such scope."""
import scope_reduce

RAGGED_DOT = "ragged-dot"


def ragged_dot_ms(ctx):
    """Device ms a step in XLA's own grouped-matmul kernels
    (``lax.ragged_dot`` becomes custom calls named ``ragged-dot-*`` whose
    ``op_name`` XLA replaces with that name, so no scope of the program
    holds them); 0.0 where there are none."""
    rows = scope_reduce.table(ctx) or []
    return sum(r["ms"] for r in rows
               if r["instruction"].startswith(RAGGED_DOT))


def read(ctx):
    scoped = scope_reduce.component_ms(ctx, ("moe",))
    return scoped + ragged_dot_ms(ctx) if scoped else None
