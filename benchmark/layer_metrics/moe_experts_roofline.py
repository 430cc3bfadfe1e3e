"""Roofline time of the grouped products over the held experts a step,
over the device time under the program's ``moe_experts`` scope plus
XLA's own ``ragged-dot`` kernels (which carry no scope), in percent.

The need is an expectation, the time is not: ``expert_matmul_work`` of
the cell's model file reckons top_k * held / total assignments a token
for each grouped-product kernel call, and the calls a step are counted
in the traced window (forward, replays and backward as the program runs
them; a kernel in a branch not taken leaves no event), while the time
follows the load that the seed's weights give.  So the share moves with
the draw as well as with the layer: it compares runs on one seed, and is
not the kernel's efficiency.  Nothing to read where the step holds no
such scope or no such kernel.
"""
import scope_reduce
import trace_reduce

RAGGED_DOT = "ragged-dot"
RAGGED_DOT_METADATA = "ragged-dot-metadata"     # its tiles' bookkeeping


def ragged_dot_ms(ctx):
    """Device ms a step in XLA's own grouped-matmul kernels
    (``lax.ragged_dot`` becomes custom calls named ``ragged-dot-*`` whose
    ``op_name`` XLA replaces with that name, so no scope of the program
    holds them); 0.0 where there are none."""
    rows = scope_reduce.table(ctx) or []
    return sum(r["ms"] for r in rows
               if r["instruction"].startswith(RAGGED_DOT))


def ragged_dot_calls(ctx):
    """Executions a step of the product kernels among those (each comes
    with small ``ragged-dot-metadata`` kernels, not counted), from the
    traced window's device events."""
    trace = ctx["trace"]
    names = (trace_reduce.short_name(text) for text, *_ in trace["events"])
    events = sum(1 for n in names if n.startswith(RAGGED_DOT)
                 and not n.startswith(RAGGED_DOT_METADATA))
    return events / trace["module_runs"]


def roofline_seconds(flops, bytes_, peaks):
    """-> (seconds, which bound binds)."""
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("compute" if by_flops >= by_bytes
                                     else "memory")


def read(ctx):
    took_ms = scope_reduce.component_ms(ctx, ("moe_experts",))
    if not took_ms:
        return None
    calls = ragged_dot_calls(ctx)
    if not calls:
        ctx["log"]("[moe_experts_roofline] no ragged-dot kernel in the "
                   "traced window: nothing to count the products by")
        return None
    took_ms += ragged_dot_ms(ctx)
    flops, bytes_ = ctx["model"].expert_matmul_work(ctx["cfg"], ctx["mix"],
                                                    calls)
    need, bound = roofline_seconds(flops, bytes_, ctx["peaks"])
    ctx["log"](f"[moe_experts_roofline] {calls:.2f} ragged-dot calls a "
               f"step; at the expected load they need {need * 1000:.3f} ms "
               f"({bound}-bound: {flops:.4g} FLOPs, {bytes_:.4g} bytes); "
               f"this seed's load took {took_ms:.3f} ms under moe_experts "
               "and in the ragged-dot kernels")
    return need * 1000 / took_ms * 100
