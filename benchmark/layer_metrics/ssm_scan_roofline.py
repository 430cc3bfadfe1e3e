"""Roofline time of the state-space scan a step, over the device time
under the program's ``ssm_scan`` scope, in percent.

The need is ``ssm_scan_work`` of the cell's model file: the chunked
scan's products and the bytes of its arguments and results, the same
whatever implements it.  The forward passes are counted in the traced
window: one a state-space layer, and one more each where the trace holds
time under ``ssm_scan`` in the phase recompute replays.  The time holds
everything under the scope, the softplus and the decays too, so the
share understates a kernel's own.  Nothing to read where the step holds
no such scope or the model file has no such count."""
import scope_reduce

SCAN = ("ssm_scan",)


def roofline_seconds(flops, bytes_, peaks):
    """-> (seconds, which bound binds)."""
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("compute" if by_flops >= by_bytes
                                     else "memory")


def read(ctx):
    took_ms = scope_reduce.component_ms(ctx, SCAN)
    work = getattr(ctx["model"], "ssm_scan_work", None)
    if not took_ms or work is None:
        return None
    replayed_ms = sum(r["ms"] for r in scope_reduce.table(ctx)
                      if scope_reduce.under(r, SCAN)
                      and r["phase"] == "recompute")
    layers = ctx["cfg"]["hybrid_override_pattern"].count("M")
    forward_calls = layers * (2 if replayed_ms else 1)
    flops, bytes_ = work(ctx["cfg"], ctx["mix"], forward_calls)
    need, bound = roofline_seconds(flops, bytes_, ctx["peaks"])
    ctx["log"](f"[ssm_scan_roofline] {layers} state-space layers, "
               f"{forward_calls} forward passes a step ({replayed_ms:.3f} "
               f"ms of the scope in the replay); they need "
               f"{need * 1000:.3f} ms ({bound}-bound: {flops:.4g} FLOPs, "
               f"{bytes_:.4g} bytes), took {took_ms:.3f} ms")
    return need * 1000 / took_ms * 100
