"""Roofline time of the gated short convolution a step, over the device
time under the program's ``short_conv_op`` scope, in percent.

The need is ``short_conv_work`` of the cell's model file: the bytes of
the operator's arguments and results, each once (a forward reads
[B ; C ; z] and writes y; a backward reads [B ; C ; z] and dy and writes
d[B ; C ; z]) and its multiply-adds, the same whatever implements it.
The forward passes are counted in the traced window: one a ``conv``
layer, and one more each where the trace holds time under
``short_conv_op`` in the phase recompute replays.  The time holds
everything under the scope (the casts of the taps, the sum of their
gradient over the batch and the sublanes), so the share understates a
kernel's own.  Nothing to read where the step holds no such scope or the
model file has no such count."""
import scope_reduce

OPERATOR = ("short_conv_op",)


def read(ctx):
    took_ms = scope_reduce.component_ms(ctx, OPERATOR)
    work = getattr(ctx["model"], "short_conv_work", None)
    if not took_ms or work is None:
        return None
    replayed_ms = sum(r["ms"] for r in scope_reduce.table(ctx)
                      if scope_reduce.under(r, OPERATOR)
                      and r["phase"] == "recompute")
    layers = ctx["cfg"]["layer_types"].count("conv")
    forward_calls = layers * (2 if replayed_ms else 1)
    flops, bytes_ = work(ctx["cfg"], ctx["mix"], forward_calls)
    by_flops = flops / ctx["peaks"]["bf16_flops_per_s"]
    by_bytes = bytes_ / ctx["peaks"]["hbm_bytes_per_s"]
    need = max(by_flops, by_bytes)
    ctx["log"](f"[short_conv_op_roofline] {layers} conv layers, "
               f"{forward_calls} forward passes a step ({replayed_ms:.3f} "
               f"ms of the scope in the replay); they need "
               f"{need * 1000:.3f} ms "
               f"({'compute' if by_flops >= by_bytes else 'memory'}-bound: "
               f"{flops:.4g} FLOPs, {bytes_:.4g} bytes), took "
               f"{took_ms:.3f} ms")
    return need * 1000 / took_ms * 100
