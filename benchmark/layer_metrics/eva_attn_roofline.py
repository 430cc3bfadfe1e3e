"""Roofline time of what EVA attention needs a step, over the device
time under the program's ``eva_attention`` scope, in percent.

The need comes from shapes (``eva_attention_work`` of the cell's model
file): per layer, batch and head a forward is two matmuls over half of
W^2 a window plus W * (W / c) * w for window w's summaries, times D; a
backward is five; the forward replayed by recompute is counted as
executed; bytes are q, k, v, o, the summaries and their gradients once.

The denominator is ``eva_attention_ms`` and not the Mosaic time alone:
the chunk pooling, its backward and the layout changes run as XLA
fusions under the same scope and are part of the attention's work (the
pooling's own vector arithmetic is left out of the need, so it only
lowers the share).  Nothing to read where the step holds no such scope.
"""
import scope_reduce

EVA_ATTENTION = ("eva_attention",)


def roofline_seconds(flops, bytes_, peaks):
    """-> (seconds, which bound binds)."""
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("compute" if by_flops >= by_bytes
                                     else "memory")


def read(ctx):
    took_ms = scope_reduce.component_ms(ctx, EVA_ATTENTION)
    if not took_ms:
        return None
    mix = ctx["mix"]
    flops, bytes_ = ctx["model"].eva_attention_work(ctx["cfg"], mix["batch"],
                                                    mix["seq"])
    need, bound = roofline_seconds(flops, bytes_, ctx["peaks"])
    ctx["log"](f"[eva_attn_roofline] needs {need * 1000:.3f} ms a step "
               f"({bound}-bound: {flops:.4g} FLOPs, {bytes_:.4g} bytes), "
               f"took {took_ms:.3f} ms under eva_attention")
    return need * 1000 / took_ms * 100
