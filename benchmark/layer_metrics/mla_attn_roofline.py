"""Roofline time of what the latent attention's kernels need a step,
over the device time under the program's ``mla_attention`` scope, in
percent.

The need is ``mla_attention_work`` of the cell's model file: causal
pairs (half the square), a forward of two products (192 and 128 wide)
and a backward of five (192 / 128 / 128 / 192 / 192).  The forward calls
a step are counted in the trace (the ``flash_fwd`` instructions under
the scope), not taken from the configuration's ``recompute`` key: a
replay that keeps the kernel's ``out`` and ``lse`` runs none.  The
denominator is ``mla_attention_ms`` and not the Mosaic time alone: the
layout changes and the sum of the shared key's gradient over the heads
are part of what the attention costs.  Nothing to read where the step holds no such scope.
"""
import scope_reduce

MLA_ATTENTION = ("mla_attention",)
FORWARD_KERNEL = "flash_fwd"


def roofline_seconds(flops, bytes_, peaks):
    """-> (seconds, which bound binds)."""
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("compute" if by_flops >= by_bytes
                                     else "memory")


def read(ctx):
    took_ms = scope_reduce.component_ms(ctx, MLA_ATTENTION)
    if not took_ms:
        return None
    forwards = sum(1 for r in scope_reduce.table(ctx)
                   if r["mosaic"] and scope_reduce.under(r, MLA_ATTENTION)
                   and scope_reduce.under(r, (FORWARD_KERNEL,)))
    flops, bytes_ = ctx["model"].mla_attention_work(
        ctx["cfg"], ctx["mix"], forwards)
    need, bound = roofline_seconds(flops, bytes_, ctx["peaks"])
    ctx["log"](f"[mla_attn_roofline] {forwards} forward kernel calls a "
               f"step; needs {need * 1000:.3f} ms ({bound}-bound: "
               f"{flops:.4g} FLOPs, {bytes_:.4g} bytes), took "
               f"{took_ms:.3f} ms under mla_attention")
    return need * 1000 / took_ms * 100
