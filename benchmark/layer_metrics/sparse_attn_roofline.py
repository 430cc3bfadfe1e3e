"""Roofline time of what the sparse attention needs a step, over the
device time under the program's ``sparse_attention`` scope, in percent.

The need counts the **selected** (query, key) pairs only
(``sparse_attention_work`` of the cell's model file: min(t + 1, topk)
keys a query, two matmuls a forward and five a backward), so a kernel
that walks the whole causal triangle under a mask reads at most the
selected share of its own arithmetic.  The forward calls a step are
counted in the trace (the ``sparse_fwd`` instructions of the traced
executable), not taken from the configuration's ``recompute`` key: a
replay that keeps the kernel's ``out`` and ``lse`` runs none.  Nothing
to read where the step holds no such scope.
"""
import scope_reduce


def roofline_seconds(flops, bytes_, peaks):
    """-> (seconds, which bound binds)."""
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("compute" if by_flops >= by_bytes
                                     else "memory")

FORWARD_KERNEL = "sparse_fwd"


def read(ctx):
    took_ms = scope_reduce.component_ms(ctx, ("sparse_attention",))
    if not took_ms:
        return None
    forwards = sum(1 for r in scope_reduce.table(ctx)
                   if r["mosaic"] and scope_reduce.under(r, (FORWARD_KERNEL,)))
    flops, bytes_ = ctx["model"].sparse_attention_work(
        ctx["cfg"], ctx["mix"], forwards)
    need, bound = roofline_seconds(flops, bytes_, ctx["peaks"])
    ctx["log"](f"[sparse_attn_roofline] {forwards} forward kernel calls a "
               f"step; needs {need * 1000:.3f} ms ({bound}-bound: "
               f"{flops:.4g} FLOPs, {bytes_:.4g} bytes), took "
               f"{took_ms:.3f} ms under sparse_attention")
    return need * 1000 / took_ms * 100
