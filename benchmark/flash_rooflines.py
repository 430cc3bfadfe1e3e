"""A roofline share of the flash kernels under one of the program's
scopes, as two per-layer metrics read it (``window_attn_roofline``,
``full_attn_roofline``): the roofline time of what those calls need over
their own Mosaic time, in percent.

The need comes from a function of the cell's model file, which is handed
the forward kernel calls counted in the trace (the ``flash_fwd``
instructions among the rows: a replay that keeps the kernel's ``out`` and
``lse`` runs none).  The time is the Mosaic kernels' alone, so the share
is the kernels' own: what a window call skips shows as a smaller time
against the band's need, and a call that walked blocks it need not shows
below the full call's share.  The layout copies and ``delta`` around the
kernels are in ``window_attention_ms`` / ``attention_ms``, not here.
"""
import scope_reduce

ATTENTION = "scaled_dot_product_attention"
WINDOW = "window_attention"
FORWARD_KERNEL = "flash_fwd"


def kernel_rows(ctx, window):
    """The traced step's Mosaic rows under the attention scope: those
    inside the window scope, or those outside it.  None where nothing can
    be read."""
    rows = scope_reduce.table(ctx)
    if rows is None:
        return None
    return [r for r in rows if r["mosaic"]
            and scope_reduce.under(r, (ATTENTION,))
            and scope_reduce.under(r, (WINDOW,)) == window]


def read(ctx, who, window, work):
    """``work``: the name of the model file's function
    ``(cfg, mix, forwards) -> (FLOPs, bytes)``."""
    rows = kernel_rows(ctx, window)
    took_ms = sum(r["ms"] for r in rows or ())
    if not took_ms:
        return None
    forwards = sum(1 for r in rows
                   if scope_reduce.under(r, (FORWARD_KERNEL,)))
    flops, bytes_ = getattr(ctx["model"], work)(ctx["cfg"], ctx["mix"],
                                                forwards)
    by_flops = flops / ctx["peaks"]["bf16_flops_per_s"]
    by_bytes = bytes_ / ctx["peaks"]["hbm_bytes_per_s"]
    need = max(by_flops, by_bytes)
    ctx["log"](f"[{who}] {forwards} forward kernel calls a step; needs "
               f"{need * 1000:.3f} ms "
               f"({'compute' if by_flops >= by_bytes else 'memory'}-bound: "
               f"{flops:.4g} FLOPs, {bytes_:.4g} bytes), the kernels took "
               f"{took_ms:.3f} ms")
    return need * 1000 / took_ms * 100
