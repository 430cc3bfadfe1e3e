"""Paged attention: decode-phase attention over a page-table-indexed
KV cache (Ragged Paged Attention, PAPERS.md).

The serving KV cache (:mod:`paddle_tpu.serving.kv_cache`) stores every
sequence's keys/values in fixed-size *pages* drawn from one preallocated
pool; a per-sequence page table (int32 page indices) maps logical token
positions to physical pages.  Because the pool, the page tables, and the
query batch all have static shapes, ONE compiled decode kernel serves
any mix of ragged sequence lengths — raggedness lives in the *data*
(table entries + lengths), never in the *shapes*.

Two tiers, selected per call:

- **reference** (always available, any backend): gather the K/V pages by
  page table (``pool[page_table]``), flatten to the per-sequence logical
  KV view, mask positions ``>= length``, dense softmax attention.  This
  is the semantics oracle and the CPU/tier-1 path.
- **Pallas** (shape-gated hook): a registered TPU kernel takes over when
  :func:`paged_attention_supported` accepts the shapes AND a kernel has
  been installed via :func:`register_paged_attention_kernel`.  The gate
  mirrors ``ops/pallas/flash_attention.flash_attention_supported``
  (dtype/backed/tile-alignment checks: head dim a multiple of the
  128-lane register width, page size a multiple of the 8-sublane f32
  tile); the ragged-paged-attention kernel itself is the ROADMAP item 4
  Pallas tier — this hook is the socket it plugs into.
"""
from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..core.dispatch import apply
from ..core.tensor import Tensor

__all__ = ["paged_attention", "paged_attention_reference",
           "paged_attention_select", "paged_attention_supported",
           "register_paged_attention_kernel"]

_NEG = -1e30

# the installed Pallas-tier kernel (None until ROADMAP item 4 lands or a
# test registers one); signature must match paged_attention_reference
_PALLAS_KERNEL: Optional[Callable] = None


def register_paged_attention_kernel(fn: Optional[Callable]) -> None:
    """Install (or clear, with ``None``) the Pallas-tier kernel.

    ``fn(q, k_pool, v_pool, page_table, lengths, scale) -> out`` with the
    same array contract as :func:`paged_attention_reference`.  Dispatch
    still goes through :func:`paged_attention_supported`; registering a
    kernel never affects unsupported shapes or non-TPU backends."""
    global _PALLAS_KERNEL
    _PALLAS_KERNEL = fn


def paged_attention_supported(q_shape, kv_pool_shape, dtype,
                              page_size: int) -> bool:
    """Shape gate for the Pallas tier (capability, not profitability).

    Requires an installed kernel, a TPU backend, f32/bf16, a head dim
    aligned to the 128-lane registers, and pages aligned to the 8-row
    f32 sublane tile — the layout the ragged-paged-attention kernel
    (ops/pallas/paged_attention.py) streams without relayout.  A 5-D
    [L, N, page, Hkv, D] pool is accepted for the per-layer ``layer=``
    dispatch the serving decode step uses.  Off TPU, a kernel that
    declares ``interpret_ok`` may still dispatch when the process opts
    into interpret-mode execution with ``FLAGS_pallas_interpret``
    (tests only — interpret mode is not a performance path)."""
    if _PALLAS_KERNEL is None:
        return False
    from .pallas import support
    if not support.tier_enabled():
        return False
    if (jax.default_backend() != "tpu"
            and not getattr(_PALLAS_KERNEL, "interpret_ok", False)):
        return False
    if dtype not in (jnp.float32, jnp.bfloat16):
        return False
    if len(q_shape) != 3 or len(kv_pool_shape) not in (4, 5):
        return False
    head_dim = q_shape[-1]
    if head_dim % 128 or head_dim != kv_pool_shape[-1]:
        return False
    if page_size % 8:
        return False
    return True


def _paged_attention_impl(q, k_pool, v_pool, page_table, lengths, *,
                          scale, layer=None):
    """Gather-by-page-table reference.

    q: [S, H, D] one query token per sequence slot;
    k_pool/v_pool: [N, page, Hkv, D] the shared physical page pool —
    or the full [L, N, page, Hkv, D] stack with ``layer`` set, in which
    case the layer index is composed INTO the page gather (one fused
    gather; slicing the layer out first would materialize it);
    page_table: [S, P] int32 physical page per logical page;
    lengths: [S] int32 valid KV length (the current token included).
    Returns [S, H, D].  H must be a multiple of Hkv (grouped-query
    attention broadcasts each KV head over H/Hkv query heads)."""
    S, H, D = q.shape
    page = k_pool.shape[-3]
    Hkv = k_pool.shape[-2]
    P = page_table.shape[1]
    T = P * page                                   # logical KV capacity
    # gather pages -> the per-sequence logical KV view [S, T, Hkv, D]
    if layer is not None:
        k = k_pool[layer, page_table].reshape(S, T, Hkv, D)
        v = v_pool[layer, page_table].reshape(S, T, Hkv, D)
    else:
        k = k_pool[page_table].reshape(S, T, Hkv, D)
        v = v_pool[page_table].reshape(S, T, Hkv, D)
    if Hkv != H:                                   # grouped-query attn
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("shd,sthd->sht", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    valid = jnp.arange(T)[None, :] < lengths[:, None]       # [S, T]
    s = jnp.where(valid[:, None, :], s, _NEG)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("sht,sthd->shd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_attention_reference(q, k_pool, v_pool, page_table, lengths,
                              scale=None, layer=None):
    """The always-available reference tier (raw jnp arrays in/out)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _paged_attention_impl(q, k_pool, v_pool, page_table, lengths,
                                 scale=float(scale), layer=layer)


def _kernel_takes_layer(fn) -> bool:
    """Whether the registered kernel accepts the ``layer=`` kwarg (the
    stacked-pool contract) — decided by signature inspection, NOT by
    catching TypeError from the call: JAX raises TypeError for genuine
    trace-time shape defects too, and swallowing those would silently
    degrade every decode step to the gather reference."""
    import inspect
    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False
    return "layer" in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values())


def paged_attention_select(q, k_pool, v_pool, page_table, lengths, *,
                           scale, layer=None):
    """Raw-array tier selection: the registered Pallas kernel when the
    gates accept these shapes (incl. per-layer dispatch over a stacked
    5-D pool), else the gather reference.  The serving decode step
    (serving/models.py) calls this inside its compiled step — the hook
    is what makes TPU decode gather-free without touching engine code.

    Two gates compose: the hook-level :func:`paged_attention_supported`
    (backend, dtype, tile alignment) and, when the registered kernel
    publishes one via a ``supported`` attribute, the kernel's own
    stricter capability check (e.g. whole GQA groups) — shapes either
    gate rejects take the reference tier cleanly."""
    pool_shape = tuple(k_pool.shape)
    kernel = _PALLAS_KERNEL
    if paged_attention_supported(tuple(q.shape), pool_shape, q.dtype,
                                 int(pool_shape[-3])):
        gate = getattr(kernel, "supported", None)
        if gate is not None and not gate(tuple(q.shape), pool_shape,
                                         q.dtype, int(pool_shape[-3])):
            pass  # kernel-side gate rejected: reference tier
        elif _kernel_takes_layer(kernel):
            return kernel(q, k_pool, v_pool, page_table, lengths,
                          scale=float(scale), layer=layer)
        elif layer is None:
            # a kernel registered against the PR-7 contract (no layer
            # kwarg) still serves the 4-D un-stacked case
            return kernel(q, k_pool, v_pool, page_table, lengths,
                          scale=float(scale))
    return _paged_attention_impl(q, k_pool, v_pool, page_table,
                                 lengths, scale=float(scale),
                                 layer=layer)


def paged_attention(q, k_pool, v_pool, page_table, lengths, scale=None,
                    layer=None, name=None):
    """Decode-phase paged attention (one query token per sequence).

    Accepts Tensors or arrays; records as op ``paged_attention`` in
    static Programs (priced by the cost model's attention rule).  See
    :func:`paged_attention_reference` for the array contract; ``layer``
    selects one layer of a stacked [L, N, page, Hkv, D] pool inside the
    gather.  The Pallas tier handles per-layer (4-D) pools."""
    q_arr = q.data if isinstance(q, Tensor) else jnp.asarray(q)
    if scale is None:
        scale = 1.0 / math.sqrt(q_arr.shape[-1])
    pool_shape = tuple((k_pool.data if isinstance(k_pool, Tensor)
                        else k_pool).shape)
    if paged_attention_supported(
            q_arr.shape, pool_shape, q_arr.dtype, int(pool_shape[-3])):
        return apply(paged_attention_select, q, k_pool, v_pool,
                     page_table, lengths, op_name="paged_attention",
                     nondiff=True, scale=float(scale), layer=layer)
    return apply(_paged_attention_impl, q, k_pool, v_pool, page_table,
                 lengths, op_name="paged_attention", nondiff=True,
                 scale=float(scale), layer=layer)
