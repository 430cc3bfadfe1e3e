"""Pallas paged-attention decode kernel (Ragged Paged Attention).

The real kernel behind the shape-gated hook
``ops.attention.register_paged_attention_kernel`` that PR 7 left as a
socket: decode-phase attention (one query token per sequence slot)
over a page-table-indexed KV pool, with the page gather done by the
*grid pipeline* instead of an XLA gather.

Dataflow: grid ``(S, P)`` over (sequence slot, logical page) under a
``PrefetchScalarGridSpec`` — the page table and lengths are
scalar-prefetched, and the K/V BlockSpec index maps read
``table[s, p]``, so the pipeline DMAs exactly the physical page each
step needs from HBM into VMEM (gather-free: no [S, T, Hkv, D] logical
view ever materializes, which is what the reference tier pays).  The
online-softmax running (m, l, acc) state lives in VMEM scratch across
the page steps of one slot; positions past ``lengths[s]`` are masked,
so any mix of ragged context lengths shares one compiled kernel.
Grouped-query attention broadcasts each KV head over its query-head
group in-kernel.

Interpret mode (CPU) runs the same kernel for tests and bench;
automatic dispatch stays behind ``paged_attention_supported`` (TPU
backend, or the explicit ``FLAGS_pallas_interpret`` opt-in) plus the
existing tile-alignment gate.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...observability import scopes
from .support import NEG_INF, dot as _dot, dtype_ok, \
    interpret_mode as _interpret_mode, pltpu

__all__ = ["paged_attention_decode", "paged_decode_supported",
           "register"]


def paged_decode_supported(q_shape, kv_pool_shape, dtype,
                           page_size: int) -> bool:
    """Kernel-side capability gate (mirrors ops.attention's hook gate):
    [S, H, D] queries, a 4-D [N, page, Hkv, D] pool or the stacked
    5-D [L, N, page, Hkv, D] one, f32/bf16, the 128-lane head dim and
    8-sublane page alignment, and whole GQA groups."""
    if not dtype_ok(dtype):
        return False
    if len(q_shape) != 3 or len(kv_pool_shape) not in (4, 5):
        return False
    s, h, d = (int(x) for x in q_shape)
    hkv = int(kv_pool_shape[-2])
    if s < 1 or d % 128 or d != int(kv_pool_shape[-1]):
        return False
    if h % max(hkv, 1):
        return False
    if int(page_size) % 8 or int(kv_pool_shape[-3]) != int(page_size):
        return False
    return True


def _decode_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale, page, hkv, group,
                   layered):
    s = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)

    q = q_ref[0].astype(jnp.float32) * scale            # [H, D]
    kv_block = (k_ref[0, 0], v_ref[0, 0]) if layered \
        else (k_ref[0], v_ref[0])                        # [page, Hkv, D]
    k_blk, v_blk = kv_block

    # logical positions of this page, masked by the slot's live length
    pos = p * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid = pos < len_ref[s]                             # [1, page]

    # per-KV-head score/value rows (static python loop: Hkv is small on
    # decode models and Mosaic prefers 2-D dots over batched 3-D ones)
    score_rows = []
    for j in range(hkv):
        qj = q[j * group:(j + 1) * group, :]             # [G, D]
        kj = k_blk[:, j, :]                              # [page, D]
        score_rows.append(_dot(qj.astype(k_blk.dtype), kj,
                               ((1,), (1,))))            # [G, page]
    scores = jnp.concatenate(score_rows, axis=0)         # [H, page]
    scores = jnp.where(valid, scores, NEG_INF)

    m_prev = m_ref[...]                                  # [H, 1]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(scores, -1, keepdims=True))
    e = jnp.exp(scores - m_new)
    e = jnp.where(scores > 0.5 * NEG_INF, e, 0.0)        # fully masked
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(e, -1, keepdims=True)
    acc_rows = []
    for j in range(hkv):
        ej = e[j * group:(j + 1) * group, :]             # [G, page]
        vj = v_blk[:, j, :]                              # [page, D]
        acc_rows.append(_dot(ej.astype(v_blk.dtype), vj,
                             ((1,), (0,))))              # [G, D]
    acc_new = acc_ref[...] * alpha + jnp.concatenate(acc_rows, 0)
    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_new

    @pl.when(p == n_pages - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def paged_attention_decode(q, k_pool, v_pool, page_table, lengths,
                           scale=None, layer=None, interpret=None):
    """Gather-free decode attention; drop-in for
    ``ops.attention.paged_attention_reference`` (same array contract:
    q [S, H, D], pools [N, page, Hkv, D] — or [L, N, page, Hkv, D]
    with ``layer`` — page_table [S, P], lengths [S] -> out [S, H, D])."""
    if interpret is None:
        interpret = _interpret_mode()
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    layered = layer is not None
    S, H, D = (int(x) for x in q.shape)
    page = int(k_pool.shape[-3])
    hkv = int(k_pool.shape[-2])
    group = H // hkv
    P = int(page_table.shape[1])
    table = jnp.asarray(page_table, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)

    if layered:
        li = int(layer)
        kv_spec = pl.BlockSpec(
            (1, 1, page, hkv, D),
            lambda s, p, t, l, _li=li: (_li, t[s, p], 0, 0, 0))
    else:
        kv_spec = pl.BlockSpec(
            (1, page, hkv, D), lambda s, p, t, l: (t[s, p], 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, P),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda s, p, t, l: (s, 0, 0)),
            kv_spec, kv_spec,
        ],
        out_specs=pl.BlockSpec((1, H, D),
                               lambda s, p, t, l: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, D), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    call = pl.pallas_call(
        functools.partial(_decode_kernel, scale=float(scale),
                          page=page, hkv=hkv, group=group,
                          layered=layered),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, D), q.dtype),
        interpret=interpret,
        name=scopes.PAGED_ATTENTION,
    )
    out = call(table, lens, q, k_pool, v_pool)
    from .support import count_kernel_selection
    count_kernel_selection("paged_attention")
    return out


# marks for ops.attention's dispatcher: this kernel runs under
# interpret mode when FLAGS_pallas_interpret opts a CPU process in, and
# publishes its own (stricter) capability gate — paged_attention_select
# consults it on top of the hook-level gate, so shapes the kernel
# cannot carry (ragged GQA groups, mismatched page dims) take the
# reference tier instead of crashing at trace time
paged_attention_decode.interpret_ok = True
paged_attention_decode.supported = paged_decode_supported


def register() -> None:
    """Install this kernel behind the serving decode hook."""
    from ..attention import register_paged_attention_kernel
    register_paged_attention_kernel(paged_attention_decode)
