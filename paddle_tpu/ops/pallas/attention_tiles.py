"""What flash_attention.py, sparse_attention.py and eva_attention.py do
alike over one [block_k, block_q] score tile, written once.  A tile's
mask, the grids, the grouping of heads and every ``pallas_call`` stay in
those files; a static here names a property of the tile, never a caller.

Every kernel holds its score tile as [block_k, block_q]: k positions on
the sublanes, q positions on the lanes.  The per-query softmax state
(m, l, lse, delta) is then a [1, block_q] row of a few vregs instead of
a [block_q, 1] column of block_q / 8, its reductions run down the
sublanes on the VALU, and the (8, block_q) lse blocks need no relayout.
Operands and results keep the natural [L, D] layout: the MXU takes the
transposed operand itself and the [D, block_q] accumulators are
transposed once per grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .support import NEG_INF, dot

# Measured on one v5e (PERF.md, PR 25): 512 x 512 is the fastest of
# {256, 512, 1024}^2 for each of the kernels at [8, 16, 2048, 96]
# bf16 causal and at [64, 12, 512, 64] bf16: smaller tiles reload the
# MXU's weights for fewer rows (256 x 256 takes 1.9x as long), larger
# ones spill more.  The largest shapes flash_attention_supported admits
# compile within Mosaic's default scoped VMEM at this size.
BLOCK = 512


def prescale(x, scale):
    """``x * scale`` rounded back to x's dtype: every kernel scores with
    the same pre-scaled q, so the backward's recomputed p is the
    forward's."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def lead(ref):
    """The index of a block's leading unit dimensions: a kernel body sees
    [rows, width] under as many as its launcher's addressing leaves (two
    of a [B, H, L, D] array, one of [B, L, H*D])."""
    return (0,) * (len(ref.shape) - 2)


def rows(ref, j, block):
    """Rows [j*block, (j+1)*block) of a sequence staged whole, under any
    number of leading unit dimensions."""
    return ref[(*lead(ref), pl.ds(pl.multiple_of(j * block, block), block),
                slice(None))]


def mask_diagonal(s, qi, j, block_q, block_k):
    """Causal mask of a block that straddles the diagonal (aligned path:
    both sequences start at position 0): query c of q block ``qi`` sees
    key r of k block ``j`` iff ``c - r >= j*block_k - qi*block_q``."""
    rel = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
           - jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
    return jnp.where(rel >= j * block_k - qi * block_q, s, NEG_INF)


def mask_window(s, qi, j, block_q, block_k, window, diagonal):
    """Mask of a block that straddles a sliding window's lower edge:
    query c of q block ``qi`` sees key r of k block ``j`` iff the key is
    one of the query's last ``window`` positions, ``c - r < j*block_k -
    qi*block_q + window``; with ``diagonal`` the block straddles the
    diagonal too and takes `mask_diagonal`'s test in the same pass."""
    rel = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
           - jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
    first = j * block_k - qi * block_q
    keep = rel < first + window
    if diagonal:
        keep = keep & (rel >= first)
    return jnp.where(keep, s, NEG_INF)


def kv_spans(qi, block_q, block_k, num_kv, minimum=jnp.minimum,
             window=None):
    """Aligned causal schedule of q block ``qi``: k blocks [0, full) lie
    wholly below the diagonal, [full, end) straddle it, the rest are
    invisible.  ``minimum=min`` gives Python ints for the counters.

    ``window`` (a count of positions: query t sees keys s with
    ``t - window < s <= t``) adds the runs below the window: k blocks
    [0, start) hold no key any query of the block sees and are skipped,
    [start, inside) straddle the window's lower edge ("window", or
    "both" where they straddle the diagonal as well), and from
    ``inside`` on every query sees every key a block holds as far as the
    lower edge goes."""
    full = minimum((qi * block_q + 1) // block_k, num_kv)
    end = minimum(pl.cdiv((qi + 1) * block_q, block_k), num_kv)
    if window is None:
        return (0, full, None), (full, end, "diagonal")
    maximum = max if minimum is min else jnp.maximum
    start = minimum(maximum(qi * block_q - window + 1, 0) // block_k, num_kv)
    inside = minimum(
        pl.cdiv(maximum((qi + 1) * block_q - window, 0), block_k), num_kv)
    edge, both = minimum(inside, full), maximum(full, minimum(inside, end))
    return ((start, edge, "window"), (edge, full, None),
            (full, both, "both"), (both, end, "diagonal"))


def q_spans(kj, block_q, block_k, num_q, window=None):
    """The same schedule seen from k block ``kj``: q blocks
    [start, full) straddle the diagonal, [full, num_q) lie wholly below.
    With ``window``, q blocks [inside, stop) straddle its lower edge and
    those from ``stop`` on see nothing of the block: skipped."""
    start = jnp.minimum((kj * block_k) // block_q, num_q)
    full = jnp.minimum(pl.cdiv((kj + 1) * block_k - 1, block_q), num_q)
    if window is None:
        return (start, full, "diagonal"), (full, num_q, None)
    inside = jnp.minimum((kj * block_k + window) // block_q, num_q)
    stop = jnp.minimum(
        pl.cdiv((kj + 1) * block_k - 1 + window, block_q), num_q)
    edge, clear = jnp.minimum(full, inside), jnp.maximum(full, inside)
    return ((start, edge, "diagonal"), (edge, full, "both"),
            (full, clear, None), (clear, stop, "window"))


def block_loops(body, carry, num_blocks, causal, aligned, causal_spans):
    """Run ``body(i, carry, mask)`` over one grid step's blocks with the
    mask each needs: none without ``causal``, the position mask on every
    block of the ring path, and in the aligned causal path the
    ``(lo, hi, mask)`` runs of ``causal_spans``: the diagonal mask only
    where a block straddles the diagonal, invisible blocks skipped (a
    windowed schedule's runs carry its masks the same way)."""
    if not causal:
        spans = ((0, num_blocks, None),)
    elif not aligned:
        spans = ((0, num_blocks, "positions"),)
    else:
        spans = causal_spans
    for lo, hi, mask in spans:
        carry = jax.lax.fori_loop(
            lo, hi, functools.partial(body, mask=mask), carry)
    return carry


def online_step(carry, s, v, *, may_hide_query=False, drop=None):
    """One online-softmax step over a [keys, block] score tile ``s`` and
    its values ``v``; ``carry`` is (m, l, out^T).  ``may_hide_query``: a
    tile may hold none of a query's keys; s - m_new is 0 there, and it is
    zeroed instead of attending uniformly.  The aligned causal path needs
    no guard: key 0 is visible to every query and in the first block, so
    m is finite before any masked score.  Under a sliding window the walk
    starts at the window's lower edge, where a block's later queries see
    none of its keys: the blocks that straddle that edge take the guard.
    ``drop``: dropout's hook, p -> dropped u for the values alone (the
    denominator stays UNdropped)."""
    m, l, acc = carry
    m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
    seen = s > 0.5 * NEG_INF if may_hide_query else None
    p = jnp.exp(s - m_new)
    if seen is not None:
        p = jnp.where(seen, p, 0.0)
    alpha = jnp.exp(m - m_new)
    l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
    u = p if drop is None else drop(p)
    acc = acc * alpha + dot(v, u.astype(v.dtype), ((0,), (0,)))
    return m_new, l, acc


def p_ds(s, lse, do, v, delta, *, may_hide_query=False, drop=None):
    """(u, dS / scale) of one [BK, BQ] block from its scores.  With
    dropout off u is p and dS = p * (dP - delta); with it on,
    dS = u * dP - p * delta (the denominator is undropped)."""
    seen = s > 0.5 * NEG_INF if may_hide_query else None
    p = jnp.exp(s - lse)
    if seen is not None:
        p = jnp.where(seen, p, 0.0)
    dp = dot(v, do, ((1,), (1,)))                         # [BK, BQ]
    if drop is None:
        return p, p * (dp - delta)
    u = drop(p)
    return u, u * dp - p * delta


def dq_zero(accs):
    """A walk's float32 accumulators of a head's dQ^T, [L / block, width,
    block] a dQ output: zeroed at the head's first key block, added to
    a pair, emitted at its last (the walk's ``pl.when``s say where)."""
    for acc in accs:
        acc[...] = jnp.zeros(acc.shape, acc.dtype)


def dq_add(accs, keys, i, ds):
    """q block ``i``'s dq^T [width, BQ] gains ``ds`` [BK, BQ] against
    each accumulator's key rows [BK, width]."""
    for acc, key in zip(accs, keys):
        acc[i] += dot(key, ds, ((0,), (0,)))


def dq_emit(refs, accs, num_q, block_q, scale):
    """The head's dQ blocks [..., L, width] from their accumulators."""
    def emit(i, carry):
        at = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        # s was taken against scale * q: the chain rule's scale, once,
        # and each [D, BQ] block transposed once a head
        for ref, acc in zip(refs, accs, strict=True):
            ref[(*lead(ref), at, slice(None))] = (
                acc[i] * scale).T.astype(ref.dtype)
        return carry

    jax.lax.fori_loop(0, num_q, emit, 0)


# A per-query row (lse, delta) crosses the kernel boundary as (8, block):
# positions on the LANE dim, replicated over 8 sublanes, the minimal
# Mosaic-legal tile.  A trailing unit dim ([..., Lq, 1]) would make XLA
# tile-pad the HBM buffer 1 -> 128 lanes (128x memory: measured ~200
# MB/layer residual at BERT-base scale).  Elsewhere it is [..., L].

def delta(do, out):
    """sum(dO * out) over the width, in float32."""
    return jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), -1)


def rows8(x):
    """[..., L] -> [..., 8, L], as a kernel takes it."""
    return jnp.broadcast_to(x[..., None, :], x.shape[:-1] + (8, x.shape[-1]))


def write_row8(ref, row):
    """A kernel's [1, block] row into its (8, block) block."""
    ref[lead(ref)] = jnp.broadcast_to(row, (8, row.shape[-1]))
