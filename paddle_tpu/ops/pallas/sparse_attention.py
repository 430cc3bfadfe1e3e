"""Attention over a learned, per-query set of keys (DeepSeek Sparse
Attention, as Keye-VL-2.0's ``sa_config`` has it) over grouped-query
heads: Pallas TPU kernels (forward + custom-vjp backward) and the blocked
XLA path they are checked against.

An indexer of J light heads against ONE shared key scores every earlier
position of a row,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (float32),

the ``topk`` largest a query are kept (equal scores: the lower position;
every visible one while there are no more than ``topk``), and the A
query heads attend to those keys only, G = A / KV of them sharing each
key/value head.  The indexer learns from its own loss: per query the KL
divergence from the main attention's probabilities summed over heads
(divided by A, detached) to the softmax of I over the selected set.

Gathering 2048 selected rows of 4 key/value heads a query moves 4 MB a
token; the masked dense causal pass over the same row is cheaper by an
order of magnitude at these lengths, so the selection is a **mask**
shared by all heads: ``[B, keys, queries]`` int8, keys on the major axis
because every kernel here holds its score tile as [block_k, block_q]
like the flash kernels (attention_tiles.py has what the family shares).

Kernels (five), all [block, block] tiles of 512:

- ``dsa_scores``: I^T tiles, written below and on the diagonal only;
- ``dsa_threshold``: the topk-th largest score a query by bisection over
  the float's ordered bit pattern, a column of scores staged in VMEM (32
  counting passes); where the scores equal to it are more than fit, the
  position of the last one kept (a second bisection, over positions);
  and the logsumexp of the selected scores;
- ``sparse_fwd``: the flash forward kernel with the mask tile in place
  of the causal rule and a grid whose innermost axis walks the G query
  heads of one key/value head, so that k, v and the mask column are
  fetched once a group;
- ``sparse_bwd_dkv``: all of the backward in one walk (key block by key
  block inside a head, head by head inside a group): dk / dv sum over
  the group in VMEM, and each pair's ``ds`` makes its part of the head's
  dQ^T too, as the flash walk's does;
- ``dsa_kl``: the head-summed probabilities (one more QK pass over all
  A heads), the KL term and, in the same pass where the call is
  differentiated, its gradient to qI, kI and w up to the loss's own
  cotangent: the target is detached, so the gradient is known the moment
  the value is (the index scores are recomputed tile by tile, never read
  back).  The three gradients carry ``scopes.RESIDUALS`` names:
  ``parallel.recompute`` keeps them, and its replay runs no ``dsa_kl``.

The mask itself is one elementwise XLA pass over the scores: above the
threshold, or equal to it at a position up to the last one kept.

Layouts: q [B, T, A, D], k / v [B, T, KV, D], qI [B, T, J, d], w
[B, T, J], kI [B, T, d] in; [B, heads, T, .] inside.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...observability import scopes
from .attention_tiles import (BLOCK, delta as _delta, dq_add, dq_emit,
                              dq_zero, online_step, p_ds, prescale, rows,
                              rows8, write_row8)
from .support import (NEG_INF, count_kernel_selection, dot as _dot,
                      interpret_mode as _interpret, name_residuals, pltpu)

_VMEM_LIMIT = 100 * 1024 * 1024     # of a v5e's 128 MiB; columns are staged
_XLA_ROWS = 512                     # queries a block of the XLA path


def sparse_attention_supported(q_shape, k_shape, dtype) -> bool:
    """Shapes the attention kernels handle; everything else takes the XLA
    path.  On the chip heads must fill whole lane tiles and a row's keys
    must fit VMEM staged whole; interpret mode only needs blocks that
    tile."""
    if len(q_shape) != 4 or dtype not in (jnp.float32, jnp.bfloat16):
        return False
    _, T, A, D = q_shape
    if A % k_shape[2] or T % min(BLOCK, T):
        return False
    if _interpret():
        return True
    return (D % 128 == 0 and T % BLOCK == 0
            and T * D * jnp.dtype(dtype).itemsize <= 2 * 1024 * 1024)


def dsa_indexer_supported(index_q_shape, dtype) -> bool:
    """The same for the indexer's kernels: index_query [B, T, J, d]."""
    if len(index_q_shape) != 4 or dtype not in (jnp.float32, jnp.bfloat16):
        return False
    _, T, _, d = index_q_shape
    if T % min(BLOCK, T):
        return False
    return _interpret() or (d % 64 == 0 and T % BLOCK == 0)


# ---------------------------------------------------------------------------
# the selection rule, shared by both paths
# ---------------------------------------------------------------------------

def _causal(Tk, Tq, first=0):
    """[Tk, Tq]: key s is visible to query first + t."""
    return (jnp.arange(Tk)[:, None] <= first + jnp.arange(Tq)[None, :])


def _select_exact(IT, thr, topk, visible):
    """The mask of scores ``IT`` [..., Tk, Tq] (keys first) given each
    query's ``topk``-th largest visible score ``thr`` [..., 1, Tq]: every
    score above it, and of the equal ones the first (lowest positions)
    that fill the ``topk``."""
    above = (IT > thr) & visible
    equal = (IT == thr) & visible
    need = topk - jnp.sum(above, -2, keepdims=True, dtype=jnp.int32)
    rank = jnp.cumsum(equal.astype(jnp.int32), axis=-2)
    return above | (equal & (rank <= need))


# ---------------------------------------------------------------------------
# the blocked XLA path
# ---------------------------------------------------------------------------

def _blocks(x, rows):
    """[T, ...] -> [T / rows, rows, ...]."""
    return x.reshape((x.shape[0] // rows, rows) + x.shape[1:])


def _xla_rows(T):
    return _XLA_ROWS if T % _XLA_ROWS == 0 else T


def _scores_block(qIb, wb, kI):
    """qIb [n, J, d], wb [n, J], kI [T, d] -> I^T [T, n] float32."""
    s = jnp.einsum("sd,tjd->jst", kI, qIb,
                   preferred_element_type=jnp.float32)
    return jnp.sum(wb.astype(jnp.float32).T[:, None, :] * jax.nn.relu(s), 0)


def dsa_select_xla(qI, w, kI, topk):
    """-> (mask [B, Tk, Tq] int8, lseI [B, Tq]: the logsumexp of each
    query's selected scores), by ``lax.top_k`` a block of queries."""
    B, T = qI.shape[:2]
    rows = _xla_rows(T)
    qI, w, kI = (jax.lax.stop_gradient(a) for a in (qI, w, kI))

    def block(args):
        qIb, wb, kI_row, first = args
        visible = _causal(T, rows, first)
        with jax.named_scope(scopes.DSA_INDEXER):
            IT = jnp.where(visible, _scores_block(qIb, wb, kI_row), -jnp.inf)
        with jax.named_scope(scopes.DSA_SELECT):
            kth = jax.lax.top_k(IT.T, min(topk, T))[0][:, -1]
            sel = _select_exact(IT, kth[None, :], topk, visible)
            return sel, jax.nn.logsumexp(jnp.where(sel, IT, -jnp.inf), axis=0)

    def row(args):
        qI_row, w_row, kI_row = args
        m, lseI = jax.lax.map(lambda a: block((*a[:2], kI_row, a[2])), (
            _blocks(qI_row, rows), _blocks(w_row, rows),
            jnp.arange(0, T, rows)))                      # [nb, Tk, rows]
        return jnp.moveaxis(m, 0, 1).reshape(T, T), lseI.reshape(T)

    mask, lseI = jax.lax.map(row, (qI, w, kI))
    return mask.astype(jnp.int8), lseI


def _main_scores(qb, k, scale):
    """qb [n, A, D], k [T, KV, D] -> [KV, G, T, n] float32 scores."""
    n, A, D = qb.shape
    KV = k.shape[1]
    return jnp.einsum("sgd,tgnd->gnst", k, qb.reshape(n, KV, A // KV, D),
                      preferred_element_type=jnp.float32) * scale


def sparse_attention_xla(q, k, v, mask):
    """-> (out [B, T, A, D], lse [B, A, T] float32, detached): a block of
    queries' scores (times D^-1/2) against the whole row at once."""
    B, T, A, D = q.shape
    KV = k.shape[2]
    scale = 1.0 / math.sqrt(D)
    rows = _xla_rows(T)

    @jax.checkpoint
    def block(qb, k_row, v_row, mb):
        s = jnp.where(mb[None, None] != 0, _main_scores(qb, k_row, scale),
                      -jnp.inf)
        lse = jax.nn.logsumexp(s, axis=2)                 # [KV, G, n]
        p = jnp.exp(s - lse[:, :, None, :]).astype(v_row.dtype)
        o = jnp.einsum("gnst,sgd->tgnd", p, v_row,
                       preferred_element_type=jnp.float32)
        return o.reshape(rows, A, D).astype(qb.dtype), lse.reshape(A, rows)

    def row(args):
        q_row, k_row, v_row, m_row = args
        o, lse = jax.lax.map(
            lambda a: block(a[0], k_row, v_row, a[1]),
            (_blocks(q_row, rows),
             jnp.moveaxis(m_row.reshape(T, T // rows, rows), 1, 0)))
        return o.reshape(T, A, D), jnp.moveaxis(lse, 0, 1).reshape(A, T)

    out, lse = jax.lax.map(row, (q, k, v, mask))
    return out, jax.lax.stop_gradient(lse)


def dsa_kl_xla(qI, w, kI, mask, lseI, q, k, lse):
    """The indexer's loss, mean over every query of the batch.  Gradients
    reach qI, w and kI only: the main attention's probabilities are
    detached."""
    B, T, A, D = q.shape
    scale = 1.0 / math.sqrt(D)
    rows = _xla_rows(T)
    q, k, lse = (jax.lax.stop_gradient(a) for a in (q, k, lse))

    @jax.checkpoint
    def block(qIb, wb, kI_row, qb, k_row, lseb, mb):
        sel = mb != 0
        # lseI is not handed in: its gradient is part of the softmax's
        logpI = jax.nn.log_softmax(
            jnp.where(sel, _scores_block(qIb, wb, kI_row), -jnp.inf), axis=0)
        s = _main_scores(qb, k_row, scale)
        p = jnp.where(sel, jnp.exp(s - lseb.reshape(
            s.shape[0], s.shape[1], 1, rows)), 0.0)
        ph = jnp.sum(p, (0, 1)) / A                       # [T, n]
        live = sel & (ph > 0)
        return jnp.sum(jnp.where(live, ph * (
            jnp.log(jnp.where(live, ph, 1.0))
            - jnp.where(live, logpI, 0.0)), 0.0))

    def row(args):
        qI_row, w_row, kI_row, q_row, k_row, lse_row, m_row = args
        return jnp.sum(jax.lax.map(
            lambda a: block(a[0], a[1], kI_row, a[2], k_row, a[3], a[4]),
            (_blocks(qI_row, rows), _blocks(w_row, rows),
             _blocks(q_row, rows),
             jnp.moveaxis(lse_row.reshape(A, T // rows, rows), 1, 0),
             jnp.moveaxis(m_row.reshape(T, T // rows, rows), 1, 0))))

    return jnp.sum(jax.lax.map(row, (qI, w, kI, q, k, lse, mask))) / (B * T)


# ---------------------------------------------------------------------------
# kernels: the indexer
# ---------------------------------------------------------------------------

def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _index_tile(kI, qI_ref, w_ref, heads):
    """I^T tile [BK, BQ] float32 of key rows ``kI`` [BK, d] against the
    query block held in ``qI_ref`` [1, J, BQ, d] / ``w_ref`` [1, J, 8, BQ]
    (each head's weights a row, on 8 sublanes like lse)."""
    def head(j, acc):
        s = _dot(kI, qI_ref[0, j], ((1,), (1,)))
        return acc + w_ref[0, j][0:1, :] * jnp.maximum(s, 0.0)

    bq = qI_ref.shape[2]
    return jax.lax.fori_loop(
        0, heads, head, jnp.zeros((kI.shape[0], bq), jnp.float32))


def _scores_kernel(qI_ref, w_ref, kI_ref, it_ref, *, heads):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j <= i)
    def _():
        it_ref[0] = _index_tile(kI_ref[0], qI_ref, w_ref, heads)


def _dsa_scores(qIt, wt, kI, block, interpret):
    """qIt [B, J, T, d], wt [B, J, 8, T] float32, kI [B, T, d] -> I^T
    [B, Tk, Tq] float32; the blocks above the diagonal are never
    written (nor read: every reader masks by position)."""
    B, J, T, d = qIt.shape
    n = T // block
    return pl.pallas_call(
        functools.partial(_scores_kernel, heads=J),
        grid=(B, n, n),
        in_specs=[
            pl.BlockSpec((1, J, block, d), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, J, 8, block), lambda b, i, j: (b, 0, 0, i)),
            # a block above the diagonal maps to the diagonal one, which
            # is resident: nothing is fetched or written back for it
            pl.BlockSpec((1, block, d),
                         lambda b, i, j: (b, jnp.minimum(j, i), 0)),
        ],
        out_specs=pl.BlockSpec((1, block, block),
                               lambda b, i, j: (b, jnp.minimum(j, i), i)),
        out_shape=jax.ShapeDtypeStruct((B, T, T), jnp.float32),
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name=scopes.DSA_SCORES,
    )(qIt, wt, kI)


_KEY_OF_NEG_INF = -2 ** 31 + 0x7FFFFF     # _ordered(-inf)


def _ordered(x):
    """float32 -> int32 whose signed order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ (jax.lax.shift_right_arithmetic(bits, 31) & 0x7FFFFFFF)


def _threshold_kernel(it_ref, thr_ref, cut_ref, lse_ref, keys_ref, *, topk,
                      block, chunk, pos_bits):
    """One block of queries: its column of scores [Tk, BQ] is turned into
    ordered keys once (positions past the query: the key of -inf), then
    the topk-th largest a query is found bit by bit.  Where the scores
    equal to it are more than the ``topk`` has room for (two float32
    scores of some 4,000 do coincide at the threshold about once a row of
    8192), a second bisection finds the position up to which they are
    kept."""
    i = pl.program_id(1)
    bq = it_ref.shape[2]
    rows = (i + 1) * (block // chunk)         # chunks at or below the diagonal
    least = jnp.int32(_KEY_OF_NEG_INF)

    def at(r):
        return pl.ds(pl.multiple_of(r * chunk, chunk), chunk)

    def key_pos(r):
        return r * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, bq), 0)

    def load(r, _):
        pos_q = i * block + jax.lax.broadcasted_iota(jnp.int32,
                                                     (chunk, bq), 1)
        keys_ref[at(r), :] = jnp.where(
            key_pos(r) <= pos_q, _ordered(it_ref[0, at(r), :]), least)
        return 0

    jax.lax.fori_loop(0, rows, load, 0)

    def count(hit):
        """[1, BQ]: how many keys of each query's column ``hit`` takes."""
        def body(r, acc):
            return acc + jnp.sum(hit(keys_ref[at(r), :], r).astype(jnp.int32),
                                 axis=0, keepdims=True)
        return jax.lax.fori_loop(0, rows, body,
                                 jnp.zeros((1, bq), jnp.int32))

    def reach(cand):
        return count(lambda keys, r: keys >= cand)

    # the sign first, then the 31 bits below it, most significant first:
    # the largest key that at least ``topk`` keys reach.  A column with
    # fewer visible scores than ``topk`` ends at the key of -inf: all kept
    zero = jnp.zeros((1, bq), jnp.int32)
    prefix = jnp.where(reach(zero) >= topk, zero, jnp.int32(-2 ** 31))

    def bit(b, prefix):
        cand = prefix | jax.lax.shift_left(jnp.int32(1), 30 - b)
        return jnp.where(reach(cand) >= topk, cand, prefix)

    prefix = jnp.maximum(jax.lax.fori_loop(0, 31, bit, prefix), least)
    # room left for the scores equal to the threshold, and the position of
    # the last of them that fits: everything where all of them fit
    room = topk - count(lambda keys, r: keys > prefix)
    tied = reach(prefix) - (topk - room) > room

    def last_kept(_):
        def bit(b, cut):
            cand = cut + jax.lax.shift_left(jnp.int32(1), pos_bits - 1 - b)
            below = count(lambda keys, r: (keys == prefix)
                          & (key_pos(r) < cand))
            return jnp.where(below < room, cand, cut)
        return jax.lax.fori_loop(0, pos_bits, bit, zero)

    everything = jnp.full((1, bq), 2 ** pos_bits, jnp.int32)
    cut = jax.lax.cond(jnp.max(tied.astype(jnp.int32)) > 0,
                       lambda _: jnp.where(tied, last_kept(0), everything),
                       lambda _: everything, 0)

    def stats(r, carry):
        m, l = carry
        keys = keys_ref[at(r), :]
        kept = (keys > least) & ((keys > prefix) | (
            (keys == prefix) & (key_pos(r) <= cut)))
        x = jnp.where(kept, it_ref[0, at(r), :], NEG_INF)
        m_new = jnp.maximum(m, jnp.max(x, axis=0, keepdims=True))
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.where(kept, jnp.exp(x - m_new), 0.0), axis=0, keepdims=True)
        return m_new, l

    m, l = jax.lax.fori_loop(
        0, rows, stats, (jnp.full((1, bq), NEG_INF, jnp.float32),
                         jnp.zeros((1, bq), jnp.float32)))
    # back to the float
    bits = prefix ^ (jax.lax.shift_right_arithmetic(prefix, 31) & 0x7FFFFFFF)
    write_row8(thr_ref, jax.lax.bitcast_convert_type(bits, jnp.float32))
    write_row8(cut_ref, cut)
    write_row8(lse_ref, m + jnp.log(l))


def _dsa_threshold(IT, topk, block, interpret):
    """I^T [B, Tk, Tq] -> (thr, cut, lseI), each [B, Tq]: the topk-th
    largest visible score a query (-inf where fewer are visible), the last
    position at which a score equal to it is still kept, and the
    logsumexp of the kept scores."""
    B, T, _ = IT.shape
    bq = min(block, 256)            # a column of 8192 keys: 8 MB staged
    row = pl.BlockSpec((1, 8, bq), lambda b, i: (b, 0, i))
    thr, cut, lse = pl.pallas_call(
        functools.partial(_threshold_kernel, topk=topk, block=bq, chunk=bq,
                          pos_bits=max((T - 1).bit_length(), 1)),
        grid=(B, T // bq),
        in_specs=[pl.BlockSpec((1, T, bq), lambda b, i: (b, 0, i))],
        out_specs=[row, row, row],
        out_shape=[jax.ShapeDtypeStruct((B, 8, T), jnp.float32),
                   jax.ShapeDtypeStruct((B, 8, T), jnp.int32),
                   jax.ShapeDtypeStruct((B, 8, T), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((T, bq), jnp.int32)],
        compiler_params=_params("parallel", "parallel"),
        interpret=interpret,
        name=scopes.DSA_THRESHOLD,
    )(IT)
    return thr[:, 0], cut[:, 0], lse[:, 0]


def _head_rows(w):
    """w [B, T, J] -> [B, J, 8, T] float32: a head's weights as a row
    the kernels index by head."""
    return rows8(jnp.swapaxes(w.astype(jnp.float32), 1, 2))


def dsa_select(qI, w, kI, topk, block=None):
    """-> (mask [B, Tk, Tq] int8, lseI [B, Tq]: the logsumexp of each
    query's selected scores) through the kernels, a row of the batch at
    a time: a row's float32 scores (0.27 GB at 8192) live only until its
    mask is made, in one elementwise pass."""
    T = qI.shape[1]
    block = min(block or BLOCK, T)
    interpret = _interpret()
    count_kernel_selection("dsa_indexer")
    # the selection carries no gradient, and a kernel has no rule to skip
    qI, w, kI = (jax.lax.stop_gradient(a) for a in (qI, w, kI))
    key_pos = jnp.arange(T)[:, None]

    def row(args):
        qIt, wt, kI_row = (a[None] for a in args)
        with jax.named_scope(scopes.DSA_INDEXER):
            IT = _dsa_scores(qIt, wt, kI_row, block, interpret)
        with jax.named_scope(scopes.DSA_SELECT):
            thr, cut, lseI = (a[0] for a in _dsa_threshold(
                IT, topk, block, interpret))
            mask = ((IT[0] > thr[None, :])
                    | ((IT[0] == thr[None, :]) & (key_pos <= cut[None, :]))
                    ) & _causal(T, T)
        return mask.astype(jnp.int8), lseI

    return jax.lax.map(row, (jnp.swapaxes(qI, 1, 2), _head_rows(w), kI))


# ---------------------------------------------------------------------------
# kernels: the main attention under the mask
# ---------------------------------------------------------------------------

def _masked_scores(k, q, mask_tile):
    """[BK, BQ] float32 scores of pre-scaled q against k where the mask
    tile (int8) is set, NEG_INF elsewhere."""
    s = _dot(k, q, ((1,), (1,)))
    return jnp.where(mask_tile.astype(jnp.int32) != 0, s, NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, *, scale,
                block):
    i = pl.program_id(2)
    q = prescale(q_ref[0, 0], scale)                      # [BQ, D]
    bq, d = q.shape

    def body(j, carry):
        s = _masked_scores(rows(k_ref, j, block), q,
                           rows(mask_ref, j, block))
        return online_step(carry, s, rows(v_ref, j, block),
                           may_hide_query=True)

    m, l, acc = jax.lax.fori_loop(0, i + 1, body, (
        jnp.full((1, bq), NEG_INF, jnp.float32),
        jnp.zeros((1, bq), jnp.float32), jnp.zeros((d, bq), jnp.float32)))
    # every query keeps at least one key (itself or better): l > 0
    o_ref[0, 0] = (acc / l).T.astype(o_ref.dtype)
    write_row8(lse_ref, m + jnp.log(l))


def _bwd_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, scale,
                block, num_q):
    """The backward walk: key block ``j`` of one head against the query
    blocks at or after it.  Each pair's ``ds`` meets q for dK, and k for
    that query block's dQ^T, which adds up in float32 over the head's key
    blocks (``dq_acc`` [T / block, D, block]); dK and dV add up over the
    group's heads in ``dk_acc`` / ``dv_acc`` [T, D] and leave with the
    group's last head.  No second walk recomputes the scores, p and dP."""
    h, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        dq_zero([dq_acc])

        @pl.when(h == 0)
        def _():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

    k = k_ref[0, 0]                                       # [BK, D]
    v = v_ref[0, 0]

    def body(i, carry):
        dk, dv = carry
        q = prescale(rows(q_ref, i, block), scale)
        do = rows(do_ref, i, block)
        cols = pl.ds(pl.multiple_of(i * block, block), block)
        s = _masked_scores(k, q, mask_ref[0, :, cols])
        p, ds = p_ds(s, lse_ref[0, 0, 0:1, cols], do, v,
                     delta_ref[0, 0, 0:1, cols], may_hide_query=True)
        ds = ds.astype(q.dtype)
        dq_add([dq_acc], (k,), i, ds)                     # [D, BQ]
        return (dk + _dot(ds, q, ((1,), (0,))),
                dv + _dot(p.astype(do.dtype), do, ((1,), (0,))))

    zero = jnp.zeros(k.shape, jnp.float32)
    dk, dv = jax.lax.fori_loop(j, num_q, body, (zero, zero))
    at = pl.ds(pl.multiple_of(j * block, block), block)
    dk_acc[at, :] += dk
    dv_acc[at, :] += dv

    @pl.when(h == pl.num_programs(2) - 1)
    def _():
        dk_ref[0, 0, at, :] = dk_acc[at, :].astype(dk_ref.dtype)
        dv_ref[0, 0, at, :] = dv_acc[at, :].astype(dv_ref.dtype)

    @pl.when(j == num_q - 1)
    def _():
        dq_emit([dq_ref], [dq_acc], num_q, block, scale)


def _group_specs(T, D, G, block):
    """Block specs on the grid (batch, key/value head, block, head of the
    group): a query block of one head, a key/value head staged whole, the
    mask column of the query block, an (8, block) row of lse."""
    q_block = pl.BlockSpec((1, 1, block, D),
                           lambda b, g, i, h: (b, g * G + h, i, 0))
    kv_whole = pl.BlockSpec((1, 1, T, D), lambda b, g, i, h: (b, g, 0, 0))
    mask_col = pl.BlockSpec((1, T, block), lambda b, g, i, h: (b, 0, i))
    row = pl.BlockSpec((1, 1, 8, block),
                       lambda b, g, i, h: (b, g * G + h, 0, i))
    return q_block, kv_whole, mask_col, row


def _fwd(q, k, v, mask, scale, block):
    """q [B, A, T, D], k / v [B, KV, T, D] -> (out, lse [B, A, T])."""
    B, A, T, D = q.shape
    KV = k.shape[1]
    G = A // KV
    q_block, kv_whole, mask_col, row = _group_specs(T, D, G, block)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, block=block),
        grid=(B, KV, T // block, G),
        in_specs=[q_block, kv_whole, kv_whole, mask_col],
        out_specs=[q_block, row],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, A, 8, T), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=_interpret(),
        name=scopes.SPARSE_FWD,
    )(q, k, v, mask)
    return out, lse[:, :, 0, :]


def _bwd(q, k, v, mask, out, lse, do, scale, block):
    """One kernel -> (dq, dk, dv), on the grid (batch, key/value head, head
    of the group, key block).  A head's q, dO, lse and delta and its dQ
    are whole [T, .] blocks whose index holds over its key blocks; dk / dv
    are whole blocks whose index holds over the group; k, v and the
    mask's [block, T] strip come in a step.  At [.., 8192, 128] bfloat16,
    counted as ``flash_attention._staging`` counts (two buffers a block):
    q, dO and dQ 3 x 2 x 2 MiB, dk and dv 2 x 2 x 2, the strip 2 x 4,
    lse and delta 1, k and v 0.5, the float32 accumulators 4 + 4 + 4:
    41.5 MiB before the [block, block] float32 tiles."""
    from ...utils import monitor
    B, A, T, D = q.shape
    KV = k.shape[1]
    G = A // KV
    delta = _delta(do, out)

    def head(b, g, h, j):
        return b, g * G + h, 0, 0

    q_whole = pl.BlockSpec((1, 1, T, D), head)
    row_whole = pl.BlockSpec((1, 1, 8, T), head)
    kv_block = pl.BlockSpec((1, 1, block, D), lambda b, g, h, j: (b, g, j, 0))
    kv_whole = pl.BlockSpec((1, 1, T, D), lambda b, g, h, j: (b, g, 0, 0))
    monitor.stat_add("pallas.sparse.bwd_fused")
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, block=block,
                          num_q=T // block),
        grid=(B, KV, G, T // block),
        in_specs=[q_whole, kv_block, kv_block,
                  pl.BlockSpec((1, block, T), lambda b, g, h, j: (b, j, 0)),
                  q_whole, row_whole, row_whole],
        out_specs=[q_whole, kv_whole, kv_whole],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((T // block, D, block), jnp.float32),
                        pltpu.VMEM((T, D), jnp.float32),
                        pltpu.VMEM((T, D), jnp.float32)],
        # dq is summed over a head's key blocks, dk / dv over the group too
        compiler_params=_params("parallel", "parallel", "arbitrary",
                                "arbitrary"),
        interpret=_interpret(),
        name=scopes.SPARSE_BWD_DKV,
    )(q, k, v, mask, do, rows8(lse), rows8(delta))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _sparse(q, k, v, mask, scale, block):
    return _fwd(q, k, v, mask, scale, block)


def _sparse_fwd(q, k, v, mask, scale, block):
    # named before the kernel reads them: one buffer serves the forward
    # kernel and the backward walk, and a replay that is handed all six
    # runs nothing upstream of the call for them (the selection least of
    # all)
    q, k, v, mask = name_residuals(q, k, v, mask,
                                   names=scopes.SPARSE_OPERANDS)
    out, lse = name_residuals(*_fwd(q, k, v, mask, scale, block))
    return (out, lse), (q, k, v, mask, out, lse)


def _sparse_bwd(scale, block, res, cts):
    q, k, v, mask, out, lse = res
    do, _ = cts         # lse is handed on detached: it carries no cotangent
    dq, dk, dv = _bwd(q, k, v, mask, out, lse, do, scale, block)
    return dq, dk, dv, None


_sparse.defvjp(_sparse_fwd, _sparse_bwd)


def sparse_attention(q, k, v, mask, block=None):
    """q [B, T, A, D], k / v [B, T, KV, D], mask [B, Tk, Tq] int8 ->
    (out [B, T, A, D], lse [B, A, T] float32, detached) through the
    kernels; scores times D^-1/2."""
    T, D = q.shape[1], q.shape[3]
    scale = 1.0 / math.sqrt(D)
    count_kernel_selection("sparse_attention")
    qt, kt, vt = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
    out, lse = _sparse(qt, kt, vt, mask, scale, min(block or BLOCK, T))
    return jnp.swapaxes(out, 1, 2), jax.lax.stop_gradient(lse)


# ---------------------------------------------------------------------------
# kernel: the indexer's loss with its gradient
# ---------------------------------------------------------------------------

def _kl_tile(j, q_ref, k_ref, lse_ref, qI_ref, w_ref, kI_ref, lseI,
             mask_ref, *, scale, block, heads, idx_heads, group):
    """For key block ``j`` of the query block the refs hold: (the tile's
    KL summed over keys [1, BQ], pI - ph on the selected pairs [BK, BQ],
    kI's rows [BK, d])."""
    sel = rows(mask_ref, j, block).astype(jnp.int32) != 0
    kI = rows(kI_ref, j, block)
    logpI = _index_tile(kI, qI_ref, w_ref, idx_heads) - lseI

    def head(h, acc):
        q = prescale(q_ref[0, h], scale)
        k = k_ref[0, h // group,
                  pl.ds(pl.multiple_of(j * block, block), block), :]
        s = _dot(k, q, ((1,), (1,)))
        return acc + jnp.exp(s - lse_ref[0, h][0:1, :])

    ph = jnp.where(sel, jax.lax.fori_loop(
        0, heads, head, jnp.zeros(sel.shape, jnp.float32)) / heads, 0.0)
    live = ph > 0
    kl = jnp.where(live, ph * (jnp.log(jnp.where(live, ph, 1.0)) - logpI),
                   0.0)
    dI = jnp.where(sel, jnp.exp(logpI) - ph, 0.0)
    return jnp.sum(kl, axis=0, keepdims=True), dI, kI


def _kl_kernel(q_ref, k_ref, lse_ref, qI_ref, w_ref, kI_ref, lseI_ref,
               mask_ref, kl_ref, *grad_refs, **statics):
    """One query block's KL terms and, where ``grad_refs`` (dqI, dw, dkI
    and a VMEM accumulator for each) are handed in, d KL / d (qI, w, kI)
    of the block from the same tiles, up to the loss's own cotangent:
    dI = pI - ph on the selected pairs, then the index scores' chain
    rule head by head."""
    i = pl.program_id(1)
    block, J = statics["block"], statics["idx_heads"]
    lseI = lseI_ref[0][0:1, :]
    bq = lseI.shape[1]

    if grad_refs:
        dqI_ref, dw_ref, dkI_ref, dqI_acc, dw_acc, dkI_acc = grad_refs

        @pl.when(i == 0)
        def _():
            dkI_acc[...] = jnp.zeros_like(dkI_acc)

        dqI_acc[...] = jnp.zeros_like(dqI_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    def accumulate(j, dI, kI):
        at = pl.ds(pl.multiple_of(j * block, block), block)

        def head(n, dkI):
            qI = qI_ref[0, n]                             # [BQ, d]
            w = w_ref[0, n][0:1, :]                       # [1, BQ]
            s = _dot(kI, qI, ((1,), (1,)))
            dw_acc[n] += jnp.broadcast_to(jnp.sum(
                dI * jnp.maximum(s, 0.0), axis=0, keepdims=True),
                dw_acc.shape[1:])
            ds = jnp.where(s > 0, dI * w, 0.0).astype(qI.dtype)
            dqI_acc[n] += _dot(kI, ds, ((0,), (0,)))      # [d, BQ]
            return dkI + _dot(ds, qI, ((1,), (0,)))       # [BK, d]

        dkI_acc[at, :] += jax.lax.fori_loop(
            0, J, head, jnp.zeros(kI.shape, jnp.float32))

    def body(j, kl):
        tile_kl, dI, kI = _kl_tile(j, q_ref, k_ref, lse_ref, qI_ref, w_ref,
                                   kI_ref, lseI, mask_ref, **statics)
        if grad_refs:
            accumulate(j, dI, kI)
        return kl + tile_kl

    kl = jax.lax.fori_loop(0, i + 1, body, jnp.zeros((1, bq), jnp.float32))
    write_row8(kl_ref, kl)
    if grad_refs:
        dqI_ref[0] = dqI_acc[...]
        dw_ref[0] = dw_acc[...]

        @pl.when(i == pl.num_programs(1) - 1)
        def _():
            dkI_ref[0] = dkI_acc[...]


def _kl_call(q, k, lse, qIt, wt, kI, lseI, mask, scale, block, grads):
    """-> (the KL term of every query [B, T], gradients): with ``grads``
    (static: the differentiated call) the latter are (dqI^T [B, J, d, T],
    dw [B, J, T], dkI [B, T, d]), float32, of the KL terms summed over
    every query; without, the kernel has neither those outputs nor their
    scratch, and the tuple is empty."""
    B, A, T, D = q.shape
    KV = k.shape[1]
    J, d = qIt.shape[1], qIt.shape[3]
    row = pl.BlockSpec((1, 8, block), lambda b, i: (b, 0, i))
    head_rows = pl.BlockSpec((1, J, 8, block), lambda b, i: (b, 0, 0, i))
    kI_whole = pl.BlockSpec((1, T, d), lambda b, i: (b, 0, 0))
    out_specs = [row]
    out_shape = [jax.ShapeDtypeStruct((B, 8, T), jnp.float32)]
    scratch = []
    if grads:
        out_specs += [
            pl.BlockSpec((1, J, d, block), lambda b, i: (b, 0, 0, i)),
            head_rows, kI_whole]
        out_shape += [jax.ShapeDtypeStruct((B, J, d, T), jnp.float32),
                      jax.ShapeDtypeStruct((B, J, 8, T), jnp.float32),
                      jax.ShapeDtypeStruct((B, T, d), jnp.float32)]
        scratch = [pltpu.VMEM((J, d, block), jnp.float32),
                   pltpu.VMEM((J, 8, block), jnp.float32),
                   pltpu.VMEM((T, d), jnp.float32)]
    kl, *grad = pl.pallas_call(
        functools.partial(_kl_kernel, scale=scale, block=block, heads=A,
                          idx_heads=J, group=A // KV),
        grid=(B, T // block),
        in_specs=[
            pl.BlockSpec((1, A, block, D), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, KV, T, D), lambda b, i: (b, 0, 0, 0)),
            pl.BlockSpec((1, A, 8, block), lambda b, i: (b, 0, 0, i)),
            pl.BlockSpec((1, J, block, d), lambda b, i: (b, 0, i, 0)),
            head_rows, kI_whole, row,
            pl.BlockSpec((1, T, block), lambda b, i: (b, 0, i)),
        ],
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
        # kI's gradient is summed over the query blocks
        compiler_params=_params("parallel",
                                "arbitrary" if grads else "parallel"),
        interpret=_interpret(),
        name=scopes.DSA_KL,
    )(q, k, rows8(lse), qIt, wt, kI, rows8(lseI), mask)
    if grads:
        dqIt, dw, dkI = grad
        grad = (dqIt, dw[:, :, 0], dkI)
    return kl[:, 0], tuple(grad)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _kl(qIt, wt, kI, q, k, lse, lseI, mask, scale, block):
    kl, _ = _kl_call(q, k, lse, qIt, wt, kI, lseI, mask, scale, block,
                     grads=False)
    return jnp.mean(kl)


def _kl_fwd(qIt, wt, kI, q, k, lse, lseI, mask, scale, block):
    """The value and, from the same pass, the gradient up to the loss's
    cotangent (the target is detached, so it is known with the value).
    The three are named, so that a checkpoint whose policy keeps
    ``scopes.RESIDUALS`` replays no kernel for them; beside them a scalar
    of each input's type, for the cotangents' casts."""
    kl, grads = _kl_call(q, k, lse, qIt, wt, kI, lseI, mask, scale, block,
                         grads=True)
    grads = name_residuals(*grads, names=scopes.DSA_KL_GRADS)
    return jnp.mean(kl), (grads, tuple(
        jnp.zeros((), a.dtype) for a in (qIt, wt, kI)))


def _kl_bwd(scale, block, res, g):
    (dqIt, dw, dkI), like = res
    B, _, T = dw.shape
    g = g / (B * T)
    # wt is w's rows on 8 sublanes: the whole gradient goes to the first
    dw = jnp.pad(dw[:, :, None], ((0, 0), (0, 0), (0, 7), (0, 0)))
    return tuple((g * x).astype(a.dtype) for x, a in zip(
        (jnp.swapaxes(dqIt, 2, 3), dw, dkI), like)) + (None,) * 5


_kl.defvjp(_kl_fwd, _kl_bwd)


def dsa_kl(qI, w, kI, mask, lseI, q, k, lse, block=None):
    """The indexer's loss through the kernels: mean over every query of
    KL(ph || softmax of I over the selected keys).  ``lseI`` is
    ``dsa_select``'s; gradients reach qI, w and kI only."""
    T, D = q.shape[1], q.shape[3]
    scale = 1.0 / math.sqrt(D)
    count_kernel_selection("dsa_kl")
    qt, kt = jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2)
    return _kl(jnp.swapaxes(qI, 1, 2), _head_rows(w), kI,
               *(jax.lax.stop_gradient(a) for a in (qt, kt, lse)),
               jax.lax.stop_gradient(lseI), mask, scale,
               min(block or BLOCK, T))
