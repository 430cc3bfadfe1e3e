"""EVA attention (Zheng et al., "Efficient Attention via Control
Variates", ICLR 2023) in the deterministic chunked form EvaByte trains
with: Pallas TPU kernels (fwd + custom-vjp bwd) and the windowed XLA
path they are checked against.

A row of S positions is cut into windows of W and each window into
chunks of c.  Every chunk's keys and values are pooled into one summary
pair by a softmax inside the chunk against a learned per-head vector
(``mu`` for the key summary, ``phi`` for the value summary; both score
the chunk's keys).  A query then attends, under ONE softmax, to

- the exact keys of its own window up to itself, and
- the summaries of every chunk of every earlier window.

A row no longer than one window is plain causal attention.  The plain
form would hold [H, S, S] scores; this one holds a [block, block] tile.

The kernels are the flash kernels (their tile orientation, block size
and tile mathematics: attention_tiles.py) with a second loop: a
query block of window w walks its window's causal key blocks, then the
w summary blocks before it (W / c rows each), carrying one running
max / sum.  ``eva_bwd_dq`` makes the same walk and, since it holds each
summary tile's p and dS anyway, also accumulates the summaries' dk~ and
dv~ over the query blocks.  dk / dv of the exact keys need nothing new:
with the joint ``lse`` and ``delta`` they are the flash backward walk's
(asked for dk and dv alone: its dq would lack the summaries' part), run
with the windows folded into the head axis.  The pooling and its
backward are XLA fusions (two passes over k and v; ``eva_pool`` scope).

Layout [B, S, H, D] in, [B, H, S, D] inside, as flash attention.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...observability import scopes
from .attention_tiles import (BLOCK, block_loops, delta as _delta, kv_spans,
                              online_step, p_ds, prescale, rows, rows8,
                              write_row8)
from .flash_attention import bwd_dkv, scores, zero_off, zero_seed
from .support import (NEG_INF, count_kernel_selection, dot as _dot,
                      interpret_mode as _interpret, name_residuals, pltpu)


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------

def eva_windows(seq, window_size, chunk_size):
    """(window, windows) of a row of ``seq``: one window of the whole row
    where it is no longer than ``window_size``; ValueError where the row
    does not split into whole windows of whole chunks."""
    window = min(int(window_size), seq)
    if seq % window or (seq > window and window % chunk_size):
        raise ValueError(
            f"eva_attention: a row of {seq} does not split into windows of "
            f"{window_size} made of chunks of {chunk_size}")
    return window, seq // window


def eva_attention_supported(q_shape, dtype, window_size, chunk_size) -> bool:
    """Shapes the kernels handle; everything else takes the XLA path.  On
    the chip the summary blocks (window / chunk rows) must fill whole
    tiles and a window's keys must fit VMEM staged whole, as flash
    attention's; interpret mode only needs the blocks to tile."""
    if len(q_shape) != 4 or dtype not in (jnp.float32, jnp.bfloat16):
        return False
    _, S, _, D = q_shape
    try:
        window, windows = eva_windows(S, window_size, chunk_size)
    except ValueError:
        return False
    if window % min(BLOCK, window):
        return False
    if _interpret():
        return True
    itemsize = jnp.dtype(dtype).itemsize
    if D % 128 or window % 128 or window * D * itemsize > 2 * 1024 * 1024:
        return False
    return windows == 1 or (window // chunk_size) % 16 == 0


def _count_blocks(blocks_per_window, windows):
    """Trace-time counters, once per kernel traced: block iterations a
    (batch, head) over exact keys (a query block walks its window's
    blocks up to its own) and over summaries (one block per earlier
    window)."""
    from ...utils import monitor
    per_window = blocks_per_window * (blocks_per_window + 1) // 2
    monitor.stat_add("pallas.eva.blocks_local", windows * per_window)
    monitor.stat_add("pallas.eva.blocks_summary",
                     blocks_per_window * windows * (windows - 1) // 2)


# ---------------------------------------------------------------------------
# pooling (XLA)
# ---------------------------------------------------------------------------

@jax.named_scope(scopes.EVA_POOL)
def eva_pool(k, v, mu, phi, chunk_size, scale, seq_axis=1):
    """Chunk summaries of k, v ([B, S, H, D], or [B, H, S, D] with
    ``seq_axis=2``) -> the same layout with S / chunk rows.  Softmax and
    sums in float32, the summaries in the inputs' dtype.  Two layouts,
    because the kernels' path pools what it has already transposed: in
    the EvaByte cell, pooling [B, S, H, D] and transposing the summaries
    cost 11 ms a step more and 0.2 GB (PERF.md, PR 26)."""
    shape = list(k.shape)
    shape[seq_axis:seq_axis + 1] = [shape[seq_axis] // chunk_size,
                                    chunk_size]
    mu, phi = mu.astype(jnp.float32), phi.astype(jnp.float32)
    if seq_axis == 2:                                     # heads lead
        mu, phi = mu[:, None, None, :], phi[:, None, None, :]
    ax = seq_axis + 1
    kc = k.reshape(shape).astype(jnp.float32)
    vc = v.reshape(shape).astype(jnp.float32)
    wk = jax.nn.softmax(scale * jnp.sum(kc * mu, -1, keepdims=True),
                        axis=ax)
    wv = jax.nn.softmax(scale * jnp.sum(kc * phi, -1, keepdims=True),
                        axis=ax)
    return (jnp.sum(wk * kc, ax).astype(k.dtype),
            jnp.sum(wv * vc, ax).astype(v.dtype))


# ---------------------------------------------------------------------------
# the windowed XLA path
# ---------------------------------------------------------------------------

def eva_attention_xla(q, k, v, mu, phi, window_size, chunk_size, scale=None):
    """[B, S, H, D] -> [B, S, H, D], every window's scores at once: the
    path off the chip and the oracle of the kernels' tests."""
    B, S, H, D = q.shape
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
    W, nw = eva_windows(S, window_size, chunk_size)
    qw, kw, vw = (a.reshape(B, nw, W, H, D) for a in (q, k, v))
    s = jnp.einsum("bnqhd,bnkhd->bnhqk", qw, kw,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(jnp.tril(jnp.ones((W, W), bool)), s, -jnp.inf)
    if nw > 1:
        ks, vs = eva_pool(k, v, mu, phi, chunk_size, scale)
        t = jnp.einsum("bnqhd,bchd->bnhqc", qw, ks,
                       preferred_element_type=jnp.float32) * scale
        earlier = (jnp.arange(S // chunk_size)[None] // (W // chunk_size)
                   < jnp.arange(nw)[:, None])             # [nw, S/c]
        t = jnp.where(earlier[None, :, None, None], t, -jnp.inf)
        s = jnp.concatenate([s, t], -1)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bnhqk,bnkhd->bnqhd", p[..., :W].astype(v.dtype), vw,
                     preferred_element_type=jnp.float32)
    if nw > 1:
        out = out + jnp.einsum("bnhqc,bchd->bnqhd",
                               p[..., W:].astype(v.dtype), vs,
                               preferred_element_type=jnp.float32)
    return out.reshape(B, S, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# kernels ([B, H, S, D]; score tiles [keys, block] as the flash kernels')
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, lse_ref, *,
                scale, block, blocks_per_window, summary_rows):
    i = pl.program_id(2)
    w, qi = i // blocks_per_window, i % blocks_per_window
    q = prescale(q_ref[0, 0], scale)                      # [BQ, D]
    bq, d = q.shape
    carry = (jnp.full((1, bq), NEG_INF, jnp.float32),
             jnp.zeros((1, bq), jnp.float32),
             jnp.zeros((d, bq), jnp.float32))             # m, l, out^T

    def local(j, carry, mask):
        s = scores(rows(k_ref, j, block), q, mask, qi, j, None, None,
                   block, block)
        return online_step(carry, s, rows(v_ref, j, block))

    # the exact keys first: key 0 of the window is visible to every query
    # of it, so m is finite before any masked score
    carry = block_loops(local, carry, blocks_per_window, True, True,
                        kv_spans(qi, block, block, blocks_per_window))

    def summary(u, carry):
        s = _dot(rows(ks_ref, u, summary_rows), q, ((1,), (1,)))
        return online_step(carry, s, rows(vs_ref, u, summary_rows))

    m, l, acc = jax.lax.fori_loop(0, w, summary, carry)
    o_ref[0, 0] = (acc / l).T.astype(o_ref.dtype)
    write_row8(lse_ref, m + jnp.log(l))


def _bwd_dq_kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dks_ref, dvs_ref, dks_acc, dvs_acc, *,
                   scale, block, blocks_per_window, summary_rows):
    i = pl.program_id(2)
    w, qi = i // blocks_per_window, i % blocks_per_window

    @pl.when(i == 0)
    def _():
        dks_acc[...] = jnp.zeros_like(dks_acc)
        dvs_acc[...] = jnp.zeros_like(dvs_acc)

    q = prescale(q_ref[0, 0], scale)                      # [BQ, D]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0][0:1, :]                           # [1, BQ]
    delta = delta_ref[0, 0][0:1, :]
    bq, d = q.shape

    def local(j, dq, mask):
        k = rows(k_ref, j, block)
        s = scores(k, q, mask, qi, j, None, None, block, block)
        _, ds = p_ds(s, lse, do, rows(v_ref, j, block), delta)
        return dq + _dot(k, ds.astype(k.dtype), ((0,), (0,)))

    dq = block_loops(local, jnp.zeros((d, bq), jnp.float32),
                     blocks_per_window, True, True,
                     kv_spans(qi, block, block, blocks_per_window))

    def summary(u, dq):
        ks = rows(ks_ref, u, summary_rows)
        s = _dot(ks, q, ((1,), (1,)))
        p, ds = p_ds(s, lse, do, rows(vs_ref, u, summary_rows), delta)
        at = pl.ds(pl.multiple_of(u * summary_rows, summary_rows),
                   summary_rows)
        dvs_acc[at, :] += _dot(p.astype(do.dtype), do, ((1,), (0,)))
        # against the pre-scaled q: dk~ needs no scale of its own
        dks_acc[at, :] += _dot(ds.astype(q.dtype), q, ((1,), (0,)))
        return dq + _dot(ks, ds.astype(ks.dtype), ((0,), (0,)))

    dq = jax.lax.fori_loop(0, w, summary, dq)
    dq_ref[0, 0] = (dq * scale).T.astype(dq_ref.dtype)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        dks_ref[0, 0] = dks_acc[...].astype(dks_ref.dtype)
        dvs_ref[0, 0] = dvs_acc[...].astype(dvs_ref.dtype)


def _plan(q, ks, window, block):
    """What both kernels share: their static arguments, the grid, and the
    block specs of a query block, of its window's keys or values staged
    whole, of every summary staged whole, and of an (8, block) lse row."""
    B, H, S, D = q.shape
    block = min(block or BLOCK, window)
    per_window, windows = window // block, S // window
    _count_blocks(per_window, windows)
    # one summary block per earlier window; a single window reads none
    statics = dict(block=block, blocks_per_window=per_window,
                   summary_rows=ks.shape[2] // windows)
    q_block = pl.BlockSpec((1, 1, block, D), lambda b, h, i: (b, h, i, 0))
    kv_window = pl.BlockSpec(
        (1, 1, window, D), lambda b, h, i: (b, h, i // per_window, 0))
    whole = pl.BlockSpec((1, 1, ks.shape[2], D),
                         lambda b, h, i: (b, h, 0, 0))
    lse_row = pl.BlockSpec((1, 1, 8, block), lambda b, h, i: (b, h, 0, i))
    return statics, (B, H, S // block), q_block, kv_window, whole, lse_row


def _fwd(q, k, v, ks, vs, scale, window, block):
    """-> (out [B, H, S, D], lse [B, H, S])."""
    B, H, S, D = q.shape
    statics, grid, q_block, kv_window, whole, lse_row = _plan(
        q, ks, window, block)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, **statics),
        grid=grid,
        in_specs=[q_block, kv_window, kv_window, whole, whole],
        out_specs=[q_block, lse_row],
        out_shape=[jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, 8, S), jnp.float32)],
        interpret=_interpret(),
        name=scopes.EVA_FWD,
    )(q, k, v, ks, vs)
    return out, lse[:, :, 0, :]


def _bwd(q, k, v, ks, vs, out, lse, do, scale, window, block):
    B, H, S, D = q.shape
    statics, grid, q_block, kv_window, whole, lse_row = _plan(
        q, ks, window, block)
    block, windows = statics["block"], S // window
    delta = _delta(do, out)
    dq, dks, dvs = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, **statics),
        grid=grid,
        in_specs=[q_block, kv_window, kv_window, whole, whole, q_block,
                  lse_row, lse_row],
        out_specs=[q_block, whole, whole],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(ks.shape, ks.dtype),
                   jax.ShapeDtypeStruct(vs.shape, vs.dtype)],
        scratch_shapes=[pltpu.VMEM(ks.shape[2:], jnp.float32),
                        pltpu.VMEM(vs.shape[2:], jnp.float32)],
        # the summaries' gradients are summed over the query blocks
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name=scopes.EVA_BWD_DQ,
    )(q, k, v, ks, vs, do, rows8(lse), rows8(delta))

    # the exact keys' dk, dv: p = exp(s - lse) under the joint lse is what
    # the flash dk/dv kernel computes, window by window
    def fold(a):
        return a.reshape(B, H * windows, window, *a.shape[3:])

    dk, dv = bwd_dkv(fold(q), fold(k), fold(v), zero_off(), zero_off(),
                     zero_seed(), fold(do), rows8(fold(lse)),
                     rows8(fold(delta)), scale, True, (block, block), True,
                     0.0)
    return dq, dk.reshape(k.shape), dv.reshape(v.shape), dks, dvs


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _eva(q, k, v, ks, vs, scale, window, block):
    return _fwd(q, k, v, ks, vs, scale, window, block)[0]


def _eva_fwd(q, k, v, ks, vs, scale, window, block):
    out, lse = name_residuals(*_fwd(q, k, v, ks, vs, scale, window, block))
    return out, (q, k, v, ks, vs, out, lse)


def _eva_bwd(scale, window, block, res, do):
    return _bwd(*res, do, scale, window, block)


_eva.defvjp(_eva_fwd, _eva_bwd)


def eva_attention(q, k, v, mu, phi, window_size, chunk_size, scale=None,
                  block: int | None = None):
    """q/k/v: [B, S, H, D], mu/phi: [H, D] -> [B, S, H, D] through the
    kernels (``eva_attention_supported`` says for which shapes).  ``block``
    left at None is flash attention's (512, or a shorter window whole);
    an explicit one is for tests."""
    B, S, H, D = q.shape
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
    window, windows = eva_windows(S, window_size, chunk_size)
    count_kernel_selection("eva_attention")
    qt, kt, vt = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
    if windows > 1:
        ks, vs = eva_pool(kt, vt, mu, phi, chunk_size, scale, seq_axis=2)
    else:
        ks = vs = jnp.zeros((B, H, 8, D), q.dtype)        # never read
    return jnp.swapaxes(_eva(qt, kt, vt, ks, vs, scale, window, block), 1, 2)
