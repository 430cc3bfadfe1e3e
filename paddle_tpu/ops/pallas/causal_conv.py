"""The depthwise causal convolution of ops/ssm.py as two Pallas TPU
kernels, ``conv_fwd`` and ``conv_bwd``, that read their channels out of a
wider array where they lie and write each part of them as an array of its
own.  The mathematics is ``ops.ssm.causal_conv1d``'s: K shifted
multiply-adds, the bias and silu in float32, the result in x's type.
At the file's end a second pair over the same walk, ``short_conv_fwd``
and ``short_conv_bwd``: the gated form ``C * conv(B * z)`` that is a
convolution mixer's whole operator (``ops.ssm.gated_short_conv``).

What they are for: a Mamba-2 mixer convolves the middle ``conv_dim``
channels of its in-projection's output [B, T, W] and hands the result to
the scan as x [B, T, d_inner], B and C [B, T, G*N].  Left to XLA that is,
in a cell's compiled step (PERF.md section 6, PR 48): a ``slice`` of the
channels out of the projection as a pass of its own, the taps' fusion,
a ``slice`` of x and a fusion for B and C out of its result (the scan's
kernels take three arrays), all of it again in the replay; and in the
backward a float32 [B, T, conv_dim] (805 MB at [2, 8192, 6144]) written
by one fusion and read by the next, which sends the K shifted products
through HBM as K arrays for a third to add: 5 GB a mixer where 1.4 are
needed.  Here the operand is the projection's output **in rows, as the
matmul leaves it**; a part is picked out of it by the block's index map
(so a part starts on a multiple of its own width), the taps shift along
the sublanes in VMEM, the outputs are the scan's operands as it takes
them, and nothing is kept for the backward but the operand.

Both kernels walk the grid (batch, T block) and take a T block's rows of
every part in one step: a part's rows are whole contiguous runs of the
operand.  Inside a step a part is walked in slabs of lanes (a loop: the taps and
the bias of a slab stay in registers) and a slab in chunks of rows (a
loop inside it; ``_ROWS``, ``_SLAB``: a body a part, so the
kernels trace and lower in a fraction of a second at any width), so that a chunk's chain of
operations, about twenty a vreg forward and fifty backward, runs out of
the register file.  A chunk's K - 1 earlier rows are the eight before it:
of its own block, or for a block's first chunk of the block before,
whose last rows come in through a second, 16-row block of the same
operand (zeros at a row's start: a row is one sequence, no state is
handed in).

- ``conv_fwd``: taps, bias, silu, cast; the T blocks in any order.
- ``conv_bwd`` walks the T blocks and a block's chunks from the row's
  end: it remakes a chunk's shifted operands and pre-activation,
  ``g = dy silu'(pre)``, the taps' gradient ``sum_t g_t x_{t-s}`` and
  the bias's ``sum_t g_t`` (float32, eight sublanes each, added up in the
  output block along T: the wrapper sums the sublanes and the batch),
  and ``dx_t = sum_k w_k g_{t + (K-1-k)}``: the taps transposed, the
  rows after a chunk being the first eight of the chunk before in the
  walk, which ride the loop and, across blocks, a VMEM scratch.  It
  writes d(xBC) [B, T, conv_dim] once; the wrapper pads it to the
  operand's width (XLA fuses that with dz and d(dt) into the
  projection's cotangent).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...observability import scopes
from .support import (count_kernel_selection, dtype_ok,
                      interpret_mode as _interpret, once_a_shape, pltpu)

_LANES = 128
_SUB = 8                 # a float32 tile's rows: where a chunk's earlier
                         # (later) rows sit, so K - 1 <= 8
_MAX_TAPS = 8
_HALO = 16               # the block of rows before a T block: one
                         # bfloat16 tile, two float32 ones
# a chunk's rows and a slab's lanes, in both kernels.  Alone at the two
# cells' shapes on a v5e, forward / forward + backward: (128, 128) read
# 1.749 / 4.769 ms (Nemotron's) and 0.726 / 1.553 (Granite's), (64, 128)
# 1.816 / 4.963 and 0.737 / 1.620, (32, 128) 2.046 / 5.544, (64, 256)
# 1.765 / 4.950, (32, 256) 1.837 / 5.003 (PERF.md section 6, PR 48)
_ROWS, _SLAB = 128, 128
# what a backward step's blocks (x, dy, dx: double-buffered) may take of
# Mosaic's default scoped VMEM (16 MiB on a v5e), the taps' gradient and
# the scratch beside them
_BLOCK_BYTES = 10 * 2 ** 20
_T_BLOCKS = (512, 256, 128, 64, 32, 16)


def _starts(first, parts):
    at, out = first, []
    for w in parts:
        out.append(at)
        at += w
    return out


def _t_block(T, channels, itemsize, arrays=3):
    """Rows a grid step takes: the most of ``_T_BLOCKS`` that divide T
    and whose blocks (``arrays`` of ``channels``, double-buffered: x, dy
    and dx) fit ``_BLOCK_BYTES`` in the backward; 0 if none."""
    for tb in _T_BLOCKS:
        if (T % tb == 0
                and 2 * arrays * tb * channels * itemsize <= _BLOCK_BYTES):
            return tb
    return 0


def causal_conv1d_supported(x_shape, w_shape, dtype, first, parts,
                            activation) -> bool:
    """Shapes the kernels take: x [B, T, W], weight [K, C] with K <= 8
    taps over the channels ``first`` to ``first + C``, returned in
    ``parts`` (their widths).  The first channel and every part fill
    whole lane tiles and a part starts on a multiple of its own width
    (the index map picks it as a block); T is a whole number of T blocks
    (``_t_block``); bfloat16 or float32; activation None or "silu"."""
    if len(x_shape) != 3 or len(w_shape) != 2 or not dtype_ok(dtype):
        return False
    K, C = w_shape
    if (activation not in (None, "silu") or not 1 <= K <= _MAX_TAPS
            or not parts or sum(parts) != C or first < 0
            or first + C > x_shape[2]):
        return False
    if first % _LANES or any(w <= 0 or w % _LANES for w in parts):
        return False
    if any(s % w for s, w in zip(_starts(first, parts), parts)):
        return False
    return _t_block(x_shape[1], C, jnp.dtype(dtype).itemsize) > 0


def _each_slab(parts, slab, body):
    """``body(part, the slab's lanes in the part, its lanes among all the
    convolved channels)`` slab by slab: the parts in a static loop, a
    part's slabs in a loop of the program (one body a part, whatever its
    width)."""
    at = 0
    for p, width in enumerate(parts):
        n = math.gcd(slab, width)

        def slab_of(j, carry, p=p, n=n, at=at):
            lo = pl.multiple_of(j * n, n)
            body(p, pl.ds(lo, n), pl.ds(pl.multiple_of(at + lo, _LANES), n))
            return carry

        jax.lax.fori_loop(0, width // n, slab_of, 0)
        at += width


def _chunk_rows(x_ref, lanes, c, rows, before):
    """Chunk ``c`` of a block's rows in float32 with the eight rows
    before it on top, [8 + rows, lanes]; ``before``: those of chunk 0."""
    f32 = jnp.float32
    r0 = pl.multiple_of(c * rows, rows)
    cur = x_ref[0, pl.ds(r0, rows), lanes].astype(f32)
    if x_ref.shape[1] == rows:         # one chunk a block
        return r0, cur, jnp.concatenate([before, cur], 0)
    up = pl.multiple_of(jnp.maximum(r0 - _HALO, 0), _HALO)
    prev = x_ref[0, pl.ds(up, _HALO), lanes].astype(f32)[_HALO - _SUB:]
    return r0, cur, jnp.concatenate(
        [jnp.where(c == 0, before, prev), cur], 0)


def _shifted(ext, cur, K):
    """The operand of each tap: tap K - 1 meets the position itself, tap
    k the one K - 1 - k back."""
    return [pltpu.roll(ext, K - 1 - k, 0)[_SUB:] for k in range(K - 1)] \
        + [cur]


def _before(halo_ref, lanes, at_row_start):
    """The last eight rows of the block before, zeros at a row's start."""
    rows = halo_ref[0, :, lanes].astype(jnp.float32)[_HALO - _SUB:]
    return jnp.where(at_row_start, 0.0, rows)


def _fwd_kernel(*refs, parts, K, silu, rows, slab):
    n = len(parts)
    x_refs, halo_refs = refs[:n], refs[n:2 * n]
    w_ref, b_ref = refs[2 * n:2 * n + 2]
    y_refs = refs[2 * n + 2:]
    at_row_start = pl.program_id(1) == 0
    chunks = x_refs[0].shape[1] // rows

    def a_slab(p, lanes, among):
        x_ref, y_ref = x_refs[p], y_refs[p]
        w = [w_ref[k:k + 1, among] for k in range(K)]
        bias = b_ref[:, among]
        before = _before(halo_refs[p], lanes, at_row_start)

        def chunk(c, carry):
            r0, cur, ext = _chunk_rows(x_ref, lanes, c, rows, before)
            out = bias
            for wk, xk in zip(w, _shifted(ext, cur, K)):
                out = out + xk * wk
            if silu:
                out = jax.nn.silu(out)
            y_ref[0, pl.ds(r0, rows), lanes] = out.astype(y_ref.dtype)
            return carry

        jax.lax.fori_loop(0, chunks, chunk, 0)

    _each_slab(parts, slab, a_slab)


def _fold(a):
    """[rows, lanes] -> [8, lanes]: the sum of its sublane tiles."""
    return sum(a[i:i + _SUB] for i in range(0, a.shape[0], _SUB))


def _bwd_kernel(*refs, parts, K, silu, rows, slab):
    n = len(parts)
    x_refs, halo_refs, dy_refs = refs[:n], refs[n:2 * n], refs[2 * n:3 * n]
    w_ref, b_ref, dx_ref, dwb_ref, after_ref = refs[3 * n:]
    f32 = jnp.float32
    step = pl.program_id(1)            # T blocks from the row's end
    at_row_start = step == pl.num_programs(1) - 1
    chunks = x_refs[0].shape[1] // rows

    @pl.when(step == 0)
    def _():
        dwb_ref[...] = jnp.zeros_like(dwb_ref)
        after_ref[...] = jnp.zeros_like(after_ref)

    def a_slab(p, lanes, among):
        x_ref, dy_ref = x_refs[p], dy_refs[p]
        w = [w_ref[k:k + 1, among] for k in range(K)]
        bias = b_ref[:, among]
        before = _before(halo_refs[p], lanes, at_row_start)

        def chunk(i, carry):
            after, sums = carry[0], carry[1:]
            r0, cur, ext = _chunk_rows(x_ref, lanes, chunks - 1 - i, rows,
                                       before)
            xs = _shifted(ext, cur, K)
            g = dy_ref[0, pl.ds(r0, rows), lanes].astype(f32)
            if silu:
                pre = bias
                for wk, xk in zip(w, xs):
                    pre = pre + xk * wk
                s = jax.nn.sigmoid(pre)
                g = g * (s * (1.0 + pre * (1.0 - s)))
            sums = tuple(acc + _fold(g * xk) for acc, xk in zip(sums, xs)) \
                + (sums[K] + _fold(g),)
            # the taps transposed: tap k meets the row K - 1 - k on
            gext = jnp.concatenate([g, after], 0)
            dx = g * w[K - 1]
            for k in range(K - 1):
                dx = dx + pltpu.roll(gext, rows + _SUB - (K - 1 - k),
                                     0)[:rows] * w[k]
            dx_ref[0, pl.ds(r0, rows), among] = dx.astype(dx_ref.dtype)
            return (g[:_SUB],) + sums

        zero = jnp.zeros((_SUB, bias.shape[1]), f32)
        carry = jax.lax.fori_loop(0, chunks, chunk,
                                  (after_ref[:, among],) + (zero,) * (K + 1))
        after_ref[:, among] = carry[0]
        for k, acc in enumerate(carry[1:]):
            dwb_ref[0, k * _SUB:(k + 1) * _SUB, among] += acc

    _each_slab(parts, slab, a_slab)


def _part_specs(first, parts, tb, t_of):
    """A T block's rows of each part, and the 16 rows before each."""
    def block(rows, start, width, row_of):
        i = start // width
        return pl.BlockSpec((1, rows, width),
                            lambda b, t: (b, row_of(t_of(t)), i))
    per = tb // _HALO
    main = [block(tb, s, w, lambda t: t)
            for s, w in zip(_starts(first, parts), parts)]
    halo = [block(_HALO, s, w, lambda t: jnp.maximum(t * per - 1, 0))
            for s, w in zip(_starts(first, parts), parts)]
    return main, halo


def _taps_specs(K, C):
    return [pl.BlockSpec((K, C), lambda b, t: (0, 0)),
            pl.BlockSpec((1, C), lambda b, t: (0, 0))]


def _taps(weight, bias):
    f32 = jnp.float32
    return weight.astype(f32), bias.astype(f32).reshape(1, -1)


@once_a_shape(3, 4, 5, 6)
def _fwd_call(x, weight, bias, first, parts, silu, interpret):
    B, T, _ = x.shape
    K, C = weight.shape
    tb = _t_block(T, C, x.dtype.itemsize)
    main, halo = _part_specs(first, parts, tb, lambda t: t)
    n = len(parts)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, parts=parts, K=K, silu=silu,
                          rows=min(_ROWS, tb), slab=_SLAB),
        grid=(B, T // tb),
        in_specs=main + halo + _taps_specs(K, C),
        out_specs=[pl.BlockSpec((1, tb, w), lambda b, t: (b, t, 0))
                   for w in parts],
        out_shape=[jax.ShapeDtypeStruct((B, T, w), x.dtype) for w in parts],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=scopes.CONV_FWD,
    )(*([x] * (2 * n)), *_taps(weight, bias))


@once_a_shape(4, 5, 6, 7)
def _bwd_call(x, weight, bias, dys, first, parts, silu, interpret):
    """-> (d(the convolved channels) [B, T, C] in x's type; the taps'
    and the bias's gradient [B, K + 1, 8, C] float32, to be summed over
    the batch and the sublanes)."""
    B, T, _ = x.shape
    K, C = weight.shape
    tb = _t_block(T, C, x.dtype.itemsize)
    nt = T // tb
    main, halo = _part_specs(first, parts, tb, lambda t: nt - 1 - t)
    n = len(parts)
    dx, dwb = pl.pallas_call(
        functools.partial(_bwd_kernel, parts=parts, K=K, silu=silu,
                          rows=min(_ROWS, tb), slab=_SLAB),
        grid=(B, nt),
        in_specs=main + halo
        + [pl.BlockSpec((1, tb, w), lambda b, t: (b, nt - 1 - t, 0))
           for w in parts] + _taps_specs(K, C),
        out_specs=[pl.BlockSpec((1, tb, C), lambda b, t: (b, nt - 1 - t, 0)),
                   pl.BlockSpec((1, (K + 1) * _SUB, C),
                                lambda b, t: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, T, C), x.dtype),
                   jax.ShapeDtypeStruct((B, (K + 1) * _SUB, C),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_SUB, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=scopes.CONV_BWD,
    )(*([x] * (2 * n)), *dys, *_taps(weight, bias))
    return dx, dwb.reshape(B, K + 1, _SUB, C)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv(x, weight, bias, first, parts, silu):
    return tuple(_fwd_call(x, weight, bias, first, parts, silu,
                           _interpret()))


def _conv_fwd(x, weight, bias, first, parts, silu):
    return _conv(x, weight, bias, first, parts, silu), (x, weight, bias)


def _conv_bwd(first, parts, silu, res, dys):
    x, weight, bias = res
    K, C = weight.shape
    dx, dwb = _bwd_call(x, weight, bias, tuple(dys), first, parts, silu,
                        _interpret())
    sums = jnp.sum(dwb, (0, 2))
    return (jnp.pad(dx, ((0, 0), (0, 0),
                         (first, x.shape[2] - first - C))),
            sums[:K].astype(weight.dtype), sums[K].astype(bias.dtype))


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv1d(x, weight, bias=None, activation=None, first=0,
                  parts=None):
    """``ops.ssm.causal_conv1d`` over the channels ``first`` to ``first +
    C`` of x [B, T, W] (``weight`` [K, C]) through the kernels, for what
    ``causal_conv1d_supported`` takes -> a tuple, an array [B, T, width]
    a part (``parts`` None: one part of all C)."""
    count_kernel_selection("causal_conv1d")
    if bias is None:
        bias = jnp.zeros((weight.shape[1],), jnp.float32)
    return _conv(x, weight, bias, int(first),
                 tuple(parts) if parts else (weight.shape[1],),
                 activation == "silu")


# -- the gated form: y = C * conv(B * z) --------------------------------------
# A convolution mixer's operator (``ops.ssm.gated_short_conv``): the
# operand is its in-projection's output [batch, T, 3 H], B, C and z three
# parts of H lanes, each starting on a multiple of its own width, so the
# index-map rule above holds.  Left to XLA at [4, 8192, 6144] bfloat16 it
# is three slices of the operand (403 MB read, 403 written), a product, the
# taps, a product, and in the backward the float32 shifted products the
# header speaks of.  ``short_conv_fwd`` reads the operand's rows once and
# writes y [batch, T, H]: 537 MB; ``short_conv_bwd`` reads the operand and
# dy and writes d[B ; C ; z] [batch, T, 3 H] once, as the in-projection's
# cotangent: 940 MB.  They share the walk above: `_each_slab`, the chunks,
# the halo block, the tap-gradient sums.

# blocks of H lanes a grid step holds: B, C, z in and y out forward; B, C,
# z and dy in, d[B ; C ; z] out backward
_GATED_FWD_ARRAYS, _GATED_BWD_ARRAYS = 4, 7


def gated_short_conv_supported(x_shape, w_shape, dtype) -> bool:
    """Shapes the gated kernels take: x [B, T, 3 H], weight [K, H] with
    K <= 8 taps, H whole lane tiles, T a whole number of T blocks
    (``_t_block``), bfloat16 or float32."""
    if len(x_shape) != 3 or len(w_shape) != 2 or not dtype_ok(dtype):
        return False
    K, H = w_shape
    if not 1 <= K <= _MAX_TAPS or H % _LANES or x_shape[2] != 3 * H:
        return False
    return _t_block(x_shape[1], H, jnp.dtype(dtype).itemsize,
                    _GATED_BWD_ARRAYS) > 0


def _gated_operands(b_ref, z_ref, before, lanes, c, rows, K):
    """Chunk ``c`` of ``v = B * z`` -> (its first row in the block, B's
    and z's rows, the taps' operands); ``before``: B's and z's eight rows
    before the block."""
    r0, b, b_ext = _chunk_rows(b_ref, lanes, c, rows, before[0])
    _, z, z_ext = _chunk_rows(z_ref, lanes, c, rows, before[1])
    return r0, b, z, _shifted(b_ext * z_ext, b * z, K)


def _tap_sum(w, vs):
    out = vs[0] * w[0]
    for wk, vk in zip(w[1:], vs[1:]):
        out = out + vk * wk
    return out


def _gated_fwd_kernel(b_ref, c_ref, z_ref, bh_ref, zh_ref, w_ref, y_ref, *,
                      K, rows, slab):
    at_row_start = pl.program_id(1) == 0
    chunks = b_ref.shape[1] // rows

    def a_slab(_, lanes, among):
        w = [w_ref[k:k + 1, among] for k in range(K)]
        before = [_before(h, lanes, at_row_start) for h in (bh_ref, zh_ref)]

        def chunk(c, carry):
            r0, _, _, vs = _gated_operands(b_ref, z_ref, before, lanes, c,
                                           rows, K)
            gate = c_ref[0, pl.ds(r0, rows), lanes].astype(jnp.float32)
            y_ref[0, pl.ds(r0, rows), lanes] = (
                gate * _tap_sum(w, vs)).astype(y_ref.dtype)
            return carry

        jax.lax.fori_loop(0, chunks, chunk, 0)

    _each_slab((y_ref.shape[2],), slab, a_slab)


def _gated_bwd_kernel(b_ref, c_ref, z_ref, bh_ref, zh_ref, dy_ref, w_ref,
                      dx_ref, dw_ref, after_ref, *, K, rows, slab):
    f32 = jnp.float32
    H = dy_ref.shape[2]
    step = pl.program_id(1)            # T blocks from the row's end
    at_row_start = step == pl.num_programs(1) - 1
    chunks = b_ref.shape[1] // rows

    @pl.when(step == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        after_ref[...] = jnp.zeros_like(after_ref)

    def a_slab(_, lanes, among):
        w = [w_ref[k:k + 1, among] for k in range(K)]
        before = [_before(h, lanes, at_row_start) for h in (bh_ref, zh_ref)]

        def part(p):                   # the slab's lanes in part p of dx
            return pl.ds(pl.multiple_of(p * H + lanes.start, lanes.size),
                         lanes.size)

        def chunk(i, carry):
            after, sums = carry[0], carry[1:]
            r0, b, z, vs = _gated_operands(b_ref, z_ref, before, lanes,
                                           chunks - 1 - i, rows, K)
            here = pl.ds(r0, rows)
            dy = dy_ref[0, here, lanes].astype(f32)
            dx_ref[0, here, part(1)] = (dy * _tap_sum(w, vs)).astype(
                dx_ref.dtype)
            g = dy * c_ref[0, here, lanes].astype(f32)    # to the taps' sum
            sums = tuple(acc + _fold(g * vk) for acc, vk in zip(sums, vs))
            # the taps transposed: tap k meets the row K - 1 - k on
            gext = jnp.concatenate([g, after], 0)
            dv = g * w[K - 1]
            for k in range(K - 1):
                dv = dv + pltpu.roll(gext, rows + _SUB - (K - 1 - k),
                                     0)[:rows] * w[k]
            dx_ref[0, here, part(0)] = (dv * z).astype(dx_ref.dtype)
            dx_ref[0, here, part(2)] = (dv * b).astype(dx_ref.dtype)
            return (g[:_SUB],) + sums

        zero = jnp.zeros((_SUB, lanes.size), f32)
        carry = jax.lax.fori_loop(0, chunks, chunk,
                                  (after_ref[:, among],) + (zero,) * K)
        after_ref[:, among] = carry[0]
        for k, acc in enumerate(carry[1:]):
            dw_ref[0, k * _SUB:(k + 1) * _SUB, among] += acc

    _each_slab((H,), slab, a_slab)


def _gated_specs(H, tb, t_of):
    """A T block's rows of B, C and z, and the 16 rows before B's and
    z's (C meets no tap)."""
    main, halo = _part_specs(0, (H, H, H), tb, t_of)
    return main + [halo[0], halo[2]]


@once_a_shape(2)
def _gated_fwd_call(x, weight, interpret):
    B, T, _ = x.shape
    K, H = weight.shape
    tb = _t_block(T, H, x.dtype.itemsize, _GATED_FWD_ARRAYS)
    return pl.pallas_call(
        functools.partial(_gated_fwd_kernel, K=K, rows=min(_ROWS, tb),
                          slab=_SLAB),
        grid=(B, T // tb),
        in_specs=_gated_specs(H, tb, lambda t: t)
        + [pl.BlockSpec((K, H), lambda b, t: (0, 0))],
        out_specs=pl.BlockSpec((1, tb, H), lambda b, t: (b, t, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, H), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=scopes.SHORT_CONV_FWD,
    )(*([x] * 5), weight.astype(jnp.float32))


@once_a_shape(3)
def _gated_bwd_call(x, weight, dy, interpret):
    """-> (d[B ; C ; z] [B, T, 3 H] in x's type; the taps' gradient
    [B, K, 8, H] float32, to be summed over the batch and the
    sublanes)."""
    B, T, W = x.shape
    K, H = weight.shape
    tb = _t_block(T, H, x.dtype.itemsize, _GATED_BWD_ARRAYS)
    nt = T // tb

    def rows_of(width):
        return pl.BlockSpec((1, tb, width), lambda b, t: (b, nt - 1 - t, 0))

    dx, dw = pl.pallas_call(
        functools.partial(_gated_bwd_kernel, K=K, rows=min(_ROWS, tb),
                          slab=_SLAB),
        grid=(B, nt),
        in_specs=_gated_specs(H, tb, lambda t: nt - 1 - t)
        + [rows_of(H), pl.BlockSpec((K, H), lambda b, t: (0, 0))],
        out_specs=[rows_of(W),
                   pl.BlockSpec((1, K * _SUB, H), lambda b, t: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, T, W), x.dtype),
                   jax.ShapeDtypeStruct((B, K * _SUB, H), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_SUB, H), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=scopes.SHORT_CONV_BWD,
    )(*([x] * 5), dy, weight.astype(jnp.float32))
    return dx, dw.reshape(B, K, _SUB, H)


@jax.custom_vjp
def _gated(x, weight):
    return _gated_fwd_call(x, weight, _interpret())


def _gated_fwd(x, weight):
    return _gated(x, weight), (x, weight)


def _gated_bwd(res, dy):
    x, weight = res
    dx, dw = _gated_bwd_call(x, weight, dy, _interpret())
    return dx, jnp.sum(dw, (0, 2)).astype(weight.dtype)


_gated.defvjp(_gated_fwd, _gated_bwd)


def gated_short_conv(x, weight):
    """``ops.ssm.gated_short_conv`` (no bias) through the kernels, for
    what ``gated_short_conv_supported`` takes: x [B, T, 3 H] -> y
    [B, T, H]."""
    count_kernel_selection("gated_short_conv")
    return _gated(x, weight)
