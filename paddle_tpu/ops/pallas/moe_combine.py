"""The expert layers' sums over a token's held slots as one Pallas TPU
kernel, ``moe_combine``: ``out[t] = sum of w[r] * rows[r] over the rows r
of token t``, for rows [R, H] that lie in token order.

ops/moe.py sorts a chunk's (token, slot) assignments by held expert into
a buffer of R rows; two of its passes go back from rows to tokens: the
gate-weighted sum of the experts' results (the forward pass) and the
unweighted sum of the rows' gradients (the transpose of the gather to
the experts).  As gathers they fetch ``tokens * top_k`` rows of H, one
for every slot, held or not; as scatter-adds XLA runs them a row at a
time.  Here the rows are streamed once: with the rows put in token order
(one permutation of R rows, the caller's), a block of ``_TOKENS`` tokens
owns a contiguous range of them, and the sum is a product on the MXU,
which the gathers leave idle: for each ``_TILE`` rows of the range the
[tokens, rows] matrix ``(tok[r] == t) * w[r]`` is built from an iota
compare and multiplied into the [rows, H] tile, accumulated in float32.

The grid is a list of (token block, row tile) pairs, made by the entry
from the rows' token ids and scalar-prefetched (XLA's grouped matmul
walks its groups the same way): every token block is visited at least
once, so a block with no row writes zeros, consecutively, so its
accumulator stays in VMEM, and once for every tile its range touches.
The list has a static length (blocks + tiles - 1 bounds it); the pairs
past the last one repeat it and do nothing.  How many rows a token has is
not bounded: a token with every slot held and a chunk with none held
take the same path.  A row whose token id is no token's (the caller's
mark for a row that holds nothing: the last ones) matches no column of
any block, and is read as zeros whatever it holds: the one tile that
straddles the end of the held rows is masked, so the caller need not
clean what a grouped product left past its groups.

Precision.  The weights are float32 and so is the accumulator; rows in
bfloat16 are exact MXU operands, and a float32 weight is the sum of three
bfloat16 terms exactly (3 x 8 bits of mantissa), so the weighted sum
runs three one-pass products where a float32 product at ``HIGHEST``
would run six, and every ``w[r] * rows[r]`` is the float32 product to
the last bit or two.  Without weights the matrix is 0 / 1, exact in any
type: one pass.  Rows in float32 (the tests) take ``support.dot``'s
``HIGHEST``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...observability import scopes
from .support import (count_kernel_selection, dot as _dot, dtype_ok,
                      interpret_mode as _interpret, pltpu)

__all__ = ["moe_combine", "moe_combine_supported", "padded_rows"]

_TOKENS = 128            # a token block: the MXU's rows of one pass
_TILE = 256              # rows of a tile
_LANES = 128
_NN = ((1,), (0,))


def moe_combine_supported(n, H, dtype) -> bool:
    """n tokens of width H in whole blocks and lane tiles."""
    return dtype_ok(dtype) and n % _TOKENS == 0 and H % _LANES == 0


def padded_rows(rows):
    """``rows`` up to whole tiles: what the entry takes."""
    return -(-rows // _TILE) * _TILE


def _terms(w, dtype):
    """``w`` [tokens, rows] float32 as MXU operands beside rows of
    ``dtype``, summing to it exactly: itself beside float32 rows, three
    bfloat16 terms beside bfloat16 ones."""
    if dtype != jnp.bfloat16:
        return (w,)
    terms = []
    for _ in range(3):
        terms.append(w.astype(jnp.bfloat16))
        w = w - terms[-1].astype(jnp.float32)
    return terms


def _kernel(block_ref, tile_ref, count_ref, tok_ref, *rest, weighted):
    w_ref = rest[0] if weighted else None
    rows_ref, out_ref, acc_ref = rest[-3:]
    g = pl.program_id(0)
    last = count_ref[0] - 1
    block = block_ref[g]

    def add(rows):
        hit = tok_ref[...] == (block * _TOKENS + jax.lax.broadcasted_iota(
            jnp.int32, (_TOKENS, _TILE), 0))
        if weighted:
            terms = _terms(jnp.where(hit, w_ref[...], 0.0), rows.dtype)
        else:
            terms = (jnp.where(hit, 1.0, 0.0).astype(rows.dtype),)
        acc_ref[...] += sum(_dot(term, rows, _NN) for term in terms)

    @pl.when(g <= last)
    def _():
        @pl.when((g == 0) | (block_ref[jnp.maximum(g - 1, 0)] != block))
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # of this tile's rows, those that hold something: the first ones
        held = count_ref[1] - tile_ref[g] * _TILE

        @pl.when(held >= _TILE)
        def _():
            add(rows_ref[...])

        @pl.when(held < _TILE)
        def _():
            row = jax.lax.broadcasted_iota(jnp.int32, rows_ref.shape, 0)
            add(jnp.where(row < held, rows_ref[...], 0))

        @pl.when((g == last)
                 | (block_ref[jnp.minimum(g + 1, last)] != block))
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _pairs(tok, n):
    """The grid: (token block [G], row tile [G], [how many of the G are
    pairs, how many rows hold something]) from the rows' nondecreasing
    token ids ``tok`` [R]."""
    blocks, tiles = n // _TOKENS, tok.shape[0] // _TILE
    edges = jnp.arange(blocks + 1, dtype=jnp.int32) * _TOKENS
    # lo[i]: the first row of block i; lo[blocks]: the rows that hold one
    lo = jnp.sum(tok[None, :] < edges[:, None], 1, dtype=jnp.int32)
    # a block with no row visits the tile its range would start in (the
    # last one where that is past the end)
    first = jnp.minimum(lo[:-1] // _TILE, tiles - 1)
    visits = jnp.where(lo[1:] > lo[:-1], (lo[1:] - 1) // _TILE, first) \
        - first + 1
    start = jnp.cumsum(visits) - visits
    total = jnp.sum(visits)
    g = jnp.minimum(jnp.arange(blocks + tiles - 1, dtype=jnp.int32),
                    total - 1)
    block = jnp.sum(start[None, :] <= g[:, None], 1, dtype=jnp.int32) - 1
    return block, first[block] + g - start[block], jnp.stack([total, lo[-1]])


def moe_combine(rows, tok, w, n, dtype=jnp.float32):
    """rows [R, H] in token order, R whole tiles (``padded_rows``); tok [R]
    int32, nondecreasing: the token a row belongs to, ``n`` for a row that
    holds nothing (the last ones; whatever they hold is not read); w [R]
    float32 or None -> [n, H] of ``dtype``: each token's rows, weighted,
    summed in float32."""
    count_kernel_selection("moe_combine")
    R, H = rows.shape
    if R % _TILE or n % _TOKENS:
        raise ValueError(f"{R} rows for {n} tokens: whole tiles of {_TILE} "
                         f"and whole blocks of {_TOKENS} only")
    tok = tok.astype(jnp.int32)
    weighted = w is not None
    ids = pl.BlockSpec((1, _TILE),
                       lambda g, block, tile, count: (0, tile[g]))
    operands = [tok.reshape(1, R)] + (
        [w.astype(jnp.float32).reshape(1, R)] if weighted else []) + [rows]
    return pl.pallas_call(
        functools.partial(_kernel, weighted=weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // _TOKENS + R // _TILE - 1,),
            in_specs=[ids] * (1 + weighted) + [pl.BlockSpec(
                (_TILE, H), lambda g, block, tile, count: (tile[g], 0))],
            out_specs=pl.BlockSpec(
                (_TOKENS, H), lambda g, block, tile, count: (block[g], 0)),
            scratch_shapes=[pltpu.VMEM((_TOKENS, H), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n, H), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
        name=scopes.MOE_COMBINE,
    )(*_pairs(tok, n), *operands)
