"""Per-chunk matmul kernel for the fused collective-matmul lowerings.

The ring forms in :mod:`paddle_tpu.ops.collective_matmul` interleave
one chunk transfer with one chunk matmul per step; this kernel is the
compute half — a tiled MXU matmul over the chunk that just
arrived, so each ring step is one ``pallas_call`` the scheduler can
slot against the next ``ppermute``.  Communication stays in JAX
(ppermute between kernel invocations): Mosaic's cross-chip RDMA form
of the same loop is a later tier, and keeping the wire in JAX keeps
the composite's bitwise-vs-oracle property intact on every backend.

Shape gates follow the f32 (8, 128) sublane/lane tile: rows % 8 == 0,
contraction and chunk-column dims % 128 == 0.  Selection counts
``pallas.selected.collective_matmul`` (trace-time, like every tier
kernel).  Interpret mode (CPU) runs the same kernel for tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...observability import scopes
from .support import block_rows, dot, dtype_ok, \
    interpret_mode as _interpret_mode

__all__ = ["chunk_matmul", "chunk_matmul_supported"]

_LANES = 128
_SUBLANES = 8


def chunk_matmul_supported(x_shape, w_shape, x_dtype, w_dtype) -> bool:
    """Tile-alignment + dtype gate: 2-D ``[M, K] @ [K, Nc]`` with M a
    sublane multiple and K, Nc lane multiples, f32/bf16 operands."""
    if len(x_shape) != 2 or len(w_shape) != 2:
        return False
    m, k = x_shape
    k2, nc = w_shape
    return (k == k2 and m % _SUBLANES == 0 and k % _LANES == 0
            and nc % _LANES == 0 and dtype_ok(x_dtype)
            and dtype_ok(w_dtype))


def _mm_kernel(x_ref, w_ref, o_ref):
    # the f32 output block is the accumulator: it stays resident while
    # the innermost grid axis walks the contraction
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    o_ref[...] += dot(x_ref[...], w_ref[...], ((1,), (0,)))


def _lane_block(n: int) -> int:
    """Largest of 512/256/128 that tiles a lane-multiple dim."""
    return next(b for b in (512, 256, 128) if n % b == 0)


def chunk_matmul(x, w, *, interpret=None):
    """One chunk's ``x @ w`` as a tiled Pallas pass (f32 accumulation).
    Rows, columns and the contraction are all blocked, so the VMEM the
    kernel needs (a few MB) does not grow with the chunk.  Callers gate
    via :func:`chunk_matmul_supported`."""
    if interpret is None:
        interpret = _interpret_mode()
    m, k = x.shape
    _, nc = w.shape
    bm, bn, bk = block_rows(m, 256), _lane_block(nc), _lane_block(k)
    out = pl.pallas_call(
        _mm_kernel,
        grid=(m // bm, nc // bn, k // bk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, c: (i, c)),
                  pl.BlockSpec((bk, bn), lambda i, j, c: (c, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, c: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, nc), jnp.float32),
        interpret=interpret,
        name=scopes.COLLECTIVE_MATMUL_CHUNK,
    )(x, w)
    from .support import count_kernel_selection
    count_kernel_selection("collective_matmul")
    return out.astype(jnp.result_type(x.dtype, w.dtype))
