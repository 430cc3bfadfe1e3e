"""Fused compute-collective matmul lowerings for tensor parallelism.

An mp-sharded matmul has two canonical forms (Megatron's column/row
split; "Optimizing Distributed ML Communication with Fused
Computation-Collective Operations" motivates fusing the collective INTO
the matmul so chunk transfers overlap chunk compute):

- **column-parallel** — the weight is sharded on its OUTPUT (non-
  contracting) dim: ``y = x @ all_gather(w)``.  Because the gather dim
  never enters the contraction, the fused per-chunk form — rotate the
  shards around the ring with ``ppermute``, matmul each chunk as it
  arrives, place its column block — equals the unfused
  gather-then-matmul sequence to the rounding of one K-term sum: each
  output column block is the same ``x @ w_j`` contraction, but a dot of
  another shape, and the backend picks a dot's inner order by its shape.
  That makes the composite correct on every backend and oracle-testable.
- **row-parallel** — the weight is sharded on its INPUT (contracting)
  dim: each rank holds a partial product and the results
  reduce-scatter: ``y_mine = my rows of psum(x_part @ w_part)``.  The
  ring form accumulates partials in ascending absolute device order
  (:func:`paddle_tpu.distributed.grad_comm._ascending_sum`), which is
  bitwise-identical to ``psum`` + slice at fp32.

The composite lowering is the default everywhere.  Where shapes meet
the MXU tile gates and the Pallas tier is on
(:func:`paddle_tpu.ops.pallas.support.tier_enabled`), the per-chunk
matmul runs as the Pallas kernel
(:mod:`paddle_tpu.ops.pallas.collective_matmul`) — the selection counts
``pallas.selected.collective_matmul`` and rides ``record_compile
(kernels=)`` like every other tier kernel.  The static Executor's
hybrid grad path lowers whole-layer gathers through the same machinery
(``grad_comm.gather_param`` + the layer's own matmul + chunk-keep at
the shard_map boundary) and records the lowering on its compile
record; calling these entry points directly is how custom layers opt
into the finer-grained per-chunk overlap.

Call these INSIDE shard_map over the mesh axis that shards the weight.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..distributed.grad_comm import (_ascending_sum, _chunked_all_to_all,
                                     gather_param)

__all__ = ["all_gather_matmul", "matmul_reduce_scatter",
           "lowering_label"]


def _chunk_mm(x, w):
    """One column chunk's matmul — the Pallas tier kernel when enabled
    and the shapes meet the tile gates, else the plain jnp matmul (the
    same op the unfused sequence lowers to, keeping the composite path
    bitwise vs its oracle)."""
    from .pallas.support import tier_enabled
    if tier_enabled() and x.ndim == 2:
        from .pallas.collective_matmul import (chunk_matmul,
                                               chunk_matmul_supported)
        if chunk_matmul_supported(x.shape, w.shape, x.dtype, w.dtype):
            return chunk_matmul(x, w)
    return jnp.matmul(x, w)


def lowering_label() -> str:
    """Which per-chunk matmul form the tier would select right now —
    for compile-record attribution (``kernels=``)."""
    from .pallas.support import tier_enabled
    return "pallas" if tier_enabled() else "composite"


def all_gather_matmul(x, w, axis_name: str, axis_size: int, *,
                      ring: bool = True):
    """Column-parallel fused all_gather+matmul: ``w`` is this rank's
    ``[K, N/size]`` shard of a weight sharded on its output dim over
    ``axis_name``; returns the full ``x @ W`` (``[..., N]``), equal to
    ``jnp.matmul(x, gather_param(w, ...))`` to a dot's rounding.

    ``ring=True`` (default) rotates the shards with ``size-1``
    single-chunk ppermutes and matmuls each chunk as it arrives — the
    fused compute-collective form, giving even a static scheduler
    independent units to interleave.  ``ring=False`` is the unfused
    gather-then-matmul sequence (one collective for the latency-hiding
    scheduler to split)."""
    size = int(axis_size)
    if size <= 1:
        return _chunk_mm(x, w)
    if not ring:
        return jnp.matmul(
            x, gather_param(w, axis_name, size, dim=w.ndim - 1))
    nc = w.shape[-1]
    out = jnp.zeros(x.shape[:-1] + (nc * size,),
                    jnp.result_type(x.dtype, w.dtype))
    idx = jax.lax.axis_index(axis_name)
    cur = w
    for step in range(size):
        # after `step` rotations device r holds shard (r + step) % size
        src = jax.lax.rem(idx + step, size)
        y = _chunk_mm(x, cur)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, y.astype(out.dtype), src * nc, axis=out.ndim - 1)
        if step < size - 1:
            perm = [(d, (d - 1) % size) for d in range(size)]
            cur = jax.lax.ppermute(cur, axis_name, perm)
    return out


def matmul_reduce_scatter(x, w, axis_name: str, axis_size: int, *,
                          ring: bool = True):
    """Row-parallel fused matmul+reduce_scatter: ``x`` is this rank's
    ``[M, K/size]`` activation slice, ``w`` its matching ``[K/size, N]``
    weight shard; returns this rank's ``[M/size, N]`` row block of the
    full product (``M % size == 0`` required).

    The ring form reduces partials in ascending absolute device order —
    bitwise-identical at fp32 to the unfused
    ``psum(x @ w)`` + row-slice oracle; ``ring=False`` leaves one fused
    ``psum_scatter`` for the latency-hiding scheduler."""
    size = int(axis_size)
    partial = _chunk_mm(x, w)
    if size <= 1:
        return partial
    m = partial.shape[0]
    if m % size:
        raise ValueError(
            f"matmul_reduce_scatter: leading dim {m} is not divisible "
            f"by axis size {size} — pad the batch or keep the matmul "
            f"column-parallel.")
    if ring:
        rows = partial.reshape((size, m // size) + partial.shape[1:])
        return _ascending_sum(
            _chunked_all_to_all(rows, axis_name, size), size)
    return jax.lax.psum_scatter(partial, axis_name,
                                scatter_dimension=0, tiled=True)
