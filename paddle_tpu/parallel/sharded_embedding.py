"""ShardedEmbedding — the parameter-server replacement for huge tables.

Reference analog: the PSCore sparse table stack
(paddle/fluid/distributed/table/common_sparse_table.cc,
memory_sparse_table.cc) serving embeddings too large for one device, and
``paddle.static.nn.sparse_embedding``.  TPU-native re-architecture
(SURVEY §7): instead of RPC lookups against parameter servers, the table's
ROWS are sharded over a mesh axis — each chip holds ``vocab / n`` rows in
its own HBM — and the lookup is a shard-local gather + ``psum``, riding
ICI instead of DCN.  Optionally the table (and its optimizer slots, which
inherit the placement) lives in host memory (``offload='pinned_host'``),
the analog of the reference's SSD/heterogeneous PS tiers.

Row-sharded lookup (runs inside the SPMD train step, mesh axis ``axis``):
each shard gathers the rows it owns (out-of-shard ids clamp to row 0 and
mask to zero) and a psum assembles the full result — the collective the
reference implements as prefetch + RPC (distributed/parameter_prefetch.cc).

Gradient: the psum-of-masked-gathers formulation makes the weight's
gradient a scatter-add of ONLY the touched rows on the owning shard —
SelectedRows semantics realized by sharding (eager single-chip code gets
real SelectedRows grads via ``sparse=True`` embedding + lazy optimizers).
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..distributed.mesh import get_mesh
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer_base import Layer
from .tp_layers import set_placement


def _row_sharded_lookup(w, ids, mesh, axis):
    """Shard-local gather + psum over ``axis``; differentiable (shard_map
    has full AD support), grads land as shard-local scatter-adds."""
    from jax import shard_map

    n = mesh.shape[axis]
    rows_per = w.shape[0] // n

    def f(w_shard, ids_rep):
        idx = jax.lax.axis_index(axis)
        local = ids_rep - idx * rows_per
        ok = (local >= 0) & (local < rows_per)
        safe = jnp.clip(local, 0, rows_per - 1)
        out = jnp.take(w_shard, safe, axis=0)
        out = out * ok[..., None].astype(out.dtype)
        return jax.lax.psum(out, axis)

    return shard_map(
        f, mesh=mesh,
        in_specs=(PartitionSpec(axis), PartitionSpec()),
        out_specs=PartitionSpec())(w, ids)


class ShardedEmbedding(Layer):
    """Embedding whose rows are sharded over a mesh axis.

    Args:
        num_embeddings / embedding_dim: table shape.
        axis: mesh axis to shard rows over (default 'dp': capacity
            sharding like ZeRO-3, every data rank owns vocab/n rows).
        offload: None or 'pinned_host' — keep the table (and, via
            placement inheritance, its optimizer slots) in host memory.
        sparse: eager mode uses SelectedRows grads (sparse=True lookup).
    """

    def __init__(self, num_embeddings: int, embedding_dim: int,
                 axis: str = "dp", offload=None, sparse: bool = True,
                 weight_attr=None, name=None):
        super().__init__()
        self._num_embeddings = num_embeddings
        self._embedding_dim = embedding_dim
        self._axis = axis
        self._sparse = sparse
        mesh = get_mesh()
        if (mesh is not None and axis in mesh.shape
                and num_embeddings % mesh.shape[axis] != 0):
            raise ValueError(
                f"num_embeddings ({num_embeddings}) must be divisible by "
                f"mesh axis '{axis}' size ({mesh.shape[axis]}) — otherwise "
                f"the table would silently replicate onto every chip; pad "
                f"the vocab to a multiple")
        self.weight = self.create_parameter(
            [num_embeddings, embedding_dim], attr=weight_attr,
            default_initializer=I.Normal(0.0, 0.02))
        set_placement(self.weight, axis)
        if offload:
            self._try_offload(offload)

    def _try_offload(self, kind: str):
        """Host-memory placement (reference analog: the PS SSD tier /
        heterogeneous PS).  Needs a TPU runtime with memory_kinds; on
        other backends the table stays in device memory."""
        try:
            mesh = get_mesh()
            if mesh is not None and self._axis in mesh.shape:
                s = NamedSharding(mesh, PartitionSpec(self._axis),
                                  memory_kind=kind)
            else:
                dev = jax.devices()[0]
                s = jax.sharding.SingleDeviceSharding(dev, memory_kind=kind)
            self.weight.data = jax.device_put(self.weight.data, s)
        except Exception as e:  # pragma: no cover - backend-dependent
            warnings.warn(f"host offload unavailable on this backend "
                          f"({type(e).__name__}: {e}); table stays in "
                          f"device memory")

    def forward(self, ids):
        mesh = get_mesh()
        arr = ids.data if hasattr(ids, "data") else ids
        traced = isinstance(arr, jax.core.Tracer)
        if (mesh is not None and self._axis in mesh.shape
                and mesh.shape[self._axis] > 1
                and self._num_embeddings % mesh.shape[self._axis] == 0
                and traced):
            from ..core.dispatch import apply
            return apply(
                lambda w, i: _row_sharded_lookup(w, i, mesh, self._axis),
                self.weight, ids, op_name="sharded_embedding")
        return F.embedding(ids, self.weight, sparse=self._sparse)

    def extra_repr(self):
        return (f"{self._num_embeddings}, {self._embedding_dim}, "
                f"axis={self._axis!r}")
