"""Collective communication API.

Reference analog: python/paddle/distributed/collective.py:294-735 (the
paddle.distributed.all_reduce/... functions emitting c_* ops backed by NCCL,
operators/collective/ — SURVEY §2.1 'Collective op library').

TPU-native mapping (SURVEY §2.3): the c_* op zoo collapses into
``jax.lax`` collectives over named mesh axes.  Two execution contexts:

- **Inside an SPMD region** (``paddle_tpu.distributed.spmd`` /
  ``shard_map``): ops lower to lax.psum / all_gather / ppermute over ICI —
  this is the performance path, fully fused by XLA.
- **Eager (global view)**: a single controller sees the *global* array —
  every "rank" logically holds the same replicated value.  Collectives
  whose result is well-defined under that replication are computed
  mathematically (all_reduce SUM -> n·x, PROD -> x^n, all_gather -> n
  stacked copies, broadcast -> x); collectives whose result is
  *per-rank-divergent* (scatter, reduce_scatter, alltoall, p2p) cannot be
  represented by one global array and raise UnimplementedError pointing
  at the spmd()/shard_map path.

The reference's stream-ordering ops (c_sync_calc_stream, c_wait_compute)
have NO equivalent: XLA schedules communication and compute itself.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec
from jax import shard_map

from ..core.dispatch import apply, as_array
from ..core.enforce import UnimplementedError
from ..core.tensor import Tensor
from .mesh import DP_AXIS, axis_size, ensure_mesh, get_mesh

_tls = threading.local()


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """Parity shim for paddle.distributed.new_group: a Group names a mesh
    axis (the ring_id → axis-name mapping, SURVEY §2.3)."""

    def __init__(self, axis_name: str = DP_AXIS, ranks=None, id=0):
        self.axis_name = axis_name
        self.ranks = ranks
        self.id = id

    @property
    def nranks(self):
        from .mesh import axis_size
        return axis_size(self.axis_name)


_default_group = Group(DP_AXIS)


def new_group(ranks=None, backend=None, axis_name: str = DP_AXIS):
    """reference: collective.py:163.  On TPU a group IS a mesh axis."""
    return Group(axis_name, ranks)


def _axis(group) -> str:
    if group is None:
        return DP_AXIS
    if isinstance(group, Group):
        return group.axis_name
    if isinstance(group, str):
        return group
    return DP_AXIS


def in_spmd() -> Optional[str]:
    """Axis names of the innermost spmd() region, or None."""
    return getattr(_tls, "axes", None)


@contextlib.contextmanager
def _spmd_scope(axes):
    prev = getattr(_tls, "axes", None)
    _tls.axes = axes
    try:
        yield
    finally:
        _tls.axes = prev


def spmd(fn=None, *, in_specs=None, out_specs=None, axes=None,
         check_vma=False):
    """Enter per-device SPMD code: a Tensor-level wrapper over
    ``jax.shard_map``.  Inside, the collective API routes to lax
    collectives over the named axes.

    ``in_specs``/``out_specs``: PartitionSpecs (or tuples) per argument.
    """
    mesh = ensure_mesh()
    axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)

    def decorate(f):
        def wrapper(*tensors):
            arrays = [as_array(t) for t in tensors]
            ispecs = in_specs if in_specs is not None else tuple(
                PartitionSpec(*([None] * a.ndim)) for a in arrays)
            ospecs = out_specs

            def per_device(*arrs):
                with _spmd_scope(axes):
                    out = f(*[Tensor(a) for a in arrs])
                return jax.tree.map(
                    lambda t: t.data if isinstance(t, Tensor) else t, out,
                    is_leaf=lambda x: isinstance(x, Tensor))

            sm = shard_map(per_device, mesh=mesh, in_specs=ispecs,
                           out_specs=ospecs, check_vma=check_vma)
            out = sm(*arrays)
            return jax.tree.map(Tensor, out)
        return wrapper
    if fn is not None:
        return decorate(fn)
    return decorate


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------

def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """reference: collective.py:294 (c_allreduce_* ops)."""
    ax = _axis(group)
    if in_spmd():
        def _ar(a):
            if op == ReduceOp.SUM:
                return jax.lax.psum(a, ax)
            if op == ReduceOp.MAX:
                return jax.lax.pmax(a, ax)
            if op == ReduceOp.MIN:
                return jax.lax.pmin(a, ax)
            if op == ReduceOp.AVG:
                return jax.lax.pmean(a, ax)
            if op == ReduceOp.PROD:
                # exact for zero/negative inputs (no exp/log trick)
                return jnp.prod(jax.lax.all_gather(a, ax), axis=0)
            raise ValueError(op)
        out = apply(_ar, tensor, op_name="all_reduce")
        tensor._rebind(out)
        return tensor
    # eager global view: every rank holds the same replicated value, so
    # the reduction is computed mathematically (n ranks contribute x)
    n = axis_size(ax)
    if n > 1 and op in (ReduceOp.SUM, ReduceOp.PROD):
        out = (apply(lambda a: a * n, tensor, op_name="all_reduce")
               if op == ReduceOp.SUM
               else apply(lambda a: a ** n, tensor, op_name="all_reduce"))
        tensor._rebind(out)
    # MAX/MIN/AVG of n equal values is the value itself
    return tensor


def all_gather(tensor_list, tensor, group=None, sync_op=True, axis=0):
    """reference: collective.py (c_allgather)."""
    ax = _axis(group)
    if in_spmd():
        out = apply(lambda a: jax.lax.all_gather(a, ax, tiled=True),
                    tensor, op_name="all_gather")
        if tensor_list is not None:
            n = axis_size(ax)
            parts = out.split(n, axis=0)
            tensor_list.extend(parts)
        return out
    # eager: n replicated ranks each contribute the same value
    n = axis_size(ax)
    out = apply(lambda a: jnp.concatenate([a] * n, axis=0), tensor,
                op_name="all_gather") if n > 1 else tensor
    if tensor_list is not None:
        # independent per-rank tensors: mutating one entry must not alias
        # the others (or the source), matching a real all_gather
        tensor_list.extend(Tensor(tensor.data) for _ in range(n))
    return out


def all_gather_object(obj_list, obj, group=None):
    obj_list.append(obj)
    return obj_list


def broadcast(tensor, src=0, group=None, sync_op=True):
    """reference: collective.py (c_broadcast).  In SPMD the value from the
    src index along the axis wins."""
    ax = _axis(group)
    if in_spmd():
        def _bc(a):
            # mask-and-psum: O(|a|) bytes on the wire vs all_gather's
            # O(n·|a|) received per member
            mine = jax.lax.axis_index(ax) == src
            return jax.lax.psum(jnp.where(mine, a, jnp.zeros_like(a)), ax)
        out = apply(_bc, tensor, op_name="broadcast")
        tensor._rebind(out)
        return tensor
    # eager: replicated global view — every rank already holds src's value
    return tensor


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    """Global view cannot express a dst-only result; computed as
    all_reduce (the value every rank would see on gather)."""
    return all_reduce(tensor, op, group)


def reduce_scatter(tensor, tensor_list=None, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    ax = _axis(group)
    if in_spmd():
        def _rs(a):
            return jax.lax.psum_scatter(a, ax, tiled=True)
        out = apply(_rs, tensor, op_name="reduce_scatter")
        tensor._rebind(out)
        return tensor
    raise UnimplementedError(
        "reduce_scatter outside an spmd() region: the per-rank result is "
        "divergent and cannot be represented by one global array — wrap "
        "the code in paddle_tpu.distributed.spmd(...)")


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    ax = _axis(group)
    if in_spmd():
        n = axis_size(ax)
        if tensor.shape[0] % n:
            raise ValueError(
                f"scatter: leading dim {tensor.shape[0]} is not divisible "
                f"by the {ax!r} axis size {n}")

        def _sc(a):
            idx = jax.lax.axis_index(ax)
            chunk = a.shape[0] // n
            return jax.lax.dynamic_slice_in_dim(a, idx * chunk, chunk, 0)
        out = apply(_sc, tensor, op_name="scatter")
        tensor._rebind(out)
        return tensor
    raise UnimplementedError(
        "scatter outside an spmd() region: the per-rank result is "
        "divergent — wrap the code in paddle_tpu.distributed.spmd(...)")


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    """reference: alltoall — the Ulysses/sequence-parallel primitive."""
    ax = _axis(group)
    if in_spmd():
        t = (in_tensor_list if isinstance(in_tensor_list, Tensor)
             else paddle_concat(in_tensor_list))
        def _a2a(a):
            from .mesh import axis_size
            n = axis_size(ax)
            parts = a.reshape(n, a.shape[0] // n, *a.shape[1:])
            return jax.lax.all_to_all(parts, ax, 0, 0, tiled=False).reshape(
                a.shape)
        out = apply(_a2a, t, op_name="alltoall")
        if out_tensor_list is not None:
            out_tensor_list.extend(out.split(axis_size(ax), axis=0))
        return out
    raise UnimplementedError(
        "alltoall outside an spmd() region: the per-rank result is "
        "divergent — wrap the code in paddle_tpu.distributed.spmd(...)")


_P2P_MSG = (
    "independent point-to-point {} does not exist under single-controller "
    "SPMD: a matched send/recv pair across a mesh axis IS a collective "
    "permutation.  Use paddle_tpu.distributed.shift(t, offset) for ring "
    "hops or collective_permute(t, perm) for general patterns (the "
    "send_v2/recv_v2 analog used at pipeline stage boundaries).")


def send(tensor, dst=0, group=None, sync_op=True):
    """p2p send (reference: operators/collective/send_v2_op.cc)."""
    raise UnimplementedError(_P2P_MSG.format("send"))


def recv(tensor, src=0, group=None, sync_op=True):
    """p2p recv (reference: operators/collective/recv_v2_op.cc)."""
    raise UnimplementedError(_P2P_MSG.format("recv"))


def shift(tensor, offset: int = 1, group=None):
    """Ring shift over the axis via ppermute: every member receives the
    value held by the member ``offset`` positions before it — the
    SPMD-native form of the send_v2/recv_v2 pipeline hop."""
    ax = _axis(group)
    n = axis_size(ax)
    perm = [(i, (i + offset) % n) for i in range(n)]
    return collective_permute(tensor, perm, group)


def collective_permute(tensor, perm, group=None):
    """Explicit ppermute (the TPU-native send_v2/recv_v2 pair for pipeline
    boundaries; reference: operators/collective/send_v2_op.cc)."""
    ax = _axis(group)
    if in_spmd():
        return apply(lambda a: jax.lax.ppermute(a, ax, perm), tensor,
                     op_name="collective_permute")
    raise UnimplementedError(
        "collective_permute outside an spmd() region: the per-rank result "
        "is divergent — wrap the code in paddle_tpu.distributed.spmd(...)")


def barrier(group=None):
    """reference: barrier_op.  XLA programs are bulk-synchronous; eager
    barrier just blocks the host on outstanding work."""
    (jnp.zeros(()) + 0).block_until_ready()


def get_group(id=0):
    return _default_group


def paddle_concat(tensors):
    import paddle_tpu as paddle
    return paddle.concat(tensors, axis=0)


def split_tensor(tensor, num, axis=0):
    return tensor.split(num, axis=axis)
