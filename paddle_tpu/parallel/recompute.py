"""Activation recompute (gradient checkpointing).

Reference: RecomputeOptimizer (recompute_optimizer.py:18) →
``_append_backward_ops_with_checkpoints_`` (fluid/backward.py:725) which
re-emits forward ops in the backward program.

TPU-native: ``jax.checkpoint`` (rematerialisation) on the wrapped segment —
XLA re-runs the segment in the backward pass, trading FLOPs for HBM
exactly like the reference's checkpoint mechanism.

**What is kept across the replay.**  The segment's arguments, as under any
checkpoint, and the values ``observability.scopes.RESIDUALS`` names:

- the ``out`` and ``lse`` of an attention kernel (``flash_fwd``, ``eva_fwd``,
  ``sparse_fwd``) inside the segment.  The kernel's backward takes both and
  only the forward kernel can regenerate them, and that kernel is the one
  part of a block whose replay costs about three times its FLOPs' worth of
  time (it runs at a third of its roofline where the matmuls around it run
  at 60-87 % of the MXU's peak: PERF.md, PR 29).  ``out`` is exactly as
  large as the block input the checkpoint keeps anyway and ``lse`` a 2 D-th
  of it.  It is not free: jax pins a kept value that the forward pass also
  consumes with a ``reduce_precision``, which XLA:TPU executes as one copy
  of ``out`` a layer, about a fifth of the forward kernel it saves;
- the three gradients ``dsa_kl`` makes with the indexer's loss, in one pass
  (its target is detached, so they are known with the value): 145 MB a Keye
  layer in float32 against a block input of 268 MB.  Unkept, the replay
  would run the whole kernel again for them (PERF.md, PR 31);
- what the sparse-attention kernels take (``sparse_fwd`` and its backward
  walk): the selection's mask, int8 [B, keys, queries], and q, k and v
  as the kernels read them.  Nothing but the walk reads them in the
  backward pass, and unkept the replay runs everything upstream of the
  call to make them again: the indexer's projections, ``dsa_scores``,
  ``dsa_threshold`` and the mask's pass for the first (64 ms of a Keye
  step), ``v``'s projection, the rotation of q and k, the q / k norms'
  scaled outputs and three layout copies for the others (20 ms; q's and
  k's projections stay, a norm's backward reads its input).  Kept, they
  are T^2 bytes of mask a layer (268 MB at [4, 8192, 8192]) and q, k and
  v once more (335 MB at 32 / 4 heads of 128): 2.4 GB over Keye's four
  layers, which compile to 3.0 GB more footprint and leave that cell
  0.56 GB under the chip's limit (PERF.md, PR 41).  It is a choice by
  shape, as the rest of this list is, and any caller of
  ``F.sparse_attention`` under ``recompute`` makes it: a policy that
  reads the chip's room (ROADMAP.md, S1) will own it.  The flash and EVA
  rules do not name their operands (in GPT's cell the three would be
  3.6 GB against 1.8 GB of room).

A segment with no named value inside is the bare checkpoint.  This is the
only behaviour: no argument selects it.

**Several outputs leave a segment together**, through one
``optimization_barrier``.  A block that returns its stream and a loss term
would otherwise have the term's kernel put off by XLA:TPU's scheduler until
the backward pass needs what it keeps, its operands held all the while
(1.6 GB in the Keye cell).  A segment with one output has no barrier.

**Device counters** (``observability.device_counter``) emitted inside a
segment leave its checkpoint as outputs and are emitted again outside.
They are not the block's outputs: the barrier's count does not see them,
and the replay's copies of them are dead code."""
from __future__ import annotations

import functools

import jax

from ..core import autograd, dispatch
from ..core.tensor import Tensor
from ..jit.bind import bind, param_list
from ..observability import device_counters, scopes
from ..utils import monitor

_keeps_named = jax.checkpoint_policies.save_only_these_names(
    *scopes.RESIDUALS)


def _keep_attention_residuals(prim, *avals, **params):
    """The checkpoint's policy: jax's ``save_only_these_names`` over
    ``scopes.RESIDUALS``, counting ``recompute.kept.<name>`` for each value
    it keeps (trace time, like ``pallas.selected.*``: once a named value a
    differentiated segment, so a program's count says how many kernel calls
    its replay does without)."""
    keep = _keeps_named(prim, *avals, **params)
    if keep:
        monitor.stat_add(f"recompute.kept.{params['name']}")
    return keep


def recompute(function, *args, **kwargs):
    """paddle.distributed.fleet.utils.recompute parity.

    ``function`` may be a Layer or a Tensor-level callable; its forward is
    evaluated under jax.checkpoint so residuals are rematerialised in the
    backward sweep, all but the values ``scopes.RESIDUALS`` names (see the
    module's docstring)."""
    from ..nn.layer_base import Layer

    preserve = kwargs.pop("preserve_rng_state", True)
    if isinstance(function, Layer):
        layer = function
        fn = layer.forward
        params = param_list(layer)
    else:
        layer = getattr(function, "__self__", None)
        layer = layer if isinstance(layer, Layer) else None
        fn = function
        params = param_list(layer) if layer else []

    tensors = [a for a in args if isinstance(a, Tensor)]
    statics = [a for a in args if not isinstance(a, Tensor)]
    n_p = len(params)
    # a collector of the segment's own, where somebody collects outside
    collects = device_counters.collecting()

    @functools.partial(jax.checkpoint, policy=_keep_attention_residuals)
    def pure_fn(*arrays):
        p_arr = list(arrays[:n_p])
        in_arr = arrays[n_p:]
        it = iter(in_arr)
        rebuilt = [Tensor(next(it)) if isinstance(a, Tensor) else a
                   for a in args]
        with autograd.no_grad(), \
                device_counters.collect(collects) as counted:
            if layer is not None:
                # ``forward`` is called, not ``__call__``, so the layer's
                # named scope is entered here
                with bind(layer, p_arr), \
                        jax.named_scope(layer._scope_name()):
                    out = fn(*rebuilt, **kwargs)
            else:
                out = fn(*rebuilt, **kwargs)
            counted = counted.stacked()
        return jax.tree.map(
            lambda t: t.data if isinstance(t, Tensor) else t, out,
            is_leaf=lambda x: isinstance(x, Tensor)), counted

    def segment(*arrays):
        out, counted = pure_fn(*arrays)
        device_counters.re_emit(counted)
        # see the module's docstring: the output the next segment does not
        # read is not put off past it
        if len(jax.tree.leaves(out)) > 1:
            out = jax.lax.optimization_barrier(out)
        return out

    return dispatch.apply(segment, *params, *tensors, op_name="recompute")


def recompute_sequential(ctx, functions, *args):
    """Sequentially recompute a list of layers (paddle incubate parity)."""
    out = args
    for f in functions:
        out = recompute(f, *(out if isinstance(out, tuple) else (out,)))
    return out
