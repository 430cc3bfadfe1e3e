"""Activation recompute (gradient checkpointing).

Reference: RecomputeOptimizer (recompute_optimizer.py:18) →
``_append_backward_ops_with_checkpoints_`` (fluid/backward.py:725) which
re-emits forward ops in the backward program.

TPU-native: ``jax.checkpoint`` (rematerialisation) on the wrapped segment —
XLA re-runs the segment in the backward pass, trading FLOPs for HBM
exactly like the reference's checkpoint mechanism."""
from __future__ import annotations

import jax

from ..core import autograd, dispatch
from ..core.tensor import Tensor
from ..jit.bind import bind, param_list


def recompute(function, *args, **kwargs):
    """paddle.distributed.fleet.utils.recompute parity.

    ``function`` may be a Layer or a Tensor-level callable; its forward is
    evaluated under jax.checkpoint so residuals are rematerialised in the
    backward sweep."""
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

    @jax.checkpoint
    def pure_fn(*arrays):
        p_arr = list(arrays[:n_p])
        in_arr = arrays[n_p:]
        it = iter(in_arr)
        rebuilt = [Tensor(next(it)) if isinstance(a, Tensor) else a
                   for a in args]
        with autograd.no_grad():
            if layer is not None:
                # ``forward`` is called, not ``__call__``, so the layer's
                # named scope is entered here
                with bind(layer, p_arr), \
                        jax.named_scope(layer._scope_name()):
                    out = fn(*rebuilt, **kwargs)
            else:
                out = fn(*rebuilt, **kwargs)
        return jax.tree.map(
            lambda t: t.data if isinstance(t, Tensor) else t, out,
            is_leaf=lambda x: isinstance(x, Tensor))

    return dispatch.apply(pure_fn, *params, *tensors, op_name="recompute")


def recompute_sequential(ctx, functions, *args):
    """Sequentially recompute a list of layers (paddle incubate parity)."""
    out = args
    for f in functions:
        out = recompute(f, *(out if isinstance(out, tuple) else (out,)))
    return out
