"""A looped (weight-shared, recurrent-depth) decoder's two layers: one
stack of blocks run several times on the same parameters, and the gate
that says after which pass a token may leave (functionals:
``F.loop_exit_distribution``, ``F.loop_exit_loss``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dispatch import apply
from ...observability import scopes
from ...utils import monitor
from .. import initializer
from ..layer_base import Layer
from .container import LayerList


class LoopedStack(Layer):
    """``steps`` passes of one stack of blocks over the same parameters
    (Universal Transformer, Dehghani et al. 2019; the LoopLM family):
    ``s^t = blocks(h^{t-1})``, ``h^t = norm(s^t)``, with ``h^0`` the input
    and ``h^t`` both exit t's state and pass t + 1's input.  ``forward``
    returns the ``steps`` exit states stacked, ``[steps, *x.shape]``, in
    the input's type (a float32 stream stays float32 where ``norm``
    returns it so).

    The blocks, ``norm`` and whatever reads the exits exist once: a
    parameter's gradient is the sum over its ``steps`` uses, added by jax
    in the parameter's own type as each pass's backward delivers its part
    (under ``amp`` O2 four bfloat16 partial gradients are added in
    bfloat16: three roundings of 2^-9 beside the one each part already
    carries; ``tests/test_looped.py`` holds it against the float32 sum).

    ``recompute`` replays each block application in the backward pass
    (``parallel.recompute``, which keeps what ``scopes.RESIDUALS`` names):
    a block replayed T times keeps T of each.  The passes are unrolled into
    the trace, not a ``lax.scan`` over them: on a v5e the scan's step was
    3.7 % slower and its footprint 1.9 GB larger at 8 blocks of 2048 x 5632
    over 8,192 tokens (its residuals stacked a pass, its carry's layout
    fixed), for a compile of 19 s against 63 (PERF.md, PR 37).  Every pass
    runs under the scope ``loop_stack``.  Counted at trace time:
    ``loop.steps`` (set) and ``loop.block_calls`` (a block application
    traced)."""

    def __init__(self, blocks, steps, norm=None, recompute=False,
                 name=None):
        super().__init__()
        self.blocks = blocks if isinstance(blocks, LayerList) \
            else LayerList(list(blocks))
        self.steps = int(steps)
        if self.steps < 1:
            raise ValueError(f"LoopedStack: steps={steps}; at least 1")
        self.norm = norm
        self.recompute = bool(recompute)

    def forward(self, x):
        from ...ops.manipulation import stack
        from ...parallel import recompute
        monitor.stat_set("loop.steps", self.steps)
        exits, h = [], x
        for _ in range(self.steps):
            with jax.named_scope(scopes.LOOP_STACK):
                for blk in self.blocks:
                    monitor.stat_add("loop.block_calls")
                    h = recompute(blk, h) if self.recompute else blk(h)
                if self.norm is not None:
                    h = self.norm(h).astype(h.dtype)
            exits.append(h)
        return stack(exits)


class LoopExitGate(Layer):
    """The exit gate of a looped model: one ``Linear(hidden_size, 1)`` with
    bias, shared over the passes, read on each exit state.  ``forward``
    takes the stacked exit states [steps, ..., hidden_size] and returns
    the gate's logits [steps, ...] in float32 (``lambda_t`` is their
    sigmoid; ``F.loop_exit_distribution`` takes the logits, so that
    ``log lambda`` and ``log(1 - lambda)`` never pass through 0 or 1).

    A product and a sum over the last axis in float32, not a matmul: one
    output column would leave the MXU idle and, at the chip's default
    precision, round a float32 state to bfloat16 on its way in."""

    def __init__(self, hidden_size, name=None):
        super().__init__()
        self.weight = self.create_parameter(
            [hidden_size, 1], default_initializer=initializer.Normal(0.0, 0.02))
        self.bias = self.create_parameter(
            [1], is_bias=True, default_initializer=initializer.Constant(0.0))

    def forward(self, states):
        def _gate(h, w, b):
            with jax.named_scope(scopes.LOOP_EXIT):
                h = h.astype(jnp.float32)
                return (jnp.sum(h * w[:, 0].astype(jnp.float32), -1)
                        + b[0].astype(jnp.float32))
        return apply(_gate, states, self.weight, self.bias,
                     op_name="loop_exit_gate")
