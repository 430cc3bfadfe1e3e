"""A Mamba-2 state-space mixer as a layer (functionals:
``F.causal_conv1d``, ``F.ssd_scan``, ``F.gated_group_rms_norm``;
mathematics: ops/ssm.py)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dispatch import apply
from ...observability import device_counters, scopes
from .. import functional as F
from .. import initializer as I
from ..layer_base import Layer
from .common import Linear


class Mamba2Mixer(Layer):
    """Mamba-2's mixer (Dao & Gu 2024), without biases in its
    projections: the form of two families' modelling code, ``nemotron_h``
    (8 groups of 8 heads, the norm over each group's channels) and
    ``granitemoehybrid`` (Mamba-2's published default, ``n_groups=1``: all
    heads read ONE B and C, and the gated norm runs over all of d_inner).

    ``[z ; xBC ; dt] = h W_in`` (hidden -> d_inner + (d_inner + 2 G N) +
    heads, with d_inner = ``num_heads * head_dim``);
    ``xBC = silu(conv(xBC) + conv_bias)``, a depthwise causal convolution
    of ``conv_kernel`` taps (``conv_weight`` [taps, channels], the last
    tap on the position itself); ``[x ; B ; C] = xBC``;
    ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)``, a head each,
    in float32; the scan ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
    ``y_t = S_t C_t + D x_t`` with a state [head_dim, state_size] a head,
    zero at the start of every row, ``n_groups`` groups of heads sharing
    B and C (``F.ssd_scan``: chunks of ``chunk_size``; on a TPU at chunk
    128 its two kernels, 16 or 8 heads a grid step: a group of more,
    ``n_groups=1`` at 64 heads, is walked in blocks of heads);
    ``y = RMSNorm_group(y * silu(z)) * norm_weight`` over each group's
    channels; ``out = y W_out``.  ``forward`` takes the normed hidden
    state [B, T, hidden] in the weights' type and returns the branch
    [B, T, hidden]; a row is one sequence (no packing, no state handed
    in).

    Inside a step that collects device counters (``jit.TrainStep``) a
    call emits ``ssm.state_share`` and ``ssm.mean_decay``
    (observability/scopes.py); elsewhere nothing is computed for them."""

    def __init__(self, hidden_size, num_heads, head_dim, n_groups,
                 state_size, conv_kernel=4, chunk_size=128, epsilon=1e-5,
                 name=None):
        super().__init__()
        if num_heads % n_groups:
            raise ValueError(f"{n_groups} groups do not divide "
                             f"{num_heads} heads")
        self.num_heads, self.head_dim = int(num_heads), int(head_dim)
        self.n_groups, self.state_size = int(n_groups), int(state_size)
        self.chunk_size, self.epsilon = int(chunk_size), float(epsilon)
        self.d_inner = self.num_heads * self.head_dim
        self.conv_dim = self.d_inner + 2 * self.n_groups * self.state_size
        self.in_proj = Linear(hidden_size, self.d_inner + self.conv_dim
                              + self.num_heads, bias_attr=False)
        self.conv_weight = self.create_parameter(
            [int(conv_kernel), self.conv_dim],
            default_initializer=I.Normal(0.0, 0.02))
        self.conv_bias = self.create_parameter(
            [self.conv_dim], default_initializer=I.Constant(0.0))
        self.dt_bias = self.create_parameter(
            [self.num_heads], default_initializer=I.Constant(0.0))
        self.A_log = self.create_parameter(
            [self.num_heads], default_initializer=I.Constant(0.0))
        self.D = self.create_parameter(
            [self.num_heads], default_initializer=I.Constant(1.0))
        self.norm_weight = self.create_parameter(
            [self.d_inner], default_initializer=I.Constant(1.0))
        self.out_proj = Linear(self.d_inner, hidden_size, bias_attr=False)

    def _scan(self, x, dt, B, C):
        """softplus, the decays' rates, the scan; the counters where a
        step collects them."""
        heads = self.num_heads
        dt = apply(lambda a, b: jax.nn.softplus(
            a.astype(jnp.float32) + b.astype(jnp.float32)), dt, self.dt_bias,
            op_name="softplus")
        A = apply(lambda a: -jnp.exp(a.astype(jnp.float32)), self.A_log,
                  op_name="neg_exp")
        D = self.D.astype("float32")
        y = F.ssd_scan(x, dt, A, B, C, D, self.chunk_size)
        if device_counters.collecting():
            yf, xf = y.data.astype(jnp.float32), x.data.astype(jnp.float32)
            state = yf - D.data.reshape(1, 1, heads, 1) * xf
            device_counters.device_counter(
                scopes.SSM_STATE_SHARE,
                jnp.sqrt(jnp.mean(jnp.square(state))
                         / jnp.maximum(jnp.mean(jnp.square(yf)), 1e-30)))
            device_counters.device_counter(
                scopes.SSM_MEAN_DECAY, jnp.mean(jnp.exp(dt.data * A.data)))
        return y

    def forward(self, h):
        with jax.named_scope(scopes.SSM):
            Bt, T = h.shape[0], h.shape[1]
            di, G, N = self.d_inner, self.n_groups, self.state_size
            zxbcdt = self.in_proj(h)
            z = zxbcdt[:, :, :di]
            # the projection whole: the convolution finds its channels
            # where they lie and returns the scan's operands apart
            x, B, C = F.causal_conv1d(
                zxbcdt, self.conv_weight, self.conv_bias, "silu",
                first_channel=di, parts=(di, G * N, G * N))
            x = x.reshape([Bt, T, self.num_heads, self.head_dim])
            B, C = B.reshape([Bt, T, G, N]), C.reshape([Bt, T, G, N])
            with jax.named_scope(scopes.SSM_SCAN):
                y = self._scan(x, zxbcdt[:, :, di + self.conv_dim:], B, C)
            y = F.gated_group_rms_norm(y.reshape([Bt, T, di]), z,
                                       self.norm_weight, G, self.epsilon)
            return self.out_proj(y)

    def extra_repr(self):
        return (f"heads={self.num_heads} x {self.head_dim}, groups="
                f"{self.n_groups}, state={self.state_size}, chunk="
                f"{self.chunk_size}")
