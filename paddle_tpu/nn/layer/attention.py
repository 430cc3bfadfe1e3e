"""Causal grouped-query self-attention as a layer whose instances may
differ in kind inside one model (functionals:
``F.scaled_dot_product_attention``, ``F.rotary_embedding``,
``F.attention_output_gate``; kernels: ops/pallas/flash_attention.py)."""
from __future__ import annotations

import jax

from ...observability import scopes
from .. import functional as F
from ..layer_base import Layer
from .common import Linear
from .norm import RMSNorm


class GroupedQueryAttention(Layer):
    """Causal self-attention of ``num_heads`` query heads on
    ``num_kv_heads`` key/value heads of ``head_dim``, without biases,
    each layer told its kind:

    - ``window`` (None or a count of positions): a sliding-window layer,
      query t sees the keys s with ``t - window < s <= t``; None sees
      every ``s <= t``;
    - ``rope_theta`` (None or the base): rotate-half rotary positions over
      all ``head_dim`` dims of q and k; None gives the layer NO position
      signal (a model whose window layers carry positions and whose full
      layers do not builds both from this class);
    - ``qk_norm``: an RMSNorm per head over the ``head_dim`` of q and of k
      (a gain vector each), before the rotation;
    - ``output_gate``: a fourth projection ``gate`` of the same normed
      state, ``num_heads * head_dim`` wide, whose sigmoid multiplies the
      heads' outputs elementwise before ``o``.

    ``forward`` takes the normed hidden state [B, S, hidden] in the
    weights' type and returns the branch [B, S, hidden]; positions are
    0..S-1."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 window=None, rope_theta=None, qk_norm=False,
                 output_gate=False, epsilon=1e-6, name=None):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_kv_heads} key/value heads do not divide "
                             f"{num_heads} query heads")
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.window = None if window is None else int(window)
        self.rope_theta = None if rope_theta is None else float(rope_theta)

        def linear(n_in, n_out):
            return Linear(n_in, n_out, bias_attr=False)

        A, KV, D = self.num_heads, self.num_kv_heads, self.head_dim
        self.q, self.k, self.v = (linear(hidden_size, A * D),
                                  linear(hidden_size, KV * D),
                                  linear(hidden_size, KV * D))
        self.gate = linear(hidden_size, A * D) if output_gate else None
        self.q_norm = RMSNorm(D, epsilon) if qk_norm else None
        self.k_norm = RMSNorm(D, epsilon) if qk_norm else None
        self.o = linear(A * D, hidden_size)

    def forward(self, h):
        B, S = h.shape[0], h.shape[1]
        A, KV, D = self.num_heads, self.num_kv_heads, self.head_dim
        q = self.q(h).reshape([B, S, A, D])
        k = self.k(h).reshape([B, S, KV, D])
        if self.q_norm is not None:
            with jax.named_scope(scopes.QK_NORM):
                q, k = self.q_norm(q), self.k_norm(k)
        if self.rope_theta is not None:
            q = F.rotary_embedding(q, self.rope_theta)
            k = F.rotary_embedding(k, self.rope_theta)
        v = self.v(h).reshape([B, S, KV, D])
        a = F.scaled_dot_product_attention(
            q, k, v, is_causal=True, window=self.window).reshape(
                [B, S, A * D])
        if self.gate is not None:
            a = F.attention_output_gate(a, self.gate(h))
        return self.o(a)

    def extra_repr(self):
        return (f"heads={self.num_heads} on {self.num_kv_heads} of "
                f"{self.head_dim}, "
                + (f"window {self.window}" if self.window else "full")
                + (f", rope {self.rope_theta:g}" if self.rope_theta
                   else ", no positions")
                + (", gated" if self.gate is not None else ""))
