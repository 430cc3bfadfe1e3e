"""Multi-head latent attention as a layer (functional:
``F.mla_attention``, kernels: ops/pallas/flash_attention.py)."""
from __future__ import annotations

from .. import functional as F
from ..layer_base import Layer
from .common import Linear
from .norm import RMSNorm


class MLAttention(Layer):
    """DeepSeek-V2's multi-head latent attention (section 2.1; V3's
    2.1.1) in its training form, causal, without biases.

    The query passes a latent of ``q_lora_rank`` (``q_a``, an RMSNorm,
    ``q_b``) and comes out a head ``qk_nope_head_dim`` wide without
    position plus ``qk_rope_head_dim`` rotated.  Keys and values share a
    latent of ``kv_lora_rank`` (``kv_a``, whose last ``qk_rope_head_dim``
    outputs are ONE rotated key a position for all heads and bypass the
    norm; an RMSNorm on the rest; ``kv_b`` up-projects it a head to
    ``qk_nope_head_dim`` key dims and ``v_head_dim`` value dims).  Scores
    are scaled by ``(qk_nope_head_dim + qk_rope_head_dim)^-1/2``; ``o``
    projects the heads' ``v_head_dim`` back.  ``forward`` takes the
    normed hidden state [B, S, hidden] in the weights' type and returns
    the branch [B, S, hidden]; positions are 0..S-1."""

    def __init__(self, hidden_size, num_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 rope_theta=10000.0, rope_interleave=True, epsilon=1e-6,
                 name=None):
        super().__init__()
        self.num_heads = int(num_heads)
        self.kv_lora_rank = int(kv_lora_rank)
        self.dn, self.dr, self.dv = (int(qk_nope_head_dim),
                                     int(qk_rope_head_dim), int(v_head_dim))
        self.rope_theta = float(rope_theta)
        self.rope_interleave = bool(rope_interleave)

        def linear(n_in, n_out):
            return Linear(n_in, n_out, bias_attr=False)

        A = self.num_heads
        self.q_a = linear(hidden_size, q_lora_rank)
        self.q_norm = RMSNorm(q_lora_rank, epsilon)
        self.q_b = linear(q_lora_rank, A * (self.dn + self.dr))
        self.kv_a = linear(hidden_size, kv_lora_rank + self.dr)
        self.kv_norm = RMSNorm(kv_lora_rank, epsilon)
        self.kv_b = linear(kv_lora_rank, A * (self.dn + self.dv))
        self.o = linear(A * self.dv, hidden_size)

    def _rope(self, x):
        return F.rotary_embedding(x, self.rope_theta,
                                  interleaved=self.rope_interleave)

    def forward(self, h):
        B, S = h.shape[0], h.shape[1]
        A, dn, dr, dv = self.num_heads, self.dn, self.dr, self.dv
        q = self.q_b(self.q_norm(self.q_a(h))).reshape([B, S, A, dn + dr])
        q_nope, q_rope = q[:, :, :, :dn], self._rope(q[:, :, :, dn:])
        ckv = self.kv_a(h)
        k_rope = self._rope(ckv[:, :, self.kv_lora_rank:].reshape(
            [B, S, 1, dr])).reshape([B, S, dr])
        kv = self.kv_b(self.kv_norm(ckv[:, :, :self.kv_lora_rank])).reshape(
            [B, S, A, dn + dv])
        out = F.mla_attention(q_nope, q_rope, kv[:, :, :, :dn], k_rope,
                              kv[:, :, :, dn:])
        return self.o(out.reshape([B, S, A * dv]))

    def extra_repr(self):
        return (f"heads={self.num_heads}, keys {self.dn}+{self.dr}, values "
                f"{self.dv}, kv latent {self.kv_lora_rank}")
