"""A gated feed-forward layer (Shazeer 2020, "GLU Variants Improve
Transformer"): SwiGLU, the gate through ``silu``."""
from __future__ import annotations

import jax

from ...observability import scopes
from .. import functional as F
from ..layer_base import Layer
from .common import Linear


class GatedFFN(Layer):
    """``[a ; b] = h W_in`` (hidden -> 2 x intermediate, ONE fused
    in-projection: the gate's half first, then the value's),
    ``out = (silu(a) * b) W_out`` (intermediate -> hidden), without
    biases.  ``forward`` takes [..., hidden] in the weights' type and
    returns the branch [..., hidden].  Everything it runs, its two
    projections too, sits under the scope ``ffn``
    (observability/scopes.py)."""

    def __init__(self, hidden_size, intermediate_size, name=None):
        super().__init__()
        self.intermediate_size = int(intermediate_size)
        self.in_proj = Linear(hidden_size, 2 * self.intermediate_size,
                              bias_attr=False)
        self.out_proj = Linear(self.intermediate_size, hidden_size,
                               bias_attr=False)

    def forward(self, h):
        with jax.named_scope(scopes.FFN):
            ab = self.in_proj(h)
            n = self.intermediate_size
            return self.out_proj(F.silu(ab[..., :n]) * ab[..., n:])

    def extra_repr(self):
        return f"intermediate={self.intermediate_size}"
