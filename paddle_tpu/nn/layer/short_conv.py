"""A gated short-convolution mixer as a layer (functional:
``F.gated_short_conv``; mathematics: ops/ssm.py; kernels:
ops/pallas/causal_conv.py)."""
from __future__ import annotations

import jax

from ...observability import scopes
from .. import functional as F
from .. import initializer as I
from ..layer_base import Layer
from .common import Linear


class ShortConv(Layer):
    """The token mixer of LFM2's ``conv`` layers (``Lfm2ShortConv`` of the
    public modelling code): ``[B ; C ; z] = h W_in`` (ONE in-projection,
    hidden -> 3 x hidden, thirds in that order); ``v = B * z``; a
    depthwise causal convolution of ``taps`` taps over time
    (``conv_weight`` [taps, hidden], the last tap on the position itself,
    torch's ``[hidden, 1, taps]`` transposed; no activation);
    ``y = C * conv(v)``; ``out = y W_out`` (hidden -> hidden).  ``bias``
    gives the two projections and the convolution one each, as the
    family's ``conv_bias`` does.  ``forward`` takes the normed hidden
    state [B, T, hidden] in the weights' type and returns the branch
    [B, T, hidden]; a row is one sequence (no packing, no state handed
    in).  Everything it runs sits under the scope ``short_conv``, the
    gates and the taps alone under ``short_conv_op``
    (observability/scopes.py)."""

    def __init__(self, hidden_size, taps=3, bias=False, name=None):
        super().__init__()
        self.hidden_size, self.taps = int(hidden_size), int(taps)
        bias_attr = None if bias else False
        self.in_proj = Linear(hidden_size, 3 * self.hidden_size,
                              bias_attr=bias_attr)
        self.conv_weight = self.create_parameter(
            [self.taps, self.hidden_size],
            default_initializer=I.Normal(0.0, 0.02))
        self.conv_bias = self.create_parameter(
            [self.hidden_size],
            default_initializer=I.Constant(0.0)) if bias else None
        self.out_proj = Linear(self.hidden_size, hidden_size,
                               bias_attr=bias_attr)

    def forward(self, h):
        with jax.named_scope(scopes.SHORT_CONV):
            # the projection whole: the operator finds B, C and z where
            # they lie
            return self.out_proj(F.gated_short_conv(
                self.in_proj(h), self.conv_weight, self.conv_bias))

    def extra_repr(self):
        return f"hidden={self.hidden_size}, taps={self.taps}"
