"""A routed mixture of experts as a layer that is told which experts it
holds (functional: ``F.moe_experts``, mathematics: ops/moe.py)."""
from __future__ import annotations

from .. import functional as F
from .. import initializer as I
from ..layer_base import Layer


class MoELayer(Layer):
    """SwiGLU experts behind a softmax top-k router.

    The router spans all ``num_experts`` experts (``router_weight``
    [hidden, num_experts]; its matmul, softmax and top-k run in float32);
    the expert weights are the slice ``held`` (a ``range``; default all),
    stacked ``[len(held), ...]``.  ``forward`` takes the float32 normed
    stream [..., hidden] and returns the held experts' part of the
    layer's result in float32: every assignment of a token to a held
    expert is computed, none dropped, none padded to a capacity; what the
    absent experts would add is left out (an expert-parallel group's
    members sum their parts)."""

    def __init__(self, hidden_size, expert_width, num_experts, top_k,
                 held=None, norm_topk_prob=True, name=None):
        super().__init__()
        held = range(num_experts) if held is None else held
        if (held.step != 1 or not 0 <= held.start < held.stop <= num_experts):
            raise ValueError(f"held={held!r} is not a run of the "
                             f"{num_experts} experts")
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held, self.norm_topk_prob = held, bool(norm_topk_prob)
        n = len(held)
        init = I.Normal(0.0, 0.02)
        self.router_weight = self.create_parameter(
            [hidden_size, num_experts], default_initializer=init)
        self.w_gate = self.create_parameter(
            [n, hidden_size, expert_width], default_initializer=init)
        self.w_up = self.create_parameter(
            [n, hidden_size, expert_width], default_initializer=init)
        self.w_down = self.create_parameter(
            [n, expert_width, hidden_size], default_initializer=init)

    def forward(self, x):
        return F.moe_experts(x, self.router_weight, self.w_gate, self.w_up,
                             self.w_down, self.top_k, self.held.start,
                             self.norm_topk_prob)

    def extra_repr(self):
        return (f"experts {self.held.start}..{self.held.stop - 1} of "
                f"{self.num_experts}, top_k={self.top_k}")
