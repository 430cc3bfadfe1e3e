"""A routed mixture of experts as a layer that is told which experts it
holds and which form they have (functional: ``F.moe_experts``,
mathematics: ops/moe.py)."""
from __future__ import annotations

from .. import functional as F
from .. import initializer as I
from ..layer_base import Layer


class MoELayer(Layer):
    """Experts behind a top-k router that scores by a softmax over the
    experts or by a sigmoid of each logit (``scoring``).  ``expert_form``:
    ``"swiglu"``, ``(silu(x Wg) * (x Wu)) Wd`` (``w_gate``, ``w_up``,
    ``w_down``), or ``"relu2"``, ``relu(x Wu)^2 Wd``: two matrices an
    expert, no ``w_gate`` (and no ``shared_gate``) is created and two
    products run where the SwiGLU runs three.

    The router spans all ``num_experts`` experts (``router_weight``
    [hidden, num_experts]; its matmul, scores and top-k run in float32);
    the expert weights are the slice ``held`` (a ``range``; default all),
    stacked ``[len(held), ...]``.  ``forward`` takes the float32 normed
    stream [..., hidden] and returns the held experts' part of the
    layer's result in float32: every assignment of a token to a held
    expert is computed, none dropped, none padded to a capacity; what the
    absent experts would add is left out (an expert-parallel group's
    members sum their parts).

    ``selection_bias`` adds a per-expert parameter ``router_bias``
    [num_experts] (initialised 0) to the scores for the selection alone:
    the gates stay the chosen scores', and no gradient reaches it (it is
    there for a balancing rule to move; none runs here).  The gates are
    multiplied by ``routed_scaling_factor``.  ``shared_width`` adds a
    shared expert, one expert of the layer's form and that width over
    every token (``shared_gate`` / ``shared_up`` / ``shared_down``), which
    every member of a group computes alike.

    ``train_router=False`` holds the router still: its weight gets no
    gradient and the stream none through it.  For a member that trains
    without its group, whose partial gradient would only teach the router
    to prefer the experts held here (ops/moe.py).

    ``gate_epsilon``: what the gates' normalisation adds to the sum of a
    token's chosen scores, where a family's public code states its own
    (None: ops/moe.py's)."""

    def __init__(self, hidden_size, expert_width, num_experts, top_k,
                 held=None, norm_topk_prob=True, name=None,
                 scoring="softmax", selection_bias=False,
                 routed_scaling_factor=1.0, shared_width=None,
                 train_router=True, expert_form="swiglu",
                 gate_epsilon=None):
        super().__init__()
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring {scoring!r}: 'softmax' or 'sigmoid'")
        if expert_form not in ("swiglu", "relu2"):
            raise ValueError(f"expert_form {expert_form!r}: 'swiglu' or "
                             "'relu2'")
        self.expert_form = expert_form
        gated = expert_form == "swiglu"
        held = range(num_experts) if held is None else held
        if (held.step != 1 or not 0 <= held.start < held.stop <= num_experts):
            raise ValueError(f"held={held!r} is not a run of the "
                             f"{num_experts} experts")
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held, self.norm_topk_prob = held, bool(norm_topk_prob)
        self.scoring = scoring
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.train_router = bool(train_router)
        self.gate_epsilon = (None if gate_epsilon is None
                             else float(gate_epsilon))
        n = len(held)
        init = I.Normal(0.0, 0.02)
        self.router_weight = self.create_parameter(
            [hidden_size, num_experts], default_initializer=init)
        self.w_gate = self.create_parameter(
            [n, hidden_size, expert_width],
            default_initializer=init) if gated else None
        self.w_up = self.create_parameter(
            [n, hidden_size, expert_width], default_initializer=init)
        self.w_down = self.create_parameter(
            [n, expert_width, hidden_size], default_initializer=init)
        self.selection_bias = bool(selection_bias)
        self.shared_width = int(shared_width or 0)
        if self.selection_bias:
            self.router_bias = self.create_parameter(
                [num_experts], default_initializer=I.Constant(0.0))
        if self.shared_width:
            self.shared_gate = self.create_parameter(
                [hidden_size, shared_width],
                default_initializer=init) if gated else None
            self.shared_up = self.create_parameter(
                [hidden_size, shared_width], default_initializer=init)
            self.shared_down = self.create_parameter(
                [shared_width, hidden_size], default_initializer=init)

    def forward(self, x):
        return F.moe_experts(
            x, self.router_weight, self.w_gate, self.w_up, self.w_down,
            self.top_k, self.held.start, self.norm_topk_prob,
            scoring=self.scoring,
            router_bias=self.router_bias if self.selection_bias else None,
            routed_scaling_factor=self.routed_scaling_factor,
            shared=(self.shared_gate, self.shared_up, self.shared_down)
            if self.shared_width else None,
            train_router=self.train_router, gate_epsilon=self.gate_epsilon)

    def extra_repr(self):
        return (f"experts {self.held.start}..{self.held.stop - 1} of "
                f"{self.num_experts}, top_k={self.top_k}, "
                f"scoring={self.scoring}, {self.expert_form} experts")
