"""LFM2-24B-A2B (``lfm2_moe``) as the program builds it: paddle_tpu ``nn``
layers, every layer two sublayers into a float32 residual stream,
``x + Mixer(RMSNorm(x))`` then ``x + FF(RMSNorm(x))``: ``nn.ShortConv``
(one in-projection to [B ; C ; z], ``F.gated_short_conv``, an
out-projection) for ``conv``; ``nn.GroupedQueryAttention`` with per-head
q/k norms and rotary positions, no window, no gate, for
``full_attention``; ``nn.GatedFFN`` in the leading dense layer,
``nn.MoELayer`` with sigmoid scores, a selection bias and the public
code's epsilon in the gates' normalisation, told which experts it holds
and to hold its router still, in the others; per-block recompute and the
chunked ``linear_cross_entropy`` head on the TRANSPOSE of the embedding
(one matrix).  Plus which program parameter is which reference leaf, the
FLOPs a step needs, and what the full attention calls, the expert
matmuls and the convolution operator need for their rooflines.
"""

CONV, FULL = "conv", "full_attention"
_SHORT = {CONV: "c", FULL: "a"}
# what the public code adds to the sum of a token's chosen scores
# (modeling_lfm2_moe.py: ``routing_weights.sum(-1, keepdim=True) + 1e-6``)
GATE_EPSILON = 1e-6


def _require_the_layers():
    """Fail while the cell's files are loaded, before the reference has
    spent a minute, on a program from before these layers existed."""
    import inspect

    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    missing = [name for name, home in (
        ("nn.ShortConv", nn), ("nn.GroupedQueryAttention", nn),
        ("nn.GatedFFN", nn), ("nn.MoELayer", nn),
        ("F.gated_short_conv", F))
        if not hasattr(home, name.split(".")[1])]
    if hasattr(nn, "MoELayer") and "gate_epsilon" not in inspect.signature(
            nn.MoELayer.__init__).parameters:
        missing.append("nn.MoELayer(gate_epsilon=)")
    if missing:
        raise ImportError("models/lfm2_moe.py needs " + ", ".join(missing)
                          + ", which this paddle_tpu does not have")


_require_the_layers()


def _kinds(cfg):
    kinds = tuple(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {CONV, FULL}:
        raise ValueError("models/lfm2_moe.py: layer_types names "
                         f"{len(kinds)} layers of kinds {sorted(set(kinds))}")
    return kinds


def build(cfg, variant):
    """-> (model, loss_fn).  The model returns the final normed state."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    from paddle_tpu.parallel import recompute

    if cfg["recompute"] != "per_block" or not cfg["tie_word_embeddings"]:
        raise ValueError("models/lfm2_moe.py builds per-block recompute "
                         "and a tied head")
    if (cfg["num_dense_layers"] != 1 or not cfg["use_expert_bias"]
            or cfg["rope_parameters"]["rope_type"] != "default"):
        raise ValueError("models/lfm2_moe.py builds one leading dense "
                         "layer, a selection bias and plain rotary "
                         "positions")
    V, H, eps = cfg["vocab_size"], cfg["hidden_size"], cfg["norm_eps"]
    first = cfg["held_experts"]["first"]
    held = range(first, first + cfg["num_experts"])

    def mixer(kind):
        if kind == CONV:
            return nn.ShortConv(H, cfg["conv_L_cache"], cfg["conv_bias"])
        return nn.GroupedQueryAttention(
            H, cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], rope_theta=cfg["rope_parameters"]["rope_theta"],
            qk_norm=True, epsilon=eps)

    class Block(nn.Layer):
        """A mixer of the layer's kind and a feed-forward part, a norm
        before each: the dense SwiGLU (the leading layer) or the routed
        experts."""

        def __init__(self, kind, dense):
            super().__init__()
            self.norm_op, self.norm_ffn = nn.RMSNorm(H, eps), nn.RMSNorm(H, eps)
            self.mixer = mixer(kind)
            if dense:
                self.ffn = nn.GatedFFN(H, cfg["intermediate_size"])
            else:
                self.moe = nn.MoELayer(
                    H, cfg["moe_intermediate_size"],
                    cfg["published"]["num_experts"],
                    cfg["num_experts_per_tok"], held=held,
                    norm_topk_prob=cfg["norm_topk_prob"], scoring="sigmoid",
                    selection_bias=True,
                    routed_scaling_factor=cfg["routed_scaling_factor"],
                    train_router=cfg["train_router"],
                    gate_epsilon=GATE_EPSILON)
            self.dense = dense

        def forward(self, x):
            # x is the float32 residual stream; the norms hand the
            # weights' type to the matmuls
            x = x + self.mixer(self.norm_op(x)).astype("float32")
            if self.dense:
                return x + self.ffn(self.norm_ffn(x)).astype("float32")
            # the router wants the normed stream in float32
            return x + self.moe(F.rms_norm(
                x, self.norm_ffn.weight.astype("float32"), eps))

    class Lfm2Moe(nn.Layer):
        def __init__(self):
            super().__init__()
            self.tok = nn.Embedding(V, H)
            self.blocks = nn.LayerList([Block(kind, dense=i == 0)
                                        for i, kind in enumerate(_kinds(cfg))])
            self.norm_f = nn.RMSNorm(H, eps)

        def forward(self, ids):
            x = self.tok(ids).astype("float32")
            for blk in self.blocks:
                x = recompute(blk, x)
            return self.norm_f(x)

    model = Lfm2Moe()

    def loss_fn(z, labels):
        """Float32 logits over the held rows of the tied matrix (float32
        operands holding the weights' values: one MXU pass, float32
        accumulation); the mean cross-entropy over every position."""
        n = z.shape[0] * z.shape[1]
        return F.linear_cross_entropy(
            z.astype("float32").reshape([n, H]),
            paddle.transpose(model.tok.weight, [1, 0]).astype("float32"),
            paddle.zeros([V], dtype="float32"), labels.reshape([n]))

    return model, loss_fn


_NORMS = {"norm_op.weight": "norm_op.g", "norm_ffn.weight": "norm_ffn.g"}
_MIXER = {
    CONV: {"mixer.in_proj.weight": "in.w", "mixer.conv_weight": "taps",
           "mixer.out_proj.weight": "out.w"},
    FULL: {"mixer.q.weight": "q.w", "mixer.k.weight": "k.w",
           "mixer.v.weight": "v.w", "mixer.q_norm.weight": "q_norm.g",
           "mixer.k_norm.weight": "k_norm.g", "mixer.o.weight": "o.w"},
}
_EXPERTS = {"moe.router_weight": "router.w", "moe.router_bias": "router.bias",
            "moe.w_gate": "experts.gate", "moe.w_up": "experts.up",
            "moe.w_down": "experts.down"}


def param_map(cfg, variant):
    """program parameter name -> (reference leaf, the nth layer of its
    kind or None).  The dense layer's ``ffn.in_proj.weight`` is the ONE
    fused in-projection [hidden, 2 x intermediate], W1's half first: the
    reference's ``dense.ff_in.w``.  ``tok.weight`` is the embedding and
    the head."""
    kinds = _kinds(cfg)
    out = {"tok.weight": ("tok", None), "norm_f.weight": ("norm_f.g", None)}
    for p, leaf in {**_NORMS, **_MIXER[kinds[0]]}.items():
        out["blocks.0." + p] = ("dense." + leaf, None)
    out["blocks.0.ffn.in_proj.weight"] = ("dense.ff_in.w", None)
    out["blocks.0.ffn.out_proj.weight"] = ("dense.ff_down.w", None)
    seen = {}
    for i, kind in enumerate(kinds[1:], start=1):
        nth = seen.get(kind, 0)
        seen[kind] = nth + 1
        for p, leaf in {**_NORMS, **_MIXER[kind], **_EXPERTS}.items():
            out[f"blocks.{i}.{p}"] = (f"layers.{_SHORT[kind]}.{leaf}", nth)
    return out


# ------------------------------------------------------------- the counts --
def _mixer_weights(cfg, kind):
    """Matmul weights of one mixer: a conv mixer's two projections, an
    attention mixer's q and o over all query heads and k and v over the
    key/value heads (the taps and the gains are elementwise)."""
    H, D = cfg["hidden_size"], cfg["head_dim"]
    if kind == CONV:
        return 4 * H * H
    return H * D * (2 * cfg["num_attention_heads"]
                    + 2 * cfg["num_key_value_heads"])


def _expert_layer_weights(cfg):
    """Matmul weights a token uses in one expert layer's feed-forward
    part, in expectation: the router over all experts and top_k * held /
    total routed experts."""
    H, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    E = cfg["published"]["num_experts"]
    return (H * E
            + cfg["num_experts_per_tok"] * cfg["num_experts"] / E * 3 * H * F)


def train_flops_per_token(cfg, seq):
    """FLOPs the forward and backward passes need for one token of a
    ``seq``-long row.  6 per matmul weight a token uses here (each
    mixer's projections, the dense SwiGLU, the expert layers at the held
    experts' EXPECTED load, the tied [H, V] head over the held vocabulary
    rows, a real product; the embedding's look-up is none); causal
    attention's pairs in each ``full_attention`` layer, (seq + 1) / 2 keys
    a query, a score and a value product ``head_dim`` wide, each one
    forward and two backward; no elementwise work (the gates and the
    taps are 2 + 2 x taps operations a channel).  The forward replayed by
    recompute is not counted."""
    H, kinds = cfg["hidden_size"], _kinds(cfg)
    dense = cfg["num_dense_layers"]
    weights = (sum(_mixer_weights(cfg, k) for k in kinds)
               + dense * 3 * H * cfg["intermediate_size"]
               + (len(kinds) - dense) * _expert_layer_weights(cfg)
               + H * cfg["vocab_size"])
    pairs = kinds.count(FULL) * (seq + 1) / 2
    return (6 * weights
            + 3 * 2 * cfg["num_attention_heads"] * 2 * cfg["head_dim"] * pairs)


def attention_calls(cfg, batch, seq):
    """The ``full_attention`` layers' calls of one step; a replay keeps
    the forward kernel's ``out`` and ``lse`` and runs none again."""
    return {"calls": _kinds(cfg).count(FULL), "batch": batch,
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "seq": seq,
            "head_dim": cfg["head_dim"], "causal": True,
            "forward_replays": 0}


def full_attention_work(cfg, mix, forwards):
    """(FLOPs, bytes) of the flash kernels over the ``full_attention``
    layers one step: ``forwards`` forward kernel calls (counted in the
    trace: a replay that keeps ``out`` and ``lse`` runs none) of two
    products, and one backward a layer of five (the scores again, dP, dV,
    dQ, dK), each ``head_dim`` wide over half the square.  Bytes, each
    once: q and out over the query heads, k and v over the key/value
    heads a forward; those, dO, dq and the key/value heads' dk and dv a
    backward."""
    B, T, D = mix["batch"], mix["seq"], cfg["head_dim"]
    A, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layers = _kinds(cfg).count(FULL)
    pairs = B * A * T * (T + 1) / 2
    flops = 2 * pairs * D * (2 * forwards + 5 * layers)
    row = B * T * D * 2                                   # bfloat16, a head
    bytes_ = (forwards * row * (2 * A + 2 * KV)
              + layers * row * (4 * A + 4 * KV))
    return flops, bytes_


def expert_matmul_work(cfg, mix, product_calls):
    """(FLOPs, bytes) of the grouped products over the held routed experts
    one step, at the EXPECTED load: a token sends top_k * held / total
    assignments here, half an assignment (the routers are held still, so
    the load stays the draw's).  ``product_calls``: grouped-product kernel
    calls a step, counted from the trace (each is one H x F product over
    one sequence's rows).  Bytes: the held experts' weights of the
    product once a call, a third of the SwiGLU's rows in and out."""
    T = mix["seq"]
    H, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, E = cfg["num_experts"], cfg["published"]["num_experts"]
    rows = T * cfg["num_experts_per_tok"] * held / E
    flops = product_calls * 2 * rows * H * F
    bytes_ = product_calls * (held * H * F * 2
                              + rows * (2 * H + 3 * F) * 2 / 3)
    return flops, bytes_


def short_conv_work(cfg, mix, forward_calls):
    """(FLOPs, bytes) of the gated short convolution alone over one step:
    ``forward_calls`` forward passes (counted from the trace: the ``conv``
    layers' own and the ones a replay runs) and one backward a ``conv``
    layer.  Bytes of the operator's arguments and results in bfloat16,
    each once, **the same whatever implements it**: a forward reads
    [B ; C ; z] [tokens, 3 H] and writes y [tokens, H]; a backward reads
    [B ; C ; z] and dy and writes d[B ; C ; z].  FLOPs a token and
    channel: the two gates and ``taps`` multiply-adds forward (2 + 2 x
    taps); backward the operand remade (1 + 2 x taps), dC, the gradient
    to the taps' sum, the taps transposed and the taps' own gradient
    (2 + 4 x taps), dB and dz (2).  The taps themselves are 12 KB."""
    tokens = mix["batch"] * mix["seq"]
    H, K = cfg["hidden_size"], cfg["conv_L_cache"]
    layers = _kinds(cfg).count(CONV)
    flops = tokens * H * (forward_calls * (2 + 2 * K)
                          + layers * (5 + 6 * K))
    bytes_ = tokens * H * 2 * (forward_calls * 4 + layers * 7)
    return flops, bytes_
