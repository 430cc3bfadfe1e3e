"""Trinity-Mini (``afmoe``) as the program builds it: paddle_tpu ``nn``
layers (sandwich RMSNorms, ``nn.GroupedQueryAttention`` told each layer's
kind: a sliding window with rotary positions or full with none, per-head
q/k norms and the sigmoid gate on attention's output; ``nn.GatedFFN`` in
the leading dense layer; ``nn.MoELayer`` with sigmoid scores, a selection
bias, a scale and a shared expert, told which experts it holds and to
hold its router still), a float32 residual stream, per-block recompute
and the chunked ``linear_cross_entropy`` head; plus which program
parameter is which reference leaf, the FLOPs a step needs, and what the
window calls, the full calls and the expert matmuls need for their
rooflines.
"""


def _require_the_layers():
    """Fail while the cell's files are loaded, before the reference has
    spent a minute, on a program from before these layers existed."""
    import inspect

    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    missing = [name for name, home in (
        ("nn.GroupedQueryAttention", nn), ("nn.GatedFFN", nn),
        ("nn.MoELayer", nn), ("F.attention_output_gate", F))
        if not hasattr(home, name.split(".")[1])]
    if "window" not in inspect.signature(
            F.scaled_dot_product_attention).parameters:
        missing.append("F.scaled_dot_product_attention(window=)")
    if missing:
        raise ImportError("models/afmoe.py needs " + ", ".join(missing)
                          + ", which this paddle_tpu does not have")


_require_the_layers()

SLIDING, FULL = "sliding_attention", "full_attention"


def _kinds(cfg):
    kinds = tuple(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {SLIDING, FULL}:
        raise ValueError("models/afmoe.py: layer_types names "
                         f"{len(kinds)} layers of kinds {sorted(set(kinds))}")
    return kinds


def build(cfg, variant):
    """-> (model, loss_fn).  The model returns the final normed state."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    from paddle_tpu.parallel import recompute

    if cfg["recompute"] != "per_block" or cfg["tie_word_embeddings"]:
        raise ValueError("models/afmoe.py builds per-block recompute and an "
                         "untied head")
    if (cfg["num_dense_layers"] != 1 or cfg["num_shared_experts"] != 1
            or cfg["score_func"] != "sigmoid" or cfg["n_group"] != 1
            or cfg["topk_group"] != 1):
        raise ValueError("models/afmoe.py builds one leading dense layer, "
                         "one shared expert and an ungrouped sigmoid router")
    V, H = cfg["vocab_size"], cfg["hidden_size"]
    eps = cfg["rms_norm_eps"]
    first = cfg["held_experts"]["first"]
    held = range(first, first + cfg["num_experts"])
    embed_scale = H ** 0.5 if cfg["mup_enabled"] else 1.0

    class Block(nn.Layer):
        """Sandwich norms: each branch is normed on its way in and on its
        way out.  Attention of the layer's kind, then the dense SwiGLU
        (the leading layer) or the routed experts with their shared
        expert."""

        def __init__(self, kind, dense):
            super().__init__()
            window = kind == SLIDING
            self.norm1, self.norm2 = nn.RMSNorm(H, eps), nn.RMSNorm(H, eps)
            self.attn = nn.GroupedQueryAttention(
                H, cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"],
                window=cfg["sliding_window"] if window else None,
                rope_theta=cfg["rope_theta"] if window else None,
                qk_norm=True, output_gate=True, epsilon=eps)
            self.norm3, self.norm4 = nn.RMSNorm(H, eps), nn.RMSNorm(H, eps)
            if dense:
                self.ffn = nn.GatedFFN(H, cfg["intermediate_size"])
            else:
                self.moe = nn.MoELayer(
                    H, cfg["moe_intermediate_size"],
                    cfg["published"]["num_experts"],
                    cfg["num_experts_per_tok"], held=held,
                    norm_topk_prob=cfg["route_norm"],
                    scoring=cfg["score_func"], selection_bias=True,
                    routed_scaling_factor=cfg["route_scale"],
                    shared_width=cfg["num_shared_experts"]
                    * cfg["moe_intermediate_size"],
                    train_router=cfg["train_router"])
            self.dense = dense

        def forward(self, x):
            # x is the float32 residual stream; the norms hand the
            # weights' type to the matmuls and to the additions' casts
            x = x + self.norm2(self.attn(self.norm1(x))).astype("float32")
            if self.dense:
                m = self.ffn(self.norm3(x))
            else:
                # the router wants the normed stream in float32
                m = self.moe(F.rms_norm(
                    x, self.norm3.weight.astype("float32"), eps))
            return x + self.norm4(m).astype("float32")

    class Afmoe(nn.Layer):
        def __init__(self):
            super().__init__()
            self.tok = nn.Embedding(V, H)
            self.blocks = nn.LayerList([Block(kind, dense=i == 0)
                                        for i, kind in enumerate(_kinds(cfg))])
            self.norm_f = nn.RMSNorm(H, eps)
            self.head = nn.Linear(H, V, bias_attr=False)

        def forward(self, ids):
            x = self.tok(ids).astype("float32") * embed_scale
            for blk in self.blocks:
                x = recompute(blk, x)
            return self.norm_f(x)

    model = Afmoe()

    def loss_fn(z, labels):
        """Float32 logits over the held vocabulary rows (float32 operands
        holding the weights' values: one MXU pass, float32
        accumulation)."""
        n = z.shape[0] * z.shape[1]
        return F.linear_cross_entropy(
            z.astype("float32").reshape([n, H]),
            model.head.weight.astype("float32"),
            paddle.zeros([V], dtype="float32"), labels.reshape([n]))

    return model, loss_fn


_ATTENTION = {"norm1.weight": "norm1.g", "norm2.weight": "norm2.g",
              "norm3.weight": "norm3.g", "norm4.weight": "norm4.g",
              "attn.q.weight": "q.w", "attn.k.weight": "k.w",
              "attn.v.weight": "v.w", "attn.gate.weight": "gate.w",
              "attn.q_norm.weight": "q_norm.g",
              "attn.k_norm.weight": "k_norm.g", "attn.o.weight": "o.w"}
_EXPERTS = {"moe.router_weight": "router.w", "moe.router_bias": "router.bias",
            "moe.w_gate": "experts.gate", "moe.w_up": "experts.up",
            "moe.w_down": "experts.down", "moe.shared_gate": "shared.gate.w",
            "moe.shared_up": "shared.up.w", "moe.shared_down": "shared.down.w"}


def param_map(cfg, variant):
    """program parameter name -> (reference leaf, block or None).  The
    dense layer's ``ffn.in_proj.weight`` is the ONE fused in-projection
    [hidden, 2 x intermediate], the gate's half first: the reference's
    ``dense.ff_in.w``."""
    out = {"tok.weight": ("tok", None), "norm_f.weight": ("norm_f.g", None),
           "head.weight": ("head.w", None)}
    for p, leaf in _ATTENTION.items():
        out["blocks.0." + p] = ("dense." + leaf, None)
    out["blocks.0.ffn.in_proj.weight"] = ("dense.ff_in.w", None)
    out["blocks.0.ffn.out_proj.weight"] = ("dense.ff_down.w", None)
    for p, leaf in {**_ATTENTION, **_EXPERTS}.items():
        for i in range(1, cfg["num_hidden_layers"]):
            out[f"blocks.{i}.{p}"] = ("layers." + leaf, i - 1)
    return out


# ------------------------------------------------------------- the counts --
def _attention_weights(cfg):
    """Matmul weights of one attention: q, the gate and o over all query
    heads, k and v over the key/value heads."""
    H, D = cfg["hidden_size"], cfg["head_dim"]
    return H * D * (3 * cfg["num_attention_heads"]
                    + 2 * cfg["num_key_value_heads"])


def _expert_layer_weights(cfg):
    """Matmul weights a token uses in one expert layer's feed-forward
    part, in expectation: the router over all experts, the shared expert,
    and top_k * held / total routed experts."""
    H, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    E = cfg["published"]["num_experts"]
    routed = cfg["num_experts_per_tok"] * cfg["num_experts"] / E * 3 * H * F
    return H * E + cfg["num_shared_experts"] * 3 * H * F + routed


def pairs_a_head(cfg, seq, kind):
    """(query, key) pairs one head of one row needs: half the square on a
    full layer, the band ``W L - W (W - 1) / 2`` on a window layer (the
    first W queries see 1..W keys, the others W), which is the triangle
    itself where the window is no shorter than the row."""
    W = cfg["sliding_window"]
    if kind == FULL or W >= seq:
        return seq * (seq + 1) / 2
    return W * seq - W * (W - 1) / 2


def train_flops_per_token(cfg, seq):
    """FLOPs the forward and backward passes need for one token of a
    ``seq``-long row.  6 per matmul weight a token uses here (attention's
    five projections a layer, the dense SwiGLU, the expert layers at the
    held experts' EXPECTED load, the [H, V] head over the held vocabulary
    rows); the pairs each layer's kind needs (`pairs_a_head`: the band on
    a window layer), a score and a value product ``head_dim`` wide, each
    one forward and two backward; no embedding look-up, no elementwise
    work.  The forward replayed by recompute is not counted."""
    H, kinds = cfg["hidden_size"], _kinds(cfg)
    dense = cfg["num_dense_layers"]
    weights = (len(kinds) * _attention_weights(cfg)
               + dense * 3 * H * cfg["intermediate_size"]
               + (len(kinds) - dense) * _expert_layer_weights(cfg)
               + H * cfg["vocab_size"])
    pairs = sum(pairs_a_head(cfg, seq, kind) for kind in kinds) / seq
    return (6 * weights
            + 3 * 2 * cfg["num_attention_heads"] * 2 * cfg["head_dim"] * pairs)


def _attention_work(cfg, mix, kind, forwards):
    """(FLOPs, bytes) of the flash kernels over the layers of ``kind`` one
    step: ``forwards`` forward kernel calls (counted in the trace: a
    replay that keeps ``out`` and ``lse`` runs none) of two products, and
    one backward a layer of five (the scores again, dP, dV, dQ, dK), each
    ``head_dim`` wide over the pairs the kind needs.  Bytes, each once: q
    and out over the query heads, k and v over the key/value heads a
    forward; those, dO, dq and the key/value heads' dk and dv a
    backward."""
    B, T, D = mix["batch"], mix["seq"], cfg["head_dim"]
    A, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layers = _kinds(cfg).count(kind)
    pairs = B * A * pairs_a_head(cfg, T, kind)
    flops = 2 * pairs * D * (2 * forwards + 5 * layers)
    row = B * T * D * 2                                   # bfloat16, a head
    bytes_ = (forwards * row * (2 * A + 2 * KV)
              + layers * row * (4 * A + 4 * KV))
    return flops, bytes_


def window_attention_work(cfg, mix, forwards):
    """What the window layers' kernels need a step (`_attention_work`:
    the band's pairs)."""
    return _attention_work(cfg, mix, SLIDING, forwards)


def full_attention_work(cfg, mix, forwards):
    """What the full layers' kernels need a step (half the square)."""
    return _attention_work(cfg, mix, FULL, forwards)


def expert_matmul_work(cfg, mix, product_calls):
    """(FLOPs, bytes) of the grouped products over the held routed experts
    one step, at the EXPECTED load: a token sends top_k * held / total
    assignments here, one assignment (the routers are held still, so the
    load stays the draw's).  ``product_calls``: grouped-product kernel
    calls a step, counted from the trace (each is one H x F product over
    one sequence's rows).  Bytes: the held experts' weights of the
    product once a call, a third of the SwiGLU's rows in and out.  The
    shared expert is a plain matmul under a scope of its own and is not
    part of this."""
    T = mix["seq"]
    H, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, E = cfg["num_experts"], cfg["published"]["num_experts"]
    rows = T * cfg["num_experts_per_tok"] * held / E
    flops = product_calls * 2 * rows * H * F
    bytes_ = product_calls * (held * H * F * 2
                              + rows * (2 * H + 3 * F) * 2 / 3)
    return flops, bytes_
