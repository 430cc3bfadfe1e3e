"""NVIDIA-Nemotron-3-Nano-30B-A3B (``nemotron_h``) as the program builds
it: paddle_tpu ``nn`` layers, every block ``x + Mixer(RMSNorm(x))`` with
one norm and one mixer by the pattern's letter: ``nn.Mamba2Mixer`` (over
``F.causal_conv1d``, ``F.ssd_scan``, ``F.gated_group_rms_norm``) for
``M``; ``nn.MoELayer`` with sigmoid scores, a selection bias, a scaling
factor, ``relu2`` experts (two matrices an expert, no gate) and a shared
expert, told which experts it holds and to hold its router still, for
``E``; grouped-query ``F.scaled_dot_product_attention`` (32 query heads
on 2 key/value heads, no position signal) for ``*``; a float32 residual
stream, per-block recompute, and the chunked ``linear_cross_entropy``
head.  Plus which program parameter is which reference leaf, the FLOPs a
step needs by kind of block, and what the scan and the expert matmuls
need for their rooflines.
"""

_KINDS = {"M": "m", "E": "e", "*": "a"}


def _require_the_layers():
    """Fail while the cell's files are loaded, before the reference has
    spent a minute, on a program from before these layers existed."""
    import inspect

    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    missing = [name for name, home in (
        ("nn.Mamba2Mixer", nn), ("nn.MoELayer", nn), ("F.ssd_scan", F),
        ("F.causal_conv1d", F), ("F.gated_group_rms_norm", F))
        if not hasattr(home, name.split(".")[1])]
    if "nn.MoELayer" not in missing and "expert_form" not in \
            inspect.signature(nn.MoELayer.__init__).parameters:
        missing.append("nn.MoELayer(expert_form=)")
    if missing:
        raise ImportError("models/nemotron_h.py needs " + ", ".join(missing)
                          + ", which this paddle_tpu does not have")


_require_the_layers()


def _kinds(cfg):
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"] or set(pattern) - set(_KINDS):
        raise ValueError(f"pattern {pattern!r} is not "
                         f"{cfg['num_hidden_layers']} letters of M, E, *")
    return [_KINDS[c] for c in pattern]


def build(cfg, variant):
    """-> (model, loss_fn).  The model returns the final normed state."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    from paddle_tpu.parallel import recompute

    if cfg["recompute"] != "per_block" or cfg["tie_word_embeddings"]:
        raise ValueError("models/nemotron_h.py builds per-block recompute "
                         "and an untied head")
    if (cfg["n_shared_experts"] != 1 or cfg["n_group"] != 1
            or cfg["topk_group"] != 1 or cfg["mlp_hidden_act"] != "relu2"
            or cfg["mamba_hidden_act"] != "silu"):
        raise ValueError("models/nemotron_h.py builds one shared expert, an "
                         "ungrouped bias-corrected selection, relu2 experts "
                         "and a silu mixer")
    V, H = cfg["vocab_size"], cfg["hidden_size"]
    eps = cfg["layer_norm_epsilon"]
    heads, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    first = cfg["held_experts"]["first"]
    held = range(first, first + cfg["n_routed_experts"])

    def linear(n_in, n_out):
        return nn.Linear(n_in, n_out, bias_attr=False)

    class Attention(nn.Layer):
        """Causal grouped-query attention, no position signal."""

        def __init__(self):
            super().__init__()
            self.q = linear(H, heads * hd)
            self.k, self.v = linear(H, kv * hd), linear(H, kv * hd)
            self.o = linear(heads * hd, H)

        def forward(self, h):
            B, S = h.shape[0], h.shape[1]
            out = F.scaled_dot_product_attention(
                self.q(h).reshape([B, S, heads, hd]),
                self.k(h).reshape([B, S, kv, hd]),
                self.v(h).reshape([B, S, kv, hd]), is_causal=True)
            return self.o(out.reshape([B, S, heads * hd]))

    def mixer(kind):
        if kind == "m":
            return nn.Mamba2Mixer(
                H, cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"],
                cfg["chunk_size"], eps)
        if kind == "a":
            return Attention()
        return nn.MoELayer(
            H, cfg["moe_intermediate_size"],
            cfg["published"]["n_routed_experts"], cfg["num_experts_per_tok"],
            held=held, norm_topk_prob=cfg["norm_topk_prob"],
            scoring="sigmoid", selection_bias=True,
            routed_scaling_factor=cfg["routed_scaling_factor"],
            shared_width=cfg["moe_shared_expert_intermediate_size"],
            train_router=cfg["train_router"], expert_form="relu2")

    class Block(nn.Layer):
        """One norm, one mixer."""

        def __init__(self, kind):
            super().__init__()
            self.kind = kind
            self.norm = nn.RMSNorm(H, eps)
            self.mixer = mixer(kind)

        def forward(self, x):
            # x is the float32 residual stream; the norm hands the
            # weights' type to the matmuls, the router wants float32
            if self.kind == "e":
                return x + self.mixer(F.rms_norm(
                    x, self.norm.weight.astype("float32"), eps))
            return x + self.mixer(self.norm(x)).astype("float32")

    class NemotronH(nn.Layer):
        def __init__(self):
            super().__init__()
            self.tok = nn.Embedding(V, H)
            self.blocks = nn.LayerList([Block(k) for k in _kinds(cfg)])
            self.norm_f = nn.RMSNorm(H, eps)
            self.head = linear(H, V)

        def forward(self, ids):
            x = self.tok(ids).astype("float32")
            for blk in self.blocks:
                x = recompute(blk, x)
            return self.norm_f(x)

    model = NemotronH()

    def loss_fn(z, labels):
        """Float32 logits over the held vocabulary rows (float32 operands
        holding the weights' values: one MXU pass, float32 accumulation),
        the mean cross-entropy over every position."""
        n = z.shape[0] * z.shape[1]
        return F.linear_cross_entropy(
            z.astype("float32").reshape([n, H]),
            model.head.weight.astype("float32"),
            paddle.zeros([V], dtype="float32"), labels.reshape([n]))

    return model, loss_fn


_LEAVES = {
    "m": {"norm.weight": "norm.g", "mixer.in_proj.weight": "in.w",
          "mixer.conv_weight": "conv.w", "mixer.conv_bias": "conv.b",
          "mixer.dt_bias": "dt_bias", "mixer.A_log": "A_log",
          "mixer.D": "D", "mixer.norm_weight": "gate_norm.g",
          "mixer.out_proj.weight": "out.w"},
    "e": {"norm.weight": "norm.g", "mixer.router_weight": "router.w",
          "mixer.router_bias": "router.bias", "mixer.w_up": "experts.up",
          "mixer.w_down": "experts.down", "mixer.shared_up": "shared.up.w",
          "mixer.shared_down": "shared.down.w"},
    "a": {"norm.weight": "norm.g", "mixer.q.weight": "q.w",
          "mixer.k.weight": "k.w", "mixer.v.weight": "v.w",
          "mixer.o.weight": "o.w"},
}


def param_map(cfg, variant):
    """program parameter name -> (reference leaf, block of its kind)."""
    out = {"tok.weight": ("tok", None), "norm_f.weight": ("norm_f.g", None),
           "head.weight": ("head.w", None)}
    seen = {}
    for i, kind in enumerate(_kinds(cfg)):
        nth = seen.get(kind, 0)
        seen[kind] = nth + 1
        for p, leaf in _LEAVES[kind].items():
            out[f"blocks.{i}.{p}"] = (f"layers.{kind}.{leaf}", nth)
    return out


# ------------------------------------------------------------- the counts --
def _mixer_inner(cfg):
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def _matmul_weights(cfg, kind):
    """Matmul weights a token uses in one block of ``kind``, in
    expectation: an ``M`` block its two projections; an ``E`` block the
    router over all experts, the shared expert and top_k * held / total
    routed experts of two matrices each; a ``*`` block q, k, v and o."""
    H = cfg["hidden_size"]
    if kind == "m":
        di = _mixer_inner(cfg)
        conv = di + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
        return H * (di + conv + cfg["mamba_num_heads"]) + di * H
    if kind == "e":
        E = cfg["published"]["n_routed_experts"]
        routed = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / E
                  * 2 * H * cfg["moe_intermediate_size"])
        return (H * E + 2 * H * cfg["moe_shared_expert_intermediate_size"]
                + routed)
    hd = cfg["head_dim"]
    return 2 * H * hd * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])


def _pair_flops(cfg, seq):
    """FLOPs a token and ``*`` block over (query, key) pairs, forward and
    backward, at what causal attention needs: (seq + 1) / 2 keys a query,
    the score and the value product each ``head_dim`` wide, each one
    forward and two backward."""
    return (3 * 2 * cfg["num_attention_heads"] * 2 * cfg["head_dim"]
            * (seq + 1) / 2)


def _scan_flops(cfg):
    """FLOPs a token of one forward pass of the scan at ``chunk_size``, a
    head: ``C B^T`` over the chunk (shared by a group's heads), the
    masked product inside the chunk, the token's part into the chunk's
    state and its reading out of the state the chunk started from."""
    Q, N, P = cfg["chunk_size"], cfg["ssm_state_size"], cfg["mamba_head_dim"]
    heads = cfg["mamba_num_heads"]
    per_group = heads // cfg["n_groups"]
    return heads * (2 * Q * N / per_group + 2 * Q * P + 2 * N * P + 2 * N * P)


def train_flops_per_token(cfg, seq):
    """FLOPs the forward and backward passes need for one token of a
    ``seq``-long row.  6 per matmul weight a token uses
    (``_matmul_weights`` a block and the [H, V] head over the held
    vocabulary rows); causal attention's pairs in each ``*`` block; the
    scan's products in each ``M`` block, forward and twice that backward;
    no embedding look-up, no elementwise work.  The forward replayed by
    recompute is not counted."""
    kinds = _kinds(cfg)
    weights = (sum(_matmul_weights(cfg, k) for k in kinds)
               + cfg["hidden_size"] * cfg["vocab_size"])
    return (6 * weights + kinds.count("a") * _pair_flops(cfg, seq)
            + kinds.count("m") * 3 * _scan_flops(cfg))


def attention_calls(cfg, batch, seq):
    """The ``*`` blocks' attention calls of one step; a replay keeps the
    forward kernel's ``out`` and ``lse`` and runs none again."""
    return {"calls": _kinds(cfg).count("a"), "batch": batch,
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "seq": seq,
            "head_dim": cfg["head_dim"], "causal": True,
            "forward_replays": 0}


def ssm_scan_work(cfg, mix, forward_calls):
    """(FLOPs, bytes) of the scan over one step: ``forward_calls`` forward
    passes (counted from the trace: the ``M`` blocks' own and the ones a
    replay runs) of ``_scan_flops`` a token, and one backward an ``M``
    block of twice that.  Bytes: x and y [tokens, heads * head_dim] and B
    and C [tokens, groups * state] in bfloat16 and dt [tokens, heads] in
    float32, each once in or out a pass: a forward reads x, B, C, dt and
    writes y; a backward reads those and dy and writes dx, dB, dC, ddt.
    The same work whatever implements it, XLA or a kernel."""
    tokens = mix["batch"] * mix["seq"]
    layers = _kinds(cfg).count("m")
    di = _mixer_inner(cfg)
    bc = cfg["n_groups"] * cfg["ssm_state_size"]
    flops = tokens * _scan_flops(cfg) * (forward_calls + 2 * layers)
    inputs = tokens * (di * 2 + 2 * bc * 2 + cfg["mamba_num_heads"] * 4)
    y = tokens * di * 2
    bytes_ = forward_calls * (inputs + y) + layers * (2 * inputs + y)
    return flops, bytes_


def expert_matmul_work(cfg, mix, product_calls):
    """(FLOPs, bytes) of the grouped products over the held routed experts
    one step, at the EXPECTED load: a token sends top_k * held / total
    assignments here (0.375; the routers are held still, so the load
    stays the draw's).  ``product_calls``: grouped-product kernel calls a
    step, counted from the trace (each is one H x F product over one
    sequence's rows; an expert is TWO of them, up and down).  Bytes: the
    held experts' weights of the product once a call, the rows in and
    out.  The shared expert is a plain matmul under a scope of its own
    and is not part of this."""
    T = mix["seq"]
    H, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, E = cfg["n_routed_experts"], cfg["published"]["n_routed_experts"]
    rows = T * cfg["num_experts_per_tok"] * held / E
    flops = product_calls * 2 * rows * H * F
    bytes_ = product_calls * (held * H * F * 2 + rows * (H + F) * 2)
    return flops, bytes_
