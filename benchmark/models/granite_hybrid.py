"""granite-4.0-h-micro (``granitemoehybrid``) as the program builds it:
paddle_tpu ``nn`` layers, every layer two sublayers scaled into a float32
residual stream, ``x + r * Mixer(RMSNorm(x))`` then
``x + r * FFN(RMSNorm(x))``: ``nn.Mamba2Mixer`` at ONE group of 64 heads
(over ``F.causal_conv1d``, ``F.ssd_scan``, ``F.gated_group_rms_norm``) for
``mamba``; grouped-query ``F.scaled_dot_product_attention`` (32 query
heads of 64 on 8 key/value heads, ``scale`` the family's attention
multiplier, no position signal) for ``attention``; ``nn.GatedFFN`` (one
fused in-projection) after either; per-block recompute; the chunked
``linear_cross_entropy`` head on the TRANSPOSE of the embedding (one
matrix).  The family's four multipliers are scalars of this file: the
embedding's on the looked-up rows, the residual's on each branch, the
attention's as the kernel's ``scale``, and the logits' as its inverse on
the state the head reads (``logits = (h / s) E^T``: what the reference
computes as ``(h E^T) / s``).  Plus which program parameter is which
reference leaf, the FLOPs a step needs by kind of layer, and what the
scan needs for its roofline.
"""

_KINDS = {"mamba": "m", "attention": "a"}


def _require_the_layers():
    """Fail while the cell's files are loaded, before the reference has
    spent a minute, on a program from before these layers existed."""
    import inspect

    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    missing = [name for name, home in (
        ("nn.GatedFFN", nn), ("nn.Mamba2Mixer", nn), ("F.ssd_scan", F))
        if not hasattr(home, name.split(".")[1])]
    if "scale" not in inspect.signature(
            F.scaled_dot_product_attention).parameters:
        missing.append("F.scaled_dot_product_attention(scale=)")
    if missing:
        raise ImportError("models/granite_hybrid.py needs "
                          + ", ".join(missing)
                          + ", which this paddle_tpu does not have")


_require_the_layers()


def _kinds(cfg):
    types = cfg["layer_types"]
    if len(types) != cfg["num_hidden_layers"] or set(types) - set(_KINDS):
        raise ValueError(f"layer_types {types!r} is not "
                         f"{cfg['num_hidden_layers']} of mamba, attention")
    return [_KINDS[t] for t in types]


def build(cfg, variant):
    """-> (model, loss_fn).  The model returns the final normed state."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    from paddle_tpu.parallel import recompute

    if cfg["recompute"] != "per_block" or not cfg["tie_word_embeddings"]:
        raise ValueError("models/granite_hybrid.py builds per-block "
                         "recompute and a tied head")
    if (cfg["num_local_experts"] or cfg["hidden_act"] != "silu"
            or cfg["position_embedding_type"] != "nope"
            or cfg["normalization_function"] != "rmsnorm"
            or not cfg["mamba_conv_bias"] or cfg["mamba_proj_bias"]
            or cfg["attention_bias"]):
        raise ValueError("models/granite_hybrid.py builds no routed "
                         "experts, a silu feed-forward layer, RMSNorm, no "
                         "rotary embedding and no bias but the "
                         "convolution's")
    V, H, eps = cfg["vocab_size"], cfg["hidden_size"], cfg["rms_norm_eps"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = H // heads
    r = cfg["residual_multiplier"]

    def linear(n_in, n_out):
        return nn.Linear(n_in, n_out, bias_attr=False)

    class Attention(nn.Layer):
        """Causal grouped-query attention, no position signal, the
        scores scaled by the family's multiplier."""

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
                self.v(h).reshape([B, S, kv, hd]), is_causal=True,
                scale=cfg["attention_multiplier"])
            return self.o(out.reshape([B, S, heads * hd]))

    def mixer(kind):
        if kind == "a":
            return Attention()
        if cfg["mamba_n_heads"] * cfg["mamba_d_head"] != \
                cfg["mamba_expand"] * H:
            raise ValueError("mamba_n_heads x mamba_d_head is not "
                             "mamba_expand x hidden_size")
        return nn.Mamba2Mixer(
            H, cfg["mamba_n_heads"], cfg["mamba_d_head"],
            cfg["mamba_n_groups"], cfg["mamba_d_state"], cfg["mamba_d_conv"],
            cfg["scan_chunk"], eps)

    class Layer(nn.Layer):
        """A mixer and a feed-forward layer, a norm before each."""

        def __init__(self, kind):
            super().__init__()
            self.norm1 = nn.RMSNorm(H, eps)
            self.mixer = mixer(kind)
            self.norm2 = nn.RMSNorm(H, eps)
            self.ffn = nn.GatedFFN(H, cfg["shared_intermediate_size"])

        def forward(self, x):
            # x is the float32 residual stream; the norms hand the
            # weights' type to the matmuls
            x = x + r * self.mixer(self.norm1(x)).astype("float32")
            return x + r * self.ffn(self.norm2(x)).astype("float32")

    class GraniteHybrid(nn.Layer):
        def __init__(self):
            super().__init__()
            self.tok = nn.Embedding(V, H)
            self.blocks = nn.LayerList([Layer(k) for k in _kinds(cfg)])
            self.norm_f = nn.RMSNorm(H, eps)

        def forward(self, ids):
            x = self.tok(ids).astype("float32") * cfg["embedding_multiplier"]
            for blk in self.blocks:
                x = recompute(blk, x)
            return self.norm_f(x)

    model = GraniteHybrid()

    def loss_fn(z, labels):
        """Float32 logits over the held rows of the tied matrix (float32
        operands holding the weights' values: one MXU pass, float32
        accumulation), ``logits_scaling`` as its inverse on the state;
        the mean cross-entropy over every position."""
        n = z.shape[0] * z.shape[1]
        return F.linear_cross_entropy(
            z.astype("float32").reshape([n, H]) * (1.0 / cfg["logits_scaling"]),
            paddle.transpose(model.tok.weight, [1, 0]).astype("float32"),
            paddle.zeros([V], dtype="float32"), labels.reshape([n]))

    return model, loss_fn


_FFN = {"norm2.weight": "norm2.g", "ffn.in_proj.weight": "ffn.in.w",
        "ffn.out_proj.weight": "ffn.out.w"}
_LEAVES = {
    "m": {"norm1.weight": "norm1.g", "mixer.in_proj.weight": "in.w",
          "mixer.conv_weight": "conv.w", "mixer.conv_bias": "conv.b",
          "mixer.dt_bias": "dt_bias", "mixer.A_log": "A_log",
          "mixer.D": "D", "mixer.norm_weight": "gate_norm.g",
          "mixer.out_proj.weight": "out.w", **_FFN},
    "a": {"norm1.weight": "norm1.g", "mixer.q.weight": "q.w",
          "mixer.k.weight": "k.w", "mixer.v.weight": "v.w",
          "mixer.o.weight": "o.w", **_FFN},
}


def param_map(cfg, variant):
    """program parameter name -> (reference leaf, layer of its kind).
    ``ffn.in_proj.weight`` is the ONE fused in-projection [hidden, 2 x
    intermediate], the gate's half first: the reference's ``ffn.in.w``
    as it lies.  ``tok.weight`` is the embedding and the head."""
    out = {"tok.weight": ("tok", None), "norm_f.weight": ("norm_f.g", None)}
    seen = {}
    for i, kind in enumerate(_kinds(cfg)):
        nth = seen.get(kind, 0)
        seen[kind] = nth + 1
        for p, leaf in _LEAVES[kind].items():
            out[f"blocks.{i}.{p}"] = (f"layers.{kind}.{leaf}", nth)
    return out


# ------------------------------------------------------------- the counts --
def _mixer_inner(cfg):
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def _matmul_weights(cfg, kind):
    """Matmul weights a token uses in one layer of ``kind``: the mixer's
    projections (a ``mamba`` layer its two, an ``attention`` layer q, k,
    v and o) and the feed-forward layer's two."""
    H = cfg["hidden_size"]
    ffn = 3 * H * cfg["shared_intermediate_size"]
    if kind == "m":
        di = _mixer_inner(cfg)
        conv = di + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
        return H * (di + conv + cfg["mamba_n_heads"]) + di * H + ffn
    hd = H // cfg["num_attention_heads"]
    return 2 * H * hd * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"]) + ffn


def _pair_flops(cfg, seq):
    """FLOPs a token and ``attention`` layer over (query, key) pairs,
    forward and backward, at what causal attention needs: (seq + 1) / 2
    keys a query, the score and the value product each a head wide, each
    one forward and two backward."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 3 * 2 * cfg["num_attention_heads"] * 2 * hd * (seq + 1) / 2


def _scan_flops(cfg):
    """FLOPs a token of one forward pass of the scan at ``scan_chunk``
    (128, the chunk the program walks: the lower count; the published
    kernels' 256 would double the two products inside a chunk), all
    heads: ``C B^T`` over the chunk ONCE (one group: all heads share it),
    the masked product inside the chunk, the token's part into the
    chunk's state and its reading out of the state the chunk started
    from, a head each."""
    Q, N, P = cfg["scan_chunk"], cfg["mamba_d_state"], cfg["mamba_d_head"]
    return (cfg["mamba_n_groups"] * 2 * Q * N
            + cfg["mamba_n_heads"] * (2 * Q * P + 2 * N * P + 2 * N * P))


def train_flops_per_token(cfg, seq):
    """FLOPs the forward and backward passes need for one token of a
    ``seq``-long row.  6 per matmul weight a token uses
    (``_matmul_weights`` a layer and the tied [H, V] head over the held
    vocabulary rows, a real product; the embedding's look-up is none);
    causal attention's pairs in each ``attention`` layer; the scan's
    products in each ``mamba`` layer, forward and twice that backward; no
    elementwise work.  The forward replayed by recompute is not
    counted."""
    kinds = _kinds(cfg)
    weights = (sum(_matmul_weights(cfg, k) for k in kinds)
               + cfg["hidden_size"] * cfg["vocab_size"])
    return (6 * weights + kinds.count("a") * _pair_flops(cfg, seq)
            + kinds.count("m") * 3 * _scan_flops(cfg))


def attention_calls(cfg, batch, seq):
    """The ``attention`` layers' calls of one step; a replay keeps the
    forward kernel's ``out`` and ``lse`` and runs none again."""
    return {"calls": _kinds(cfg).count("a"), "batch": batch,
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "seq": seq,
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
            "causal": True, "forward_replays": 0}


def ssm_scan_work(cfg, mix, forward_calls):
    """(FLOPs, bytes) of the scan over one step: ``forward_calls`` forward
    passes (counted from the trace: the ``mamba`` layers' own and the ones
    a replay runs) of ``_scan_flops`` a token, and one backward a
    ``mamba`` layer of twice that.  Bytes: x and y [tokens, heads x
    d_head] and B and C [tokens, d_state] (ONE group wide) in bfloat16
    and dt [tokens, heads] in float32, each once in or out a pass: a
    forward reads x, B, C, dt and writes y; a backward reads those and dy
    and writes dx, dB, dC, ddt.  The same work whatever implements it: a
    kernel that walks the group in blocks of heads reads B and C once a
    block and writes a float32 dB and dC a block, and none of that is
    counted as needed."""
    tokens = mix["batch"] * mix["seq"]
    layers = _kinds(cfg).count("m")
    di = _mixer_inner(cfg)
    bc = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    flops = tokens * _scan_flops(cfg) * (forward_calls + 2 * layers)
    inputs = tokens * (di * 2 + 2 * bc * 2 + cfg["mamba_n_heads"] * 4)
    y = tokens * di * 2
    bytes_ = forward_calls * (inputs + y) + layers * (2 * inputs + y)
    return flops, bytes_
