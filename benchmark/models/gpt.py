"""GPT causal LM as the program builds it: paddle_tpu ``nn`` layers with
per-block recompute and a tied head inside the fused chunked loss (copy
of ``bench.py::build_gpt`` and ``bench_gpt``'s loss, which may change or
go), plus which program parameter is which reference leaf, and the FLOPs
a step needs.
"""


def build(cfg, variant):
    """-> (model, loss_fn)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    from paddle_tpu.parallel import recompute

    if cfg["recompute"] != "per_block" or not cfg["tie_word_embeddings"]:
        raise ValueError("models/gpt.py builds per-block recompute and a "
                         "tied head")
    V, H, heads = cfg["vocab_size"], cfg["n_embd"], cfg["n_head"]
    ffn, hd = cfg["n_inner"], cfg["n_embd"] // cfg["n_head"]

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.ln1 = nn.LayerNorm(H, epsilon=cfg["layer_norm_epsilon"])
            self.q = nn.Linear(H, H)
            self.k = nn.Linear(H, H)
            self.v = nn.Linear(H, H)
            self.proj = nn.Linear(H, H)
            self.ln2 = nn.LayerNorm(H, epsilon=cfg["layer_norm_epsilon"])
            self.fc1 = nn.Linear(H, ffn)
            self.fc2 = nn.Linear(ffn, H)
            self.drop = nn.Dropout(cfg["resid_pdrop"])

        def forward(self, x):
            B, S = x.shape[0], x.shape[1]
            h = self.ln1(x)
            q = self.q(h).reshape([B, S, heads, hd])
            k = self.k(h).reshape([B, S, heads, hd])
            v = self.v(h).reshape([B, S, heads, hd])
            a = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, dropout_p=cfg["attn_pdrop"],
                training=self.training)
            x = x + self.drop(self.proj(a.reshape([B, S, H])))
            h = self.ln2(x)
            return x + self.drop(self.fc2(F.gelu(self.fc1(h),
                                                 approximate=True)))

    class GPT(nn.Layer):
        def __init__(self):
            super().__init__()
            self.tok = nn.Embedding(V, H)
            self.pos = nn.Embedding(cfg["n_positions"], H)
            self.drop = nn.Dropout(cfg["embd_pdrop"])
            self.blocks = nn.LayerList(
                [Block() for _ in range(cfg["n_layer"])])
            self.ln_f = nn.LayerNorm(H, epsilon=cfg["layer_norm_epsilon"])

        def forward(self, ids):
            pos_ids = paddle.arange(ids.shape[1]).unsqueeze(0)
            x = self.drop(self.tok(ids) + self.pos(pos_ids))
            for blk in self.blocks:
                x = recompute(blk, x)
            return self.ln_f(x)

    model = GPT()

    def loss_fn(out, labels):
        w = paddle.transpose(model.tok.weight, [1, 0])
        bias = paddle.zeros([V], dtype=w.dtype)
        return F.linear_cross_entropy(
            out.reshape([-1, H]), w, bias, labels.reshape([-1]))

    return model, loss_fn


def param_map(cfg, variant):
    """program parameter name -> (reference leaf, block or None)."""
    out = {"tok.weight": ("tok", None), "pos.weight": ("pos", None),
           "ln_f.weight": ("ln_f.g", None), "ln_f.bias": ("ln_f.b", None)}
    subs = {"q": "q", "k": "k", "v": "v", "proj": "o", "fc1": "fc1",
            "fc2": "fc2"}
    for i in range(cfg["n_layer"]):
        for prog, ref in subs.items():
            out[f"blocks.{i}.{prog}.weight"] = (f"layers.{ref}.w", i)
            out[f"blocks.{i}.{prog}.bias"] = (f"layers.{ref}.b", i)
        for n in ("1", "2"):
            out[f"blocks.{i}.ln{n}.weight"] = (f"layers.ln{n}.g", i)
            out[f"blocks.{i}.ln{n}.bias"] = (f"layers.ln{n}.b", i)
    return out


def train_flops_per_token(cfg, seq):
    """FLOPs the forward and backward passes need for one token of a
    ``seq``-long row: 6 per matmul weight, the tied head's real
    [T,H]x[H,V] matmul included and the embedding look-ups not, plus
    causal attention's scores and values at half the square,
    6 * L * seq * H.  The forward replayed by recompute is not counted."""
    H, F, L = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    n_matmul = L * (4 * H * H + 2 * H * F) + H * cfg["vocab_size"]
    return 6 * n_matmul + 6 * L * seq * H


def attention_calls(cfg, batch, seq):
    """The attention calls one step executes, for the flash-attention
    roofline; ``forward_replays`` is 1 under per-block recompute: each
    block's forward kernel runs a second time in the backward pass."""
    return dict(calls=cfg["n_layer"], batch=batch, heads=cfg["n_head"],
                seq=seq, head_dim=cfg["n_embd"] // cfg["n_head"],
                causal=True,
                forward_replays=1 if cfg["recompute"] == "per_block" else 0)
