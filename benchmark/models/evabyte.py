"""EvaByte as the program builds it: paddle_tpu ``nn`` layers (RMSNorm
with unit offset, rotary positions, ``F.eva_attention``, a SwiGLU
feed-forward), a float32 residual stream, per-block recompute and the
multi-byte head through the chunked ``linear_cross_entropy``; plus which
program parameter is which reference leaf, the FLOPs a step needs, and
what EVA attention needs for its roofline.
"""


def _require_the_layers():
    """Fail while the cell's files are loaded, before the reference has
    spent a minute, on a program from before these layers existed."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    missing = [name for name, home in (
        ("nn.RMSNorm", nn), ("F.rotary_embedding", F),
        ("F.eva_attention", F)) if not hasattr(home, name.split(".")[1])]
    if missing:
        raise ImportError("models/evabyte.py needs " + ", ".join(missing)
                          + ", which this paddle_tpu does not have")


_require_the_layers()


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def build(cfg, variant):
    """-> (model, loss_fn)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    from paddle_tpu.parallel import recompute

    if cfg["recompute"] != "per_block" or cfg["tie_word_embeddings"]:
        raise ValueError("models/evabyte.py builds per-block recompute and "
                         "an untied head")
    V, H, heads = (cfg["vocab_size"], cfg["hidden_size"],
                   cfg["num_attention_heads"])
    ffn, hd, P = cfg["intermediate_size"], head_dim(cfg), cfg["num_pred_heads"]
    eps, offset = cfg["rms_norm_eps"], cfg["norm_add_unit_offset"]
    zeros = nn.initializer.Constant(0.0)

    def linear(n_in, n_out):
        return nn.Linear(n_in, n_out, bias_attr=False)

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.norm1 = nn.RMSNorm(H, eps, unit_offset=offset)
            self.q, self.k, self.v = linear(H, H), linear(H, H), linear(H, H)
            self.mu = self.create_parameter([heads, hd],
                                            default_initializer=zeros)
            self.phi = self.create_parameter([heads, hd],
                                             default_initializer=zeros)
            self.o = linear(H, H)
            self.norm2 = nn.RMSNorm(H, eps, unit_offset=offset)
            self.gate, self.up = linear(H, ffn), linear(H, ffn)
            self.down = linear(ffn, H)

        def forward(self, x):
            # x is the float32 residual stream (fp32_skip_add); the norms
            # hand the weights' type to the matmuls
            B, S = x.shape[0], x.shape[1]
            h = self.norm1(x)
            q = F.rotary_embedding(self.q(h).reshape([B, S, heads, hd]),
                                   cfg["rope_theta"])
            k = F.rotary_embedding(self.k(h).reshape([B, S, heads, hd]),
                                   cfg["rope_theta"])
            v = self.v(h).reshape([B, S, heads, hd])
            a = F.eva_attention(q, k, v, self.mu, self.phi,
                                cfg["window_size"], cfg["chunk_size"])
            x = x + self.o(a.reshape([B, S, H])).astype("float32")
            h = self.norm2(x)
            y = self.down(F.silu(self.gate(h)) * self.up(h))
            return x + y.astype("float32")

    class EvaByte(nn.Layer):
        def __init__(self):
            super().__init__()
            self.tok = nn.Embedding(V, H)
            self.blocks = nn.LayerList(
                [Block() for _ in range(cfg["num_hidden_layers"])])
            self.norm_f = nn.RMSNorm(H, eps, unit_offset=offset)
            self.head = linear(H, P * V)

        def forward(self, ids):
            x = self.tok(ids).astype("float32")
            for blk in self.blocks:
                x = recompute(blk, x)
            return self.norm_f(x)

    model = EvaByte()

    def loss_fn(out, labels):
        """Head j at position t against ``labels[t + j]``, the pairs with
        t + j >= S dropped; float32 logits (fp32_logits): float32 operands
        holding the weights' values, so the MXU takes them in one pass
        and accumulates and returns float32."""
        B, S = out.shape[0], out.shape[1]
        z = out.astype("float32")
        w = model.head.weight.astype("float32")
        bias = paddle.zeros([V], dtype="float32")
        total, pairs = 0.0, 0
        for j in range(P):
            n = B * (S - j)
            total = total + n * F.linear_cross_entropy(
                z[:, :S - j].reshape([n, H]), w[:, j * V:(j + 1) * V], bias,
                labels[:, j:].reshape([n]))
            pairs += n
        return total / pairs

    return model, loss_fn


def param_map(cfg, variant):
    """program parameter name -> (reference leaf, block or None)."""
    out = {"tok.weight": ("tok", None), "norm_f.weight": ("norm_f.g", None),
           "head.weight": ("head.w", None)}
    for i in range(cfg["num_hidden_layers"]):
        for n in ("q", "k", "v", "o", "gate", "up", "down"):
            out[f"blocks.{i}.{n}.weight"] = (f"layers.{n}.w", i)
        for n in ("mu", "phi"):
            out[f"blocks.{i}.{n}"] = (f"layers.{n}", i)
        for n in ("norm1", "norm2"):
            out[f"blocks.{i}.{n}.weight"] = (f"layers.{n}.g", i)
    return out


def eva_keys_per_query(cfg, seq):
    """(mean exact keys, mean summaries) a query of a ``seq``-long row
    attends to: half a window (plus the diagonal) and, in window w, the
    w * window / chunk summaries before it."""
    window = min(cfg["window_size"], seq)
    windows = seq // window
    per_window = window // cfg["chunk_size"]
    return (window + 1) / 2, per_window * (windows - 1) / 2


def train_flops_per_token(cfg, seq):
    """FLOPs the forward and backward passes need for one token of a
    ``seq``-long row: 6 per matmul weight (the layers and the
    [H, heads * V] head; no embedding look-up), plus attention at what EVA
    needs and not at the full square: per layer 3 * 4 * H * (mean exact
    keys + mean summaries).  The pooling (vector work, 8 * H a token) and
    the forward replayed by recompute are not counted."""
    H, F, L = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_hidden_layers"])
    n_matmul = (L * (4 * H * H + 3 * H * F)
                + H * cfg["num_pred_heads"] * cfg["vocab_size"])
    return 6 * n_matmul + 12 * L * H * sum(eva_keys_per_query(cfg, seq))


def eva_attention_work(cfg, batch, seq, itemsize=2):
    """(FLOPs, bytes) of EVA attention over one step, for its roofline.
    Per layer, batch and head a forward is two matmuls (scores, values)
    over half of W^2 a window plus W * (W / c) * w for window w's
    summaries, times D; a backward is five; the forward replayed by
    per-block recompute counts as executed.  Bytes: q, k, v, o and the
    two summaries once a forward; q, k, v, o, do, dq, dk, dv, the
    summaries and their gradients once a backward."""
    A, D, L = cfg["num_attention_heads"], head_dim(cfg), cfg["num_hidden_layers"]
    window = min(cfg["window_size"], seq)
    windows = seq // window
    per_window = window // cfg["chunk_size"]
    pairs = (windows * window * window / 2
             + window * per_window * windows * (windows - 1) / 2)
    forwards = 2 if cfg["recompute"] == "per_block" else 1
    matmul = 2 * batch * A * pairs * D
    flops = L * matmul * (2 * forwards + 5)
    tensor = batch * seq * A * D * itemsize
    summaries = 0 if windows == 1 else tensor / cfg["chunk_size"]
    bytes_ = L * (forwards * (4 * tensor + 2 * summaries)
                  + 8 * tensor + 4 * summaries)
    return flops, bytes_
