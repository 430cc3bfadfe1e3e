"""BERT masked-LM as the program builds it: paddle_tpu ``nn`` layers
(copy of ``bench.py::build_model`` and ``bench_bert``'s loss, which may
change or go), plus what the benchmark needs to know about it: which
program parameter is which reference leaf, and the FLOPs a step needs.
"""


def build(cfg, variant):
    """-> (model, loss_fn).  ``loss_fn(out, labels)`` is the fused chunked
    head + cross-entropy: the [tokens, vocab] logits never materialise."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn

    if not variant.get("final_norm") or variant.get("gelu") != "exact":
        raise ValueError("models/bert.py builds exact GELU and a final "
                         f"LayerNorm, the cell asks for {variant}")
    V, H = cfg["vocab_size"], cfg["hidden_size"]
    drop = cfg["hidden_dropout_prob"]

    class BertMLM(nn.Layer):
        def __init__(self):
            super().__init__()
            self.tok = nn.Embedding(V, H)
            self.pos = nn.Embedding(cfg["max_position_embeddings"], H)
            enc = nn.TransformerEncoderLayer(
                H, cfg["num_attention_heads"], cfg["intermediate_size"],
                dropout=drop, activation="gelu",
                attn_dropout=cfg["attention_probs_dropout_prob"],
                act_dropout=drop)
            self.encoder = nn.TransformerEncoder(
                enc, cfg["num_hidden_layers"])
            self.norm = nn.LayerNorm(H)
            self.head = nn.Linear(H, V)

        def forward(self, ids):
            pos_ids = paddle.arange(ids.shape[1]).unsqueeze(0)
            x = self.tok(ids) + self.pos(pos_ids)
            return self.norm(self.encoder(x))

    model = BertMLM()

    def loss_fn(out, labels):
        return F.linear_cross_entropy(
            out.reshape([-1, H]), model.head.weight, model.head.bias,
            labels.reshape([-1]))

    return model, loss_fn


def param_map(cfg, variant):
    """program parameter name -> (reference leaf, block or None)."""
    out = {"tok.weight": ("tok", None), "pos.weight": ("pos", None),
           "norm.weight": ("ln_f.g", None), "norm.bias": ("ln_f.b", None),
           "head.weight": ("head.w", None), "head.bias": ("head.b", None)}
    subs = {"self_attn.q_proj": "q", "self_attn.k_proj": "k",
            "self_attn.v_proj": "v", "self_attn.out_proj": "o",
            "linear1": "fc1", "linear2": "fc2"}
    for i in range(cfg["num_hidden_layers"]):
        for prog, ref in subs.items():
            out[f"encoder.layers.{i}.{prog}.weight"] = (f"layers.{ref}.w", i)
            out[f"encoder.layers.{i}.{prog}.bias"] = (f"layers.{ref}.b", i)
        for n in ("1", "2"):
            out[f"encoder.layers.{i}.norm{n}.weight"] = (f"layers.ln{n}.g", i)
            out[f"encoder.layers.{i}.norm{n}.bias"] = (f"layers.ln{n}.b", i)
    return out


def train_flops_per_token(cfg, seq):
    """FLOPs the forward and backward passes need for one token of a
    ``seq``-long row: 6 per matmul weight (the head's included, the
    embedding look-ups not), plus bidirectional attention's scores and
    values, 12 * L * seq * H (PaLM appendix B).  Recomputed operations
    are not counted."""
    H, F, L = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_hidden_layers"])
    n_matmul = L * (4 * H * H + 2 * H * F) + H * cfg["vocab_size"]
    return 6 * n_matmul + 12 * L * seq * H


def attention_calls(cfg, batch, seq):
    """The attention calls one step executes, for the flash-attention
    roofline: (how many, batch, heads, seq, head_dim, causal)."""
    A = cfg["num_attention_heads"]
    return dict(calls=cfg["num_hidden_layers"], batch=batch, heads=A,
                seq=seq, head_dim=cfg["hidden_size"] // A, causal=False,
                forward_replays=0)
