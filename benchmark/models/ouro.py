"""Ouro (a LoopLM) as the program builds it: paddle_tpu ``nn`` layers
(sandwich RMSNorms, rotary positions, ``F.scaled_dot_product_attention``,
a SwiGLU feed-forward) in ``nn.LoopedStack``, which runs the one stack of
blocks ``total_ut_steps`` times on the same parameters under per-block
recompute with the final norm at the end of every pass; a float32 residual
stream; ``nn.LoopExitGate`` on the four exit states; and
``F.loop_exit_loss``, the expected loss over the exits with its entropy
term, through the chunked ``linear_cross_entropy`` head weighted by the
exit distribution.  Plus which program parameter is which reference leaf,
the FLOPs a step needs (a block application, not a parameter, is the
unit), and what its attention calls need for the flash roofline.
"""


def _require_the_layers():
    """Fail while the cell's files are loaded, before the reference has
    spent a minute, on a program from before these layers existed."""
    import inspect

    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    missing = [name for name, home in (
        ("nn.LoopedStack", nn), ("nn.LoopExitGate", nn),
        ("F.loop_exit_loss", F)) if not hasattr(home, name.split(".")[1])]
    if "token_weight" not in inspect.signature(
            F.linear_cross_entropy).parameters:
        missing.append("F.linear_cross_entropy(token_weight=)")
    if missing:
        raise ImportError("models/ouro.py needs " + ", ".join(missing)
                          + ", which this paddle_tpu does not have")


_require_the_layers()


def build(cfg, variant):
    """-> (model, loss_fn).  The model returns (the four exit states
    [T, B, S, H] float32, the gate's logits [T, B, S] float32)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn

    if cfg["recompute"] != "per_block" or cfg["tie_word_embeddings"]:
        raise ValueError("models/ouro.py builds per-block recompute and an "
                         "untied head")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("models/ouro.py builds plain multi-head attention")
    V, H, heads = (cfg["vocab_size"], cfg["hidden_size"],
                   cfg["num_attention_heads"])
    ffn, hd, eps = cfg["intermediate_size"], cfg["head_dim"], \
        cfg["rms_norm_eps"]
    T = cfg["total_ut_steps"]

    def linear(n_in, n_out):
        return nn.Linear(n_in, n_out, bias_attr=False)

    class Block(nn.Layer):
        """Sandwich norms: each branch is normed on its way in and on its
        way out."""

        def __init__(self):
            super().__init__()
            self.norm1, self.norm2 = nn.RMSNorm(H, eps), nn.RMSNorm(H, eps)
            self.q, self.k, self.v = (linear(H, heads * hd) for _ in "qkv")
            self.o = linear(heads * hd, H)
            self.norm3, self.norm4 = nn.RMSNorm(H, eps), nn.RMSNorm(H, eps)
            self.gate, self.up = linear(H, ffn), linear(H, ffn)
            self.down = linear(ffn, H)

        def forward(self, x):
            # x is the float32 residual stream; the norms hand the
            # weights' type to the matmuls and to the additions' casts
            B, S = x.shape[0], x.shape[1]
            h = self.norm1(x)
            q = F.rotary_embedding(self.q(h).reshape([B, S, heads, hd]),
                                   cfg["rope_theta"])
            k = F.rotary_embedding(self.k(h).reshape([B, S, heads, hd]),
                                   cfg["rope_theta"])
            v = self.v(h).reshape([B, S, heads, hd])
            a = F.scaled_dot_product_attention(q, k, v, is_causal=True)
            a = self.o(a.reshape([B, S, heads * hd]))
            x = x + self.norm2(a).astype("float32")
            h = self.norm3(x)
            m = self.down(F.silu(self.gate(h)) * self.up(h))
            return x + self.norm4(m).astype("float32")

    class StreamNorm(nn.RMSNorm):
        """The final norm, its result left in float32: it is the next
        pass's residual stream as well as an exit's state."""

        def forward(self, x):
            return F.rms_norm(x, self.weight.astype("float32"), eps)

    class Ouro(nn.Layer):
        def __init__(self):
            super().__init__()
            self.tok = nn.Embedding(V, H)
            self.stack = nn.LoopedStack(
                [Block() for _ in range(cfg["num_hidden_layers"])], T,
                norm=StreamNorm(H, eps), recompute=True)
            self.exit_gate = nn.LoopExitGate(H)
            self.head = linear(H, V)

        def forward(self, ids):
            exits = self.stack(self.tok(ids).astype("float32"))
            return exits, self.exit_gate(exits)

    model = Ouro()

    def loss_fn(out, labels):
        """Float32 logits (float32 operands holding the weights' values:
        one MXU pass, float32 accumulation) at every exit, mixed by the
        exit distribution, less beta times its entropy."""
        exits, gate_logits = out
        n = exits.shape[1] * exits.shape[2]
        return F.loop_exit_loss(
            exits.reshape([T, n, H]), gate_logits.reshape([T, n]),
            model.head.weight.astype("float32"),
            paddle.zeros([V], dtype="float32"), labels.reshape([n]),
            beta=cfg["exit_entropy_beta"])

    return model, loss_fn


def param_map(cfg, variant):
    """program parameter name -> (reference leaf, block or None)."""
    out = {"tok.weight": ("tok", None),
           "stack.norm.weight": ("norm_f.g", None),
           "exit_gate.weight": ("gate.w", None),
           "exit_gate.bias": ("gate.b", None),
           "head.weight": ("head.w", None)}
    for i in range(cfg["num_hidden_layers"]):
        for n in ("q", "k", "v", "o", "gate", "up", "down"):
            out[f"stack.blocks.{i}.{n}.weight"] = (f"layers.{n}.w", i)
        for n in ("norm1", "norm2", "norm3", "norm4"):
            out[f"stack.blocks.{i}.{n}.weight"] = (f"layers.{n}.g", i)
    return out


def train_flops_per_token(cfg, seq):
    """FLOPs the forward and backward passes need for one token of a
    ``seq``-long row, counted PER APPLICATION, not per parameter: every
    one of the T passes runs the L blocks' matmuls (4 H^2 + 3 H F weights
    a block) and the one [H, V] head, 6 FLOPs a weight a use, and causal
    attention's scores and values at half the square, 6 * seq * H a block
    application.  No embedding look-up, no gate (2 H a token a pass); the
    forward replayed by recompute is not counted."""
    H, F, L, T = (cfg["hidden_size"], cfg["intermediate_size"],
                  cfg["num_hidden_layers"], cfg["total_ut_steps"])
    n_matmul = T * L * (4 * H * H + 3 * H * F) + T * H * cfg["vocab_size"]
    return 6 * n_matmul + 6 * T * L * seq * H


def attention_calls(cfg, batch, seq):
    """The attention calls one step executes, for the flash-attention
    roofline: a call a block application, T x L.  ``forward_replays`` is
    the share of them whose forward kernel the replay runs again, read
    from the program and not from the config: ``parallel.recompute``
    counts at trace time each ``attn_out`` and ``attn_lse`` it keeps
    across a replay (0 replays while it keeps all 32 of each; a process
    that traced the step more than once has counted more, and a program
    from before those counters is one whose policy keeps them all)."""
    from paddle_tpu.utils import monitor
    calls = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    stats = monitor.all_stats()
    kept = min(calls, stats.get("recompute.kept.attn_out", calls),
               stats.get("recompute.kept.attn_lse", calls))
    return dict(calls=calls, batch=batch, heads=cfg["num_attention_heads"],
                seq=seq, head_dim=cfg["head_dim"], causal=True,
                forward_replays=(calls - kept) / calls)
