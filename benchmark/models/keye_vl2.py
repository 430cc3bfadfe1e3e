"""Keye-VL-2.0's language model as the program builds it: paddle_tpu
``nn`` layers (RMSNorm, per-head q/k norm, rotary positions, the DSA
indexer with ``F.dsa_indexer`` / ``F.sparse_attention`` /
``F.dsa_indexer_loss``, ``nn.MoELayer`` told which experts it holds), a
float32 residual stream, per-block recompute and the chunked
``linear_cross_entropy`` head; plus which program parameter is which
reference leaf, the FLOPs a step needs, and what the sparse attention
and the expert matmuls need for their rooflines.
"""


def _require_the_layers():
    """Fail while the cell's files are loaded, before the reference has
    spent a minute, on a program from before these layers existed."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    missing = [name for name, home in (
        ("nn.MoELayer", nn), ("F.dsa_indexer", F), ("F.sparse_attention", F),
        ("F.dsa_indexer_loss", F)) if not hasattr(home, name.split(".")[1])]
    if missing:
        raise ImportError("models/keye_vl2.py needs " + ", ".join(missing)
                          + ", which this paddle_tpu does not have")


_require_the_layers()


def build(cfg, variant):
    """-> (model, loss_fn).  The model returns (the final normed state,
    the mean of the layers' indexer losses)."""
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    from paddle_tpu.core.dispatch import apply
    from paddle_tpu.observability import scopes
    from paddle_tpu.parallel import recompute

    if cfg["recompute"] != "per_block" or cfg["tie_word_embeddings"]:
        raise ValueError("models/keye_vl2.py builds per-block recompute and "
                         "an untied head")
    V, H, L = cfg["vocab_size"], cfg["hidden_size"], cfg["num_hidden_layers"]
    A, KV, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    sa = cfg["sa_config"]
    J, DI, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    first = cfg["held_experts"]["first"]
    held = range(first, first + cfg["num_experts"])

    def linear(n_in, n_out):
        return nn.Linear(n_in, n_out, bias_attr=False)

    def detach(x):
        return apply(jax.lax.stop_gradient, x, op_name="stop_gradient")

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.norm1 = nn.RMSNorm(H, eps)
            self.q, self.k, self.v = (linear(H, A * D), linear(H, KV * D),
                                      linear(H, KV * D))
            self.q_norm, self.k_norm = nn.RMSNorm(D, eps), nn.RMSNorm(D, eps)
            self.idx_q, self.idx_k = linear(H, J * DI), linear(H, DI)
            self.idx_k_norm = nn.LayerNorm(DI, epsilon=eps)
            self.idx_w = linear(H, J)
            self.o = linear(A * D, H)
            self.norm2 = nn.RMSNorm(H, eps)
            self.moe = nn.MoELayer(
                H, cfg["moe_intermediate_size"],
                cfg["published"]["num_experts"], cfg["num_experts_per_tok"],
                held=held, norm_topk_prob=cfg["norm_topk_prob"])

        def forward(self, x):
            # x is the float32 residual stream; the norms hand the
            # weights' type to the matmuls
            B, S = x.shape[0], x.shape[1]
            h = self.norm1(x)
            q, k = self.q(h), self.k(h)
            with jax.named_scope(scopes.QK_NORM):
                q = self.q_norm(q.reshape([B, S, A, D]))
                k = self.k_norm(k.reshape([B, S, KV, D]))
            q = F.rotary_embedding(q, theta)
            k = F.rotary_embedding(k, theta)
            v = self.v(h).reshape([B, S, KV, D])
            # the indexer reads the normed state detached and learns from
            # its own loss alone
            hi = detach(h)
            qi = F.rotary_embedding(self.idx_q(hi).reshape([B, S, J, DI]),
                                    theta)
            ki = F.rotary_embedding(
                self.idx_k_norm(self.idx_k(hi)).reshape([B, S, 1, DI]),
                theta).reshape([B, S, DI])
            wi = self.idx_w(hi).astype("float32") * (J ** -0.5 * DI ** -0.5)
            mask, idx_lse = F.dsa_indexer(qi, ki, wi, topk)
            a, lse = F.sparse_attention(q, k, v, mask, return_lse=True)
            kl = F.dsa_indexer_loss(qi, ki, wi, mask, idx_lse, q, k, lse)
            x = x + self.o(a.reshape([B, S, A * D])).astype("float32")
            # the router wants the normed stream in float32
            h2 = F.rms_norm(x, self.norm2.weight.astype("float32"), eps)
            return x + self.moe(h2), kl

    class KeyeVL2(nn.Layer):
        def __init__(self):
            super().__init__()
            self.tok = nn.Embedding(V, H)
            self.blocks = nn.LayerList([Block() for _ in range(L)])
            self.norm_f = nn.RMSNorm(H, eps)
            self.head = linear(H, V)

        def forward(self, ids):
            x = self.tok(ids).astype("float32")
            kl = 0.0
            for blk in self.blocks:
                x, kl_i = recompute(blk, x)
                kl = kl + kl_i
            return self.norm_f(x), kl * (1.0 / L)

    model = KeyeVL2()

    def loss_fn(out, labels):
        """Float32 logits over the held vocabulary rows (float32 operands
        holding the weights' values: one MXU pass, float32 accumulation)
        plus the mean of the layers' indexer losses."""
        z, kl = out
        n = z.shape[0] * z.shape[1]
        ce = F.linear_cross_entropy(
            z.astype("float32").reshape([n, H]),
            model.head.weight.astype("float32"),
            paddle.zeros([V], dtype="float32"), labels.reshape([n]))
        return ce + kl

    return model, loss_fn


def param_map(cfg, variant):
    """program parameter name -> (reference leaf, block or None)."""
    out = {"tok.weight": ("tok", None), "norm_f.weight": ("norm_f.g", None),
           "head.weight": ("head.w", None)}
    for i in range(cfg["num_hidden_layers"]):
        for n in ("q", "k", "v", "o", "idx_q", "idx_k", "idx_w"):
            out[f"blocks.{i}.{n}.weight"] = (f"layers.{n}.w", i)
        for n in ("norm1", "norm2", "q_norm", "k_norm", "idx_k_norm"):
            out[f"blocks.{i}.{n}.weight"] = (f"layers.{n}.g", i)
        out[f"blocks.{i}.idx_k_norm.bias"] = ("layers.idx_k_norm.b", i)
        out[f"blocks.{i}.moe.router_weight"] = ("layers.router.w", i)
        for n in ("gate", "up", "down"):
            out[f"blocks.{i}.moe.w_{n}"] = (f"layers.experts.{n}", i)
    return out


# ------------------------------------------------------------- the counts --
def selected_keys_per_query(cfg, seq):
    """(mean keys a query may see, mean keys it keeps) over a ``seq``-long
    row: query t sees t + 1 and keeps min(t + 1, topk)."""
    topk = min(cfg["sa_config"]["topk"], seq)
    kept = (topk * (topk + 1) / 2 + (seq - topk) * topk) / seq
    return (seq + 1) / 2, kept


def _pair_flops(cfg, seq):
    """FLOPs a token and layer over (query, key) pairs, forward and
    backward, at what the selection needs: (main attention, index scores,
    the head-summed probabilities for the indexer's loss)."""
    A, D = cfg["num_attention_heads"], cfg["head_dim"]
    sa = cfg["sa_config"]
    JD = sa["indexer_num_heads"] * sa["indexer_head_dim"]
    visible, kept = selected_keys_per_query(cfg, seq)
    main = 3 * 4 * A * D * kept               # QK and PV, forward + backward
    # scores once over the causal keys (the selection), their two backward
    # products over the selected pairs
    index = 2 * JD * visible + 2 * 2 * JD * kept
    ph = 2 * A * D * kept                     # one QK pass
    return main, index, ph


def train_flops_per_token(cfg, seq):
    """FLOPs the forward and backward passes need for one token of a
    ``seq``-long row.  6 per matmul weight a token uses here: attention's
    four projections, the router, top_k * held / total experts in
    expectation, the [H, V] head over the held vocabulary rows; 4 for the
    indexer's three projections, whose input carries no gradient; no
    embedding look-up.  Attention at what the selection needs, not the
    causal square (``_pair_flops``).  The forward replayed by recompute
    is not counted."""
    H, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    A, KV, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    sa = cfg["sa_config"]
    J, DI = sa["indexer_num_heads"], sa["indexer_head_dim"]
    E = cfg["published"]["num_experts"]
    attn = H * A * D * 2 + H * KV * D * 2
    experts = (cfg["num_experts_per_tok"] * cfg["num_experts"] / E
               * 3 * H * cfg["moe_intermediate_size"])
    indexer = H * (J * DI + DI + J)
    per_layer = (6 * (attn + H * E + experts) + 4 * indexer
                 + sum(_pair_flops(cfg, seq)))
    return L * per_layer + 6 * H * cfg["vocab_size"]


def sparse_attention_work(cfg, mix, forward_calls):
    """(FLOPs, bytes) of the main attention over one step, selected pairs
    only, for its roofline: ``forward_calls`` forward kernel calls a step
    (counted from the trace: a replay that keeps ``out`` and ``lse`` runs
    none) of two matmuls each and one backward of five a layer.  Bytes:
    q, k, v, out and the int8 mask column a forward; q, k, v, out, do,
    dq, dk, dv and the mask twice a backward (dq walks it by columns,
    dk/dv by rows)."""
    B, T, L = mix["batch"], mix["seq"], cfg["num_hidden_layers"]
    A, KV, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    _, kept = selected_keys_per_query(cfg, T)
    matmul = 2 * B * T * A * D * kept
    flops = matmul * (2 * forward_calls + 5 * L)
    q_bytes, kv_bytes = B * T * A * D * 2, B * T * KV * D * 2
    mask = KV * B * T * (T + 512) / 2           # a group reads it once
    bytes_ = (forward_calls * (2 * q_bytes + 2 * kv_bytes + mask)
              + L * (4 * q_bytes + 4 * kv_bytes + 2 * mask))
    return flops, bytes_


def expert_matmul_work(cfg, mix, product_calls):
    """(FLOPs, bytes) of the grouped products over the held experts one
    step, at the EXPECTED load: a token sends top_k * held / total
    assignments here (the mean over rows and seeds; the load a seed's
    weights give is data the step does not return: 8,192 +- 300 a (row,
    layer) on the CPU at these widths, reference/keye_vl2.py::
    ATTENTION_NORM_GAIN).
    ``product_calls``: grouped-product kernel calls a step, counted from
    the trace (each is one H x F product over one sequence's rows: three
    a forward and six a backward of a chunk, and whatever the replays
    run besides).  Bytes: the held experts' weights of the product once
    a call, a third of the SwiGLU's rows in and out."""
    T = mix["seq"]
    H, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, E = cfg["num_experts"], cfg["published"]["num_experts"]
    rows = T * cfg["num_experts_per_tok"] * held / E
    flops = product_calls * 2 * rows * H * F
    bytes_ = product_calls * (held * H * F * 2
                              + rows * (2 * H + 3 * F) * 2 / 3)
    return flops, bytes_
