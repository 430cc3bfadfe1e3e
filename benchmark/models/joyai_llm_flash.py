"""JoyAI-LLM-Flash as the program builds it: paddle_tpu ``nn`` layers
(RMSNorm, ``nn.MLAttention`` over ``F.mla_attention``, a SwiGLU in the
leading dense layer, ``nn.MoELayer`` with sigmoid scores, a selection
bias, a scaling factor and a shared expert, told which experts it
holds and to hold its router still), a float32 residual stream, per-block recompute, one
multi-token-prediction module and the chunked ``linear_cross_entropy``
head used twice; plus which program parameter is which reference leaf,
the FLOPs a step needs, and what the latent attention and the expert
matmuls need for their rooflines.
"""


def _require_the_layers():
    """Fail while the cell's files are loaded, before the reference has
    spent a minute, on a program from before these layers existed."""
    import inspect

    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    missing = [name for name, home in (
        ("nn.MLAttention", nn), ("nn.MoELayer", nn), ("F.mla_attention", F))
        if not hasattr(home, name.split(".")[1])]
    if "nn.MoELayer" not in missing and "train_router" not in \
            inspect.signature(nn.MoELayer.__init__).parameters:
        missing.append("nn.MoELayer(scoring=, selection_bias=, "
                       "routed_scaling_factor=, shared_width=, "
                       "train_router=)")
    if missing:
        raise ImportError("models/joyai_llm_flash.py needs "
                          + ", ".join(missing)
                          + ", which this paddle_tpu does not have")


_require_the_layers()


def build(cfg, variant):
    """-> (model, loss_fn).  The model returns (the main model's final
    normed state, the MTP module's)."""
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    from paddle_tpu.observability import scopes
    from paddle_tpu.parallel import recompute
    from paddle_tpu.utils import monitor

    if cfg["recompute"] != "per_block" or cfg["tie_word_embeddings"]:
        raise ValueError("models/joyai_llm_flash.py builds per-block "
                         "recompute and an untied head")
    if (cfg["first_k_dense_replace"] != 1 or cfg["n_shared_experts"] != 1
            or cfg["num_nextn_predict_layers"] != 1 or cfg["n_group"] != 1
            or cfg["topk_method"] != "noaux_tc"):
        raise ValueError("models/joyai_llm_flash.py builds one leading dense "
                         "layer, one shared expert, one MTP module and an "
                         "ungrouped bias-corrected selection")
    V, H, L = cfg["vocab_size"], cfg["hidden_size"], cfg["num_hidden_layers"]
    eps = cfg["rms_norm_eps"]
    first = cfg["held_experts"]["first"]
    held = range(first, first + cfg["n_routed_experts"])

    def linear(n_in, n_out):
        return nn.Linear(n_in, n_out, bias_attr=False)

    class Block(nn.Layer):
        """Latent attention, then the dense SwiGLU (layer 0) or the
        routed experts with their shared expert."""

        def __init__(self, dense):
            super().__init__()
            self.norm1 = nn.RMSNorm(H, eps)
            self.attn = nn.MLAttention(
                H, cfg["num_attention_heads"], cfg["q_lora_rank"],
                cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                rope_theta=cfg["rope_theta"],
                rope_interleave=cfg["rope_interleave"], epsilon=eps)
            self.norm2 = nn.RMSNorm(H, eps)
            if dense:
                ffn = cfg["intermediate_size"]
                self.gate, self.up = linear(H, ffn), linear(H, ffn)
                self.down = linear(ffn, H)
            else:
                self.moe = nn.MoELayer(
                    H, cfg["moe_intermediate_size"],
                    cfg["published"]["n_routed_experts"],
                    cfg["num_experts_per_tok"], held=held,
                    norm_topk_prob=cfg["norm_topk_prob"],
                    scoring=cfg["scoring_func"], selection_bias=True,
                    routed_scaling_factor=cfg["routed_scaling_factor"],
                    shared_width=cfg["n_shared_experts"]
                    * cfg["moe_intermediate_size"],
                    train_router=cfg["train_router"])
            self.dense = dense

        def forward(self, x):
            # x is the float32 residual stream; the norms hand the
            # weights' type to the matmuls
            x = x + self.attn(self.norm1(x)).astype("float32")
            if self.dense:
                h = self.norm2(x)
                y = self.down(F.silu(self.gate(h)) * self.up(h))
                return x + y.astype("float32")
            # the router wants the normed stream in float32
            h = F.rms_norm(x, self.norm2.weight.astype("float32"), eps)
            return x + self.moe(h)

    class MTPModule(nn.Layer):
        """DeepSeek-V3's section 2.2, depth 1: the next token's embedding
        and the main model's last state, each normed, joined and
        projected, through one more expert block and a norm."""

        def __init__(self):
            super().__init__()
            self.enorm, self.hnorm = nn.RMSNorm(H, eps), nn.RMSNorm(H, eps)
            self.proj = linear(2 * H, H)
            self.block = Block(dense=False)
            self.norm_f = nn.RMSNorm(H, eps)

        def forward(self, x, next_emb):
            with jax.named_scope(scopes.MTP):
                h = self.proj(paddle.concat(
                    [self.enorm(next_emb), self.hnorm(x)], axis=-1))
                h = recompute(self.block, h.astype("float32"))
                return self.norm_f(h)

    class JoyAI(nn.Layer):
        def __init__(self):
            super().__init__()
            self.tok = nn.Embedding(V, H)
            self.dense = Block(dense=True)
            self.blocks = nn.LayerList([Block(dense=False)
                                        for _ in range(L - 1)])
            self.norm_f = nn.RMSNorm(H, eps)
            self.head = linear(H, V)
            self.mtp = MTPModule()

        def forward(self, ids):
            monitor.stat_set("mtp.modules", cfg["num_nextn_predict_layers"])
            x = recompute(self.dense, self.tok(ids).astype("float32"))
            for blk in self.blocks:
                x = recompute(blk, x)
            # the token one position on; what the last position reads is
            # left out of the loss below
            nxt = self.tok(paddle.roll(ids, -1, axis=1)).astype("float32")
            return self.norm_f(x), self.mtp(x, nxt)

    model = JoyAI()

    def loss_fn(out, labels):
        """Float32 logits over the held vocabulary rows (float32 operands
        holding the weights' values: one MXU pass, float32 accumulation),
        the main model's at every position plus ``mtp_loss_weight`` times
        the module's against the labels one position on, the last
        position ignored."""
        z, z_mtp = out
        B, S = z.shape[0], z.shape[1]
        n = B * S
        w = model.head.weight.astype("float32")
        bias = paddle.zeros([V], dtype="float32")
        main = F.linear_cross_entropy(z.astype("float32").reshape([n, H]),
                                      w, bias, labels.reshape([n]))
        with jax.named_scope(scopes.MTP):
            ahead = paddle.concat(
                [labels[:, 1:], paddle.full([B, 1], -100, dtype=labels.dtype)],
                axis=1)
            mtp = F.linear_cross_entropy(
                z_mtp.astype("float32").reshape([n, H]), w, bias,
                ahead.reshape([n]), ignore_index=-100)
        return main + cfg["mtp_loss_weight"] * mtp

    return model, loss_fn


_ATTENTION = {"norm1.weight": "norm1.g", "norm2.weight": "norm2.g",
              "attn.q_a.weight": "q_a.w", "attn.q_norm.weight": "q_norm.g",
              "attn.q_b.weight": "q_b.w", "attn.kv_a.weight": "kv_a.w",
              "attn.kv_norm.weight": "kv_norm.g",
              "attn.kv_b.weight": "kv_b.w", "attn.o.weight": "o.w"}
_EXPERTS = {"moe.router_weight": "router.w", "moe.router_bias": "router.bias",
            "moe.w_gate": "experts.gate", "moe.w_up": "experts.up",
            "moe.w_down": "experts.down", "moe.shared_gate": "shared.gate.w",
            "moe.shared_up": "shared.up.w", "moe.shared_down": "shared.down.w"}


def param_map(cfg, variant):
    """program parameter name -> (reference leaf, block or None)."""
    out = {"tok.weight": ("tok", None), "norm_f.weight": ("norm_f.g", None),
           "head.weight": ("head.w", None),
           "mtp.enorm.weight": ("mtp.enorm.g", None),
           "mtp.hnorm.weight": ("mtp.hnorm.g", None),
           "mtp.proj.weight": ("mtp.proj.w", None),
           "mtp.norm_f.weight": ("mtp.norm_f.g", None)}
    for p, leaf in _ATTENTION.items():
        out["dense." + p] = ("dense." + leaf, None)
    for n in ("gate", "up", "down"):
        out[f"dense.{n}.weight"] = (f"dense.{n}.w", None)
    for p, leaf in {**_ATTENTION, **_EXPERTS}.items():
        out["mtp.block." + p] = ("mtp." + leaf, None)
        for i in range(cfg["num_hidden_layers"] - 1):
            out[f"blocks.{i}.{p}"] = ("layers." + leaf, i)
    return out


# ------------------------------------------------------------- the counts --
def _attention_weights(cfg):
    """Matmul weights of one latent attention: the two query products,
    the joint key/value down-projection, its up-projection, the output."""
    H, A = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (H * rq + rq * A * (dn + dr) + H * (rkv + dr)
            + rkv * A * (dn + dv) + A * dv * H)


def _expert_layer_weights(cfg):
    """Matmul weights a token uses in one expert layer, in expectation:
    attention, the router over all experts, the shared expert, and
    top_k * held / total routed experts."""
    H, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    E = cfg["published"]["n_routed_experts"]
    routed = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / E
              * 3 * H * F)
    return (_attention_weights(cfg) + H * E
            + cfg["n_shared_experts"] * 3 * H * F + routed)


def _pair_flops(cfg, seq):
    """FLOPs a token and layer over (query, key) pairs, forward and
    backward, at what causal attention needs: (seq + 1) / 2 keys a query,
    the score product ``qk_head_dim`` wide and the value product
    ``v_head_dim`` wide, each one forward and two backward."""
    A = cfg["num_attention_heads"]
    d = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    return 3 * 2 * A * d * (seq + 1) / 2


def train_flops_per_token(cfg, seq):
    """FLOPs the forward and backward passes need for one token of a
    ``seq``-long row.  6 per matmul weight a token uses here: the leading
    dense layer, the expert layers (``_expert_layer_weights``), the MTP
    module (one more expert layer and its [2H, H] projection) and the
    [H, V] head over the held vocabulary rows twice (the module's pass
    over seq - 1 of seq positions is counted whole); causal attention's
    pairs in each of the L + 1 blocks; no embedding look-up.  The forward
    replayed by recompute is not counted."""
    H, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    mtp = cfg["num_nextn_predict_layers"]
    dense = _attention_weights(cfg) + 3 * H * cfg["intermediate_size"]
    expert_blocks = L - cfg["first_k_dense_replace"] + mtp
    weights = (dense + expert_blocks * _expert_layer_weights(cfg)
               + mtp * 2 * H * H + (1 + mtp) * H * cfg["vocab_size"])
    return 6 * weights + (L + mtp) * _pair_flops(cfg, seq)


def mla_attention_work(cfg, mix, forward_calls):
    """(FLOPs, bytes) of the latent attention's kernels over one step, for
    its roofline: ``forward_calls`` forward kernel calls a step (counted
    from the trace: a replay that keeps ``out`` and ``lse`` runs none) of
    two products each, 192 and 128 wide, and one backward a block of
    five, 192 / 128 / 128 / 192 / 192 wide (the scores again, dP, dV, dQ,
    dK), over half the square.  Bytes: q, k (192 wide), v and out (128) a
    forward; q, k, v, out, dO, dq, dk, dv a backward, each once."""
    B, T = mix["batch"], mix["seq"]
    A = cfg["num_attention_heads"]
    blocks = cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    pairs = B * A * T * (T + 1) / 2
    flops = 2 * pairs * (forward_calls * (dqk + dv)
                         + blocks * (3 * dqk + 2 * dv))
    rows = B * T * A * 2                                  # bfloat16
    bytes_ = (forward_calls * rows * (2 * dqk + 2 * dv)
              + blocks * rows * (4 * dqk + 4 * dv))
    return flops, bytes_


def expert_matmul_work(cfg, mix, product_calls):
    """(FLOPs, bytes) of the grouped products over the held routed experts
    one step, at the EXPECTED load: a token sends top_k * held / total
    assignments here, half an assignment (the mean over rows and seeds;
    the load a seed's weights give is data the step does not return:
    3,157 to 4,742 a (row, layer) where 4,096 are expected, on the CPU at
    these widths, reference/joyai_llm_flash.py::LATENT_VALUE_GAIN; it
    stays the draw's because the routers are held still, see the cell's
    ``optimizer_why``).  ``product_calls``:
    grouped-product kernel calls a step, counted from the trace (each is
    one H x F product over one sequence's rows).  Bytes: the held
    experts' weights of the product once a call, a third of the SwiGLU's
    rows in and out.  The shared expert is a plain matmul under a scope
    of its own and is not part of this."""
    T = mix["seq"]
    H, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, E = cfg["n_routed_experts"], cfg["published"]["n_routed_experts"]
    rows = T * cfg["num_experts_per_tok"] * held / E
    flops = product_calls * 2 * rows * H * F
    bytes_ = product_calls * (held * H * F * 2
                              + rows * (2 * H + 3 * F) * 2 / 3)
    return flops, bytes_
