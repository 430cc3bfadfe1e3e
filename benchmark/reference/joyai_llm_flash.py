"""Plain reference of the training step of JoyAI-LLM-Flash: float32
jax.numpy, no kernels, nothing imported from the program.

The model (huggingface.co/jdopensource/JoyAI-LLM-Flash, ``model_type:
joyai_llm_flash``, 48B-A2.7B) carries DeepSeek-V3's key names, and its
layers are that report's (sections 2.1.1, 2.1.2, 2.2) and its public
modelling code's.  x is the float32 residual stream of one row, T
positions:

    a        = RMSNorm(x; g1)
    cq       = RMSNorm(a Wqa; gq) [T, 1536];  q = cq Wqb [T, 32, 192]
                                                = [qn (128) ; qr (64)]
    [ckv;kr] = a Wkva [T, 512 + 64];  ckv = RMSNorm(ckv; gkv)
    [kn (128) ; v (128)] = ckv Wkvb [T, 32, 256]
    qr, kr  <- RoPE(theta 3.2e7, pairs (2i, 2i + 1)); kr is ONE key for
               the 32 heads
    P[t,h,s] = softmax over s <= t of 192^-1/2 (qn[t,h].kn[s,h] + qr[t,h].kr[s])
    x <- x + concat_h(sum_s P[t,h,s] v[s,h]) Wo
    b  = RMSNorm(x; g2)
    layer 0:     x <- x + (silu(b Wg) * (b Wu)) Wd            width 7168
    layers 1..:  s = sigmoid(b Wr) over all 256, float32
                 Top_t = the 8 largest of s + beta (ties: the lower index)
                 gate[t,e] = 2.5 s[t,e] / (sum_{e' in Top_t} s[t,e'] + 1e-20)
                 x <- x + sum_{e in Top_t, held} gate[t,e] SwiGLU_e(b)
                        + SwiGLU_shared(b)                    both 768 wide
    main head:   L_main = mean_t CE(RMSNorm(x_L; gf) Wh, labels[t])
    MTP module:  h' = [RMSNorm(Emb(ids[t+1]); ge) ; RMSNorm(x_L; gh)] M
                 h'' = one more block as layers 1.. (leaves of its own)
                 L_mtp = mean over t = 0..T-2 of
                         CE(RMSNorm(h''; gm) Wh, labels[t+1])
    loss = L_main + mtp_loss_weight * L_mtp

Emb and Wh of the MTP term are the main model's (two uses of one leaf a
step).  The module runs over all T positions with ``ids`` and ``labels``
moved one position on and the last position left out of the mean:
attention is causal and everything else is per token, so positions
0..T-2 see nothing of what sits in the last one, and its gradient is
zero.  The selection bias beta is a leaf that only ``top_k`` reads: its
gradient is zero by the mathematics, and the rule that moves it by the
experts' load (the report's section 2.1.2) is not run.

**One chip's share.**  The configuration holds ``held_experts`` of the
256 routed experts of every expert layer and a slice of the vocabulary
(benchmark/configs/joyai_llm_flash.json: 16 chips share each layer).
The router spans all experts and a token's gates are normalised over its
8 whatever is held; what the absent experts would add is left out; the
shared expert is computed here as on every member of the group.
``held`` may be handed in to compute another chip's share (the tests add
the shares up and count the shared expert once).  **The routers are held
still on one chip's share** (``train_router`` false in the configuration:
the gates are constants of the backward pass, so ``router.w`` has a zero
gradient, as ``router.bias`` has, and the stream gets none through the
router).  A token's gates depend on the ratios of its eight scores, so
their gradient says which of the eight to prefer, and needs all eight
<dL/dy, SwiGLU_e(b)>.  This chip has them for its own experts; the
absent ones' read as zero, so every step says "prefer the held", and
Adam at the cell's lr turns that into 2.5 to 7 times the held load
inside 35 steps, which no member of a whole group sees (PERF.md section
6, PR 32).  What a member could learn alone, the preference among held
experts that one token chose together, concerns 8.2 % of the tokens.

What ``config.json`` does not fix is in the configuration file's
``assumed``.  Weights are ``[in, out]``.  ``qz`` is applied to every
matmul operand: the identity here, a quantiser in the control
(benchmark/check.py).  Attention runs ``QUERY_ROWS`` queries at a time
and the routed experts one at a time, each replayed in the backward
pass, so that the float32 step fits one chip beside its state.
"""
import jax
import jax.numpy as jnp

QUERY_ROWS = 512    # queries whose scores are live at a time

# The gain of the norm on the key/value latent is drawn around 0.02, the
# embedding's own scale and the draw's width, not around 1.  At this
# draw the scores are small and attention is nearly a running mean of the
# values; around 1 that mean is a few tenths of the stream and the same
# for every query of a row, the normed stream the next latent reads
# carries it on, and from the second expert layer on a row's tokens pick
# the same experts: the held assignments a (row, layer) read 62 to 347
# where 256 are expected (512 tokens, these widths, the CPU), and many
# held experts get no token.  The block's first norm cannot cure it as
# in keye_vl2's cell: the latent norms re-scale whatever it hands on.
# Around 0.02 attention's branch is small beside the feed-forward
# branches, which are each token's own, and the routers read a stream
# that differs from token to token: 3,157 to 4,742 held assignments a
# (row, layer) where 4,096 are expected, within 3.5 % over a row's five
# blocks (8192 tokens, these widths, four seeds, the CPU; what is left is
# the selection bias's own draw: the configuration file's
# ``assumed.init`` and PERF.md section 6).  The keys' 128 unrotated dims
# shrink with the values'; the 64 rotated ones do not pass that norm.
LATENT_VALUE_GAIN = 0.02


def sizes(cfg):
    return dict(H=cfg["hidden_size"], A=cfg["num_attention_heads"],
                RQ=cfg["q_lora_rank"], RKV=cfg["kv_lora_rank"],
                DN=cfg["qk_nope_head_dim"], DR=cfg["qk_rope_head_dim"],
                DV=cfg["v_head_dim"], FD=cfg["intermediate_size"],
                F=cfg["moe_intermediate_size"],
                E=cfg["published"]["n_routed_experts"],
                held=cfg["n_routed_experts"],
                K=cfg["num_experts_per_tok"],
                dense=cfg["first_k_dense_replace"],
                L=cfg["num_hidden_layers"], V=cfg["vocab_size"])


def held_ids(cfg):
    """The expert ids this chip holds: ``n_routed_experts`` of them from
    ``held_experts.first``."""
    first = cfg["held_experts"]["first"]
    return tuple(range(first, first + cfg["n_routed_experts"]))


def _attention_shapes(z):
    H, A = z["H"], z["A"]
    return {
        "norm1.g": ((H,), 1.0), "norm2.g": ((H,), 1.0),
        "q_a.w": ((H, z["RQ"]), 0.0), "q_norm.g": ((z["RQ"],), 1.0),
        "q_b.w": ((z["RQ"], A * (z["DN"] + z["DR"])), 0.0),
        "kv_a.w": ((H, z["RKV"] + z["DR"]), 0.0),
        "kv_norm.g": ((z["RKV"],), LATENT_VALUE_GAIN),
        "kv_b.w": ((z["RKV"], A * (z["DN"] + z["DV"])), 0.0),
        "o.w": ((A * z["DV"], H), 0.0),
    }


def _expert_block_shapes(z):
    H, F, held = z["H"], z["F"], z["held"]
    return {
        **_attention_shapes(z),
        "router.w": ((H, z["E"]), 0.0), "router.bias": ((z["E"],), 0.0),
        "experts.gate": ((held, H, F), 0.0),
        "experts.up": ((held, H, F), 0.0),
        "experts.down": ((held, F, H), 0.0),
        "shared.gate.w": ((H, F), 0.0), "shared.up.w": ((H, F), 0.0),
        "shared.down.w": ((F, H), 0.0),
    }


def param_shapes(cfg, variant):
    """name -> (shape, base): a leaf is ``base + 0.02 * normal``.  The
    expert layers' leaves (``layers.*``) are stacked: axis 0 is the
    layer.  The leading dense layer (``dense.*``) and the MTP module
    (``mtp.*``) have leaves of their own.  The experts' leaves hold the
    held experts only, in the order of their ids.  Every norm's gain is
    drawn around 1 but the key/value latent's (``LATENT_VALUE_GAIN``)."""
    z = sizes(cfg)
    if z["dense"] != 1 or cfg["num_nextn_predict_layers"] != 1 \
            or cfg["n_shared_experts"] != 1:
        raise ValueError("reference/joyai_llm_flash.py computes one leading "
                         "dense layer, one MTP module, one shared expert")
    H = z["H"]
    out = {"tok": ((z["V"], H), 0.0), "norm_f.g": ((H,), 1.0),
           "head.w": ((H, z["V"]), 0.0)}
    for n, (shape, base) in _attention_shapes(z).items():
        out["dense." + n] = (shape, base)
    out["dense.gate.w"] = ((H, z["FD"]), 0.0)
    out["dense.up.w"] = ((H, z["FD"]), 0.0)
    out["dense.down.w"] = ((z["FD"], H), 0.0)
    for n, (shape, base) in _expert_block_shapes(z).items():
        out["layers." + n] = ((z["L"] - z["dense"],) + shape, base)
        out["mtp." + n] = (shape, base)
    out["mtp.enorm.g"] = ((H,), 1.0)
    out["mtp.hnorm.g"] = ((H,), 1.0)
    out["mtp.proj.w"] = ((2 * H, H), 0.0)
    out["mtp.norm_f.g"] = ((H,), 1.0)
    return out


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def rope(x, theta):
    """Interleaved rotary embedding of ``x`` [T, ..., D] at positions
    0..T-1: the pair (2i, 2i + 1) turns by ``pos * theta^(-2i/D)``."""
    T, D = x.shape[0], x.shape[-1]
    freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None]
    shape = (T,) + (1,) * (x.ndim - 2) + (D // 2,)
    # in x's type, so that a control held in bfloat16 stays in it
    cos = jnp.cos(ang).reshape(shape).astype(x.dtype)
    sin = jnp.sin(ang).reshape(shape).astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def swiglu(b, wg, wu, wd, qz):
    return qz(jax.nn.silu(qz(b) @ qz(wg)) * (qz(b) @ qz(wu))) @ qz(wd)


def latent_attention(a, p, cfg, qz):
    """One row.  a [T, H] (normed) -> the heads' outputs [T, A * DV]."""
    z = sizes(cfg)
    T, A, DN, DR, DV = a.shape[0], z["A"], z["DN"], z["DR"], z["DV"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    cq = rms_norm(qz(a) @ qz(p["q_a.w"]), p["q_norm.g"], eps)
    q = (qz(cq) @ qz(p["q_b.w"])).reshape(T, A, DN + DR)
    qn, qr = q[..., :DN], rope(q[..., DN:], theta)
    ckv = qz(a) @ qz(p["kv_a.w"])
    kr = rope(ckv[:, z["RKV"]:], theta)                   # [T, DR]
    ckv = rms_norm(ckv[:, :z["RKV"]], p["kv_norm.g"], eps)
    kv = (qz(ckv) @ qz(p["kv_b.w"])).reshape(T, A, DN + DV)
    kn, v = kv[..., :DN], kv[..., DN:]
    rows = QUERY_ROWS if T % QUERY_ROWS == 0 else T
    scale = (DN + DR) ** -0.5

    @jax.checkpoint
    def block(args):
        qnb, qrb, first = args
        pos = first + jnp.arange(rows)
        causal = jnp.arange(T)[None, :] <= pos[:, None]
        s = (jnp.einsum("tad,sad->ats", qz(qnb), qz(kn))
             + jnp.einsum("tad,sd->ats", qz(qrb), qz(kr))) * scale
        P = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("ats,sad->tad", qz(P), qz(v)).reshape(rows, A * DV)

    def blocks(x):
        return x.reshape((T // rows, rows) + x.shape[1:])

    o = jax.lax.map(block, (blocks(qn), blocks(qr), jnp.arange(0, T, rows)))
    return o.reshape(T, A * DV)


def route(b, p, cfg, qz):
    """b [T, H] -> (gates [T, 8] float32, the experts chosen [T, 8] over
    all E).  The selection reads score + bias, the gates the scores."""
    s = jax.nn.sigmoid((qz(b) @ qz(p["router.w"])).astype(jnp.float32))
    _, idx = jax.lax.top_k(s + p["router.bias"].astype(jnp.float32),
                           cfg["num_experts_per_tok"])
    vals = jnp.take_along_axis(s, idx, -1)
    gate = vals / (jnp.sum(vals, -1, keepdims=True) + 1e-20) \
        if cfg["norm_topk_prob"] else vals
    if not cfg["train_router"]:
        gate = jax.lax.stop_gradient(gate)
    return gate * cfg["routed_scaling_factor"], idx


def routed(b, p, cfg, held, qz):
    """b [T, H] (normed) -> (the held experts' part of the routed result,
    the experts each token chose [T, 8])."""
    gate, idx = route(b, p, cfg, qz)

    @jax.checkpoint
    def expert(args):
        wg, wu, wd, e = args
        # in b's type, so that a control held in bfloat16 stays in it
        return jnp.sum(jnp.where(idx == e, gate, 0.0), -1).astype(
            b.dtype)[:, None] * swiglu(b, wg, wu, wd, qz)

    parts = jax.lax.map(expert, (p["experts.gate"], p["experts.up"],
                                 p["experts.down"], jnp.asarray(held)))
    return jnp.sum(parts, 0), idx


def shared(b, p, qz):
    return swiglu(b, p["shared.gate.w"], p["shared.up.w"],
                  p["shared.down.w"], qz)


def _attend(x, p, cfg, qz):
    """x [T, H] -> (x after attention's branch, the normed stream the
    feed-forward part reads)."""
    eps = cfg["rms_norm_eps"]
    a = rms_norm(x, p["norm1.g"], eps)
    x = x + qz(latent_attention(a, p, cfg, qz)) @ qz(p["o.w"])
    return x, rms_norm(x, p["norm2.g"], eps)


def dense_block(x, p, cfg, qz):
    """Rows x [B, T, H] through the leading dense layer."""
    def row(x):
        x, b = _attend(x, p, cfg, qz)
        return x + swiglu(b, p["gate.w"], p["up.w"], p["down.w"], qz)
    return jax.lax.map(row, x)


def expert_block(x, p, cfg, held, qz):
    """Rows x [B, T, H] through one expert layer -> (x, the experts every
    token chose [B, T, 8])."""
    def row(x):
        x, b = _attend(x, p, cfg, qz)
        y, chosen = routed(b, p, cfg, held, qz)
        return x + y + shared(b, p, qz), chosen
    return jax.lax.map(row, x)


def _under(params, prefix):
    return {n[len(prefix):]: a for n, a in params.items()
            if n.startswith(prefix)}


def forward(params, ids, cfg, qz=lambda a: a, held=None):
    """-> (the main model's normed final state [rows, seq, H], the MTP
    module's [rows, seq, H] (its last position predicts nothing), the
    experts every token chose [L - 1 + 1, rows, seq, 8]: the expert
    layers, then the module's)."""
    held = held_ids(cfg) if held is None else held
    eps = cfg["rms_norm_eps"]
    x = params["tok"][ids]
    x = jax.checkpoint(lambda x, p: dense_block(x, p, cfg, qz))(
        x, _under(params, "dense."))
    # a scan over the stacked expert layers, one live at a time in the
    # backward pass
    block = jax.checkpoint(lambda x, p: expert_block(x, p, cfg, held, qz))
    x, chosen = jax.lax.scan(block, x, _under(params, "layers."))
    mtp = _under(params, "mtp.")
    nxt = jnp.roll(ids, -1, axis=1)       # the last position: left out
    h = jnp.concatenate([rms_norm(params["tok"][nxt], mtp["enorm.g"], eps),
                         rms_norm(x, mtp["hnorm.g"], eps)], -1)
    h, chosen_mtp = block(qz(h) @ qz(mtp["proj.w"]), mtp)
    return (rms_norm(x, params["norm_f.g"], eps),
            rms_norm(h, mtp["norm_f.g"], eps),
            jnp.concatenate([chosen, chosen_mtp[None]], 0))


def _cross_entropy(z, w, labels, qz):
    """Per-position cross-entropy [rows, seq] of z [rows, seq, H]."""
    logits = (qz(z) @ qz(w)).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def loss(params, ids, labels, cfg, variant, qz=lambda a: a, held=None):
    """Cross-entropy of ``ids`` [rows, seq] against ``labels`` plus
    ``mtp_loss_weight`` times the MTP module's, which predicts
    ``labels`` one position on from positions 0..seq-2."""
    z, z_mtp, _ = forward(params, ids, cfg, qz, held)
    main = jnp.mean(_cross_entropy(z, params["head.w"], labels, qz))
    mtp = jnp.mean(_cross_entropy(z_mtp, params["head.w"],
                                  jnp.roll(labels, -1, axis=1), qz)[:, :-1])
    return main + cfg["mtp_loss_weight"] * mtp
