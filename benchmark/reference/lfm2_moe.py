"""Plain reference of the training step of LFM2-24B-A2B (``model_type:
lfm2_moe``): float32 jax.numpy, no kernels, nothing imported from the
program.

The model (huggingface.co/LiquidAI/LFM2-24B-A2B, LiquidAI, 24B-A2B) as
its ``config.json`` gives the sizes and the public ``modeling_lfm2_moe.py``
of ``transformers`` the order.  x is the float32 residual stream of one
row, T positions; every layer has two RMSNorms with plain gains:

    h_0      = Emb(ids)
    u        = RMSNorm(x; g_op);   x <- x + Mixer_kind(u)
    b        = RMSNorm(x; g_ffn);  x <- x + FF(b)

    Mixer "conv" (Lfm2MoeShortConv), 3 taps, no bias, no activation:
      [B ; C ; z] = u W_in          [T, 3 x 2048], thirds in that order
      v        = B * z
      c_t      = sum_{k=0..2} w_k * v_{t-2+k}        v_s = 0 for s < 0
      Mixer    = (C * c) W_out

    Mixer "full_attention":
      q = u Wq [T, 32, 64];  k = u Wk, v = u Wv [T, 8, 64]
      q <- RMSNorm(q; gq), k <- RMSNorm(k; gk)     per head, over its 64
      q, k <- RoPE(theta 1e6, rotate-half, all 64 dims)
      P[t,h,s] = softmax over s <= t of 64^-1/2 q[t,h].k[s,h // 4]
      Mixer    = concat_h(sum_s P[t,h,s] v[s,h // 4]) Wo

    FF of a leading dense layer:  (silu(b W1) * (b W3)) W2     width 11776
                                  ([W1 ; W3] is ONE leaf, ``ff_in.w``)
    FF of the others:
      s        = sigmoid(float32(b) Wr) over all 64
      Top_t    = the 4 largest of s + beta      (ties: the lower index)
      gate[t,e] = s[t,e] / (sum_{e' in Top_t} s[t,e'] + 1e-6)    (times 1)
      FF       = sum_{e in Top_t, held} gate[t,e] SwiGLU_e(b)    1536 wide

    loss     = mean_t CE(RMSNorm(x_L; gf) Emb^T, labels[t])     (tied)

beta (``expert_bias`` of the public code) is a leaf that only ``top_k``
reads: its gradient is zero by the mathematics, and the rule that moves
it by the experts' load is not run.  There is no auxiliary loss.

**One chip's share.**  The configuration holds ``held_experts`` of the 64
routed experts of every expert layer and a slice of the vocabulary
(benchmark/configs/lfm2_24b_a2b.json: 8 chips share each layer).  The
router spans all experts and a token's gates are normalised over its 4
whatever is held; what the absent experts would add is left out, and the
partial sum is what enters the stream.  ``held`` may be handed in to
compute another chip's share (`feed_forward`; the tests add the shares
up).  **The routers are held still on one chip's share** (``train_router``
false: the gates are constants of the backward pass), joyai_llm_flash's
reason, in the configuration file's ``assumed``.

**Departures from the published description**, each in the configuration
file's ``assumed``: the share above; seeded weights at the draw below;
rows of 8,192 positions from 0 with no document boundary; the loss over
ids and labels drawn independently; the head tied to the embedding (the
catalog row has no ``tie_word_embeddings``; the family's published
configs tie it).

Weights are ``[in, out]``.  ``qz`` is applied to every matmul operand:
the identity here, a quantiser in the control (benchmark/check.py).
Attention runs ``QUERY_ROWS`` queries at a time against all keys; the
feed-forward parts (the routed experts one at a time) and the logits
over ``TOKEN_ROWS`` tokens at a time (they are per token, so the blocks
change nothing); each replayed in the backward pass, so the float32 step
fits one chip beside its state.
"""
import jax
import jax.numpy as jnp

QUERY_ROWS = 128    # queries whose scores are live at a time
TOKEN_ROWS = 2048   # tokens whose feed-forward states or logits are live at a time

# THE DRAW.  Every leaf is ``base + 0.02 * normal`` (benchmark/weights.py):
# 0 for the matrices, 1 for the gains of the stream's norms, and
# - the taps around TAPS_BASE.  Around 0 the convolution's result would be
#   0.02 * sqrt(3) of its operand and the mixer's branch 1e-4 of the
#   stream: no leaf of the mixer would weigh in the check.  Around 0.5
#   ``c`` has v's own size (three taps: 0.5 * sqrt(3) = 0.87);
# - the gains of the per-head norms on q and k around QK_GAIN: the scores'
#   deviation is their product (64^-1/2 q.k over 64 dims of unit size), and
#   at 1 a softmax over thousands of keys is a running mean of the values,
#   1 / sqrt(keys) of a value: attention's branch would be 1e-4 of the
#   stream's power.  At 1.5 the scores' deviation is 2.25 and a query's
#   weight lies on tens of keys;
# - the selection bias around SELECTION_BIAS_BASE: its published initial
#   value is one number for all experts (zero).  N(0, 0.02) on sigmoid
#   scores moves an expert's share by tens of percent, alike for every row
#   of a seed; around 32 the harness's rounding of every value to bfloat16
#   (spacing 0.25 there) swallows the 0.02, every expert has the same bias
#   and a common bias moves no selection (nemotron_h's way; in float32,
#   the tests', the 0.02 stays and the selection reads it).
# What the held experts' load reads at this draw: the configuration file's
# ``assumed.init``.
TAPS_BASE = 0.5
QK_GAIN = 1.5
SELECTION_BIAS_BASE = 32.0
GATE_EPSILON = 1e-6     # the public code's, added to the chosen scores' sum

KINDS = ("conv", "full_attention")
_SHORT = {"conv": "c", "full_attention": "a"}


def sizes(cfg):
    return dict(H=cfg["hidden_size"], A=cfg["num_attention_heads"],
                KV=cfg["num_key_value_heads"], D=cfg["head_dim"],
                FD=cfg["intermediate_size"], F=cfg["moe_intermediate_size"],
                E=cfg["published"]["num_experts"], held=cfg["num_experts"],
                K=cfg["num_experts_per_tok"], taps=cfg["conv_L_cache"],
                dense=cfg["num_dense_layers"], L=cfg["num_hidden_layers"],
                V=cfg["vocab_size"])


def held_ids(cfg):
    """The expert ids this chip holds: ``num_experts`` of them from
    ``held_experts.first``."""
    first = cfg["held_experts"]["first"]
    return tuple(range(first, first + cfg["num_experts"]))


def layer_kinds(cfg):
    """("conv" | "full_attention") a layer, the dense layers first."""
    kinds = tuple(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError("reference/lfm2_moe.py: layer_types names "
                         f"{len(kinds)} layers of kinds {sorted(set(kinds))}")
    return kinds


def _mixer_shapes(z, kind):
    H, A, KV, D = z["H"], z["A"], z["KV"], z["D"]
    norms = {"norm_op.g": ((H,), 1.0), "norm_ffn.g": ((H,), 1.0)}
    if kind == "conv":
        return {**norms, "in.w": ((H, 3 * H), 0.0),
                "taps": ((z["taps"], H), TAPS_BASE), "out.w": ((H, H), 0.0)}
    return {**norms, "q.w": ((H, A * D), 0.0), "k.w": ((H, KV * D), 0.0),
            "v.w": ((H, KV * D), 0.0), "q_norm.g": ((D,), QK_GAIN),
            "k_norm.g": ((D,), QK_GAIN), "o.w": ((A * D, H), 0.0)}


def _expert_shapes(z):
    H, F, held = z["H"], z["F"], z["held"]
    return {"router.w": ((H, z["E"]), 0.0),
            "router.bias": ((z["E"],), SELECTION_BIAS_BASE),
            "experts.gate": ((held, H, F), 0.0),
            "experts.up": ((held, H, F), 0.0),
            "experts.down": ((held, F, H), 0.0)}


def param_shapes(cfg, variant):
    """name -> (shape, base): a leaf is ``base + 0.02 * normal``.  The
    leading dense layer (``dense.*``) has leaves of its own.  The expert
    layers' leaves are stacked by the kind of their mixer: ``layers.c.*``
    the conv layers in their order, ``layers.a.*`` the attention layers
    (axis 0: the nth layer of the kind).  The experts' leaves hold the
    held experts only, in the order of their ids.  ``tok`` is the
    embedding and the head."""
    z, kinds = sizes(cfg), layer_kinds(cfg)
    if z["dense"] != 1 or not cfg["tie_word_embeddings"]:
        raise ValueError("reference/lfm2_moe.py computes one leading dense "
                         "layer and a tied head")
    if cfg["conv_bias"] or not cfg["use_expert_bias"]:
        raise ValueError("reference/lfm2_moe.py computes no convolution "
                         "bias and a selection bias")
    H = z["H"]
    out = {"tok": ((z["V"], H), 0.0), "norm_f.g": ((H,), 1.0)}
    for n, (shape, base) in _mixer_shapes(z, kinds[0]).items():
        out["dense." + n] = (shape, base)
    # W1's half first, then W3's: one leaf, as one fused in-projection
    # holds them
    out["dense.ff_in.w"] = ((H, 2 * z["FD"]), 0.0)
    out["dense.ff_down.w"] = ((z["FD"], H), 0.0)
    for kind in KINDS:
        n_kind = kinds[1:].count(kind)
        if not n_kind:
            continue
        for n, (shape, base) in {**_mixer_shapes(z, kind),
                                 **_expert_shapes(z)}.items():
            out[f"layers.{_SHORT[kind]}.{n}"] = ((n_kind,) + shape, base)
    return out


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def rope(x, theta, first=0):
    """Rotate-half rotary embedding of ``x`` [T, ..., D] at positions
    ``first``..``first + T - 1``: the pair (i, i + D/2) turns by
    ``pos * theta^(-2i/D)``."""
    T, D = x.shape[0], x.shape[-1]
    freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = (first + jnp.arange(T)).astype(jnp.float32)[:, None] * freq[None]
    shape = (T,) + (1,) * (x.ndim - 2) + (D // 2,)
    # in x's type, so that a control held in bfloat16 stays in it
    cos = jnp.cos(ang).reshape(shape).astype(x.dtype)
    sin = jnp.sin(ang).reshape(shape).astype(x.dtype)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(b, wg, wu, wd, qz):
    return qz(jax.nn.silu(qz(b) @ qz(wg)) * (qz(b) @ qz(wu))) @ qz(wd)


def short_conv(u, p, qz):
    """One row.  u [T, H] (normed) -> the conv mixer's branch [T, H]: the
    convolution as shifted multiply-adds, tap K - 1 on the position
    itself, zeros before the row's start."""
    T, H = u.shape
    bcz = qz(u) @ qz(p["in.w"])
    B, C, z = bcz[:, :H], bcz[:, H:2 * H], bcz[:, 2 * H:]
    taps = p["taps"]
    K = taps.shape[0]
    v = jnp.pad(B * z, ((K - 1, 0), (0, 0)))
    c = sum(v[k:k + T] * taps[k] for k in range(K))
    return qz(C * c) @ qz(p["out.w"])


def attention(u, p, cfg, qz):
    """One row.  u [T, H] (normed) -> attention's branch [T, H].  The
    keys and values of the whole row first (8 heads: small); then a block
    of ``QUERY_ROWS`` queries at a time its own q, scores and output
    projection."""
    z = sizes(cfg)
    T, A, KV, D = u.shape[0], z["A"], z["KV"], z["D"]
    eps, theta = cfg["norm_eps"], cfg["rope_parameters"]["rope_theta"]
    k = rope(rms_norm((qz(u) @ qz(p["k.w"])).reshape(T, KV, D),
                      p["k_norm.g"], eps), theta)
    v = (qz(u) @ qz(p["v.w"])).reshape(T, KV, D)
    rows = QUERY_ROWS if T % QUERY_ROWS == 0 else T

    @jax.checkpoint
    def block(args):
        ub, first = args
        q = rope(rms_norm(
            (qz(ub) @ qz(p["q.w"])).reshape(rows, KV, A // KV, D),
            p["q_norm.g"], eps), theta, first)
        t = (first + jnp.arange(rows))[:, None]
        # added, not selected: a select's backward keeps its predicate at
        # the scores' shape for every block of queries
        hidden = jnp.where(jnp.arange(T)[None, :] <= t, 0.0,
                           -jnp.inf).astype(u.dtype)
        s = jnp.einsum("tkgd,skd->kgts", qz(q), qz(k)) * D ** -0.5
        P = jax.nn.softmax(s + hidden, axis=-1)
        o = jnp.einsum("kgts,skd->tkgd", qz(P), qz(v)).reshape(rows, A * D)
        return qz(o) @ qz(p["o.w"])

    out = jax.lax.map(block, (u.reshape(T // rows, rows, -1),
                              jnp.arange(0, T, rows)))
    return out.reshape(T, -1)


def _token_blocks(fn, x):
    """``fn`` over x ([T, ...], or a tuple of such) in blocks of
    ``TOKEN_ROWS`` tokens, each replayed in the backward pass; ``fn`` is
    per token."""
    T = jax.tree.leaves(x)[0].shape[0]
    rows = TOKEN_ROWS if T % TOKEN_ROWS == 0 else T
    out = jax.lax.map(jax.checkpoint(fn), jax.tree.map(
        lambda a: a.reshape((T // rows, rows) + a.shape[1:]), x))
    return jax.tree.map(lambda a: a.reshape((T,) + a.shape[2:]), out)


def route(b, p, cfg, qz):
    """b [T, H] -> (gates [T, 4] float32, the experts chosen [T, 4] over
    all E).  The selection reads score + bias, the gates the scores."""
    s = jax.nn.sigmoid((qz(b) @ qz(p["router.w"])).astype(jnp.float32))
    _, idx = jax.lax.top_k(s + p["router.bias"].astype(jnp.float32),
                           cfg["num_experts_per_tok"])
    gate = jnp.take_along_axis(s, idx, -1)
    if cfg["norm_topk_prob"]:
        gate = gate / (jnp.sum(gate, -1, keepdims=True) + GATE_EPSILON)
    if not cfg["train_router"]:
        gate = jax.lax.stop_gradient(gate)
    return gate * cfg["routed_scaling_factor"], idx


def feed_forward(b, p, cfg, held, qz=lambda a: a):
    """An expert layer's FF over one row, b [T, H] normed -> (the held
    experts' part of the routed result, the experts each token chose
    [T, 4]).  The members' parts add up to the uncut layer's."""
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


def dense_ff(b, p, qz):
    """The leading dense layer's SwiGLU, b [T, H] normed; ``ff_in.w`` holds
    W1's half, then W3's."""
    ab = qz(b) @ qz(p["ff_in.w"])
    n = ab.shape[-1] // 2
    return qz(jax.nn.silu(ab[:, :n]) * ab[:, n:]) @ qz(p["ff_down.w"])


def layer(x, p, cfg, kind, held, qz):
    """Rows x [B, T, H] through one layer (dense where ``p`` holds
    ``ff_in.w``) -> (x, the experts every token chose [B, T, 4] or
    None)."""
    eps = cfg["norm_eps"]

    def row(x):
        u = rms_norm(x, p["norm_op.g"], eps)
        x = x + (short_conv(u, p, qz) if kind == "conv"
                 else attention(u, p, cfg, qz))
        b = rms_norm(x, p["norm_ffn.g"], eps)
        if "ff_in.w" in p:
            m, chosen = _token_blocks(lambda b: dense_ff(b, p, qz), b), None
        else:
            m, chosen = _token_blocks(
                lambda b: feed_forward(b, p, cfg, held, qz), b)
        return x + m, chosen
    return jax.lax.map(row, x)


def _under(params, prefix):
    return {n[len(prefix):]: a for n, a in params.items()
            if n.startswith(prefix)}


def forward(params, ids, cfg, qz=lambda a: a, held=None):
    """-> (the normed final state [rows, seq, H], the experts every token
    chose [L - 1, rows, seq, 4])."""
    held = held_ids(cfg) if held is None else held
    kinds = layer_kinds(cfg)
    x = params["tok"][ids]
    x, _ = jax.checkpoint(
        lambda x, p: layer(x, p, cfg, kinds[0], held, qz))(
            x, _under(params, "dense."))
    chosen, seen = [], {}
    # the kinds differ from layer to layer, so the stack is walked, not
    # scanned: one layer live at a time in the backward pass
    for kind in kinds[1:]:
        nth = seen.get(kind, 0)
        seen[kind] = nth + 1
        p = {n: a[nth] for n, a in
             _under(params, f"layers.{_SHORT[kind]}.").items()}
        x, c = jax.checkpoint(
            lambda x, p, kind=kind: layer(x, p, cfg, kind, held, qz))(x, p)
        chosen.append(c)
    return (rms_norm(x, params["norm_f.g"], cfg["norm_eps"]),
            jnp.stack(chosen))


def loss(params, ids, labels, cfg, variant, qz=lambda a: a, held=None):
    """Mean cross-entropy of ``ids`` [rows, seq] against ``labels`` over
    the held vocabulary rows, the head the embedding's transpose."""
    z, _ = forward(params, ids, cfg, qz, held)

    def nll(args):
        zb, lb = args
        logits = (qz(zb) @ qz(params["tok"]).T).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=-1)[:, 0]

    return jnp.mean(_token_blocks(
        nll, (z.reshape(-1, z.shape[-1]), labels.reshape(-1))))
