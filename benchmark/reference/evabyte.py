"""Plain reference of the EvaByte training step: float32 jax.numpy, no
kernels, nothing imported from the program.

EvaByte (huggingface.co/EvaByte/EvaByte, ``model_type: evabyte``) is a
byte-level decoder: an embedding of 320 byte values, L pre-norm blocks
(RMSNorm applied as ``1 + g``, EVA attention with rotary positions,
residual, RMSNorm, a SwiGLU feed-forward without biases, residual), a
final RMSNorm and ``num_pred_heads`` untied heads of 320 over one state:
head j at position t predicts byte t + 1 + j.

EVA (Zheng, Yuan, Wang, Kong, "Efficient Attention via Control
Variates", ICLR 2023) in the deterministic form EvaByte uses: the row is
cut into windows of ``window_size`` and each window into chunks of
``chunk_size``.  A chunk's keys and values are pooled into one summary
pair by a softmax inside the chunk against a learned per-head vector
(``mu`` for the key, ``phi`` for the value).  A query attends, under ONE
softmax, to the exact keys of its own window up to itself and to the
summaries of every chunk of every earlier window.

What ``config.json`` does not fix is listed in the configuration file
under ``assumed`` (rotate-half RoPE, summaries pooled from rotated keys,
the scale inside the pooling logits, equal weight of the heads' losses,
labels by shifting).

Weights are ``[in, out]``.  ``qz`` is applied to every matmul operand; it
is the identity here and a quantiser in the control (benchmark/check.py).
Attention runs window by window and head group by head group, the
feed-forward in blocks of the row, each replayed in the backward pass,
so that the float32 step fits one chip beside its state.
"""
import jax
import jax.numpy as jnp

HEAD_GROUPS = 4     # attention runs a quarter of the heads at a time
FFN_ROWS = 2048     # rows of the feed-forward live at a time


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def param_shapes(cfg, variant):
    """name -> (shape, base): a leaf is ``base + 0.02 * normal``.  The
    blocks' leaves (``layers.*``) are stacked: axis 0 is the block.  The
    norms' gains are stored as offsets from one (applied as ``1 + g``)."""
    H, F, L = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_hidden_layers"])
    A, D = cfg["num_attention_heads"], head_dim(cfg)
    out = {"tok": ((cfg["vocab_size"], H), 0.0),
           "norm_f.g": ((H,), 0.0),
           "head.w": ((H, cfg["num_pred_heads"] * cfg["vocab_size"]), 0.0)}
    for n in ("q", "k", "v", "o"):
        out[f"layers.{n}.w"] = ((L, H, H), 0.0)
    for n in ("gate", "up"):
        out[f"layers.{n}.w"] = ((L, H, F), 0.0)
    out["layers.down.w"] = ((L, F, H), 0.0)
    for n in ("mu", "phi"):
        out[f"layers.{n}"] = ((L, A, D), 0.0)
    for n in ("norm1", "norm2"):
        out[f"layers.{n}.g"] = ((L, H), 0.0)
    return out


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * (1.0 + g)


def rope(x, theta):
    """Rotate-half rotary embedding of ``x`` [B, S, A, D] at positions
    0..S-1: the pair (i, i + D/2) turns by ``pos * theta^(-2i/D)``."""
    S, D = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq[None]
    # in x's type, so that a control held in bfloat16 stays in it
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def pool(k, v, mu, phi, chunk, scale):
    """Chunk summaries: k, v [B, S, A, D] -> [B, S / chunk, A, D] each."""
    B, S, A, D = k.shape
    kc = k.reshape(B, S // chunk, chunk, A, D)
    vc = v.reshape(B, S // chunk, chunk, A, D)
    wk = jax.nn.softmax(scale * jnp.sum(kc * mu, -1), axis=2)
    wv = jax.nn.softmax(scale * jnp.sum(kc * phi, -1), axis=2)
    return (jnp.sum(wk[..., None] * kc, 2), jnp.sum(wv[..., None] * vc, 2))


def eva_attention(q, k, v, mu, phi, window, chunk, qz):
    """q, k (rotated) and v [B, S, A, D] -> [B, S, A, D]."""
    B, S, A, D = q.shape
    scale = D ** -0.5
    W = min(window, S)
    if S % W or (S > W and W % chunk):
        raise ValueError(f"a row of {S} does not split into windows of "
                         f"{W} made of chunks of {chunk}")
    nw = S // W
    G = HEAD_GROUPS if A % HEAD_GROUPS == 0 else 1
    if nw > 1:
        ks, vs = pool(k, v, mu, phi, chunk, scale)       # [B, S/c, A, D]
        # the window each summary belongs to
        sum_win = jnp.arange(S // chunk) // (W // chunk)
    causal = jnp.tril(jnp.ones((W, W), bool))

    def groups(x):
        """[B, n, A, D] -> [G, B, n, A/G, D]."""
        n = x.shape[1]
        return jnp.moveaxis(x.reshape(B, n, G, A // G, D), 2, 0)

    @jax.checkpoint
    def one(qg, kg, vg, ksg, vsg, w):
        """One window of one head group under one softmax."""
        s = jnp.einsum("bqhd,bkhd->bhqk", qz(qg), qz(kg)) * scale
        s = jnp.where(causal[None, None], s, -jnp.inf)
        if nw > 1:
            t = jnp.einsum("bqhd,bchd->bhqc", qz(qg), qz(ksg)) * scale
            t = jnp.where((sum_win < w)[None, None, None], t, -jnp.inf)
            s = jnp.concatenate([s, t], -1)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", qz(p[..., :W]), qz(vg))
        if nw > 1:
            out = out + jnp.einsum("bhqc,bchd->bqhd", qz(p[..., W:]),
                                   qz(vsg))
        return out

    def window(args):
        qw, kw, vw, w = args                             # [B, W, A, D]
        if nw > 1:
            run = lambda xs: one(*xs, w)
            og = jax.lax.map(run, (groups(qw), groups(kw), groups(vw),
                                   groups(ks), groups(vs)))
        else:
            og = jax.lax.map(lambda xs: one(*xs, None, None, w),
                             (groups(qw), groups(kw), groups(vw)))
        return jnp.moveaxis(og, 0, 2).reshape(B, W, A, D)

    def windows(x):
        return jnp.moveaxis(x.reshape(B, nw, W, A, D), 1, 0)

    out = jax.lax.map(window, (windows(q), windows(k), windows(v),
                               jnp.arange(nw)))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, A, D)


def _block(x, p, cfg, qz):
    """One block; ``p`` holds its slices of the ``layers.*`` leaves."""
    B, S, H = x.shape
    A, D = cfg["num_attention_heads"], head_dim(cfg)
    eps = cfg["rms_norm_eps"]

    def lin(h, n):
        return qz(h) @ qz(p[n + ".w"])

    h = rms_norm(x, p["norm1.g"], eps)
    q = rope(lin(h, "q").reshape(B, S, A, D), cfg["rope_theta"])
    k = rope(lin(h, "k").reshape(B, S, A, D), cfg["rope_theta"])
    v = lin(h, "v").reshape(B, S, A, D)
    a = eva_attention(q, k, v, p["mu"], p["phi"], cfg["window_size"],
                      cfg["chunk_size"], qz)
    x = x + lin(a.reshape(B, S, H), "o")
    h = rms_norm(x, p["norm2.g"], eps)

    @jax.checkpoint
    def ffn(hb):
        return lin(jax.nn.silu(lin(hb, "gate")) * lin(hb, "up"), "down")

    rows = FFN_ROWS if S % FFN_ROWS == 0 else S
    hb = jnp.moveaxis(h.reshape(B, S // rows, rows, H), 1, 0)
    y = jnp.moveaxis(jax.lax.map(ffn, hb), 0, 1).reshape(B, S, H)
    return x + y


def multi_byte_loss(logits, labels, heads):
    """``logits`` [B, S, heads * V] float32; head j at position t is held
    against ``labels[t + j]``; mean over the pairs with t + j < S."""
    B, S, _ = logits.shape
    logp = jax.nn.log_softmax(logits.reshape(B, S, heads, -1), axis=-1)
    total, pairs = 0.0, 0
    for j in range(heads):
        picked = jnp.take_along_axis(logp[:, :S - j, j],
                                     labels[:, j:, None], axis=-1)
        total = total - jnp.sum(picked)
        pairs += B * (S - j)
    return total / pairs


def loss(params, ids, labels, cfg, variant, qz=lambda a: a):
    """The multi-byte loss of ``ids`` [rows, seq] against ``labels``."""
    x = params["tok"][ids]
    # a scan over the stacked blocks, one block live at a time in the
    # backward pass
    blocks = {n[len("layers."):]: a for n, a in params.items()
              if n.startswith("layers.")}
    x, _ = jax.lax.scan(
        jax.checkpoint(lambda x, p: (_block(x, p, cfg, qz), None)),
        x, blocks)
    z = rms_norm(x, params["norm_f.g"], cfg["rms_norm_eps"])
    logits = (qz(z) @ qz(params["head.w"])).astype(jnp.float32)
    return multi_byte_loss(logits, labels, cfg["num_pred_heads"])
