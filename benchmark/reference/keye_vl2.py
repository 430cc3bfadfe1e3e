"""Plain reference of the training step of Keye-VL-2.0-30B-A3B's language
model: float32 jax.numpy, no kernels, nothing imported from the program.

The language model (huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B,
``model_type: KeyeVL2``) on text positions: an embedding, L pre-norm
blocks and an untied head.  A block is

- RMSNorm, grouped-query attention (32 query heads on 4 key/value heads
  of 128, RMSNorm per head on q and k, rotate-half RoPE) **over a learned
  per-query set of keys**, residual;
- RMSNorm, a mixture of experts (router over all ``published.num_experts``
  experts, softmax in float32, the 8 largest renormalised, SwiGLU
  experts of width 768), residual.

The key set is DeepSeek Sparse Attention's (DeepSeek-V3.2-Exp technical
report and public inference code): an indexer of 16 light heads of 64
against ONE shared key scores every earlier position,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]),

the ``topk`` largest are kept (ties: the lower position; every position
while t < topk), and the main attention's softmax runs over those only.
The indexer reads ``stop_gradient`` of the normed hidden state, and top-k
passes no gradient, so it learns from its own loss alone (the report's
sparse training stage): per layer, the KL divergence from the main
attention's probabilities summed over heads (detached, renormalised by
1 / heads) to the softmax of I over the selected set, mean over
positions.  ``loss`` = cross-entropy + the mean of the layers' KL terms
(the two gradient paths are disjoint, so that term's weight reaches the
parameters through the global-norm clip alone; why the mean and not the
sum: the configuration file's ``assumed.indexer_loss``).

**One chip's share.**  The configuration holds ``held_experts`` of the
experts of every layer and a slice of the vocabulary (benchmark/configs/
keye_vl2_30b_a3b.json: 8 chips share each layer).  The router spans all
experts; a token's gates are renormalised over its 8 whatever is held;
what the absent experts would add is left out.  ``held`` may be handed
in to compute another chip's share (the tests add the shares up).

What ``config.json`` does not fix is in the configuration file's
``assumed``.  Weights are ``[in, out]``.  ``qz`` is applied to every
matmul operand: the identity here, a quantiser in the control
(benchmark/check.py).  Attention runs a block of ``QUERY_ROWS`` queries
at a time and the experts one at a time, each replayed in the backward
pass, so that the float32 step fits one chip beside its state.
"""
import jax
import jax.numpy as jnp

QUERY_ROWS = 512    # queries whose scores are live at a time

# The gain of the norm before attention is drawn around the embedding's
# own scale, 0.02, not around 1: at the first step that norm then hands
# the stream on at the size it has, and attention's branch comes back at
# that size.  Around 1 it comes back 50 times the embedding (the norm
# lifts a stream of 0.02 to 1, v and o keep that), and what comes back is
# a mean over up to 2048 values, the same for every query of a row: from
# the second layer on that mean is the stream, every token of a row
# reads the router alike, and the load of the held experts is 0 to 3
# times a row's tokens by the seed (PERF.md section 6, PR 30).  A trained
# router does not see such a stream.  Read on the CPU at these widths:
# 8,192 +- 300 held assignments a (row, layer) around 0.02, 6 to 14,983
# around 1.  The configuration file's ``assumed.init`` says so.
ATTENTION_NORM_GAIN = 0.02


def sizes(cfg):
    sa = cfg["sa_config"]
    return dict(H=cfg["hidden_size"], A=cfg["num_attention_heads"],
                KV=cfg["num_key_value_heads"], D=cfg["head_dim"],
                J=sa["indexer_num_heads"], DI=sa["indexer_head_dim"],
                E=cfg["published"]["num_experts"], F=cfg["moe_intermediate_size"],
                held=cfg["num_experts"], L=cfg["num_hidden_layers"],
                V=cfg["vocab_size"])


def held_ids(cfg):
    """The expert ids this chip holds: ``num_experts`` of them from
    ``held_experts.first``."""
    first = cfg["held_experts"]["first"]
    return tuple(range(first, first + cfg["num_experts"]))


def param_shapes(cfg, variant):
    """name -> (shape, base): a leaf is ``base + 0.02 * normal``.  The
    blocks' leaves (``layers.*``) are stacked: axis 0 is the block.  The
    experts' leaves hold the held experts only, in the order of their
    ids."""
    z = sizes(cfg)
    H, L, D, held = z["H"], z["L"], z["D"], z["held"]
    out = {"tok": ((z["V"], H), 0.0), "norm_f.g": ((H,), 1.0),
           "head.w": ((H, z["V"]), 0.0)}
    layers = {
        "norm1.g": ((H,), ATTENTION_NORM_GAIN), "norm2.g": ((H,), 1.0),
        "q.w": ((H, z["A"] * D), 0.0), "k.w": ((H, z["KV"] * D), 0.0),
        "v.w": ((H, z["KV"] * D), 0.0), "o.w": ((z["A"] * D, H), 0.0),
        "q_norm.g": ((D,), 1.0), "k_norm.g": ((D,), 1.0),
        "idx_q.w": ((H, z["J"] * z["DI"]), 0.0),
        "idx_k.w": ((H, z["DI"]), 0.0),
        "idx_k_norm.g": ((z["DI"],), 1.0), "idx_k_norm.b": ((z["DI"],), 0.0),
        "idx_w.w": ((H, z["J"]), 0.0),
        "router.w": ((H, z["E"]), 0.0),
        "experts.gate": ((held, H, z["F"]), 0.0),
        "experts.up": ((held, H, z["F"]), 0.0),
        "experts.down": ((held, z["F"], H), 0.0),
    }
    for n, (shape, base) in layers.items():
        out["layers." + n] = ((L,) + shape, base)
    return out


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def rope(x, theta):
    """Rotate-half rotary embedding of ``x`` [T, ..., D] at positions
    0..T-1: the pair (i, i + D/2) turns by ``pos * theta^(-2i/D)``.  On
    text positions the three streams of ``mrope_section`` are equal, so
    this is the whole of it."""
    T, D = x.shape[0], x.shape[-1]
    freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None]
    shape = (T,) + (1,) * (x.ndim - 2) + (D // 2,)
    # in x's type, so that a control held in bfloat16 stays in it
    cos = jnp.cos(ang).reshape(shape).astype(x.dtype)
    sin = jnp.sin(ang).reshape(shape).astype(x.dtype)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def index_scores(qI, w, kI, qz):
    """qI [n, J, d], w [n, J], kI [T, d] -> I [n, T], float32."""
    s = jnp.einsum("tjd,sd->tjs", qz(qI), qz(kI))
    return jnp.sum(w[:, :, None] * jax.nn.relu(s), 1)


def select(I, causal, topk):
    """The ``topk`` visible positions of largest score a query, as a
    mask [n, T]; all the visible ones where there are no more than
    ``topk``.  ``lax.top_k`` puts the lower index first among equals."""
    n, T = I.shape
    _, idx = jax.lax.top_k(jnp.where(causal, I, -jnp.inf), min(topk, T))
    picked = jnp.zeros((n, T), bool).at[jnp.arange(n)[:, None], idx].set(True)
    return picked & causal


def sparse_attention(q, k, v, qI, w, kI, topk, qz):
    """One row.  q [T, A, D], k, v [T, KV, D] (normed and rotated), qI
    [T, J, d], w [T, J], kI [T, d] -> (out [T, A * D], the row's indexer
    loss: mean over t of KL(ph[t] || softmax of I over S_t))."""
    T, A, D = q.shape
    KV = k.shape[1]
    rows = QUERY_ROWS if T % QUERY_ROWS == 0 else T

    @jax.checkpoint
    def block(args):
        qb, qIb, wb, first = args
        pos = first + jnp.arange(rows)
        causal = jnp.arange(T)[None, :] <= pos[:, None]
        I = index_scores(qIb, wb, kI, qz)
        sel = select(jax.lax.stop_gradient(I), causal, topk)
        s = jnp.einsum("tgnd,sgd->gnts",
                       qz(qb.reshape(rows, KV, A // KV, D)), qz(k)) * D ** -0.5
        P = jax.nn.softmax(jnp.where(sel, s, -jnp.inf), axis=-1)
        o = jnp.einsum("gnts,sgd->tgnd", qz(P), qz(v)).reshape(rows, A * D)
        ph = jax.lax.stop_gradient(jnp.sum(P, (0, 1)) / A)
        logpI = jax.nn.log_softmax(jnp.where(sel, I, -jnp.inf), axis=-1)
        live = sel & (ph > 0)                             # 0 log 0 = 0
        kl = jnp.where(live, ph * (jnp.log(jnp.where(live, ph, 1.0))
                                   - jnp.where(live, logpI, 0.0)), 0.0)
        return o, jnp.sum(kl)

    def blocks(x):
        return x.reshape((T // rows, rows) + x.shape[1:])

    o, kl = jax.lax.map(block, (blocks(q), blocks(qI), blocks(w),
                                jnp.arange(0, T, rows)))
    return o.reshape(T, A * D), jnp.sum(kl) / T


def moe(b, p, cfg, held, qz):
    """b [T, H] (normed) -> (the held experts' part of the layer's result,
    the experts each token chose [T, 8]).  The router runs over all
    experts; a token's 8 gates are renormalised among themselves whatever
    is held here."""
    r = jax.nn.softmax((qz(b) @ qz(p["router.w"])).astype(jnp.float32), -1)
    vals, idx = jax.lax.top_k(r, cfg["num_experts_per_tok"])
    gate = vals / jnp.sum(vals, -1, keepdims=True)

    @jax.checkpoint
    def expert(args):
        wg, wu, wd, e = args
        y = (qz(jax.nn.silu(qz(b) @ qz(wg)) * (qz(b) @ qz(wu)))) @ qz(wd)
        # in b's type, so that a control held in bfloat16 stays in it
        return jnp.sum(jnp.where(idx == e, gate, 0.0), -1).astype(
            b.dtype)[:, None] * y

    parts = jax.lax.map(expert, (p["experts.gate"], p["experts.up"],
                                 p["experts.down"], jnp.asarray(held)))
    return jnp.sum(parts, 0), idx


def _block(x, p, cfg, held, qz):
    """One block over rows x [B, T, H] -> (x, (the block's indexer loss,
    mean over rows; the experts every token chose [B, T, 8]))."""
    z = sizes(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]

    def row(x):
        T = x.shape[0]
        a = rms_norm(x, p["norm1.g"], eps)

        def lin(h, n):
            return qz(h) @ qz(p[n + ".w"])

        q = lin(a, "q").reshape(T, z["A"], z["D"])
        k = lin(a, "k").reshape(T, z["KV"], z["D"])
        v = lin(a, "v").reshape(T, z["KV"], z["D"])
        q = rope(rms_norm(q, p["q_norm.g"], eps), theta)
        k = rope(rms_norm(k, p["k_norm.g"], eps), theta)
        ai = jax.lax.stop_gradient(a)
        qI = rope(lin(ai, "idx_q").reshape(T, z["J"], z["DI"]), theta)
        kI = rope(layer_norm(lin(ai, "idx_k"), p["idx_k_norm.g"],
                             p["idx_k_norm.b"], eps), theta)
        w = lin(ai, "idx_w") * z["J"] ** -0.5 * z["DI"] ** -0.5
        o, kl = sparse_attention(q, k, v, qI, w, kI,
                                 cfg["sa_config"]["topk"], qz)
        x = x + lin(o, "o")
        b = rms_norm(x, p["norm2.g"], eps)
        y, chosen = moe(b, p, cfg, held, qz)
        return x + y, kl, chosen

    x, kl, chosen = jax.lax.map(row, x)
    return x, (jnp.mean(kl), chosen)


def forward(params, ids, cfg, qz=lambda a: a, held=None):
    """-> (the final normed state [rows, seq, H], the layers' indexer
    losses [L], the experts every token chose [L, rows, seq, 8])."""
    held = held_ids(cfg) if held is None else held
    x = params["tok"][ids]
    blocks = {n[len("layers."):]: a for n, a in params.items()
              if n.startswith("layers.")}
    # a scan over the stacked blocks, one block live at a time in the
    # backward pass
    x, (kl, chosen) = jax.lax.scan(
        jax.checkpoint(lambda x, p: _block(x, p, cfg, held, qz)), x, blocks)
    return rms_norm(x, params["norm_f.g"], cfg["rms_norm_eps"]), kl, chosen


def loss(params, ids, labels, cfg, variant, qz=lambda a: a, held=None):
    """Cross-entropy of ``ids`` [rows, seq] against ``labels`` plus the
    mean of the layers' indexer losses."""
    z, kl, _ = forward(params, ids, cfg, qz, held)
    logits = (qz(z) @ qz(params["head.w"])).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    return ce + jnp.mean(kl)
