"""Plain reference of the Ouro (LoopLM) training step: float32 jax.numpy,
no kernels, nothing imported from the program.

Ouro-2.6B (huggingface.co/ByteDance/Ouro-2.6B, ``model_type: ouro``;
Zhu et al., "Scaling Latent Reasoning via Looped Language Models", 2025)
is a decoder whose ONE stack of L layers is run ``total_ut_steps`` = T
times on shared weights:

    h^0 = Emb(x);  for t = 1..T:  s^t = Stack(h^{t-1}),  h^t = RMSNorm_f(s^t)

``h^t`` is exit t's state and pass t + 1's input; the layers, the final
norm, the exit gate and the head exist once.  A layer has sandwich norms
(four RMSNorms):

    a = Attn(N1(x));  x = x + N2(a)
    m = W_down(silu(W_gate N3(x)) * W_up N3(x));  x = x + N4(m)

``Attn``: q, k, v without bias, rotate-half RoPE on q and k (all 128 dims
of a head, positions 0..S-1 at every pass), causal softmax attention at
scale 128^-1/2, o without bias.  The exit gate, a token:
``l_t = sigmoid(w_g . h^t + b_g)``, one Linear(H, 1) shared over t; the
exit distribution ``p_1 = l_1``, ``p_t = l_t prod_{j<t}(1 - l_j)``,
``p_T = prod_{j<T}(1 - l_j)``.  The objective (the family's pre-training
stage, a uniform prior over the exit step), mean over the tokens:

    L = sum_t p_t CE(W_head h^t, y) - beta H(p),   H(p) = -sum_t p_t log p_t

with gradients into p_t (from the exits' losses and from H) and into the
exits' losses (weighted by p_t).

Departures from the published description, each also under ``assumed`` in
the configuration's file: the gains are plain (not ``1 + g``); the gate
reads the NORMED state h^t; ``log p_t`` is summed from ``log l`` and
``log(1 - l)`` (as log-sigmoids of the gate's logit), which is the written
formula without its ``0 log 0``; beta = 0.1; ``early_exit_threshold`` is
an inference setting and is not read.

Weights are ``[in, out]``.  ``qz`` is applied to every matmul operand; it
is the identity here and a quantiser in the control (benchmark/check.py).
Attention runs head group by head group and an exit's logits a block of
rows at a time, each replayed in the backward pass, so that the float32
step fits one chip beside its state.
"""
import jax
import jax.numpy as jnp

HEAD_GROUPS = 4      # attention runs a quarter of the heads at a time
LOGIT_ROWS = 2048    # positions whose logits are live at a time


def param_shapes(cfg, variant):
    """name -> (shape, base): a leaf is ``base + 0.02 * normal``.  The
    blocks' leaves (``layers.*``) are stacked: axis 0 is the block."""
    H, F, L = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_hidden_layers"])
    A, D = cfg["num_attention_heads"], cfg["head_dim"]
    out = {"tok": ((cfg["vocab_size"], H), 0.0),
           "norm_f.g": ((H,), 1.0),
           "gate.w": ((H, 1), 0.0), "gate.b": ((1,), 0.0),
           "head.w": ((H, cfg["vocab_size"]), 0.0)}
    for n in ("q", "k", "v"):
        out[f"layers.{n}.w"] = ((L, H, A * D), 0.0)
    out["layers.o.w"] = ((L, A * D, H), 0.0)
    for n in ("gate", "up"):
        out[f"layers.{n}.w"] = ((L, H, F), 0.0)
    out["layers.down.w"] = ((L, F, H), 0.0)
    for n in ("norm1", "norm2", "norm3", "norm4"):
        out[f"layers.{n}.g"] = ((L, H), 1.0)
    return out


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


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


def attention(q, k, v, qz):
    """Causal softmax attention; q, k (rotated) and v [B, S, A, D]."""
    B, S, A, D = q.shape
    G = HEAD_GROUPS if A % HEAD_GROUPS == 0 else 1
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def one(args):
        qg, kg, vg = args                                # [B, S, A/G, D]
        s = jnp.einsum("bqhd,bkhd->bhqk", qz(qg), qz(kg)) * D ** -0.5
        w = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", qz(w), qz(vg))

    def groups(x):
        return jnp.moveaxis(x.reshape(B, S, G, A // G, D), 2, 0)

    out = jax.lax.map(one, (groups(q), groups(k), groups(v)))
    return jnp.moveaxis(out, 0, 2).reshape(B, S, A, D)


def _block(x, p, cfg, qz):
    """One layer; ``p`` holds its slices of the ``layers.*`` leaves."""
    B, S, H = x.shape
    A, D, eps = cfg["num_attention_heads"], cfg["head_dim"], \
        cfg["rms_norm_eps"]

    def lin(h, n):
        return qz(h) @ qz(p[n + ".w"])

    h = rms_norm(x, p["norm1.g"], eps)
    q = rope(lin(h, "q").reshape(B, S, A, D), cfg["rope_theta"])
    k = rope(lin(h, "k").reshape(B, S, A, D), cfg["rope_theta"])
    v = lin(h, "v").reshape(B, S, A, D)
    a = lin(attention(q, k, v, qz).reshape(B, S, A * D), "o")
    x = x + rms_norm(a, p["norm2.g"], eps)
    h = rms_norm(x, p["norm3.g"], eps)
    m = lin(jax.nn.silu(lin(h, "gate")) * lin(h, "up"), "down")
    return x + rms_norm(m, p["norm4.g"], eps)


def exit_states(params, ids, cfg, qz):
    """-> [h^1, ..., h^T], each [rows, seq, H]."""
    blocks = {n[len("layers."):]: a for n, a in params.items()
              if n.startswith("layers.")}
    h, out = params["tok"][ids], []
    for _ in range(cfg["total_ut_steps"]):
        # a scan over the stacked blocks, one block live at a time in
        # the backward pass; the same ``blocks`` at every pass
        h, _ = jax.lax.scan(
            jax.checkpoint(lambda x, p: (_block(x, p, cfg, qz), None)),
            h, blocks)
        h = rms_norm(h, params["norm_f.g"], cfg["rms_norm_eps"])
        out.append(h)
    return out


def exit_distribution(lam):
    """``lam`` [T - 1, ...], the gate's probabilities after passes 1 to
    T - 1 -> ``p`` [T, ...]: p_1 = l_1, p_t = l_t prod_{j<t}(1 - l_j),
    p_T = prod_{j<T}(1 - l_j)."""
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stay[:-1]], axis=0)
    return jnp.concatenate(
        [lam * before, jnp.prod(1.0 - lam, axis=0, keepdims=True)], axis=0)


def token_losses(h, head_w, labels, qz):
    """Cross-entropy of every position of ``h`` [rows, seq, H] against
    ``labels`` -> [rows, seq], the logits of ``LOGIT_ROWS`` positions of a
    row at a time."""
    B, S, H = h.shape
    rows = LOGIT_ROWS if S % LOGIT_ROWS == 0 else S

    @jax.checkpoint
    def part(args):
        hb, lb = args
        logp = jax.nn.log_softmax(
            (qz(hb) @ qz(head_w)).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, lb[..., None], axis=-1)[..., 0]

    hb = jnp.moveaxis(h.reshape(B, S // rows, rows, H), 1, 0)
    lb = jnp.moveaxis(labels.reshape(B, S // rows, rows), 1, 0)
    return jnp.moveaxis(jax.lax.map(part, (hb, lb)), 0, 1).reshape(B, S)


def objective(params, states, labels, cfg, qz):
    """The entropy-regularised expected loss over the exit ``states``
    [h^1, ..., h^T], mean over every position."""
    # the gate: a Linear(H, 1) with bias on the normed exit state; its
    # logit's log-sigmoids are log l and log(1 - l), so that H(p) has no
    # 0 log 0 at a saturated gate (the written formula otherwise)
    z = jnp.stack([(h @ params["gate.w"])[..., 0] + params["gate.b"][0]
                   for h in states])[:-1]
    p = exit_distribution(jax.nn.sigmoid(z))
    log_stay = jnp.cumsum(jax.nn.log_sigmoid(-z), axis=0)
    log_p = jnp.concatenate(
        [jax.nn.log_sigmoid(z)
         + jnp.concatenate([jnp.zeros_like(z[:1]), log_stay[:-1]], axis=0),
         jnp.sum(jax.nn.log_sigmoid(-z), axis=0, keepdims=True)], axis=0)
    entropy = -jnp.sum(p * log_p, axis=0)
    expected = sum(p[t] * token_losses(h, params["head.w"], labels, qz)
                   for t, h in enumerate(states))
    return jnp.mean(expected - cfg["exit_entropy_beta"] * entropy)


def loss(params, ids, labels, cfg, variant, qz=lambda a: a):
    """The objective of ``ids`` [rows, seq] against ``labels``."""
    return objective(params, exit_states(params, ids, cfg, qz), labels, cfg,
                     qz)
