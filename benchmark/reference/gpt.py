"""Plain reference of the GPT causal-LM step: float32 jax.numpy, no
kernels, nothing imported from the program.

Radford et al. 2019 / Brown et al. 2020: token + learned position
embeddings, L pre-norm blocks (LayerNorm, causal self-attention,
residual, LayerNorm, GELU-tanh feed-forward of width 4 d_model,
residual), a final LayerNorm, logits through the transposed token
embedding (tied, no bias), mean cross-entropy over every position.
Departure, stated in the configuration file: dense attention in every
layer.

Weights are ``[in, out]``.  ``qz`` is applied to every matmul operand; it
is the identity here and a quantiser in the control (benchmark/check.py).
"""
import jax
import jax.numpy as jnp


def param_shapes(cfg, variant):
    """name -> (shape, base): a leaf is ``base + 0.02 * normal``.  The
    blocks' leaves (``layers.*``) are stacked: axis 0 is the block."""
    H, F = cfg["n_embd"], cfg["n_inner"]
    out = {"tok": ((cfg["vocab_size"], H), 0.0),
           "pos": ((cfg["n_positions"], H), 0.0),
           "ln_f.g": ((H,), 1.0), "ln_f.b": ((H,), 0.0)}
    L = cfg["n_layer"]
    for n in ("q", "k", "v", "o"):
        out[f"layers.{n}.w"] = ((L, H, H), 0.0)
        out[f"layers.{n}.b"] = ((L, H), 0.0)
    out["layers.fc1.w"] = ((L, H, F), 0.0)
    out["layers.fc1.b"] = ((L, F), 0.0)
    out["layers.fc2.w"] = ((L, F, H), 0.0)
    out["layers.fc2.b"] = ((L, H), 0.0)
    for n in ("ln1", "ln2"):
        out[f"layers.{n}.g"] = ((L, H), 1.0)
        out[f"layers.{n}.b"] = ((L, H), 0.0)
    return out


def layer_norm(x, g, b, eps):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), -1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps) * g + b


def _block(x, p, cfg, qz):
    """One block; ``p`` holds its slices of the ``layers.*`` leaves."""
    B, S, H = x.shape
    A = cfg["n_head"]
    D = H // A
    eps = cfg["layer_norm_epsilon"]

    def lin(h, n):
        return qz(h) @ qz(p[n + ".w"]) + p[n + ".b"]

    h = layer_norm(x, p["ln1.g"], p["ln1.b"], eps)
    q = lin(h, "q").reshape(B, S, A, D)
    k = lin(h, "k").reshape(B, S, A, D)
    v = lin(h, "v").reshape(B, S, A, D)
    s = jnp.einsum("bqhd,bkhd->bhqk", qz(q), qz(k)) / jnp.sqrt(
        jnp.asarray(D, x.dtype))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", qz(w), qz(v)).reshape(B, S, H)
    x = x + lin(a, "o")
    h = layer_norm(x, p["ln2.g"], p["ln2.b"], eps)
    return x + lin(jax.nn.gelu(lin(h, "fc1"), approximate=True), "fc2")


def loss(params, ids, labels, cfg, variant, qz=lambda a: a):
    """Mean cross-entropy over every position of ``ids`` [rows, seq]."""
    S = ids.shape[1]
    x = params["tok"][ids] + params["pos"][:S][None]
    # a scan over the stacked blocks, one block live at a time in the
    # backward pass: the float32 reference fits a 16 GB chip and compiles
    # as one block, not as twenty-four
    blocks = {n[len("layers."):]: a for n, a in params.items()
              if n.startswith("layers.")}
    x, _ = jax.lax.scan(
        jax.checkpoint(lambda x, p: (_block(x, p, cfg, qz), None)),
        x, blocks)
    x = layer_norm(x, params["ln_f.g"], params["ln_f.b"],
                   cfg["layer_norm_epsilon"])
    logits = qz(x) @ qz(params["tok"]).T
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -jnp.mean(picked)
