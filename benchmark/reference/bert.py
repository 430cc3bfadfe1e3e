"""Plain reference of the BERT masked-LM step: float32 jax.numpy, no
kernels, nothing imported from the program.

Devlin et al. 2018: token + position embeddings, L post-norm encoder
blocks (self-attention, residual, LayerNorm, GELU feed-forward,
residual, LayerNorm), a vocabulary projection with bias, mean
cross-entropy over every position.  Departures, all stated in the
configuration file: no segment embeddings, no embedding LayerNorm, no
pooler/NSP head.  ``variant`` carries what the two programs that run
these widths differ in: ``final_norm`` (a LayerNorm before the head) and
``gelu`` ("exact" erf form or "tanh" approximation).

Weights are ``[in, out]``.  ``qz`` is applied to every matmul operand; it
is the identity here and a quantiser in the control (benchmark/check.py).
"""
import jax
import jax.numpy as jnp


def param_shapes(cfg, variant):
    """name -> (shape, base): a leaf is ``base + 0.02 * normal``.  The
    blocks' leaves (``layers.*``) are stacked: axis 0 is the block."""
    H, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    out = {"tok": ((V, H), 0.0),
           "pos": ((cfg["max_position_embeddings"], H), 0.0)}
    L = cfg["num_hidden_layers"]
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
    if variant.get("final_norm"):
        out["ln_f.g"] = ((H,), 1.0)
        out["ln_f.b"] = ((H,), 0.0)
    out["head.w"] = ((H, V), 0.0)
    out["head.b"] = ((V,), 0.0)
    return out


def layer_norm(x, g, b, eps):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), -1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps) * g + b


def _block(x, p, cfg, variant, qz):
    """One block; ``p`` holds its slices of the ``layers.*`` leaves."""
    B, S, H = x.shape
    A = cfg["num_attention_heads"]
    D = H // A
    eps = cfg["layer_norm_eps"]

    def lin(h, n):
        return qz(h) @ qz(p[n + ".w"]) + p[n + ".b"]

    q = lin(x, "q").reshape(B, S, A, D)
    k = lin(x, "k").reshape(B, S, A, D)
    v = lin(x, "v").reshape(B, S, A, D)
    s = jnp.einsum("bqhd,bkhd->bhqk", qz(q), qz(k)) / jnp.sqrt(
        jnp.asarray(D, x.dtype))
    w = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", qz(w), qz(v)).reshape(B, S, H)
    x = layer_norm(x + lin(a, "o"), p["ln1.g"], p["ln1.b"], eps)
    h = jax.nn.gelu(lin(x, "fc1"), approximate=variant["gelu"] == "tanh")
    return layer_norm(x + lin(h, "fc2"), p["ln2.g"], p["ln2.b"],
                      eps)


def loss(params, ids, labels, cfg, variant, qz=lambda a: a):
    """Mean cross-entropy over every position of ``ids`` [rows, seq]."""
    S = ids.shape[1]
    x = params["tok"][ids] + params["pos"][:S][None]
    # a scan over the stacked blocks, one block live at a time in the
    # backward pass: the float32 reference fits a 16 GB chip and compiles
    # as one block, not as twelve
    blocks = {n[len("layers."):]: a for n, a in params.items()
              if n.startswith("layers.")}
    x, _ = jax.lax.scan(
        jax.checkpoint(lambda x, p: (_block(x, p, cfg, variant, qz), None)),
        x, blocks)
    if variant.get("final_norm"):
        x = layer_norm(x, params["ln_f.g"], params["ln_f.b"],
                       cfg["layer_norm_eps"])
    logits = qz(x) @ qz(params["head.w"]) + params["head.b"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -jnp.mean(picked)
