"""Plain reference of the training step of granite-4.0-h-micro
(``model_type: granitemoehybrid``, IBM, 3B dense, 2025-10): float32
jax.numpy, no kernels, nothing imported from the program or from another
family's reference.

40 layers by ``layer_types`` (attention at 5, 15, 25, 35, Mamba-2
elsewhere).  EVERY layer is two sublayers, each scaled into the float32
residual stream x of one row of T positions, r = ``residual_multiplier``:

    x <- x + r Mixer(RMSNorm(x; g1))
    x <- x + r FFN(RMSNorm(x; g2))

and round them

    x_0 = m_e E[ids]                              ``embedding_multiplier`` 12
    logits = RMSNorm(x_L; gf) E^T / s             ``logits_scaling`` 8; E is
                                                  ONE matrix (tied)
    loss = the mean cross-entropy over all positions.

A ``mamba`` layer's mixer, with u = RMSNorm(x; g1) (Mamba-2, Dao & Gu
2024, as the family's public modelling code has it): H = 64 heads of
P = 64, d_inner = 4096, ONE group of B and C (G = 1), N = 128, 4 taps:

    [z ; xBC ; dt] = u W_in                       2048 -> 4096 + 4352 + 64
    xBC = silu(sum_k w_k xBC[t - 3 + k] + b)      a channel on its own
    [x ; B ; C] = xBC                             4096, 128, 128
    Delta_t = softplus(dt_t + dt_bias)   A = -exp(A_log)     a head each
    S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T         [P, N] a head,
    y_t = S_t C_t + D x_t                         S_0 = 0; ALL 64 heads read
                                                  the same B_t and C_t
    y = RMSNorm(y * silu(z); gn)                  over all 4096 channels
    Mixer = y W_out

**The recurrence runs as written, a position at a time** (``recurrence``:
a ``lax.scan`` over positions inside a ``lax.scan`` over stretches of
``STRETCH`` positions whose inner steps the backward pass replays: a
row's state is [64, 64, 128] float32 = 2 MB a position).  The program
computes it in chunks of 128 with matrix products and, on a TPU, in
kernels that walk the one group in blocks of heads; the reference shares
none of that.

An ``attention`` layer's mixer: q = u Wq [T, 32, 64], k = u Wk and
v = u Wv [T, 8, 64]; query head h reads key/value head h // 4; causal
softmax of ``attention_multiplier`` x q.k with the multiplier 1/64 (NOT
64^-1/2); no rotary embedding and no other position signal
(``position_embedding_type`` nope); Mixer = concat_h(P v) Wo.

The FFN (``shared_mlp``; nothing is routed, ``num_local_experts`` 0):
[a ; b] = u W_1 (2048 -> 2 x 8192, the gate's half first),
FFN = (silu(a) * b) W_2.

**One chip's share** (benchmark/configs/granite_4_0_h_micro.json): the
first period of the pattern and a slice of the tied vocabulary; ids and
labels are drawn from the slice and the logits, the softmax and the loss
are over the slice.  ``logits_of`` takes any rows of E, so that a test can
lay the eight slices' logits side by side against the uncut matrix's.

Departures from the published description, each also under ``assumed``
in the configuration's file: the recurrence has no chunk at all here
(``mamba_chunk_size`` is a schedule, not mathematics); Delta is not
clamped; the state is zero at the start of every row; weight decay
reaches every leaf; the residual stream is float32.

Weights are ``[in, out]``; the taps ``[K, channels]``, tap K - 1 on the
position itself.  ``qz`` is applied to every matmul operand, the scan's
x, B and C among them (the identity here, a quantiser in the control:
benchmark/check.py).
"""
import jax
import jax.numpy as jnp

QUERY_ROWS = 512    # queries whose scores are live at a time
STRETCH = 128       # positions of the recurrence between kept states

# The bases of the leaves (a leaf is base + 0.02 * normal,
# benchmark/weights.py): 0 for every matrix, the embedding and the
# convolution's bias, 1 for the final norm's gain and for D, and these.
# configs/granite_4_0_h_micro.json ``assumed.init`` has the readings.
#
# No router reads the stream here, so ``--seed`` cannot move the work
# (Nemotron's second worry); what is left is that the comparison has to
# SEE each part: a recurrence that carries, a softmax that is not flat,
# branches of like size under 12 *, 0.22 * and / 8.
TAP_BASE = 3.5          # every tap: with M_NORM_GAIN the convolution's sum
                        # has a deviation of 0.4 and x, B and C of 0.23;
                        # the state's part goes with the taps' cube (at 3
                        # it read 0.62 to 0.67 of y, here 0.75 to 0.78).
                        # Around 0 the taps would hand the scan x, B and C
                        # of 0.02 and S_t C_t would be 1e-3 of D x_t: a
                        # comparison that cannot see the scan
DT_BIAS_BASE = -2.0     # softplus(-2 + the projection's 0.06) is about 0.13
A_LOG_BASE = -3.0       # A about -0.05: a decay of 0.9937 a position, 0.44
                        # over a chunk of 128, so the state a chunk starts
                        # from carries and still moves (around 0, a decay
                        # of 0.88, it is forgotten by a chunk's tenth
                        # position and a scan that dropped it reads sound)
M_NORM_GAIN = 0.0625    # the norm before a state-space mixer: z stays in
                        # silu's linear part and the taps restore x,
                        # B and C (Nemotron's pair, read there at 0.77 of
                        # y from the state)
GATE_NORM_GAIN = 1.0    # the gated norm: the branch is 1.28 before r
A_NORM_GAIN = 4.0       # the norm before attention: q and k of 3.6, so
                        # that 1/64 x q.k has a deviation of 1.6 and the
                        # softmax is neither flat nor one-hot.  Around 1 it
                        # is 0.1: a running mean, which would read the same
                        # at any multiplier
F_NORM_GAIN = 1.0       # the norm before the feed-forward layer


def sizes(cfg):
    heads, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    if heads * P != cfg["mamba_expand"] * cfg["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand "
                         "x hidden_size")
    A = cfg["num_attention_heads"]
    return dict(H=cfg["hidden_size"], heads=heads, P=P, G=G, N=N,
                di=heads * P, conv=heads * P + 2 * G * N,
                K=cfg["mamba_d_conv"], A=A, KV=cfg["num_key_value_heads"],
                hd=cfg["hidden_size"] // A, F=cfg["shared_intermediate_size"],
                V=cfg["vocab_size"])


def layers_of(cfg):
    """[(kind, index among the layers of its kind)] in the model's order;
    kinds "m" and "a" for ``mamba`` and ``attention``."""
    types = cfg["layer_types"]
    if len(types) != cfg["num_hidden_layers"] or \
            set(types) - {"mamba", "attention"}:
        raise ValueError(f"layer_types {types!r} is not "
                         f"{cfg['num_hidden_layers']} of mamba, attention")
    seen, out = {}, []
    for t in types:
        kind = t[0]
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = out[-1][1] + 1
    return out


def _kind_shapes(z):
    H, di, F = z["H"], z["di"], z["F"]
    ffn = {"norm2.g": ((H,), F_NORM_GAIN), "ffn.in.w": ((H, 2 * F), 0.0),
           "ffn.out.w": ((F, H), 0.0)}
    return {
        "m": {"norm1.g": ((H,), M_NORM_GAIN),
              "in.w": ((H, di + z["conv"] + z["heads"]), 0.0),
              "conv.w": ((z["K"], z["conv"]), TAP_BASE),
              "conv.b": ((z["conv"],), 0.0),
              "dt_bias": ((z["heads"],), DT_BIAS_BASE),
              "A_log": ((z["heads"],), A_LOG_BASE),
              "D": ((z["heads"],), 1.0),
              "gate_norm.g": ((di,), GATE_NORM_GAIN),
              "out.w": ((di, H), 0.0), **ffn},
        "a": {"norm1.g": ((H,), A_NORM_GAIN),
              "q.w": ((H, z["A"] * z["hd"]), 0.0),
              "k.w": ((H, z["KV"] * z["hd"]), 0.0),
              "v.w": ((H, z["KV"] * z["hd"]), 0.0),
              "o.w": ((z["A"] * z["hd"], H), 0.0), **ffn},
    }


def param_shapes(cfg, variant):
    """name -> (shape, base): a leaf is ``base + 0.02 * normal``.  The
    layers' leaves are stacked by kind (``layers.m.*``, ``layers.a.*``):
    axis 0 counts the layers of that kind in the model's order.  ``tok``
    is the embedding AND the head (``tie_word_embeddings``)."""
    if not cfg["tie_word_embeddings"] or cfg["num_local_experts"] \
            or cfg["hidden_act"] != "silu" \
            or cfg["position_embedding_type"] != "nope":
        raise ValueError("reference/granitemoehybrid.py computes a tied "
                         "head, no routed experts, silu and no rotary "
                         "embedding")
    z = sizes(cfg)
    out = {"tok": ((z["V"], z["H"]), 0.0), "norm_f.g": ((z["H"],), 1.0)}
    kinds = [k for k, _ in layers_of(cfg)]
    for kind, leaves in _kind_shapes(z).items():
        n = kinds.count(kind)
        for name, (shape, base) in leaves.items():
            if n:
                out[f"layers.{kind}.{name}"] = ((n,) + shape, base)
    return out


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


# ------------------------------------------------------ a mamba layer --
def recurrence(x, delta, A, Bm, Cm, D):
    """One row, a position at a time.  x [T, H, P], delta [T, H], A and D
    [H], Bm and Cm [T, N] (one group: every head reads them) -> (y
    [T, H, P], the state's part of it ``S_t C_t``)."""
    T, H, P = x.shape
    N = Bm.shape[-1]
    inner = STRETCH if T % STRETCH == 0 else T

    def position(S, now):
        xt, dt, Bt, Ct = now
        S = (jnp.exp(dt * A)[:, None, None] * S
             + (dt[:, None] * xt)[:, :, None] * Bt[None, None, :])
        carried = S @ Ct                                            # [H, P]
        return S, (carried + D[:, None] * xt, carried)

    @jax.checkpoint
    def stretch(S, chunk):
        return jax.lax.scan(position, S, chunk)

    _, (y, carried) = jax.lax.scan(
        stretch, jnp.zeros((H, P, N), x.dtype),
        tuple(a.reshape((T // inner, inner) + a.shape[1:])
              for a in (x, delta, Bm, Cm)))
    return y.reshape(T, H, P), carried.reshape(T, H, P)


def mamba_mixer(u, p, cfg, qz):
    """One row.  u [T, hidden] (normed) -> (the branch [T, hidden] before
    the residual multiplier, readings [2]: the RMS of ``S_t C_t`` over
    the RMS of ``y_t``, the mean decay ``exp(Delta A)``)."""
    z = sizes(cfg)
    if z["G"] != 1:
        raise ValueError("reference/granitemoehybrid.py writes the "
                         "recurrence for one group of B and C")
    T, di, N, K = u.shape[0], z["di"], z["N"], z["K"]
    f32 = jnp.float32
    proj = qz(u) @ qz(p["in.w"])
    gate, xBC, dt = (proj[:, :di], proj[:, di:di + z["conv"]],
                     proj[:, di + z["conv"]:])
    back = jnp.pad(xBC, ((K - 1, 0), (0, 0)))
    xBC = jax.nn.silu(sum(back[k:k + T] * p["conv.w"][k] for k in range(K))
                      + p["conv.b"])
    x = xBC[:, :di].reshape(T, z["heads"], z["P"])
    Bm, Cm = xBC[:, di:di + N], xBC[:, di + N:]
    delta = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
    A = -jnp.exp(p["A_log"].astype(f32))
    y, carried = recurrence(qz(x).astype(f32), delta, A, qz(Bm).astype(f32),
                            qz(Cm).astype(f32), p["D"].astype(f32))
    readings = jnp.stack([
        jnp.sqrt(jnp.mean(jnp.square(carried)) / jnp.mean(jnp.square(y))),
        jnp.mean(jnp.exp(delta * A))])
    y = y.reshape(T, di).astype(u.dtype) * jax.nn.silu(gate)
    y = rms_norm(y, p["gate_norm.g"], cfg["rms_norm_eps"])
    return qz(y) @ qz(p["out.w"]), jax.lax.stop_gradient(readings)


# -------------------------------------------------- an attention layer --
def attention(u, p, cfg, qz):
    """One row.  u [T, hidden] (normed) -> the branch [T, hidden] before
    the residual multiplier."""
    z = sizes(cfg)
    T, A, KV, hd = u.shape[0], z["A"], z["KV"], z["hd"]
    q = (qz(u) @ qz(p["q.w"])).reshape(T, KV, A // KV, hd)
    k = (qz(u) @ qz(p["k.w"])).reshape(T, KV, hd)
    v = (qz(u) @ qz(p["v.w"])).reshape(T, KV, hd)
    rows = QUERY_ROWS if T % QUERY_ROWS == 0 else T
    multiplier = cfg["attention_multiplier"]

    @jax.checkpoint
    def some_queries(args):
        qb, first = args
        t = first + jnp.arange(rows)
        seen = jnp.arange(T)[None, :] <= t[:, None]
        s = jnp.einsum("tgrd,sgd->grts", qz(qb), qz(k)) * multiplier
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("grts,sgd->tgrd", qz(w), qz(v)).reshape(rows,
                                                                  A * hd)

    o = jax.lax.map(some_queries,
                    (q.reshape((T // rows, rows) + q.shape[1:]),
                     jnp.arange(0, T, rows)))
    return qz(o.reshape(T, A * hd)) @ qz(p["o.w"])


# ------------------------------------------------------------- the FFN --
def ffn(u, p, cfg, qz):
    F = cfg["shared_intermediate_size"]
    ab = qz(u) @ qz(p["ffn.in.w"])
    return qz(jax.nn.silu(ab[:, :F]) * ab[:, F:]) @ qz(p["ffn.out.w"])


# ----------------------------------------------------------- the model --
def layer(kind, x, p, cfg, qz):
    """Rows x [B, T, H] through one layer of ``kind`` -> (x, a ``mamba``
    layer's readings [B, 2] or None)."""
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]

    def row(x):
        u = rms_norm(x, p["norm1.g"], eps)
        if kind == "m":
            y, said = mamba_mixer(u, p, cfg, qz)
        else:
            y, said = attention(u, p, cfg, qz), None
        x = x + r * y
        return x + r * ffn(rms_norm(x, p["norm2.g"], eps), p, cfg, qz), said

    return jax.lax.map(row, x)


def _of_kind(params, kind, i):
    prefix = f"layers.{kind}."
    return {n[len(prefix):]: a[i] for n, a in params.items()
            if n.startswith(prefix)}


def forward(params, ids, cfg, qz=lambda a: a):
    """-> (the normed final state [rows, seq, H], the ``mamba`` layers'
    readings [M layers, rows, 2])."""
    x = cfg["embedding_multiplier"] * params["tok"][ids]
    readings = []
    for kind, i in layers_of(cfg):
        # one layer live at a time in the backward pass
        x, said = jax.checkpoint(
            lambda x, p, kind=kind: layer(kind, x, p, cfg, qz))(
                x, _of_kind(params, kind, i))
        if kind == "m":
            readings.append(said)
    h = rms_norm(x, params["norm_f.g"], cfg["rms_norm_eps"])
    return h, jnp.stack(readings)


def logits_of(h, rows, cfg, qz=lambda a: a):
    """The logits of the normed state ``h`` over ``rows`` of the tied
    matrix [rows held, H]: ``h rows^T / logits_scaling``."""
    return (qz(h) @ qz(rows).T).astype(jnp.float32) / cfg["logits_scaling"]


def loss(params, ids, labels, cfg, variant, qz=lambda a: a):
    """The mean cross-entropy of ``ids`` [rows, seq] against ``labels``
    over all positions, the softmax over the held vocabulary rows."""
    h, _ = forward(params, ids, cfg, qz)
    logp = jax.nn.log_softmax(logits_of(h, params["tok"], cfg, qz), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
