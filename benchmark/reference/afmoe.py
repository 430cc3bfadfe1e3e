"""Plain reference of the training step of Trinity-Mini (``model_type:
afmoe``): float32 jax.numpy, no kernels, nothing imported from the
program.

The model (huggingface.co/arcee-ai/Trinity-Mini, Arcee, 26B-A3B) as its
``config.json`` gives the sizes and the public ``modeling_afmoe.py`` of
``transformers`` the order.  x is the float32 residual stream of one row,
T positions; every layer has four RMSNorms with plain gains (the sandwich
form: each branch normed on its way in and on its way out):

    h_0      = sqrt(2048) * Emb(ids)                       (mup_enabled)
    a        = Attn_kind(RMSNorm(x; g1));  x <- x + RMSNorm(a; g2)
    m        = FF(RMSNorm(x; g3));         x <- x + RMSNorm(m; g4)

    Attn_kind(u):
      q = u Wq [T, 32, 128];  k = u Wk, v = u Wv [T, 4, 128];  g = u Wg [T, 4096]
      q <- RMSNorm(q; gq), k <- RMSNorm(k; gk)     per head, over its 128
      kind "sliding_attention":  q, k <- RoPE(theta 1e4, rotate-half, all
          128 dims);  query t sees keys s with t - 2048 < s <= t
      kind "full_attention":     NO position signal;  every s <= t
      P[t,h,s] = softmax over the visible s of 128^-1/2 q[t,h].k[s,h // 8]
      o        = concat_h(sum_s P[t,h,s] v[s,h // 8]) * sigmoid(g)
      Attn     = o Wo

    FF of a leading dense layer:  (silu(b Wg) * (b Wu)) Wd      width 6144
                                  ([Wg ; Wu] is ONE leaf, ``ff_in.w``)
    FF of the others:
      s        = sigmoid(float32(b) Wr) over all 128
      Top_t    = the 8 largest of s + beta      (ties: the lower index)
      gate[t,e] = 2.826 s[t,e] / (sum_{e' in Top_t} s[t,e'] + 1e-20)
      FF       = SwiGLU_shared(b) + sum_{e in Top_t, held} gate[t,e] SwiGLU_e(b)
                                                      both 1024 wide

    loss     = mean_t CE(RMSNorm(x_L; gf) Wh, labels[t])

beta (the expert bias of the public code) is a leaf that only ``top_k``
reads: its gradient is zero by the mathematics, and the rule that moves
it by the experts' load is not run.  ``load_balance_coeff`` is not read:
no auxiliary loss.

**One chip's share.**  The configuration holds ``held_experts`` of the
128 routed experts of every expert layer and a slice of the vocabulary
(benchmark/configs/trinity_mini.json: 8 chips share each layer).  The
router spans all experts and a token's gates are normalised over its 8
whatever is held; what the absent experts would add is left out, and the
partial sum is what the layer's last norm (g4) reads; the shared expert
is computed here as on every member of the group.  ``held`` may be
handed in to compute another chip's share (`feed_forward`; the tests add
the shares up and count the shared expert once).  **The routers are held
still on one chip's share** (``train_router`` false: the gates are
constants of the backward pass), joyai_llm_flash's reason, in the
configuration file's ``assumed``.

**Departures from the published description**, each in the
configuration file's ``assumed``: the share above; seeded weights at the
draw below; rows of 16,384 positions from 0 with no document boundary;
the loss over ids and labels drawn independently.

Weights are ``[in, out]``.  ``qz`` is applied to every matmul operand:
the identity here, a quantiser in the control (benchmark/check.py).
Attention runs ``QUERY_ROWS`` queries at a time (their q, scores, gate
and output projection), a window layer against the ``sliding_window +
QUERY_ROWS`` keys its block of queries can see and a full layer against
all of them; the feed-forward parts (the routed
experts one at a time) and the logits over ``TOKEN_ROWS`` tokens at a
time (they are per token, so the blocks change nothing); each replayed in
the backward pass: 32
heads of 16,384 x 16,384 scores never exist at once, and the float32 step
fits one chip beside its state.
"""
import jax
import jax.numpy as jnp

QUERY_ROWS = 128    # queries whose scores are live at a time
TOKEN_ROWS = 2048   # tokens whose feed-forward states or logits are live at a time

# THE DRAW.  Every leaf is ``base + 0.02 * normal`` (benchmark/weights.py).
# The gain of the norm on attention's way OUT (g2) is drawn around 0.0625,
# not around 1: the sandwich form hands attention's branch to the stream
# at exactly that gain, whatever the branch holds, and at this draw
# (scores of deviation 1 over thousands of keys) what it holds is for a
# good part a running mean of the values, alike for the queries of a row.
# Around 1 that common vector is a large part of the stream's power after
# the first layer, every router after it reads it as a bias an expert, and
# the held experts' load follows the seed: 2,380 to 6,725 held assignments
# a layer where 4,096 are expected, the fullest held expert at 2.7 to 5.3
# times the mean (one row of 4,096 at these widths, two seeds, the CPU).
# Around 0.25: 3,908 to 4,086 and 1.5 to 1.8; around 0.0625 and around
# 0.02 alike: within 5.6 % and 1.14 to 1.29, what 256 assignments an
# expert scatter by themselves.  0.0625 is the largest of the four that
# spreads the load, and leaves attention's branch 1/250 of the stream's
# power where 0.02 would leave 1/2,500 (the row of 16,384: the
# configuration file's ``assumed.init``; PERF.md section 6, PR 30, 32 and
# 39 on what a gain around 1 did to three other cells).  Every other gain
# is around 1.
ATTN_OUT_GAIN = 0.0625
# The selection bias's published initial value is one number for all
# experts (zero).  N(0, 0.02) on sigmoid scores moves an expert's share by
# tens of percent, alike for every row of a seed; around 32 the harness's
# rounding of every value to bfloat16 (spacing 0.25 there) swallows the
# 0.02, every expert has the same bias and a common bias moves no
# selection (nemotron_h's way; in float32, the tests', the 0.02 stays and
# the selection reads it).
SELECTION_BIAS_BASE = 32.0


def sizes(cfg):
    return dict(H=cfg["hidden_size"], A=cfg["num_attention_heads"],
                KV=cfg["num_key_value_heads"], D=cfg["head_dim"],
                FD=cfg["intermediate_size"], F=cfg["moe_intermediate_size"],
                E=cfg["published"]["num_experts"], held=cfg["num_experts"],
                K=cfg["num_experts_per_tok"], dense=cfg["num_dense_layers"],
                L=cfg["num_hidden_layers"], V=cfg["vocab_size"],
                W=cfg["sliding_window"])


KINDS = ("sliding_attention", "full_attention")


def held_ids(cfg):
    """The expert ids this chip holds: ``num_experts`` of them from
    ``held_experts.first``."""
    first = cfg["held_experts"]["first"]
    return tuple(range(first, first + cfg["num_experts"]))


def layer_kinds(cfg):
    """("sliding_attention" | "full_attention") a layer, the dense layers
    first."""
    kinds = tuple(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError("reference/afmoe.py: layer_types names "
                         f"{len(kinds)} layers of kinds {sorted(set(kinds))}")
    return kinds


def _attention_shapes(z):
    H, A, KV, D = z["H"], z["A"], z["KV"], z["D"]
    return {
        "norm1.g": ((H,), 1.0), "norm2.g": ((H,), ATTN_OUT_GAIN),
        "norm3.g": ((H,), 1.0), "norm4.g": ((H,), 1.0),
        "q.w": ((H, A * D), 0.0), "k.w": ((H, KV * D), 0.0),
        "v.w": ((H, KV * D), 0.0), "gate.w": ((H, A * D), 0.0),
        "q_norm.g": ((D,), 1.0), "k_norm.g": ((D,), 1.0),
        "o.w": ((A * D, H), 0.0),
    }


def _expert_shapes(z):
    H, F, held = z["H"], z["F"], z["held"]
    return {
        "router.w": ((H, z["E"]), 0.0),
        "router.bias": ((z["E"],), SELECTION_BIAS_BASE),
        "experts.gate": ((held, H, F), 0.0),
        "experts.up": ((held, H, F), 0.0),
        "experts.down": ((held, F, H), 0.0),
        "shared.gate.w": ((H, F), 0.0), "shared.up.w": ((H, F), 0.0),
        "shared.down.w": ((F, H), 0.0),
    }


def param_shapes(cfg, variant):
    """name -> (shape, base): a leaf is ``base + 0.02 * normal``.  The
    expert layers' leaves (``layers.*``) are stacked: axis 0 is the layer,
    whatever its kind (a window layer and a full layer have the same
    leaves).  The leading dense layer (``dense.*``) has leaves of its own.
    The experts' leaves hold the held experts only, in the order of their
    ids."""
    z = sizes(cfg)
    if z["dense"] != 1 or cfg["num_shared_experts"] != 1:
        raise ValueError("reference/afmoe.py computes one leading dense "
                         "layer and one shared expert")
    if cfg["score_func"] != "sigmoid" or cfg["n_group"] != 1 \
            or cfg["topk_group"] != 1:
        raise ValueError("reference/afmoe.py computes an ungrouped sigmoid "
                         "router")
    H = z["H"]
    out = {"tok": ((z["V"], H), 0.0), "norm_f.g": ((H,), 1.0),
           "head.w": ((H, z["V"]), 0.0)}
    for n, (shape, base) in _attention_shapes(z).items():
        out["dense." + n] = (shape, base)
        out["layers." + n] = ((z["L"] - 1,) + shape, base)
    # the gate's half first, then the value's: one leaf, as one fused
    # in-projection holds them
    out["dense.ff_in.w"] = ((H, 2 * z["FD"]), 0.0)
    out["dense.ff_down.w"] = ((z["FD"], H), 0.0)
    for n, (shape, base) in _expert_shapes(z).items():
        out["layers." + n] = ((z["L"] - 1,) + shape, base)
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


def attention(u, p, cfg, kind, qz):
    """One row.  u [T, H] (normed) -> attention's branch [T, H].  The
    keys and values of the whole row first (4 heads: small); then a block
    of ``QUERY_ROWS`` queries at a time its own q, scores, gate and output
    projection, so that nothing 4096 wide exists for the whole row."""
    z = sizes(cfg)
    T, A, KV, D = u.shape[0], z["A"], z["KV"], z["D"]
    eps, window = cfg["rms_norm_eps"], kind == "sliding_attention"
    k = rms_norm((qz(u) @ qz(p["k.w"])).reshape(T, KV, D),
                 p["k_norm.g"], eps)
    v = (qz(u) @ qz(p["v.w"])).reshape(T, KV, D)
    if window:                  # positions on the window layers alone
        k = rope(k, cfg["rope_theta"])
    rows = QUERY_ROWS if T % QUERY_ROWS == 0 else T
    # the keys a block of queries can see: every one up to its last
    # query, or on a window layer the last (sliding_window - 1) before
    # its first query as well (fewer near the row's start)
    span = min(T, z["W"] - 1 + rows) if window else T

    @jax.checkpoint
    def block(args):
        ub, first = args
        q = rms_norm((qz(ub) @ qz(p["q.w"])).reshape(rows, KV, A // KV, D),
                     p["q_norm.g"], eps)
        if window:
            q = rope(q, cfg["rope_theta"], first)
        start = jnp.clip(first + rows - span, 0, T - span)
        kb = jax.lax.dynamic_slice_in_dim(k, start, span)
        vb = jax.lax.dynamic_slice_in_dim(v, start, span)
        t = (first + jnp.arange(rows))[:, None]
        s_pos = (start + jnp.arange(span))[None, :]
        visible = s_pos <= t
        if window:
            visible &= s_pos > t - z["W"]
        # added, not selected: a select's backward keeps its predicate at
        # the scores' shape for every block of queries (8 GB a full layer)
        hidden = jnp.where(visible, 0.0, -jnp.inf).astype(u.dtype)
        s = jnp.einsum("tkgd,skd->kgts", qz(q), qz(kb)) * D ** -0.5
        P = jax.nn.softmax(s + hidden, axis=-1)
        o = jnp.einsum("kgts,skd->tkgd", qz(P), qz(vb)).reshape(rows, A * D)
        o = o * jax.nn.sigmoid(qz(ub) @ qz(p["gate.w"]))
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
    """b [T, H] -> (gates [T, 8] float32, the experts chosen [T, 8] over
    all E).  The selection reads score + bias, the gates the scores."""
    s = jax.nn.sigmoid((qz(b) @ qz(p["router.w"])).astype(jnp.float32))
    _, idx = jax.lax.top_k(s + p["router.bias"].astype(jnp.float32),
                           cfg["num_experts_per_tok"])
    gate = jnp.take_along_axis(s, idx, -1)
    if cfg["route_norm"]:
        gate = gate / (jnp.sum(gate, -1, keepdims=True) + 1e-20)
    if not cfg["train_router"]:
        gate = jax.lax.stop_gradient(gate)
    return gate * cfg["route_scale"], idx


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


def feed_forward(b, p, cfg, held, qz=lambda a: a, with_shared=True):
    """An expert layer's FF over one row, b [T, H] normed -> (the held
    experts' part plus, ``with_shared``, the shared expert's; the experts
    each token chose [T, 8]).  The members' parts, the shared expert
    counted once, add up to the uncut layer's."""
    y, chosen = routed(b, p, cfg, held, qz)
    if with_shared:
        y = y + swiglu(b, p["shared.gate.w"], p["shared.up.w"],
                       p["shared.down.w"], qz)
    return y, chosen


def dense_ff(b, p, qz):
    """The leading dense layer's SwiGLU, b [T, H] normed; ``ff_in.w`` holds
    the gate's half, then the value's."""
    ab = qz(b) @ qz(p["ff_in.w"])
    n = ab.shape[-1] // 2
    return qz(jax.nn.silu(ab[:, :n]) * ab[:, n:]) @ qz(p["ff_down.w"])


def layer(x, p, cfg, kind, held, qz):
    """Rows x [B, T, H] through one layer (dense where ``p`` holds
    ``ff_in.w``) -> (x, the experts every token chose [B, T, 8] or
    None)."""
    eps = cfg["rms_norm_eps"]

    def row(x):
        a = attention(rms_norm(x, p["norm1.g"], eps), p, cfg, kind, qz)
        x = x + rms_norm(a, p["norm2.g"], eps)
        b = rms_norm(x, p["norm3.g"], eps)
        if "ff_in.w" in p:
            m, chosen = _token_blocks(lambda b: dense_ff(b, p, qz), b), None
        else:
            m, chosen = _token_blocks(
                lambda b: feed_forward(b, p, cfg, held, qz), b)
        return x + rms_norm(m, p["norm4.g"], eps), chosen
    return jax.lax.map(row, x)


def _under(params, prefix):
    return {n[len(prefix):]: a for n, a in params.items()
            if n.startswith(prefix)}


def forward(params, ids, cfg, qz=lambda a: a, held=None):
    """-> (the normed final state [rows, seq, H], the experts every token
    chose [L - 1, rows, seq, 8])."""
    held = held_ids(cfg) if held is None else held
    kinds = layer_kinds(cfg)
    x = params["tok"][ids]
    if cfg["mup_enabled"]:
        x = x * cfg["hidden_size"] ** 0.5
    x, _ = jax.checkpoint(
        lambda x, p: layer(x, p, cfg, kinds[0], held, qz))(
            x, _under(params, "dense."))
    stacked, chosen = _under(params, "layers."), []
    # the kinds differ from layer to layer, so the stack is walked, not
    # scanned (a switch inside a scan keeps both branches' residuals):
    # one layer live at a time in the backward pass
    for i, kind in enumerate(kinds[1:]):
        x, c = jax.checkpoint(
            lambda x, p, kind=kind: layer(x, p, cfg, kind, held, qz))(
                x, {n: a[i] for n, a in stacked.items()})
        chosen.append(c)
    return (rms_norm(x, params["norm_f.g"], cfg["rms_norm_eps"]),
            jnp.stack(chosen))


def loss(params, ids, labels, cfg, variant, qz=lambda a: a, held=None):
    """Mean cross-entropy of ``ids`` [rows, seq] against ``labels`` over
    the held vocabulary rows."""
    z, _ = forward(params, ids, cfg, qz, held)

    def nll(args):
        zb, lb = args
        logits = (qz(zb) @ qz(params["head.w"])).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=-1)[:, 0]

    return jnp.mean(_token_blocks(
        nll, (z.reshape(-1, z.shape[-1]), labels.reshape(-1))))
