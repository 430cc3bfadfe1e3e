"""The parts of a Mamba-2 state-space mixer (Dao & Gu 2024): a depthwise
causal convolution, the selective state-space scan in its chunked
("SSD") form with a hand-written backward, and the gated group norm; and,
over the same convolution, the gated short convolution that is a
convolution mixer's whole operator (`gated_short_conv`: LFM2's ``conv``
layers).

The recurrence, for a head h of group g(h) = h // (H / G) with a state
``S_t`` [P, N] that starts at zero at the start of every row:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T      y_t = S_t C_t + D x_t

Run as written a row of T positions is T dependent steps.  ``ssd_scan``
computes it in chunks of ``chunk`` positions.  With ``cs`` the running
sum of ``dt A`` inside a chunk (float32: the decays are ``exp`` of
differences of it),

    inside a chunk   y_t += sum_{s <= t} exp(cs_t - cs_s) dt_s (C_t . B_s) x_s
    a chunk's state  S' = exp(cs_last) S + sum_s exp(cs_last - cs_s) dt_s x_s B_s^T
    from the state   y_t += exp(cs_t) S C_t        (S as the chunk starts)

so the work is matrix products over [chunk, chunk], [chunk, N] and
[chunk, P] tiles (operands in x's type, float32 accumulation, the decay
factors applied in float32) and one short loop over the T / chunk chunks
that hands the state [H, P, N] on.  No loop runs over positions and no
[T, T] matrix is formed.  The backward pass recomputes the decays, walks
the chunks the other way for the state's gradient and keeps nothing of
the forward but its arguments and the states the chunks start from
(``custom_vjp``: autodiff of the chunked form would keep every
[chunk, chunk] decay matrix, 537 MB a layer in float32 at
[2, 8192, 64 heads]).  Under ``parallel.recompute`` a replayed block
keeps nothing of the scan: with the chunk states kept across the replay
(268 MB a layer) the Nemotron cell's step read 632.36 ms against 625.18
and 1.30 GB more (PERF.md section 6, PR 39).

This is the XLA form: the CPU path, what the tests compare against and
what a shape outside the kernels' gate runs.  On a TPU ``F.ssd_scan``
takes the two Pallas kernels of ops/pallas/ssd_scan.py, which do the same
mathematics with a chunk's [chunk, chunk] matrices in VMEM (PERF.md
section 6, PR 40: the Nemotron cell's scan 94.1 -> 32.8 ms a step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["causal_conv1d", "gated_short_conv", "ssd_scan",
           "gated_group_rms_norm"]


def causal_conv1d(x, weight, bias=None, activation=None):
    """Depthwise causal convolution over time.  x [B, T, C]; ``weight``
    [K, C], tap K - 1 meets the position itself and tap 0 the one K - 1
    back (torch's ``conv1d`` weight [C, 1, K] transposed); ``bias`` [C].
    K shifted multiply-adds in float32, not a grouped convolution with C
    groups.  ``activation``: None or "silu".  The result has x's type."""
    K, T = weight.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    out = sum(padded[:, k:k + T].astype(jnp.float32) * w[k]
              for k in range(K))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    if activation == "silu":
        out = jax.nn.silu(out)
    elif activation is not None:
        raise ValueError(f"activation {activation!r}: None or 'silu'")
    return out.astype(x.dtype)


def gated_short_conv(bcz, weight, bias=None):
    """A gated short convolution (the token mixer of LFM2's ``conv``
    layers): ``[B ; C ; z] = bcz`` [batch, T, 3 H], thirds in that order;
    ``v = B * z``; ``c = causal_conv1d(v, weight)`` (``weight`` [K, H],
    tap K - 1 on the position itself, ``bias`` [H] or None, no
    activation); ``y = C * c`` [batch, T, H].  Two elementwise gates round
    K shifted multiply-adds, all in float32; the result has bcz's type.
    The state at the start of a row is zero."""
    H = weight.shape[1]
    f32 = jnp.float32
    B, C, z = (bcz[..., i * H:(i + 1) * H].astype(f32) for i in range(3))
    return (C * causal_conv1d(B * z, weight, bias)).astype(bcz.dtype)


def gated_group_rms_norm(y, z, weight, groups, epsilon=1e-5):
    """``RMSNorm_group(y * silu(z)) * weight``: the gate first, then the
    mean of squares over each of ``groups`` runs of the last axis, in
    float32; the result has the weight's type."""
    C = y.shape[-1]
    v = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    v = v.reshape(v.shape[:-1] + (groups, C // groups))
    v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), -1, keepdims=True)
                          + epsilon)
    return (v.reshape(y.shape) * weight.astype(jnp.float32)
            ).astype(weight.dtype)


# -- the chunked scan ---------------------------------------------------------

def _dot(spec, a, b):
    """An einsum with float32 accumulation; float32 operands at full
    precision (a TPU's default for them is one bfloat16 pass)."""
    prec = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    return jnp.einsum(spec, a, b, precision=prec,
                      preferred_element_type=jnp.float32)


def _chunked(x, dt, Bm, Cm, chunk):
    """Pad T to whole chunks (a padded position has dt = 0: it decays
    nothing and adds nothing) and split: x [B, c, Q, G, R, P], dt
    [B, c, Q, G, R], Bm / Cm [B, c, Q, G, N]."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    pad = -T % chunk
    if pad:
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                 (a.ndim - 2)) for a in (x, dt, Bm, Cm))
    c = (T + pad) // chunk
    return (x.reshape(B, c, chunk, G, H // G, P),
            dt.reshape(B, c, chunk, G, H // G),
            Bm.reshape(B, c, chunk, G, N), Cm.reshape(B, c, chunk, G, N))


def _decays(dt, A):
    """dt [B, c, Q, G, R] float32, A [G, R] -> (cs, the running sum of
    ``dt A`` inside each chunk; L [B, c, G, R, Q(t), Q(s)] =
    ``exp(cs_t - cs_s)`` for s <= t and 0 above the diagonal)."""
    cs = jnp.cumsum(dt * A, axis=2)
    rows = jnp.moveaxis(cs, 2, -1)                        # [B, c, G, R, Q]
    Q = rows.shape[-1]
    seen = jnp.tril(jnp.ones((Q, Q), bool))
    # the mask before the exp: above the diagonal the difference is
    # positive and may overflow
    L = jnp.exp(jnp.where(seen, rows[..., :, None] - rows[..., None, :],
                          -jnp.inf))
    return cs, L


def _to_end(cs):
    """``exp(cs_last - cs_s)``: what is left at the chunk's end of what a
    position put into the state, [B, c, Q, G, R]."""
    return jnp.exp(cs[:, :, -1:] - cs)


def _states(xs, dt, cs, Bm):
    """The state each chunk starts from, [B, c, G, R, P, N] float32.  The
    one loop: over chunks, a multiply-add of [B, H, P, N] a step."""
    w = _to_end(cs) * dt
    local = _dot("bcsgrp,bcsgn->bcgrpn",
                 (xs.astype(jnp.float32) * w[..., None]).astype(xs.dtype), Bm)

    def step(S, args):
        decay, add = args
        return decay[..., None, None] * S + add, S

    _, starts = jax.lax.scan(
        step, jnp.zeros_like(local[:, 0]),
        (jnp.moveaxis(jnp.exp(cs[:, :, -1]), 1, 0),
         jnp.moveaxis(local, 1, 0)))
    return jnp.moveaxis(starts, 0, 1)


def _inside(Cs, Bs, L, dts, dtype):
    """The masked matrix of a chunk, ``M = (C B^T) * L * dt_s`` in
    ``dtype``, with its factors ``C B^T`` [B, c, G, 1, Q, Q] and ``dt_s``
    [B, c, G, R, 1, Q]."""
    CB = _dot("bctgn,bcsgn->bcgts", Cs, Bs)[:, :, :, None]
    dt_s = jnp.moveaxis(dts, 2, -1)[..., None, :]
    return CB, dt_s, (CB * L * dt_s).astype(dtype)


def _forward(x, dt, A, Bm, Cm, D, chunk):
    B, T, H, P = x.shape
    G = Bm.shape[2]
    xs, dts, Bs, Cs = _chunked(x, dt, Bm, Cm, chunk)
    Ag, Dg = A.reshape(G, H // G), D.reshape(G, H // G)
    cs, L = _decays(dts, Ag)
    _, _, M = _inside(Cs, Bs, L, dts, x.dtype)
    y = _dot("bcgrts,bcsgrp->bctgrp", M, xs)
    starts = _states(xs, dts, cs, Bs)
    y = y + jnp.exp(cs)[..., None] * _dot(
        "bctgn,bcgrpn->bctgrp", Cs, starts.astype(x.dtype))
    y = y + Dg[..., None] * xs.astype(jnp.float32)
    return y.reshape(B, -1, H, P)[:, :T].astype(x.dtype), starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def ssd_scan(x, dt, A, Bm, Cm, D, chunk=128):
    """The scan of the header.  x [B, T, H, P]; ``dt`` [B, T, H] float32,
    positive; ``A`` [H] float32, negative; ``Bm`` and ``Cm`` [B, T, G, N]
    in x's type, head h reads group h // (H / G); ``D`` [H] float32.
    -> y [B, T, H, P] in x's type.  T need not be a multiple of
    ``chunk``."""
    return _forward(x, dt, A, Bm, Cm, D, chunk)[0]


def _ssd_fwd(x, dt, A, Bm, Cm, D, chunk):
    y, starts = _forward(x, dt, A, Bm, Cm, D, chunk)
    return y, (x, dt, A, Bm, Cm, D, starts)


def _ssd_bwd(chunk, res, dy):
    x, dt, A, Bm, Cm, D, starts = res
    B, T, H, P = x.shape
    G = Bm.shape[2]
    f32 = jnp.float32
    xs, dts, Bs, Cs = _chunked(x, dt, Bm, Cm, chunk)
    dys = _chunked(dy, dt, Bm, Cm, chunk)[0]
    Ag, Dg = A.reshape(G, H // G), D.reshape(G, H // G)
    cs, L = _decays(dts, Ag)
    x32, dy32 = xs.astype(f32), dys.astype(f32)
    dD = jnp.sum(dy32 * x32, (0, 1, 2, 5)).reshape(H)
    dx = Dg[..., None] * dy32

    # -- what the chunk's starting state gave: y_t += exp(cs_t) S C_t
    S16 = starts.astype(x.dtype)
    ecs = jnp.exp(cs)
    dyw = (dy32 * ecs[..., None]).astype(x.dtype)
    dS_y = _dot("bctgrp,bctgn->bcgrpn", dyw, Cs)
    dC = _dot("bctgrp,bcgrpn->bctgn", dyw, S16)
    from_state = ecs[..., None] * _dot("bctgn,bcgrpn->bctgrp", Cs, S16)
    dcs = jnp.sum(dy32 * from_state, -1)                  # [B, c, Q, G, R]

    # -- the states, the other way: gS(c) = dS_y(c) + exp(cs_last(c)) gS(c+1)
    last = cs[:, :, -1]
    e_last = jnp.exp(last)

    def step(g, args):
        decay, own = args
        return own + decay[..., None, None] * g, g

    _, after = jax.lax.scan(
        step, jnp.zeros_like(dS_y[:, 0]),
        (jnp.moveaxis(e_last, 1, 0), jnp.moveaxis(dS_y, 1, 0)), reverse=True)
    after = jnp.moveaxis(after, 0, 1)          # gradient to the NEXT start
    d_last = e_last * jnp.sum(after * starts, (-1, -2))

    # -- a chunk's own contribution to the next start
    to_end = _to_end(cs)
    w = to_end * dts
    RB = _dot("bcgrpn,bcsgn->bcsgrp", after.astype(x.dtype), Bs)
    dB = _dot("bcsgrp,bcgrpn->bcsgn", (x32 * w[..., None]).astype(x.dtype),
              after.astype(x.dtype))
    dx = dx + w[..., None] * RB
    dw = jnp.sum(x32 * RB, -1)
    ddt = dw * to_end
    q = dw * w
    dcs = dcs - q
    d_last = d_last + jnp.sum(q, 2)

    # -- inside the chunk: y = M x, M = CB * L * dt_s
    CB, dt_s, M = _inside(Cs, Bs, L, dts, x.dtype)
    dx = dx + _dot("bcgrts,bctgrp->bcsgrp", M, dys)
    E = _dot("bctgrp,bcsgrp->bcgrts", dys, xs) * L        # dM * L
    dCB = jnp.sum(E * dt_s, 3).astype(x.dtype)            # over a group's heads
    dC = dC + _dot("bcgts,bcsgn->bctgn", dCB, Bs)
    dB = dB + _dot("bcgts,bctgn->bcsgn", dCB, Cs)
    U = E * CB                                            # dM * L * CB
    ddt = ddt + jnp.moveaxis(jnp.sum(U, -2), -1, 2)
    W = U * dt_s                                          # dM * M
    dcs = dcs + jnp.moveaxis(jnp.sum(W, -1) - jnp.sum(W, -2), -1, 2)

    # -- cs is a running sum of dt A inside the chunk
    dcs = dcs.at[:, :, -1].add(d_last)
    da = jnp.flip(jnp.cumsum(jnp.flip(dcs, 2), 2), 2)
    ddt = ddt + da * Ag
    dA = jnp.sum(da * dts, (0, 1, 2)).reshape(H)

    def rows(a, like):
        return a.reshape((B, -1) + like.shape[2:])[:, :T].astype(like.dtype)

    return (rows(dx, x), rows(ddt, dt), dA.astype(A.dtype), rows(dB, Bm),
            rows(dC, Cm), dD.astype(D.dtype))


ssd_scan.defvjp(_ssd_fwd, _ssd_bwd)
