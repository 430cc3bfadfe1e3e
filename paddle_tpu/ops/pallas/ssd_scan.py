"""The chunked state-space scan of ops/ssm.py as two Pallas TPU kernels,
``ssd_fwd`` and ``ssd_bwd``: a chunk's [Q, Q] matrices (``C B^T``, the
decays ``L``, ``M`` and in the backward ``E``, ``U``, ``W``) live in VMEM
and never reach HBM.  The mathematics is ops/ssm.py's, whose header
states it, term for term: operands in x's type, float32 accumulation in
every product, the decays in float32 with the mask before the ``exp``,
``M`` and ``dCB`` cast to x's type where the XLA form casts them.

Both kernels walk the grid (batch, head block, chunk) with the chunk axis
sequential, and take x [B, T, H*P] and B, C [B, T, G*N] as they lie.  A
head block is the R heads one step takes (``_heads_a_step``): a whole
group of 8 or 16 (Nemotron's 8 groups of 8 heads), else 16 or 8 heads of a
larger group, which is walked in blocks (Mamba-2's published default, ONE
group for all heads, as granite-4.0-h's 64, is 4 blocks of 16; the
backward's three per-position sums a head share one [Q, 128] transpose a
step, and 16 heads are what its VMEM holds).  A block is one R*P-lane
block of x and reads its group's one N-lane block of B and C (the index
map sends a group's blocks to the same one), so nothing is transposed or
copied around a call but ``dt`` ([B, T, H] float32, 1 / P of x), which
comes in as [B, H / R, R, T]: a block's R rows of Q positions are one
float32 tile, on which the running sum ``cs`` and the weights to the
chunk's end are a few operations.  One [128, Q] transpose a step turns
those rows into the columns the [Q, Q] tiles and x's rows are scaled by; in the backward a
second one brings the per-position sums back to rows.  Heads of 64 lanes
go two to a slab of 128 (``_Slabs``): every elementwise pass and store is
over whole vregs, and a head's operand of a product is its slab with the
neighbour's lanes zeroed.

- ``ssd_fwd`` carries a block's state, transposed ([N, R*P] float32: 256
  KB at 8 heads of 64 on a state of 128, 512 KB at 16), in a VMEM scratch
  along the chunk axis: a step adds ``exp(cs_t) C_t S`` and ``D x`` to ``y = M x``,
  then moves the state on by the chunk's own ``B^T (x w)``, one product a
  block.  Where a backward will follow (the forward rule) it also writes
  the state each chunk starts from, which is all the backward keeps of
  the forward.
- ``ssd_bwd`` walks the chunks the other way with the state's gradient in
  the scratch, recomputes ``cs``, ``C B^T``, ``L`` and ``M``, and emits dx,
  ddt (``dcs`` folded through the running sum), dB, dC, and dA and dD
  summed over a (batch, block)'s chunks; the wrapper sums the rest.

What is summed where.  Over a block's heads, in the kernel: ``dCB`` (the
gradient to ``C B^T``, float32, cast to x's type once) and through it the
block's dB and dC.  Over a group's blocks, in the wrapper: dB and dC, which
the kernel writes a block in float32 ([B, T, H / R, N]; with a group a
step they ARE the group's and come out in B's type, as the XLA form gives
them); each block recomputes the group's ``C B^T`` (2 Q^2 N = 4.2 MFLOP a
step, nothing beside the vector work that bounds these kernels) and reads
B and C again.  Over the batch and the chunks: dA and dD, as before.  A
group of 8 heads compiles to the kernels it compiled to before head
blocks existed (the same jaxpr at the Nemotron cell's shape, PR 43).

With the state pass between two kernel passes in XLA instead, every
chunk's own [R*P, N] float32 contribution would travel to HBM and back
(268 MB a call at [2, 8192, 64 heads of 64, state 128], against the 340
MB of the arguments and the result): the scratch is the choice.  On a
v5e both kernels are bound by the vector unit's issue, not by HBM or the
MXU: with every block held still (no DMA after the first step) they take
the same time (PERF.md section 6, PR 40).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...observability import scopes
from .support import (count_kernel_selection, dot as _dot, dtype_ok,
                      interpret_mode as _interpret, pltpu)

_Q = 128                 # the chunk: one lane tile of positions
_LANES = 128
_NT = ((1,), (1,))       # a . b^T
_NN = ((1,), (0,))
_TN = ((0,), (0,))       # a^T . b


def _heads_a_step(R):
    """Heads of a group of R (a multiple of 8) that one grid step takes:
    16 where that divides the group, else 8.  At [1, 8192, 64 heads of
    64] in one group on a v5e a layer's forward and forward + backward
    read 1.24 / 3.38 ms in blocks of 8 and 0.95 / 2.65 in blocks of 16
    (what a step does once, ``C B^T``, the two transposes, the masks, is
    shared by twice the heads); 32 heads a step want more than Mosaic's
    default scoped VMEM in the backward, which is why a group of 32 or 40
    is walked in blocks too (PERF.md section 6, PR 43)."""
    return 16 if R % 16 == 0 else 8


def ssd_scan_supported(x_shape, b_shape, dtype, chunk) -> bool:
    """Shapes the kernels take: x [B, T, H, P], B / C [B, T, G, N].  The
    chunk is one lane tile; a state fills whole lane tiles and a head a
    half tile or whole ones; a group's heads fill sublane tiles, taken
    a block of 8 or 16 a grid step (``_heads_a_step``)."""
    if len(x_shape) != 4 or len(b_shape) != 4 or not dtype_ok(dtype):
        return False
    H, P = x_shape[2:]
    G, N = b_shape[2:]
    if chunk != _Q or H % G:
        return False
    R = H // G
    return N % _LANES == 0 and (P == 64 or P % _LANES == 0) and R % 8 == 0


def _running_sum(a, reverse=False):
    """The running sum along the lanes of ``a`` [R, Q] (from the other end
    with ``reverse``): log2(Q) shifted adds."""
    n = a.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    k = 1
    while k < n:
        if reverse:
            moved, inside = pltpu.roll(a, n - k, 1), lane < n - k
        else:
            moved, inside = pltpu.roll(a, k, 1), lane >= k
        a = a + jnp.where(inside, moved, 0.0)
        k *= 2
    return a


def _columns(*rows):
    """Rows [R, Q] each -> [Q, 128] whose lane ``i * R + r`` is row r of
    the i-th: what scales a tile's rows.  One square transpose."""
    R, Q = rows[0].shape
    fill = jnp.zeros((_LANES - len(rows) * R, Q), jnp.float32)
    return jnp.concatenate(rows + (fill,), 0).T


def _set(acc, axis, at, value):
    """``acc`` with ``value`` (broadcast along ``axis``) at index ``at`` of
    that axis."""
    index = jax.lax.broadcasted_iota(jnp.int32, acc.shape, axis)
    return jnp.where(index == at, value, acc)


def _chunk_decays(dt_ref, a_ref):
    """-> (dt, cs, cs at the chunk's end [R, 1], what is left at the
    chunk's end of what a position put in, that times dt), rows [R, Q]."""
    dtT = dt_ref[0, 0]
    csT = _running_sum(dtT * a_ref[0])
    last = csT[:, _Q - 1:]
    to_end = jnp.exp(last - csT)
    return dtT, csT, last, to_end, to_end * dtT


def _decay_tile(seen, cs_t, cs_s):
    # the mask before the exp: above the diagonal the difference is
    # positive and may overflow
    return jnp.exp(jnp.where(seen, cs_t - cs_s, -jnp.inf))


def _seen():
    t = jax.lax.broadcasted_iota(jnp.int32, (_Q, _Q), 0)
    return t >= jax.lax.broadcasted_iota(jnp.int32, (_Q, _Q), 1)


class _Slabs:
    """A block's R heads of P lanes as slabs of whole lane tiles: a head
    of 64 shares its slab with its neighbour, so that every elementwise
    pass and every store is over full vregs; a head's operand of a product
    is the slab with the neighbour's lanes zeroed."""

    def __init__(self, R, P):
        self.R, self.P = R, P
        self.width = max(P, _LANES)
        self.heads = self.width // P           # heads a slab
        lane = jax.lax.broadcasted_iota(jnp.int32, (_Q, self.width), 1)
        self.own = [None if self.heads == 1 else
                    (lane >= i * P) & (lane < (i + 1) * P)
                    for i in range(self.heads)]
        # [R, R*P]: lane l belongs to head (sublane) r
        lane = jax.lax.broadcasted_iota(jnp.int32, (R, R * P), 1)
        first = jax.lax.broadcasted_iota(jnp.int32, (R, R * P), 0) * P
        self.of_head = (lane >= first) & (lane < first + P)

    def __iter__(self):
        """(the slab's lanes, its heads)"""
        for j in range(self.R // self.heads):
            yield (slice(j * self.width, (j + 1) * self.width),
                   range(j * self.heads, (j + 1) * self.heads))

    def only(self, i, slab):
        """The slab with the lanes of its i-th head alone."""
        mask = self.own[i % self.heads]
        return slab if mask is None else jnp.where(mask, slab, 0)

    def columns(self, cols, first, heads):
        """Lanes ``first + r`` of ``cols`` [Q, 128], a head each, along the
        slab's lanes: [Q, width]."""
        out = None
        for r in heads:
            col = cols[:, first + r:first + r + 1]
            mask = self.own[r % self.heads]
            out = (jnp.broadcast_to(col, (_Q, self.width)) if out is None
                   else jnp.where(mask, col, out))
        return out

    def along_lanes(self, column):
        """A value a head [R, 1] -> [1, R*P], on each of the head's lanes."""
        return jnp.sum(jnp.where(self.of_head, column, 0.0), 0,
                       keepdims=True)

    def a_head(self, row):
        """[1, R*P] -> [R, 1]: the sum over each head's lanes."""
        return jnp.sum(jnp.where(self.of_head, row, 0.0), 1, keepdims=True)


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, *rest,
                R, P):
    state_ref = rest[-1]               # the state, transposed: [N, R*P]
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    x, Bm, Cm = x_ref[0], b_ref[0], c_ref[0]
    dtT, csT, last, _, wT = _chunk_decays(dt_ref, a_ref)
    cols = _columns(csT, wT)
    S = state_ref[...]
    if len(rest) == 2:                 # the forward rule: a backward follows
        rest[0][0, 0, 0] = S
    CB = _dot(Cm, Bm, _NT)
    from_state = _dot(Cm, S.astype(x.dtype), _NN)          # C_t S, [Q, R*P]
    x32 = x.astype(f32)
    skipped = d_ref[0] * x32                               # D x
    seen = _seen()
    slabs = _Slabs(R, P)
    xw = []
    for at, heads in slabs:
        y = (jnp.exp(slabs.columns(cols, 0, heads)) * from_state[:, at]
             + skipped[:, at])
        for r in heads:
            L = _decay_tile(seen, cols[:, r:r + 1], csT[r:r + 1])
            M = (CB * L * dtT[r:r + 1]).astype(x.dtype)
            y = y + _dot(M, slabs.only(r, x[:, at]), _NN)
        y_ref[0, :, at] = y.astype(y_ref.dtype)
        xw.append((x32[:, at] * slabs.columns(cols, R, heads)
                   ).astype(x.dtype))
    # the chunk's own (x w)^T B, transposed as the state is
    own = _dot(Bm, jnp.concatenate(xw, 1), _TN)            # [N, R*P]
    state_ref[...] = slabs.along_lanes(jnp.exp(last)) * S + own


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, dy_ref,
                starts_ref, dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dd_ref,
                grad_ref, *, R, P):
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        grad_ref[...] = jnp.zeros_like(grad_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    x, Bm, Cm, dy = x_ref[0], b_ref[0], c_ref[0], dy_ref[0]
    dtype = x.dtype
    dtT, csT, last, to_endT, wT = _chunk_decays(dt_ref, a_ref)
    e_last = jnp.exp(last)
    cols = _columns(csT, wT)
    S = starts_ref[0, 0, 0]            # [N, R*P], as ssd_fwd keeps it
    after = grad_ref[...]              # the gradient to the NEXT start
    S16, after16 = S.astype(dtype), after.astype(dtype)
    CB = _dot(Cm, Bm, _NT)
    from_state = _dot(Cm, S16, _NN)                        # C_t S, [Q, R*P]
    RB = _dot(Bm, after16, _NN)                            # B_s gS', [Q, R*P]
    x32, dy32 = x.astype(f32), dy.astype(f32)
    dd_ref[0, 0] += jnp.sum(dy32 * x32, 0, keepdims=True)
    skipped = d_ref[0] * dy32                              # D dy
    seen = _seen()
    slabs = _Slabs(R, P)
    dCB = jnp.zeros((_Q, _Q), f32)
    sums = jnp.zeros((_Q, _LANES), f32)    # per-position sums, a lane a head
    colU = jnp.zeros((R, _Q), f32)
    dyw, xw = [], []
    for at, heads in slabs:
        w = slabs.columns(cols, R, heads)
        dyw.append((dy32[:, at] * jnp.exp(slabs.columns(cols, 0, heads))
                    ).astype(dtype))
        xw.append((x32[:, at] * w).astype(dtype))
        dx = skipped[:, at] + w * RB[:, at]
        # what exp(cs_t) S C_t gave, before its exp(cs_t); and dw's terms
        gave, took = dy32[:, at] * from_state[:, at], x32[:, at] * RB[:, at]
        for r in heads:
            dt_s = dtT[r:r + 1]
            L = _decay_tile(seen, cols[:, r:r + 1], csT[r:r + 1])
            K = CB * L
            M = (K * dt_s).astype(dtype)
            dyr = slabs.only(r, dy[:, at])
            dx = dx + _dot(M, dyr, _TN)
            dM = _dot(dyr, x[:, at], _NT)                  # [Q(t), Q(s)]
            F = dM * L * dt_s                              # E * dt_s
            dCB = dCB + F
            colU = _set(colU, 0, r, jnp.sum(dM * K, 0, keepdims=True))
            for i, part in enumerate((slabs.only(r, gave),
                                      slabs.only(r, took), F * CB)):
                sums = _set(sums, 1, i * R + r,
                            jnp.sum(part, 1, keepdims=True))
        dx_ref[0, :, at] = dx.astype(dx_ref.dtype)
    dyw, xw = jnp.concatenate(dyw, 1), jnp.concatenate(xw, 1)
    dCB = dCB.astype(dtype)            # summed over the group's heads
    dc_ref[0] = (_dot(dyw, S16, _NT) + _dot(dCB, Bm, _NN)
                 ).astype(dc_ref.dtype)
    db_ref[0] = (_dot(xw, after16, _NT) + _dot(dCB, Cm, _TN)
                 ).astype(db_ref.dtype)
    grad_ref[...] = slabs.along_lanes(e_last) * after + _dot(Cm, dyw, _TN)
    rows = sums.T
    from_S, dw, rowW = (rows[i * R:(i + 1) * R] for i in range(3))
    q = dw * wT
    dcs = jnp.exp(csT) * from_S - q + rowW - dtT * colU
    kept = slabs.a_head(jnp.sum(after * S, 0, keepdims=True))
    d_last = e_last * kept + jnp.sum(q, 1, keepdims=True)
    dcs = dcs + jnp.where(jax.lax.broadcasted_iota(
        jnp.int32, dcs.shape, 1) == _Q - 1, d_last, 0.0)
    # cs is a running sum of dt A inside the chunk
    da = _running_sum(dcs, reverse=True)
    ddt_ref[0, 0] = dw * to_endT + colU + da * a_ref[0]
    da_ref[0, 0] += da * dtT


def _operands(x, dt, A, Bm, Cm, D):
    """Pad T to whole chunks (a padded position has dt = 0: it decays
    nothing and adds nothing) and lay the arguments as the kernels' blocks
    take them, x, B and C as they are; what is a head's goes by head
    block: ``dt`` [B, H / R, R, T], ``A`` [H / R, R, 1], ``D`` along the
    lanes [H / R, 1, R*P], with R the heads a step takes."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    R = _heads_a_step(H // G)
    pad = -T % _Q
    if pad:
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                 (a.ndim - 2)) for a in (x, dt, Bm, Cm))
    Tp = T + pad
    f32 = jnp.float32
    return (x.reshape(B, Tp, H * P),
            jnp.swapaxes(dt.astype(f32), 1, 2).reshape(B, H // R, R, Tp),
            A.astype(f32).reshape(H // R, R, 1), Bm.reshape(B, Tp, G * N),
            Cm.reshape(B, Tp, G * N),
            jnp.repeat(D.astype(f32), P).reshape(H // R, 1, R * P))


def _specs(R, P, N, blocks, chunk_of):
    """Block specs on the grid (batch, head block, step) for x-like, dt,
    A, B and C, D, the states, and dB and dC; ``chunk_of`` maps a step to
    its chunk.  A group's ``blocks`` head blocks read the group's one
    block of B and C, and each writes a dB and dC of its own."""
    def cols(width, of=lambda g: g):
        return pl.BlockSpec((1, _Q, width),
                            lambda b, g, c: (b, chunk_of(c), of(g)))
    return (cols(R * P),
            pl.BlockSpec((1, 1, R, _Q),
                         lambda b, g, c: (b, g, 0, chunk_of(c))),
            pl.BlockSpec((1, R, 1), lambda b, g, c: (g, 0, 0)),
            # (``g // 1`` is the same map but not the same jaxpr: a group
            # a step keeps the map it had, so that its kernels stay the
            # ones on record, tests/test_chip_compile.py)
            cols(N) if blocks == 1 else cols(N, lambda g: g // blocks),
            pl.BlockSpec((1, 1, R * P), lambda b, g, c: (g, 0, 0)),
            pl.BlockSpec((1, 1, 1, N, R * P),
                         lambda b, g, c: (b, chunk_of(c), g, 0, 0)),
            cols(N))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _fwd(x, dt, A, Bm, Cm, D, with_starts):
    """-> (y [B, T, H, P] in x's type, the state each chunk starts from
    [B, c, H / R, N, R*P] float32 or None)."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    R = _heads_a_step(H // G)
    args = _operands(x, dt, A, Bm, Cm, D)
    Tp = args[0].shape[1]
    nc = Tp // _Q
    xs, dts, As, bs, Ds, states, _ = _specs(R, P, N, H // G // R,
                                            lambda c: c)
    out_specs = [xs]
    out_shape = [jax.ShapeDtypeStruct((B, Tp, H * P), x.dtype)]
    if with_starts:
        out_specs.append(states)
        out_shape.append(
            jax.ShapeDtypeStruct((B, nc, H // R, N, R * P), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, R=R, P=P),
        grid=(B, H // R, nc),
        in_specs=[xs, dts, As, bs, bs, Ds],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((N, R * P), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=_interpret(),
        name=scopes.SSD_FWD,
    )(*args)
    y = out[0].reshape(B, Tp, H, P)[:, :T]
    return y, (out[1] if with_starts else None)


def _bwd(x, dt, A, Bm, Cm, D, starts, dy):
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    R = _heads_a_step(H // G)
    blocks = H // G // R
    f32 = jnp.float32
    args = _operands(x, dt, A, Bm, Cm, D)
    Tp = args[0].shape[1]
    nc = Tp // _Q
    dy = jnp.pad(dy, ((0, 0), (0, Tp - T), (0, 0), (0, 0))) if Tp > T else dy
    xs, dts, As, bs, Ds, states, dbs = _specs(R, P, N, blocks,
                                              lambda c: nc - 1 - c)

    def block(*shape):
        return pl.BlockSpec((1, 1) + shape, lambda b, g, c: (b, g, 0, 0))

    # a group in one step: dB and dC in their operands' type, as the XLA
    # form gives them; in blocks: a block's part in float32, summed here
    part = Bm.dtype if blocks == 1 else f32
    dx, ddt, dB, dC, dA, dD = pl.pallas_call(
        functools.partial(_bwd_kernel, R=R, P=P),
        grid=(B, H // R, nc),
        in_specs=[xs, dts, As, bs, bs, Ds, xs, states],
        out_specs=[xs, dts, dbs, dbs, block(R, _Q), block(1, R * P)],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, H * P), x.dtype),
                   jax.ShapeDtypeStruct((B, H // R, R, Tp), f32),
                   jax.ShapeDtypeStruct((B, Tp, H // R * N), part),
                   jax.ShapeDtypeStruct((B, Tp, H // R * N), part),
                   jax.ShapeDtypeStruct((B, H // R, R, _Q), f32),
                   jax.ShapeDtypeStruct((B, H // R, 1, R * P), f32)],
        scratch_shapes=[pltpu.VMEM((N, R * P), f32)],
        compiler_params=_PARAMS,
        interpret=_interpret(),
        name=scopes.SSD_BWD,
    )(*args, dy.reshape(B, Tp, H * P), starts)

    def of_group(d, like):
        if blocks == 1:
            return d.reshape(B, Tp, G, N)[:, :T]
        return jnp.sum(d.reshape(B, Tp, G, blocks, N)[:, :T], 3
                       ).astype(like.dtype)

    return (dx.reshape(B, Tp, H, P)[:, :T],
            jnp.swapaxes(ddt.reshape(B, H, Tp), 1, 2)[:, :T].astype(dt.dtype),
            jnp.sum(dA, (0, 3)).reshape(H).astype(A.dtype),
            of_group(dB, Bm), of_group(dC, Cm),
            jnp.sum(dD.reshape(B, H // R, R, P), (0, 3)).reshape(H)
            .astype(D.dtype))


@jax.custom_vjp
def _scan(x, dt, A, Bm, Cm, D):
    return _fwd(x, dt, A, Bm, Cm, D, False)[0]


def _scan_fwd(x, dt, A, Bm, Cm, D):
    y, starts = _fwd(x, dt, A, Bm, Cm, D, True)
    return y, (x, dt, A, Bm, Cm, D, starts)


def _scan_bwd(res, dy):
    return _bwd(*res, dy)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x, dt, A, Bm, Cm, D):
    """``ops.ssm.ssd_scan`` at chunk 128 through the kernels, for shapes
    ``ssd_scan_supported`` takes."""
    count_kernel_selection("ssd_scan")
    return _scan(x, dt, A, Bm, Cm, D)
