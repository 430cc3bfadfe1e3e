"""Fused matmul-epilogue kernels (fwd + custom-vjp bwd).

The consumer side of the cost model's ranked fusion candidates
(static/analysis/cost.py `_fusion_candidates` — "the MPK-style feed for
the Pallas tier"): a single-consumer chain anchored on a ``linear`` op
whose epilogue is bias / gelu / relu / residual-add / layer_norm
compiles to ONE kernel that keeps the [M, N] intermediate in VMEM —
every fused stage saves the 2x HBM round-trip of its input exactly as
the candidate's ``saved_bytes`` prices it.  The TPU analog of the
reference's hand-fused epilogue ops (reference: operators/fused/
fused_gemm_epilogue_op.cu, fused_bias_residual_layernorm; the
ir/*_fuse_pass.cc chain matchers are the executor-side pass in
static/analysis/fusion.py).

Epilogue *stages* are a static recipe — a tuple of descriptors applied
in order to the f32 matmul accumulator:

- ``("bias",)``              adds a consumed [N] operand;
- ``("relu",)`` / ``("gelu", approximate)``   activation;
- ``("add",)``               adds a consumed [M, N] residual operand;
- ``("layer_norm", eps, has_w, has_b)``  row LN over the last dim,
  consuming the affine [N] operands its flags announce.

The backward is recompute-based (FlashAttention-style): one kernel
replays the forward chain from (x, w, operands) — the [M, N]
intermediates never hit HBM in either direction — then walks the
stages in reverse producing dx (blocked), dw / d-bias / d-affine
(accumulated across row blocks in f32), and d-residual (blocked).

Interpret mode (CPU) runs the same kernels for tests; the shape gate
(`fused_epilogue_supported`) mirrors the Mosaic tile constraints so
selection is identical on every backend.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...observability import scopes
from .support import block_rows, dot as _dot, dtype_ok, \
    interpret_mode as _interpret_mode, pltpu

__all__ = ["fused_linear_epilogue", "fused_epilogue_supported",
           "reference_epilogue", "stage_label"]

# Scoped VMEM the kernels ask Mosaic for.  Its default (16 MiB on v5e)
# is below what the f32 backward needs once the weight, its f32 dw
# accumulator (both staged whole, double-buffered) and the row blocks
# are resident; every TPU generation has at least twice this.
_VMEM_LIMIT = 32 * 1024 * 1024
# what `_row_block` lets its (deliberately high) estimate reach
_VMEM_BUDGET = 30 * 1024 * 1024
# the weight and its f32 dw accumulator are staged whole: past this the
# row blocks left over are too thin to be worth a kernel
_W_VMEM_CAP = 4 * 1024 * 1024

_SQRT_2 = math.sqrt(2.0)
_SQRT_2_OVER_PI = math.sqrt(2.0 / math.pi)
_INV_SQRT_2PI = 1.0 / math.sqrt(2.0 * math.pi)


def stage_label(stages) -> str:
    """Compact kernel name for records: ``matmul+bias+gelu`` etc."""
    return "+".join(["matmul"] + [s[0] for s in stages])


def _ops_per_stage(stage) -> int:
    """How many operands a stage consumes (in order)."""
    kind = stage[0]
    if kind in ("bias", "add"):
        return 1
    if kind == "layer_norm":
        return int(bool(stage[2])) + int(bool(stage[3]))
    return 0


# Mosaic has no lowering for lax.erf, so the exact GELU evaluates the
# same clamped rational approximation XLA's own f32 erf uses (within
# 3e-7 of erf everywhere): multiplies, adds and one divide
_ERF_P = (0.00022905065861350646, 0.0034082910107109506,
          0.050955695062380861, 0.18520832239976145, 1.128379143519084)
_ERF_Q = (-1.1791602954361697e-7, 0.000023547966471313185,
          0.0010179625278914885, 0.014070470171167667,
          0.11098505178285362, 0.49746925110067538, 1.0)


def _erf_f32(x):
    x = jnp.clip(x, -3.832506856900711, 3.832506856900711)
    x2 = x * x
    p, q = _ERF_P[0], _ERF_Q[0]
    for c in _ERF_P[1:]:
        p = p * x2 + c
    for c in _ERF_Q[1:]:
        q = q * x2 + c
    return x * p / q


def _gelu_f32(z, approximate):
    if approximate:
        u = _SQRT_2_OVER_PI * (z + 0.044715 * z * z * z)
        return 0.5 * z * (1.0 + jnp.tanh(u))
    return 0.5 * z * (1.0 + _erf_f32(z / _SQRT_2))


def _dgelu_f32(z, approximate):
    if approximate:
        u = _SQRT_2_OVER_PI * (z + 0.044715 * z * z * z)
        t = jnp.tanh(u)
        du = _SQRT_2_OVER_PI * (1.0 + 3.0 * 0.044715 * z * z)
        return 0.5 * (1.0 + t) + 0.5 * z * (1.0 - t * t) * du
    cdf = 0.5 * (1.0 + _erf_f32(z / _SQRT_2))
    pdf = _INV_SQRT_2PI * jnp.exp(-0.5 * z * z)
    return cdf + z * pdf


def _ln_stats(h, eps):
    mu = jnp.mean(h, axis=-1, keepdims=True)
    d = h - mu
    var = jnp.mean(d * d, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    return d * rstd, rstd


# ---------------------------------------------------------------------------
# gate
# ---------------------------------------------------------------------------

def _row_block(m, k, n, itemsize, stages) -> int:
    """Largest power-of-two row block <= 256 tiling ``m`` with which the
    backward kernel (the bigger of the two) fits the VMEM budget, or 0
    when not even 8 rows do.  The estimate counts every pipelined block
    twice (double buffering) and eight f32 [bm, N] temporaries for the
    replayed chain — above what Mosaic was seen to allocate at every
    shape compiled in tests/test_chip_compile.py."""
    n_add = sum(st[0] == "add" for st in stages)
    fixed = 2 * k * n * (itemsize + 4)            # w + f32 dw accumulator
    per_row = (2 * (2 * k + (1 + 2 * n_add) * n) * itemsize  # x dx dy res
               + (8 * n + k) * 4)                            # temporaries
    bm = block_rows(m, 256)
    while bm >= 8 and fixed + bm * per_row > _VMEM_BUDGET:
        bm //= 2
    return bm if bm >= 8 else 0


def fused_epilogue_supported(x_shape, w_shape, dtype, stages=(),
                             operand_shapes=()) -> bool:
    """Capability gate, identical on every backend so the executor's
    selection is deterministic: Mosaic tile alignment (rows % 8,
    N % 128, K % 8), f32/bf16, the weight within its cap and a row
    block with which the kernels fit VMEM, and every operand either the
    [N] per-feature vector or the full [M, N] residual its stage
    expects."""
    if not dtype_ok(dtype):
        return False
    if len(w_shape) != 2 or len(x_shape) < 2:
        return False
    k, n = int(w_shape[0]), int(w_shape[1])
    if int(x_shape[-1]) != k:
        return False
    m = 1
    for s in x_shape[:-1]:
        m *= int(s)
    if m <= 0 or m % 8 or k % 8 or n % 128:
        return False
    if k * n * 4 > _W_VMEM_CAP:
        return False
    if not _row_block(m, k, n, jnp.dtype(dtype).itemsize, stages):
        return False
    oi = 0
    for st in stages:
        kind = st[0]
        if kind not in ("bias", "relu", "gelu", "add", "layer_norm"):
            return False
        for _ in range(_ops_per_stage(st)):
            if oi >= len(operand_shapes):
                return False
            shp = tuple(int(s) for s in operand_shapes[oi])
            oi += 1
            want_full = kind == "add"
            if want_full:
                om = 1
                for s in shp[:-1]:
                    om *= int(s)
                if not shp or shp[-1] != n or om != m:
                    return False
            elif shp != (n,) and shp != (1, n):
                return False
    return oi == len(operand_shapes)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _apply_stages(z, stages, read_op):
    """Run the epilogue recipe over the f32 accumulator; ``read_op()``
    yields the next consumed operand (already f32, (1,N) or (bm,N)).
    Returns (result, [input value of each stage] for the backward)."""
    hs = []
    for st in stages:
        hs.append(z)
        kind = st[0]
        if kind in ("bias", "add"):
            z = z + read_op()
        elif kind == "relu":
            z = jnp.maximum(z, 0.0)
        elif kind == "gelu":
            z = _gelu_f32(z, st[1])
        elif kind == "layer_norm":
            _, eps, has_w, has_b = st
            z, _ = _ln_stats(z, eps)
            if has_w:
                z = z * read_op()
            if has_b:
                z = z + read_op()
    return z, hs


def _make_fwd_kernel(stages):
    def kernel(x_ref, w_ref, *rest):
        op_refs, o_ref = rest[:-1], rest[-1]
        it = iter(op_refs)

        def read_op():
            return next(it)[...].astype(jnp.float32)

        z = _dot(x_ref[...], w_ref[...], ((1,), (0,)))
        z, _ = _apply_stages(z, stages, read_op)
        o_ref[...] = z.astype(o_ref.dtype)

    return kernel


def _op_block_spec(shape, bm):
    if shape[0] == 1:  # (1, N) per-feature vector, shared by every block
        return pl.BlockSpec((1, shape[1]), lambda i: (0, 0))
    return pl.BlockSpec((bm, shape[1]), lambda i: (i, 0))


def _fwd(stages, interpret, x2, w, ops):
    m, k = x2.shape
    n = w.shape[1]
    bm = _row_block(m, k, n, x2.dtype.itemsize, stages)
    out = pl.pallas_call(
        _make_fwd_kernel(stages),
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
        ] + [_op_block_spec(o.shape, bm) for o in ops],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), x2.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=scopes.EPILOGUE_FWD,
    )(x2, w, *ops)
    return out


# ---------------------------------------------------------------------------
# backward (recompute-based)
# ---------------------------------------------------------------------------

def _make_bwd_kernel(stages, n_ops):
    # operand slot consumed by each stage, in forward order
    slots = []
    oi = 0
    for st in stages:
        cnt = _ops_per_stage(st)
        slots.append(tuple(range(oi, oi + cnt)))
        oi += cnt

    def kernel(x_ref, w_ref, dy_ref, *rest):
        op_refs = rest[:n_ops]
        dx_ref, dw_ref = rest[n_ops], rest[n_ops + 1]
        grad_refs = rest[n_ops + 2:]
        i = pl.program_id(0)

        # accumulated outputs (dw + every [1, N] operand grad) init once
        @pl.when(i == 0)
        def _init():
            dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)
            for st, sl in zip(stages, slots):
                for j in sl:
                    if st[0] != "add":
                        grad_refs[j][...] = jnp.zeros(
                            grad_refs[j].shape, grad_refs[j].dtype)

        x = x_ref[...]
        w = w_ref[...]
        z = _dot(x, w, ((1,), (0,)))

        vals = [op_refs[j][...].astype(jnp.float32)
                for j in range(n_ops)]
        it = iter(range(n_ops))
        z_out, hs = _apply_stages(z, stages, lambda: vals[next(it)])
        del z_out

        g = dy_ref[...].astype(jnp.float32)
        for st, h_in, sl in reversed(list(zip(stages, hs, slots))):
            kind = st[0]
            if kind == "bias":
                grad_refs[sl[0]][...] += jnp.sum(g, 0, keepdims=True)
            elif kind == "add":
                grad_refs[sl[0]][...] = g.astype(grad_refs[sl[0]].dtype)
            elif kind == "relu":
                g = jnp.where(h_in > 0.0, g, 0.0)
            elif kind == "gelu":
                g = g * _dgelu_f32(h_in, st[1])
            elif kind == "layer_norm":
                _, eps, has_w, has_b = st
                xhat, rstd = _ln_stats(h_in, eps)
                si = 0
                if has_b:
                    grad_refs[sl[si + int(has_w)]][...] += jnp.sum(
                        g, 0, keepdims=True)
                if has_w:
                    grad_refs[sl[si]][...] += jnp.sum(
                        g * xhat, 0, keepdims=True)
                    g = g * vals[sl[si]]
                g = rstd * (g - jnp.mean(g, -1, keepdims=True)
                            - xhat * jnp.mean(g * xhat, -1,
                                              keepdims=True))
        dx_ref[...] = _dot(g.astype(w.dtype), w,
                           ((1,), (1,))).astype(dx_ref.dtype)
        dw_ref[...] += _dot(x, g.astype(x.dtype), ((0,), (0,)))

    return kernel


def _bwd_call(stages, interpret, x2, w, ops, dy):
    m, k = x2.shape
    n = w.shape[1]
    bm = _row_block(m, k, n, x2.dtype.itemsize, stages)
    grid = (m // bm,)
    # grads: dx blocked; dw accumulated f32; per-operand — (1, N)
    # operands accumulate in f32, [M, N] residuals are blocked
    out_shapes = [jax.ShapeDtypeStruct((m, k), x2.dtype),
                  jax.ShapeDtypeStruct((k, n), jnp.float32)]
    out_specs = [pl.BlockSpec((bm, k), lambda i: (i, 0)),
                 pl.BlockSpec((k, n), lambda i: (0, 0))]
    for o in ops:
        if o.shape[0] == 1:
            out_shapes.append(jax.ShapeDtypeStruct((1, n), jnp.float32))
            out_specs.append(pl.BlockSpec((1, n), lambda i: (0, 0)))
        else:
            out_shapes.append(jax.ShapeDtypeStruct((m, n), o.dtype))
            out_specs.append(pl.BlockSpec((bm, n), lambda i: (i, 0)))
    outs = pl.pallas_call(
        _make_bwd_kernel(stages, len(ops)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
        ] + [_op_block_spec(o.shape, bm) for o in ops],
        out_specs=out_specs,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=scopes.EPILOGUE_BWD,
    )(x2, w, dy, *ops)
    dx = outs[0]
    dw = outs[1].astype(w.dtype)
    dops = tuple(go.astype(o.dtype) for go, o in zip(outs[2:], ops))
    return dx, dw, dops


# ---------------------------------------------------------------------------
# custom-vjp core + public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _fused(stages, interpret, x2, w, ops):
    return _fwd(stages, interpret, x2, w, ops)


def _fused_fwd(stages, interpret, x2, w, ops):
    return _fwd(stages, interpret, x2, w, ops), (x2, w, ops)


def _fused_bwd(stages, interpret, res, dy):
    x2, w, ops = res
    return _bwd_call(stages, interpret, x2, w, ops, dy)


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_linear_epilogue(x, w, bias=None, stages=(), operands=(),
                          interpret=None):
    """``epilogue(x @ w (+ bias))`` as one Pallas kernel.

    ``x``: [..., K]; ``w``: [K, N]; ``stages``: the post-bias epilogue
    recipe (see module docstring); ``operands``: arrays consumed by the
    ``add`` / ``layer_norm`` stages in order ([N] vectors or
    leading-dims-matching [..., N] residuals).  Leading dims flatten to
    the row dim around the kernel.  Differentiable in x, w, bias and
    every operand via the recompute-based backward kernel."""
    if interpret is None:
        interpret = _interpret_mode()
    k, n = int(w.shape[0]), int(w.shape[1])
    lead = tuple(x.shape[:-1])
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    stages_full = tuple(stages)
    ops = []
    if bias is not None:
        stages_full = (("bias",),) + stages_full
        ops.append(bias.reshape(1, n))
    it = iter(operands)
    for st in tuple(stages):
        for _ in range(_ops_per_stage(st)):
            o = next(it)
            ops.append(o.reshape(1, n) if o.ndim == 1 or o.shape == (1, n)
                       else o.reshape(m, n))
    from .support import count_kernel_selection
    count_kernel_selection("fused_epilogue")
    out = _fused(stages_full, bool(interpret), x2, w, tuple(ops))
    return out.reshape(lead + (n,))


# ---------------------------------------------------------------------------
# jnp oracle (the composite the kernel replaces, for tests/smoke)
# ---------------------------------------------------------------------------

def reference_epilogue(x, w, bias=None, stages=(), operands=()):
    """The unfused composite: same math via jnp/jax.nn, any backend."""
    z = jnp.matmul(x, w)
    if bias is not None:
        z = z + bias
    it = iter(operands)
    for st in stages:
        kind = st[0]
        if kind == "relu":
            z = jax.nn.relu(z)
        elif kind == "gelu":
            z = jax.nn.gelu(z, approximate=st[1])
        elif kind == "add":
            z = z + next(it)
        elif kind == "layer_norm":
            _, eps, has_w, has_b = st
            mu = jnp.mean(z, axis=-1, keepdims=True)
            var = jnp.var(z, axis=-1, keepdims=True)
            z = (z - mu) * jax.lax.rsqrt(var + eps)
            if has_w:
                z = z * next(it)
            if has_b:
                z = z + next(it)
        elif kind == "bias":
            z = z + next(it)
    return z
