"""Fused Adam update as one Pallas pass over each param/slot pair.

The unfused functional update (optimizer/optimizer.py Adam.update_param
under jax) is a chain of elementwise ops XLA usually — but not always —
fuses; each miss costs extra HBM round-trips over arrays the size of
the model.  This kernel makes the single-pass contract explicit: for
every donated ``_ExecState`` param, the (p, g, m, v) quartet is read
once and (p', m', v') written once, with the bias-corrected Adam math
in f32 registers in between (reference: operators/optimizers/adam_op.h
one-kernel-per-param functor; MPK's mega-kernelized optimizer stage).

Arrays of any shape ride the same kernel: flatten, zero-pad to the
f32 (8, 128) tile, update, slice back.  Padding is self-neutralizing
(g = m = v = 0 keeps p' = p - lr*0/(0+eps) = 0).

``fused_update_for`` is the static Executor's opt-in: it returns a
drop-in replacement for ``opt.functional_update`` only when the
optimizer is a plain f32 Adam whose semantics the kernel reproduces
exactly (no grad clip, no weight decay, no per-param lr, no
multi-precision master weights) — anything else stays on the composite
path.  Interpret mode (CPU) runs the same kernel for tests.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...observability import scopes
from .support import block_rows, interpret_mode as _interpret_mode, \
    smem_scalar_spec

__all__ = ["fused_adam_update", "fused_adam_supported",
           "fused_update_for"]

_LANES = 128
_SUBLANES = 8


def fused_adam_supported(shape, dtype) -> bool:
    """f32 params only: Adam's slots are f32, and a bf16 param would
    take the master-weight path the kernel deliberately doesn't carry."""
    return jnp.dtype(dtype) == jnp.dtype(jnp.float32)


def _adam_kernel(lr_ref, step_ref, p_ref, g_ref, m_ref, v_ref,
                 po_ref, mo_ref, vo_ref, *, beta1, beta2, eps):
    g = g_ref[...]
    m = beta1 * m_ref[...] + (1.0 - beta1) * g
    v = beta2 * v_ref[...] + (1.0 - beta2) * g * g
    step = step_ref[0, 0]
    # b^step via exp(step*log(b)) — the same lowering jnp uses for a
    # traced float exponent, so the trajectory matches the composite
    bc1 = 1.0 - jnp.exp(step * math.log(beta1))
    bc2 = 1.0 - jnp.exp(step * math.log(beta2))
    mhat = m / bc1
    vhat = v / bc2
    po_ref[...] = p_ref[...] - lr_ref[0, 0] * mhat / (jnp.sqrt(vhat) + eps)
    mo_ref[...] = m
    vo_ref[...] = v


def fused_adam_update(p, g, m, v, lr, step, *, beta1=0.9, beta2=0.999,
                      eps=1e-8, interpret=None):
    """One-pass Adam: returns (p', m', v').  ``lr``/``step`` may be
    traced scalars (the executor's device-resident carry); betas/eps
    are static.  All four inputs must share p's shape; f32 only."""
    if interpret is None:
        interpret = _interpret_mode()
    shape = p.shape
    n = int(p.size)
    rows = max(-(-n // _LANES), 1)
    rows += (-rows) % _SUBLANES
    padded = rows * _LANES
    bm = block_rows(rows, 256)

    def flat(a):
        a = a.reshape(-1)
        if padded != n:
            a = jnp.pad(a, (0, padded - n))
        return a.reshape(rows, _LANES)

    lr2 = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    step2 = jnp.asarray(step, jnp.float32).reshape(1, 1)
    blk = pl.BlockSpec((bm, _LANES), lambda i: (i, 0))
    po, mo, vo = pl.pallas_call(
        functools.partial(_adam_kernel, beta1=float(beta1),
                          beta2=float(beta2), eps=float(eps)),
        grid=(rows // bm,),
        in_specs=[smem_scalar_spec(), smem_scalar_spec(),
                  blk, blk, blk, blk],
        out_specs=[blk, blk, blk],
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)] * 3,
        interpret=interpret,
        name=scopes.FUSED_ADAM,
    )(lr2, step2, flat(p), flat(g), flat(m), flat(v))

    def unflat(a):
        return a.reshape(-1)[:n].reshape(shape)

    from .support import count_kernel_selection
    count_kernel_selection("fused_adam")
    return unflat(po), unflat(mo), unflat(vo)


def fused_update_for(opt, params_meta, param_arrays):
    """A drop-in for ``opt.functional_update`` when — and only when —
    the kernel reproduces this optimizer's exact semantics, else None.

    Eligible: ``type(opt) is Adam`` (not AdamW/Lamb — decoupled decay
    and lr ratios live outside the kernel's math), no grad clip, no
    global or per-param regularizer, no multi-precision, no lazy mode,
    per-param lr multiplier 1, every param f32."""
    from ...optimizer.optimizer import Adam
    if type(opt) is not Adam:
        return None
    if opt._grad_clip is not None or opt._weight_decay is not None \
            or opt._multi_precision or opt._lazy:
        return None
    for meta in params_meta:
        if meta is None:
            continue
        if getattr(meta, "regularizer", None) is not None:
            return None
        if getattr(meta, "optimize_attr", {}).get(
                "learning_rate", 1.0) != 1.0:
            return None
    for arr in param_arrays:
        if not fused_adam_supported(arr.shape, arr.dtype):
            return None
    b1, b2, eps = opt._beta1, opt._beta2, opt._eps

    def update(param_arrays, grad_arrays, states, lr, step,
               params_meta=None):
        new_ps, new_ss = [], []
        for p, g, s in zip(param_arrays, grad_arrays, states):
            np_, nm, nv = fused_adam_update(
                p, g, s["m"], s["v"], lr, step,
                beta1=b1, beta2=b2, eps=eps)
            new_ps.append(np_)
            new_ss.append({"m": nm, "v": nv})
        return new_ps, new_ss

    return update
