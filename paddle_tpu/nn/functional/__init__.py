"""nn.functional — functional mirror of the layer API
(reference: python/paddle/nn/functional/*, lowering to
operators/activation_op.*, conv_op.*, pool_op.*, softmax_op.*, etc.).

All functions are thin wrappers over pure jnp/lax implementations dispatched
through the shared tape/trace point; convs and matmuls map directly onto the
MXU via lax.conv_general_dilated / dot_general.

The composite entry points that models call directly (attention, the fused
head loss, gelu, layer_norm, embedding, dropout) run under a
``jax.named_scope`` of their own name (observability/scopes.py), so a
device trace can be split by component.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...core.dispatch import apply, as_array
from ...core.rng import next_key, stable_draw
from ...core.tensor import Tensor
from ...observability import scopes
from ...ops.manipulation import pad as _pad_op
from ...ops.manipulation import squeeze, unsqueeze  # noqa: F401

# ---------------------------------------------------------------------------
# activations (reference: operators/activation_op.cc kernel zoo)
# ---------------------------------------------------------------------------


def _act(jfn, name):
    def op(x, name=None):
        return apply(jfn, x, op_name=name, cacheable=True)
    op.__name__ = name
    return op


relu = _act(jax.nn.relu, "relu")
relu6 = _act(jax.nn.relu6, "relu6")
sigmoid = _act(jax.nn.sigmoid, "sigmoid")
tanh = _act(jnp.tanh, "tanh")
silu = _act(jax.nn.silu, "silu")
swish = silu
mish = _act(jax.nn.mish, "mish")
softsign = _act(jax.nn.soft_sign, "softsign")
tanhshrink = _act(lambda a: a - jnp.tanh(a), "tanhshrink")
hardswish = _act(jax.nn.hard_swish, "hardswish")


@jax.named_scope(scopes.GELU)
def gelu(x, approximate=False, name=None):
    return apply(lambda a: jax.nn.gelu(a, approximate=approximate), x,
                 op_name="gelu")


def leaky_relu(x, negative_slope=0.01, name=None):
    return apply(lambda a: jax.nn.leaky_relu(a, negative_slope), x,
                 op_name="leaky_relu")


def prelu(x, weight, data_format="NCHW", name=None):
    def _prelu(a, w):
        if w.size == 1:
            return jnp.where(a >= 0, a, w.reshape(()) * a)
        shape = [1] * a.ndim
        ch = 1 if data_format.startswith("NC") else a.ndim - 1
        shape[ch] = w.size
        return jnp.where(a >= 0, a, w.reshape(shape) * a)
    return apply(_prelu, x, weight, op_name="prelu")


def elu(x, alpha=1.0, name=None):
    return apply(lambda a: jax.nn.elu(a, alpha), x, op_name="elu")


def selu(x,
         scale=1.0507009873554804934193349852946,
         alpha=1.6732632423543772848170429916717, name=None):
    return apply(lambda a: scale * jnp.where(a > 0, a, alpha * jnp.expm1(a)),
                 x, op_name="selu")


def celu(x, alpha=1.0, name=None):
    return apply(lambda a: jax.nn.celu(a, alpha), x, op_name="celu")


def hardsigmoid(x, slope=0.1666667, offset=0.5, name=None):
    return apply(lambda a: jnp.clip(a * slope + offset, 0.0, 1.0), x,
                 op_name="hardsigmoid")


def hardtanh(x, min=-1.0, max=1.0, name=None):
    return apply(lambda a: jnp.clip(a, min, max), x, op_name="hardtanh")


def hardshrink(x, threshold=0.5, name=None):
    return apply(lambda a: jnp.where(jnp.abs(a) > threshold, a, 0.0), x,
                 op_name="hardshrink")


def softshrink(x, threshold=0.5, name=None):
    return apply(lambda a: jnp.where(a > threshold, a - threshold,
                                     jnp.where(a < -threshold, a + threshold,
                                               0.0)),
                 x, op_name="softshrink")


def softplus(x, beta=1.0, threshold=20.0, name=None):
    return apply(lambda a: jnp.where(a * beta > threshold, a,
                                     jnp.log1p(jnp.exp(beta * a)) / beta),
                 x, op_name="softplus")


def maxout(x, groups, axis=1, name=None):
    def _maxout(a):
        ax = axis % a.ndim
        c = a.shape[ax]
        new_shape = a.shape[:ax] + (c // groups, groups) + a.shape[ax + 1:]
        return jnp.max(a.reshape(new_shape), axis=ax + 1)
    return apply(_maxout, x, op_name="maxout")


def softmax(x, axis=-1, dtype=None, name=None):
    return apply(lambda a: jax.nn.softmax(a, axis=axis), x, op_name="softmax")


def log_softmax(x, axis=-1, dtype=None, name=None):
    return apply(lambda a: jax.nn.log_softmax(a, axis=axis), x,
                 op_name="log_softmax")


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None):
    draw = stable_draw()  # in-trace + replay-stable (see core.rng)
    def _gs(a):
        g = jax.random.gumbel(draw.key(), a.shape, a.dtype)
        y = jax.nn.softmax((a + g) / temperature, axis=axis)
        if hard:
            idx = jnp.argmax(y, axis=axis)
            oh = jax.nn.one_hot(idx, y.shape[axis], axis=axis, dtype=y.dtype)
            y = jax.lax.stop_gradient(oh - y) + y  # straight-through
        return y
    return apply(_gs, x, op_name="gumbel_softmax")


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    def _normalize(a):
        nrm = jnp.sum(jnp.abs(a) ** p, axis=axis, keepdims=True) ** (1.0 / p)
        return a / jnp.maximum(nrm, epsilon)
    return apply(_normalize, x, op_name="normalize")


# ---------------------------------------------------------------------------
# linear / embedding
# ---------------------------------------------------------------------------

def _linear_fn(a, w):
    return jnp.matmul(a, w)


def _linear_bias_fn(a, w, b):
    return jnp.matmul(a, w) + b


def linear(x, weight, bias=None, name=None):
    """y = x @ W + b; W is [in, out] (reference: operators/matmul_v2 + fc)."""
    if bias is None:
        return apply(_linear_fn, x, weight, op_name="linear",
                     cacheable=True)
    return apply(_linear_bias_fn, x, weight, bias, op_name="linear",
                 cacheable=True)


def bilinear(x1, x2, weight, bias=None, name=None):
    def _bilinear(a, b, w):
        out = jnp.einsum("bi,oij,bj->bo", a, w, b)
        return out
    out = apply(_bilinear, x1, x2, weight, op_name="bilinear")
    if bias is not None:
        out = out + bias
    return out


@jax.named_scope(scopes.EMBEDDING)
def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    def _embedding(ids, w):
        out = jnp.take(w, ids, axis=0)
        if padding_idx is not None:
            mask = (ids != padding_idx)[..., None].astype(w.dtype)
            out = out * mask
        return out

    from ...core import autograd
    ids_arr = as_array(x)
    w_arr = as_array(weight)
    if (sparse and autograd.grad_enabled()
            and isinstance(weight, Tensor) and not weight.stop_gradient
            and weight._node is None  # leaf only: an upstream dense vjp
            #                           cannot consume SelectedRows
            and not isinstance(ids_arr, jax.core.Tracer)
            and not isinstance(w_arr, jax.core.Tracer)):
        # SelectedRows gradient (reference: lookup_table_op.cc
        # is_sparse branch): the weight cotangent is (rows, values), not
        # a [vocab, dim]-dense scatter — optimizers apply it row-wise
        from ...core.selected_rows import SelectedRows

        with autograd.no_grad():
            out_arr = _embedding(ids_arr, w_arr)
        out = Tensor(out_arr, stop_gradient=False, _produced=True)

        def vjp_fn(ct):
            rows = ids_arr.reshape(-1)
            vals = jnp.asarray(ct).reshape(-1, w_arr.shape[-1])
            if padding_idx is not None:
                keep = (rows != padding_idx)[:, None].astype(vals.dtype)
                vals = vals * keep
            return (SelectedRows(rows, vals, w_arr.shape[0]),)

        node = autograd.Node(
            inputs=[weight], vjp_fn=vjp_fn, out_ids=[out._bw_id],
            out_avals=[(out.shape_tuple, np.dtype(out_arr.dtype))],
            out_is_tuple=False)
        out._node = node
        return out
    return apply(_embedding, x, weight, op_name="embedding")


def one_hot(x, num_classes, name=None):
    return apply(lambda a: jax.nn.one_hot(a, num_classes), x,
                 op_name="one_hot", nondiff=True)


# ---------------------------------------------------------------------------
# convolution (reference: operators/conv_op.*, conv_transpose_op.*)
# ---------------------------------------------------------------------------

def _norm_tuple(v, n):
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


def _conv_padding(padding, n):
    if isinstance(padding, str):
        return padding.upper()  # SAME / VALID
    if isinstance(padding, int):
        return [(padding, padding)] * n
    padding = list(padding)
    if len(padding) == n:
        return [(p, p) for p in padding]
    if len(padding) == 2 * n:
        return [(padding[2 * i], padding[2 * i + 1]) for i in range(n)]
    raise ValueError(f"bad conv padding: {padding}")


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    """reference: operators/conv_op.cc; lowers to lax.conv_general_dilated
    which XLA tiles onto the MXU."""
    return _convnd(x, weight, bias, stride, padding, dilation, groups,
                   data_format, 2)


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL", name=None):
    return _convnd(x, weight, bias, stride, padding, dilation, groups,
                   data_format, 1)


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW", name=None):
    return _convnd(x, weight, bias, stride, padding, dilation, groups,
                   data_format, 3)


def _conv_fn(a, w, *maybe_bias, stride, pad_spec, dilation, groups, specs,
             channels_last=False):
    dn = jax.lax.conv_dimension_numbers(a.shape, w.shape, specs)
    out = jax.lax.conv_general_dilated(
        a, w, window_strides=stride,
        padding=(pad_spec if isinstance(pad_spec, str)
                 else [tuple(p) for p in pad_spec]),
        rhs_dilation=dilation, dimension_numbers=dn,
        feature_group_count=groups)
    if maybe_bias:
        # bias fused into the cached op: an eager reshape+add pair costs
        # more host dispatch than the conv itself (r4 profile: 330us vs
        # 69us per call)
        shape = [1] * out.ndim
        shape[-1 if channels_last else 1] = -1
        out = out + maybe_bias[0].reshape(shape)
    return out


def _convnd(x, weight, bias, stride, padding, dilation, groups, data_format,
            n):
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    pad_spec = _conv_padding(padding, n)
    channels_last = not data_format.startswith("NC")
    sp = "".join("DHW"[3 - n:][i] for i in range(n))
    if channels_last:
        lhs_spec = "N" + sp + "C"
    else:
        lhs_spec = "NC" + sp
    # paddle kernel layout: [out_c, in_c/groups, *spatial]
    rhs_spec = "OI" + sp
    out_spec = lhs_spec
    pad_hashable = (pad_spec if isinstance(pad_spec, str)
                    else tuple(tuple(p) for p in pad_spec))
    args = (x, weight) + ((bias,) if bias is not None else ())
    return apply(_conv_fn, *args, op_name=f"conv{n}d", cacheable=True,
                 stride=stride, pad_spec=pad_hashable, dilation=dilation,
                 groups=groups, specs=(lhs_spec, rhs_spec, out_spec),
                 channels_last=channels_last)


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     output_size=None, data_format="NCHW", name=None):
    """reference: operators/conv_transpose_op.cc — implemented as the
    gradient of conv2d (lax.conv_transpose with paddle's IOHW kernel)."""
    n = 2
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    outpad = _norm_tuple(output_padding, n)
    channels_last = not data_format.startswith("NC")
    pad_int = padding if isinstance(padding, int) else None

    def _convt(a, w):
        # paddle kernel layout for transpose conv: [in_c, out_c/groups, H, W]
        if channels_last:
            a_ = jnp.moveaxis(a, -1, 1)
        else:
            a_ = a
        k = _norm_tuple(w.shape[2], 1) + (w.shape[3],)
        pads = _conv_padding(padding, n)
        if isinstance(pads, str):
            raise ValueError("string padding unsupported for conv_transpose")
        # output_size disambiguates the stride>1 output length
        # (conv_transpose_op.cc InferShape): it overrides output_padding
        outpad_eff = list(outpad)
        if output_size is not None:
            os_ = _norm_tuple(tuple(output_size), n)
            for i in range(n):
                kk = (w.shape[2 + i] - 1) * dilation[i] + 1
                lo, hi = pads[i]
                base = (a_.shape[2 + i] - 1) * stride[i] - lo - hi + kk
                op = os_[i] - base
                if not 0 <= op < max(stride[i], 1) + 1:
                    raise ValueError(
                        f"conv_transpose: output_size[{i}]={os_[i]} not "
                        f"reachable (base {base}, stride {stride[i]})")
                outpad_eff[i] = op
        # gradient-of-conv formulation: dilate input by stride, full-pad
        lhs_dilation = stride
        pad_list = []
        for i in range(n):
            kk = (w.shape[2 + i] - 1) * dilation[i] + 1
            lo, hi = pads[i]
            pad_list.append((kk - 1 - lo, kk - 1 - hi + outpad_eff[i]))
        w_flip = jnp.flip(w, axis=(2, 3))
        w_t = jnp.swapaxes(w_flip, 0, 1)  # -> [out_c, in_c, H, W]
        if groups > 1:
            # grouped transpose: w is [in_c, out_c//g, kh, kw]
            ic = a_.shape[1]
            w_g = w_flip.reshape(groups, ic // groups, w.shape[1],
                                 *w.shape[2:])
            w_t = jnp.concatenate(
                [jnp.swapaxes(w_g[g], 0, 1) for g in range(groups)], axis=0)
        dn = jax.lax.conv_dimension_numbers(
            a_.shape, w_t.shape, ("NCHW", "OIHW", "NCHW"))
        out = jax.lax.conv_general_dilated(
            a_, w_t, window_strides=(1, 1), padding=pad_list,
            lhs_dilation=lhs_dilation, rhs_dilation=dilation,
            dimension_numbers=dn, feature_group_count=groups)
        if channels_last:
            out = jnp.moveaxis(out, 1, -1)
        return out

    out = apply(_convt, x, weight, op_name="conv2d_transpose")
    if bias is not None:
        shape = [1, 1, 1, 1]
        shape[-1 if channels_last else 1] = -1
        out = out + bias.reshape(shape)
    return out


# ---------------------------------------------------------------------------
# pooling (reference: operators/pool_op.*)
# ---------------------------------------------------------------------------

def _pool(x, kernel, stride, padding, n, reducer, init, data_format,
          ceil_mode=False, count_include_pad=True, average=False):
    kernel = _norm_tuple(kernel, n)
    stride = _norm_tuple(stride if stride is not None else kernel, n)
    pads = _conv_padding(padding, n)
    channels_last = not data_format.startswith("NC")
    if channels_last:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        pad_full = ([(0, 0)] + list(pads) + [(0, 0)]
                    if not isinstance(pads, str) else pads)
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride
        pad_full = ([(0, 0), (0, 0)] + list(pads)
                    if not isinstance(pads, str) else pads)

    no_pad = isinstance(pads, list) and all(p == (0, 0) for p in pads)
    return apply(
        _pool_fn, x, op_name="pool", cacheable=True, init=init,
        max_pool=(reducer is jax.lax.max), window=window, strides=strides,
        pad_full=(pad_full if isinstance(pad_full, str)
                  else tuple(tuple(p) for p in pad_full)),
        average=average, divisor=(float(np.prod(kernel))
                                  if (count_include_pad or no_pad)
                                  else None))


def _pool_fn(a, *, init, max_pool, window, strides, pad_full, average,
             divisor):
    reducer = jax.lax.max if max_pool else jax.lax.add
    pad = (pad_full if isinstance(pad_full, str)
           else [tuple(p) for p in pad_full])
    out = jax.lax.reduce_window(a, init, reducer, window, strides, pad)
    if average:
        if divisor is not None:
            out = out / divisor
        else:
            ones = jnp.ones_like(a)
            cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                        strides, pad)
            out = out / cnt
    return out


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCHW", name=None):
    return _pool(x, kernel_size, stride, padding, 2, jax.lax.max,
                 -jnp.inf, data_format, ceil_mode)


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCHW",
               name=None):
    return _pool(x, kernel_size, stride, padding, 2, jax.lax.add, 0.0,
                 data_format, ceil_mode, count_include_pad=not exclusive,
                 average=True)


def max_pool1d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, name=None):
    return _pool(x, kernel_size, stride, padding, 1, jax.lax.max,
                 -jnp.inf, "NCL", ceil_mode)


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, name=None):
    return _pool(x, kernel_size, stride, padding, 1, jax.lax.add, 0.0,
                 "NCL", ceil_mode, count_include_pad=not exclusive,
                 average=True)


def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCDHW", name=None):
    return _pool(x, kernel_size, stride, padding, 3, jax.lax.max,
                 -jnp.inf, data_format, ceil_mode)


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCDHW",
               name=None):
    return _pool(x, kernel_size, stride, padding, 3, jax.lax.add, 0.0,
                 data_format, ceil_mode, count_include_pad=not exclusive,
                 average=True)


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    os = _norm_tuple(output_size, 2)

    def _aap(a):
        if data_format.startswith("NC"):
            N, C, H, W = a.shape
            a_ = a
        else:
            N, H, W, C = a.shape
            a_ = jnp.moveaxis(a, -1, 1)
        # XLA-friendly: split into os windows when divisible, else mean over
        # index buckets via reshape fallback
        if H % os[0] == 0 and W % os[1] == 0:
            out = a_.reshape(N, C, os[0], H // os[0], os[1], W // os[1])
            out = out.mean(axis=(3, 5))
        else:
            # bucketed mean (static loop over output cells)
            rows = [a_[:, :, (i * H) // os[0]:-(-(i + 1) * H // os[0]), :]
                    for i in range(os[0])]
            cells = []
            for r in rows:
                cells.append(jnp.stack(
                    [r[:, :, :, (j * W) // os[1]:-(-(j + 1) * W // os[1])]
                     .mean(axis=(2, 3)) for j in range(os[1])], axis=-1))
            out = jnp.stack(cells, axis=2)
        if not data_format.startswith("NC"):
            out = jnp.moveaxis(out, 1, -1)
        return out
    return apply(_aap, x, op_name="adaptive_avg_pool2d")


def adaptive_max_pool2d(x, output_size, return_mask=False, name=None):
    os = _norm_tuple(output_size, 2)

    def _amp(a):
        N, C, H, W = a.shape
        if H % os[0] == 0 and W % os[1] == 0:
            out = a.reshape(N, C, os[0], H // os[0], os[1], W // os[1])
            return out.max(axis=(3, 5))
        rows = [a[:, :, (i * H) // os[0]:-(-(i + 1) * H // os[0]), :]
                for i in range(os[0])]
        cells = []
        for r in rows:
            cells.append(jnp.stack(
                [r[:, :, :, (j * W) // os[1]:-(-(j + 1) * W // os[1])]
                 .max(axis=(2, 3)) for j in range(os[1])], axis=-1))
        return jnp.stack(cells, axis=2)
    return apply(_amp, x, op_name="adaptive_max_pool2d")


def adaptive_avg_pool1d(x, output_size, name=None):
    os = int(output_size)

    def _aap(a):
        N, C, L = a.shape
        if L % os == 0:
            return a.reshape(N, C, os, L // os).mean(axis=3)
        return jnp.stack(
            [a[:, :, (i * L) // os:-(-(i + 1) * L // os)].mean(axis=2)
             for i in range(os)], axis=-1)
    return apply(_aap, x, op_name="adaptive_avg_pool1d")


# ---------------------------------------------------------------------------
# normalisation (reference: operators/batch_norm_op.*, layer_norm_op.*)
# ---------------------------------------------------------------------------

def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW", use_global_stats=None, name=None):
    """Functional BN. In training mode returns (out, new_mean, new_var) data
    updates through the Layer wrapper; here it computes with batch stats and
    the Layer handles running-stat updates."""
    ch_axis = 1 if data_format.startswith("NC") and as_array(x).ndim > 1 else -1
    axes = tuple(i for i in range(as_array(x).ndim) if i != ch_axis % as_array(x).ndim)

    use_batch = training and not use_global_stats

    if use_batch:
        def _bn(a, w, b):
            m = jnp.mean(a, axis=axes, keepdims=True)
            v = jnp.var(a, axis=axes, keepdims=True)
            out = (a - m) * jax.lax.rsqrt(v + epsilon)
            if w is not None:
                out = out * _chan(w, a, ch_axis)
            if b is not None:
                out = out + _chan(b, a, ch_axis)
            return out
    else:
        def _bn(a, w, b, rm=as_array(running_mean), rv=as_array(running_var)):
            out = ((a - _chan(rm, a, ch_axis))
                   * jax.lax.rsqrt(_chan(rv, a, ch_axis) + epsilon))
            if w is not None:
                out = out * _chan(w, a, ch_axis)
            if b is not None:
                out = out + _chan(b, a, ch_axis)
            return out
    return apply(_bn, x, weight, bias, op_name="batch_norm")


def _chan(v, a, ch_axis):
    shape = [1] * a.ndim
    shape[ch_axis] = -1
    return v.reshape(shape)


@jax.named_scope(scopes.LAYER_NORM)
def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5,
               name=None):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    n = len(tuple(normalized_shape))

    def _ln(a, *wb):
        w = wb[0] if len(wb) > 0 else None
        b = wb[1] if len(wb) > 1 else None
        axes = tuple(range(a.ndim - n, a.ndim))
        m = jnp.mean(a, axis=axes, keepdims=True)
        v = jnp.var(a, axis=axes, keepdims=True)
        out = (a - m) * jax.lax.rsqrt(v + epsilon)
        if w is not None:
            out = out * w
        if b is not None:
            out = out + b
        return out

    args = [x]
    if weight is not None:
        args.append(weight)
        if bias is not None:
            args.append(bias)
    return apply(_ln, *args, op_name="layer_norm")


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    def _gn(a, *wb):
        w = wb[0] if len(wb) > 0 else None
        b = wb[1] if len(wb) > 1 else None
        if not data_format.startswith("NC"):
            a = jnp.moveaxis(a, -1, 1)
        N, C = a.shape[:2]
        spatial = a.shape[2:]
        g = a.reshape(N, num_groups, C // num_groups, *spatial)
        axes = tuple(range(2, g.ndim))
        m = jnp.mean(g, axis=axes, keepdims=True)
        v = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - m) * jax.lax.rsqrt(v + epsilon)).reshape(a.shape)
        shape = [1, C] + [1] * len(spatial)
        if w is not None:
            out = out * w.reshape(shape)
        if b is not None:
            out = out + b.reshape(shape)
        if not data_format.startswith("NC"):
            out = jnp.moveaxis(out, 1, -1)
        return out

    args = [x]
    if weight is not None:
        args.append(weight)
        if bias is not None:
            args.append(bias)
    return apply(_gn, *args, op_name="group_norm")


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW", name=None):
    def _in(a, *wb):
        w = wb[0] if len(wb) > 0 else None
        b = wb[1] if len(wb) > 1 else None
        axes = tuple(range(2, a.ndim))
        m = jnp.mean(a, axis=axes, keepdims=True)
        v = jnp.var(a, axis=axes, keepdims=True)
        out = (a - m) * jax.lax.rsqrt(v + eps)
        if w is not None:
            shape = [1, -1] + [1] * (a.ndim - 2)
            out = out * w.reshape(shape)
        if b is not None:
            shape = [1, -1] + [1] * (a.ndim - 2)
            out = out + b.reshape(shape)
        return out

    args = [x]
    if weight is not None:
        args.append(weight)
        if bias is not None:
            args.append(bias)
    return apply(_in, *args, op_name="instance_norm")


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    def _lrn(a):
        sq = jnp.square(a)
        half = size // 2
        c = a.shape[1]
        pads = [(0, 0), (half, size - 1 - half)] + [(0, 0)] * (a.ndim - 2)
        padded = jnp.pad(sq, pads)
        win = sum(padded[:, i:i + c] for i in range(size))
        return a / (k + alpha * win) ** beta
    return apply(_lrn, x, op_name="local_response_norm")


# ---------------------------------------------------------------------------
# dropout (reference: operators/dropout_op.*)
# ---------------------------------------------------------------------------

def _u16_dropout_mask(key, shape, p, dtype, upscale=True):
    """Dropout keep-mask from u16 random bits: half the random bytes and no
    int->float convert vs the f32-uniform path (which cost ~25 ms/step on
    the BERT bench).  p is quantized to 1/65536; the keep scale uses the
    quantized value so E[mask * x] == x exactly.  Returns None for p<=0
    (keep everything) and 0.0 for p>=1 (drop everything)."""
    t = int(round(float(p) * 65536.0))
    if t <= 0:
        return None
    if t >= 65536:
        return 0.0
    bits = jax.random.bits(key, tuple(shape), jnp.uint16)
    keep = (bits >= jnp.uint16(t)).astype(dtype)
    if upscale:
        return keep * jnp.asarray(65536.0 / (65536 - t), dtype)
    return keep


@jax.named_scope(scopes.DROPOUT)
def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train",
            name=None):
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            # reference semantics: infer-time out = x * (1 - p)
            return apply(lambda a: a * jnp.asarray(1.0 - p, a.dtype), x,
                         op_name="dropout")
        return x if isinstance(x, Tensor) else Tensor(x)

    draw = stable_draw()

    def _dropout(a):
        # key resolved INSIDE the traced fn: under a seed_scope
        # (TrainStep, static Executor runs) it folds the per-run key so
        # static programs reseed per exe.run; the StableDraw identity
        # keeps double-backward tape replays on the SAME mask
        key = draw.key()
        shape = list(a.shape)
        if axis is not None:
            axes = axis if isinstance(axis, (list, tuple)) else [axis]
            shape = [s if i in axes else 1 for i, s in enumerate(shape)]
        mask = _u16_dropout_mask(key, shape, p, a.dtype,
                                 upscale=(mode == "upscale_in_train"))
        if mask is None:
            return a
        return a * mask
    return apply(_dropout, x, op_name="dropout")


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    ax = [0, 1] if data_format.startswith("NC") else [0, 3]
    return dropout(x, p, axis=ax, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    ax = [0, 1] if data_format.startswith("NC") else [0, 4]
    return dropout(x, p, axis=ax, training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    if not training or p == 0.0:
        return x if isinstance(x, Tensor) else Tensor(x)
    draw = stable_draw()  # in-trace + replay-stable (see core.rng)

    def _ad(a):
        alpha = 1.6732632423543772848170429916717
        scale = 1.0507009873554804934193349852946
        neg = -alpha * scale
        keep = jax.random.bernoulli(draw.key(), 1.0 - p, a.shape)
        q = 1.0 - p
        A = (q + neg ** 2 * q * p) ** -0.5
        B = -A * p * neg
        return A * jnp.where(keep, a, neg) + B
    return apply(_ad, x, op_name="alpha_dropout")


# ---------------------------------------------------------------------------
# losses (reference: operators/cross_entropy_op.*, mse, bce, kldiv,
# smooth_l1, margin_rank; python/paddle/nn/functional/loss.py)
# ---------------------------------------------------------------------------

def _reduce(out, reduction):
    if reduction == "mean":
        return jnp.mean(out)
    if reduction == "sum":
        return jnp.sum(out)
    return out


def _ce_fn(logits, lab, *w, use_softmax, axis, soft_label,
       label_smoothing, ignore_index, reduction):
    wgt = w[0] if w else None
    if use_softmax:
        logp = jax.nn.log_softmax(logits, axis=axis)
    else:
        logp = jnp.log(jnp.maximum(logits, 1e-30))
    nclass = logits.shape[axis]
    if soft_label:
        tgt = lab
    else:
        lab_ = lab
        if lab_.ndim == logp.ndim and lab_.shape[axis] == 1:
            lab_ = jnp.squeeze(lab_, axis)
        tgt = jax.nn.one_hot(lab_, nclass, axis=axis, dtype=logp.dtype)
    if label_smoothing > 0.0:
        tgt = tgt * (1.0 - label_smoothing) + label_smoothing / nclass
    loss = -jnp.sum(tgt * logp, axis=axis)
    w_row = None
    if wgt is not None and not soft_label:
        lab_ = lab
        if lab_.ndim == logp.ndim and lab_.shape[axis] == 1:
            lab_ = jnp.squeeze(lab_, axis)
        # ignore_index (e.g. -100) is out of range for the weight
        # table — jnp.take would fill NaN; ignored rows are masked to
        # zero below, so any in-range index works here
        safe = jnp.where(lab_ == ignore_index, 0, lab_)
        w_row = jnp.take(wgt, safe)
        loss = loss * w_row
    if not soft_label:
        lab_ = lab
        if lab_.ndim == logp.ndim and lab_.shape[axis] == 1:
            lab_ = jnp.squeeze(lab_, axis)
        mask = (lab_ != ignore_index).astype(loss.dtype)
        loss = loss * mask
        if reduction == "mean":
            if w_row is not None:
                # weighted mean divides by the sum of selected class
                # weights (reference: nn/functional/loss.py weighted CE)
                denom = jnp.sum(mask * w_row)
            else:
                denom = jnp.sum(mask)
            return jnp.sum(loss) / jnp.maximum(denom, 1e-12)
    return _reduce(loss, reduction)


def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0, name=None):
    args = [input, label]
    if weight is not None:
        args.append(weight)
    return apply(_ce_fn, *args, op_name="cross_entropy", cacheable=True,
                 use_softmax=use_softmax, axis=axis, soft_label=soft_label,
                 label_smoothing=float(label_smoothing),
                 ignore_index=ignore_index, reduction=reduction)


def _chunk_logits(hc, w, b):
    return (jnp.matmul(hc, w) + b).astype(jnp.float32)


def _nll_of_logits(logits, lc, *wc, ignore_index):
    """A chunk's summed (or weighted) loss and its count of kept tokens,
    from its [per, vocab] float32 logits."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    safe = jnp.where(lc == ignore_index, 0, lc)
    tgt = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    nll = lse - tgt
    keep = (lc != ignore_index)
    if wc:
        # where, not a product: an ignored token's weight may be
        # anything, and its loss gives the weight no gradient
        return jnp.sum(jnp.where(keep, nll * wc[0], 0.0)), jnp.sum(keep)
    return jnp.sum(nll * keep), jnp.sum(keep)


def _chunk_nll(hc, w, b, lc, *wc, ignore_index):
    """One chunk's loss and count; its logits live only here."""
    return _nll_of_logits(_chunk_logits(hc, w, b), lc, *wc,
                          ignore_index=ignore_index)


def _head_of(w, b, ignore_index):
    """``_chunk_nll`` closed over the head, as a scan body calls it."""
    return lambda hc, lc, *wc: _chunk_nll(hc, w, b, lc, *wc,
                                          ignore_index=ignore_index)


def _mean_or_sum(total, count, tws):
    if tws:
        return total
    return total / jnp.maximum(count, 1).astype(jnp.float32)


def _chunked_ce_value(chunk_nll, hs, ls, tws):
    """The head's loss over chunks ``hs`` [n, per, H], ``ls`` [n, per] and,
    where there are token weights, ``tws`` = ([n, per],) (else ()): the
    mean over the kept tokens, or the weighted sum; one matmul a chunk.
    ``chunk_nll(hc, lc, *wc)`` holds the head."""
    def body(carry, xs):
        s, c = carry
        ds, dc = chunk_nll(*xs)
        return (s + ds, c + dc), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.int32(0)), (hs, ls, *tws))
    return _mean_or_sum(total, count, tws)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chunked_ce(ignore_index, hs, w, b, ls, tws):
    """``_chunked_ce_value`` where no gradient is asked; differentiated,
    ``_chunked_ce_fwd`` runs in its place."""
    return _chunked_ce_value(_head_of(w, b, ignore_index), hs, ls, tws)


def _chunked_ce_fwd(ignore_index, hs, w, b, ls, tws):
    """The loss AND its gradients at a cotangent of 1, chunk by chunk in
    the one scan: each chunk's logits are differentiated where they are
    made (the logits matmul, then the two gradient products) and never
    made again.  A token's own factor is known before the scan, so the
    chunk is pulled with it: 1 / the number of kept tokens for the mean
    (counted over the labels beforehand, not in the carry), 1 for the
    weighted sum.  ``dw`` and ``db`` accumulate in ``w``'s and ``b``'s own
    types, last chunk first, which is how a scan's transpose accumulated
    them: at a cotangent of 1 every gradient is that transpose's bit for
    bit.  The chunk's rule is pulled in its two halves, the loss's down to
    the logits' cotangent ``dl`` and the logits' own, so that ``dh`` can
    walk ``_dh_rows`` rows a product (a row's ``dh`` is its own sum over
    the vocabulary: the same numbers); counted at trace time as
    ``linear_cross_entropy.dh_rows.<rows>``."""
    from ...utils import monitor
    monitor.stat_add("linear_cross_entropy.grads_in_forward")
    count = jnp.sum(ls != ignore_index)
    ct = _mean_or_sum(jnp.float32(1.0), count, tws)
    per = hs.shape[1]
    block = _dh_rows(per, w)
    monitor.stat_add(f"linear_cross_entropy.dh_rows.{block}")

    def body(carry, xs):
        hc, lc, *wc = xs
        # dh = dl @ w.T for ``block`` rows: the transpose of their logits
        dh_of = jax.linear_transpose(
            lambda hb: jnp.matmul(hb, w).astype(jnp.float32),
            jax.ShapeDtypeStruct((block, hc.shape[1]), hc.dtype))
        logits, pull_head = jax.vjp(
            lambda w, b: _chunk_logits(hc, w, b), w, b)
        part, pull_nll, _ = jax.vjp(
            lambda z, *wc: _nll_of_logits(z, lc, *wc,
                                          ignore_index=ignore_index),
            logits, *wc, has_aux=True)
        dl, *dwc_t = pull_nll(ct)
        dwc, dbc = pull_head(dl)
        dhc = jnp.concatenate([dh_of(dl[i:i + block])[0]
                               for i in range(0, per, block)])
        return ((carry[0] + dwc, carry[1] + dbc), (part, dhc, *dwc_t))

    (dw, db), (parts, dh, *dtw) = jax.lax.scan(
        body, (jnp.zeros_like(w), jnp.zeros_like(b)), (hs, ls, *tws),
        reverse=True)
    # first chunk first, as the value's carry adds them: the same loss
    total, _ = jax.lax.scan(lambda s, part: (s + part, None),
                            jnp.float32(0.0), parts)
    return _mean_or_sum(total, count, tws), (dh, dw, db, *dtw)


def _chunked_ce_bwd(ignore_index, grads, g):
    dh, dw, db, *dtw = [(g * d).astype(d.dtype) for d in grads]
    # labels take no gradient
    return dh, dw, db, None, tuple(dtw)


_chunked_ce.defvjp(_chunked_ce_fwd, _chunked_ce_bwd)


# The rows a chunk of the head holds where the caller names none, and the
# rows one ``dh`` product walks.  Both are measured constants of the
# program; the head alone (loss and gradients in one jit, 1024-row chunks
# unless said) on one v5e, ms a call (PERF.md, PR 50):
#
#   rows a chunk                 512     1024    2048    4096
#   f32 [32768, 2048] x 49152   161.4   154.7   162.4   152.2 (Ouro)
#   f32 [32768, 2048] x 18992    63.4    49.5    57.8         (Keye)
#   f32 [16384, 2048] x 25024    40.7    32.4    38.1         (Trinity)
#   f32 [16384, 2688] x 16384    36.4    29.0    31.3         (Nemotron)
#   bf16 [16384, 1536] x 50257   46.4    44.0    47.4         (GPT)
#
#   rows a dh product           1024     512     256
#   f32 x 49152                 154.7   135.2   137.4
#   f32 x 18992 / 25024 / 16384  49.5 / 32.4 / 29.0   48.4 / 32.3 / 28.2
#   bf16 x 50257                 44.0    44.8    47.8
#
# XLA tiles each product by the chunk: at 2048 rows it splits ``dw``'s
# contraction three ways and walks the float32 accumulator once a part,
# so fewer, larger chunks are slower although they move less (4096 rows
# win 1.6 % at four [rows, vocab] float32 buffers of 805 MB).  At 1024
# rows over 49,152 columns it cuts ``dh``'s [1024, H] result in four and
# reads both operands twice, and it remakes the logits' cotangent inside
# ``dh`` and inside ``dw``; handed two products of 512 rows it keeps each
# result whole, makes the cotangent once, in the bfloat16 the products
# read it in, and ``dw`` reads half the bytes.  Under 32,768 columns and
# for a bfloat16 head the one product is as fast or faster in the step.
_HEAD_ROWS = 1024
_DH_ROWS = 512
_DH_SPLIT_FROM = 32768      # vocabulary columns


def _dh_rows(per, w):
    """The rows of a chunk of ``per`` that one ``dh`` product walks: from
    what the head sees of its weight, ``_DH_ROWS`` for float32 over at
    least ``_DH_SPLIT_FROM`` columns where they divide the chunk, else the
    whole chunk."""
    if (jnp.dtype(w.dtype).itemsize == 4 and w.shape[-1] >= _DH_SPLIT_FROM
            and per % _DH_ROWS == 0):
        return _DH_ROWS
    return per


def _linear_ce_fn(h, w, b, lab, *tw, chunk, ignore_index):
    """Chunked fused head+CE: the [T, vocab] logits (and their cotangent)
    never hit HBM in full, because a chunk's logits live only inside the
    scan body that makes them.  Where gradients are asked that body turns
    them into ``dh``, ``dw`` and ``db`` at once (``_chunked_ce``: three
    matmul passes), and the backward pass only scales what the forward
    pass kept.  A head narrower than its hidden width keeps the replay
    instead (a checkpointed chunk body that jax differentiates: four
    passes): what it would keep, ``dh`` [T, H], is larger than the logits
    it would save making again, and a model with several such heads
    (EvaByte: eight of 320 columns over 4096) would hold one ``dh`` a head
    from the forward pass into the backward.  With a token weight ``tw``
    [T] the result is the weighted sum of the kept tokens' losses, not
    their mean.  The padding of a ragged tail stays outside either rule:
    its gradient is jax's slice.  ``chunk`` None: ``_HEAD_ROWS``; counted
    as ``linear_cross_entropy.rows.<rows>``."""
    from ...utils import monitor
    if chunk is None:
        chunk = _HEAD_ROWS
    monitor.stat_add(f"linear_cross_entropy.rows.{chunk}")
    T = h.shape[0]
    n = max(1, -(-T // chunk))          # ceil: pad the tail chunk
    per = -(-T // n)
    if n * per != T:
        pad = n * per - T
        h = jnp.concatenate(
            [h, jnp.zeros((pad, h.shape[-1]), h.dtype)], axis=0)
        lab = jnp.concatenate(
            [lab, jnp.full((pad,), ignore_index, lab.dtype)], axis=0)
        tw = tuple(jnp.concatenate([t, jnp.zeros((pad,), t.dtype)])
                   for t in tw)
    hs, ls = h.reshape(n, per, h.shape[-1]), lab.reshape(n, per)
    tws = tuple(t.reshape(n, per) for t in tw)
    if w.shape[-1] < h.shape[-1]:
        return _chunked_ce_value(
            jax.checkpoint(_head_of(w, b, ignore_index)), hs, ls, tws)
    return _chunked_ce(ignore_index, hs, w, b, ls, tws)


@jax.named_scope(scopes.LINEAR_CROSS_ENTROPY)
def linear_cross_entropy(hidden, weight, bias, label,
                         chunk: Optional[int] = None,
                         ignore_index: int = -100, name=None,
                         token_weight=None):
    """Fused ``cross_entropy(hidden @ weight + bias, label)`` with chunked
    logits (mean reduction).  The TPU-native extension of the reference's
    fused softmax_with_cross_entropy op (operators/softmax_with_cross_
    entropy_op.cu) to include the vocab projection: the full-vocab logits
    tensor is never materialized.  ``hidden``: [T, H]; ``weight``:
    [H, vocab]; ``label``: [T] int.

    ``chunk``: the rows of ``hidden`` a chunk holds.  ``None`` (the
    default): the head chooses, 1024 rows, the measured best on a v5e at
    every head shape and for either itemsize (the table beside
    ``_HEAD_ROWS``: XLA tiles the three products by the chunk, and at
    2048 rows it splits ``dw``'s contraction and walks the float32
    accumulator three times a chunk, so a larger chunk is slower although
    it moves fewer bytes).  An int is taken as given.  What a chunk costs
    in memory is its ``[rows, vocab]`` float32 arrays, the logits, their
    exponentials, their cotangent and the buffer the labels' gather
    scatters into (four live at once in a compiled step: 4 x 201 MB at
    1024 x 49,152).  Differentiated, a float32 head over at least 32,768
    columns makes ``dh`` 512 rows a product inside the chunk
    (``_dh_rows``: the same numbers, a row's ``dh`` is its own sum; XLA
    then keeps each product's result whole and makes the logits' cotangent
    once, not once a product: Ouro's head 142.9 -> 131.5 ms a step).

    ``token_weight`` [T] float32 turns the mean into the weighted sum
    ``sum_i token_weight_i * nll_i`` over the tokens that are not
    ``ignore_index`` (the caller's weights carry the normalisation).  The
    weight may be traced and takes a gradient, ``nll_i``, out of the same
    chunk body: a loss that mixes per-token losses by a learned
    distribution (``loop_exit_loss``) never holds them, or the logits, in
    full.

    Differentiated, a head at least as wide as its hidden width makes its
    gradients where it makes its loss (a ``jax.custom_vjp``: three matmul
    passes, the backward pass scales what the forward pass kept; a
    narrower head replays its logits, four passes: ``_linear_ce_fn``).
    Two things follow.  A traced call with gradients enabled (a ``Tensor``
    that wants one, inside somebody's ``jit``) takes ``jax.vjp`` at the
    call, so a loss that is never differentiated pays three passes for
    one there; ``no_grad``, ``TrainStep.eval_step`` and the eager tape
    (whose compiled rules run the value at the call and the three passes
    at ``backward()``) take the value path, one pass.  And forward-mode
    differentiation (``jax.jvp``, ``jacfwd``) through the head is not
    available, as it is not through the flash kernels.  Inside an outer
    ``jax.checkpoint`` the forward pass runs the value and the replay
    the three passes.

    Counted at trace time: ``linear_cross_entropy.calls``,
    ``linear_cross_entropy.rows.<rows>`` (the rows a traced call's chunks
    hold, chosen or given: a program's report says which each head got),
    and for each call whose gradients were made in its forward rule
    ``linear_cross_entropy.grads_in_forward`` and
    ``linear_cross_entropy.dh_rows.<rows>`` (the rows a ``dh`` product
    walks)."""
    from ...utils import monitor
    monitor.stat_add("linear_cross_entropy.calls")
    args = [hidden, weight, bias, label]
    if token_weight is not None:
        args.append(token_weight)
    return apply(_linear_ce_fn, *args,
                 op_name="linear_cross_entropy", cacheable=True,
                 chunk=None if chunk is None else int(chunk),
                 ignore_index=int(ignore_index))


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, axis=-1,
                               return_softmax=False):
    loss = cross_entropy(logits, label, reduction="none",
                         soft_label=soft_label, ignore_index=ignore_index,
                         axis=axis)
    loss = loss.unsqueeze(axis)
    if return_softmax:
        return loss, softmax(logits, axis=axis)
    return loss


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean",
             name=None):
    def _nll(logp, lab, *w):
        wgt = w[0] if w else None
        loss = -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
        if wgt is not None:
            loss = loss * jnp.take(wgt, lab)
        return _reduce(loss, reduction)
    args = [input, label]
    if weight is not None:
        args.append(weight)
    return apply(_nll, *args, op_name="nll_loss")


def _mse_fn(a, b, *, reduction):
    return _reduce(jnp.square(a - b), reduction)


def _l1_fn(a, b, *, reduction):
    return _reduce(jnp.abs(a - b), reduction)


# reduction rides the recorded kw (not a closure) so static analysis —
# shardcheck's sum-classifier in particular — can read it off the node
def mse_loss(input, label, reduction="mean", name=None):
    return apply(_mse_fn, input, label, op_name="mse_loss",
                 reduction=reduction)


def l1_loss(input, label, reduction="mean", name=None):
    return apply(_l1_fn, input, label, op_name="l1_loss",
                 reduction=reduction)


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    def _sl1(a, b):
        d = a - b
        abs_d = jnp.abs(d)
        loss = jnp.where(abs_d < delta, 0.5 * d * d / delta,
                         abs_d - 0.5 * delta)
        return _reduce(loss, reduction)
    return apply(_sl1, input, label, op_name="smooth_l1_loss")


def binary_cross_entropy(input, label, weight=None, reduction="mean",
                         name=None):
    def _bce(p, t, *w):
        eps = 1e-12
        loss = -(t * jnp.log(jnp.maximum(p, eps))
                 + (1 - t) * jnp.log(jnp.maximum(1 - p, eps)))
        if w:
            loss = loss * w[0]
        return _reduce(loss, reduction)
    args = [input, label] + ([weight] if weight is not None else [])
    return apply(_bce, *args, op_name="binary_cross_entropy")


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None,
                                     name=None):
    def _bcewl(z, t, *extra):
        i = 0
        w = extra[i] if weight is not None else None
        i += 1 if weight is not None else 0
        pw = extra[i] if pos_weight is not None else None
        # stable: max(z,0) - z*t + log(1+exp(-|z|))
        loss = jnp.maximum(z, 0) - z * t + jnp.log1p(jnp.exp(-jnp.abs(z)))
        if pw is not None:
            loss = loss * (t * (pw - 1) + 1)
        if w is not None:
            loss = loss * w
        return _reduce(loss, reduction)
    args = [logit, label]
    if weight is not None:
        args.append(weight)
    if pos_weight is not None:
        args.append(pos_weight)
    return apply(_bcewl, *args, op_name="bce_with_logits")


def kl_div(input, label, reduction="mean", name=None):
    def _kl(logp, t):
        loss = t * (jnp.log(jnp.maximum(t, 1e-12)) - logp)
        if reduction == "batchmean":
            return jnp.sum(loss) / logp.shape[0]
        return _reduce(loss, reduction)
    return apply(_kl, input, label, op_name="kl_div")


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean",
                        name=None):
    return apply(lambda a, b, t: _reduce(
        jnp.maximum(0.0, -t * (a - b) + margin), reduction),
        input, other, label, op_name="margin_ranking_loss")


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean",
                         name=None):
    return apply(lambda a, t: _reduce(
        jnp.where(t == 1, a, jnp.maximum(0.0, margin - a)), reduction),
        input, label, op_name="hinge_embedding_loss")


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    def _cs(a, b):
        dot = jnp.sum(a * b, axis=axis)
        na = jnp.sqrt(jnp.sum(a * a, axis=axis))
        nb = jnp.sqrt(jnp.sum(b * b, axis=axis))
        return dot / jnp.maximum(na * nb, eps)
    return apply(_cs, x1, x2, op_name="cosine_similarity")


def cosine_embedding_loss(input1, input2, label, margin=0, reduction="mean",
                          name=None):
    def _cel(a, b, t):
        cs = jnp.sum(a * b, axis=-1) / jnp.maximum(
            jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1), 1e-12)
        loss = jnp.where(t == 1, 1 - cs, jnp.maximum(0.0, cs - margin))
        return _reduce(loss, reduction)
    return apply(_cel, input1, input2, label, op_name="cosine_embedding_loss")


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum", name=None):
    def _sfl(z, t, *n):
        p = jax.nn.sigmoid(z)
        ce = jnp.maximum(z, 0) - z * t + jnp.log1p(jnp.exp(-jnp.abs(z)))
        p_t = p * t + (1 - p) * (1 - t)
        a_t = alpha * t + (1 - alpha) * (1 - t)
        loss = a_t * ((1 - p_t) ** gamma) * ce
        if n:
            loss = loss / n[0]
        return _reduce(loss, reduction)
    args = [logit, label] + ([normalizer] if normalizer is not None else [])
    return apply(_sfl, *args, op_name="sigmoid_focal_loss")


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC via optax's implementation (reference: warpctc dynload)."""
    import optax
    def _ctc(lp, lab, il, ll):
        # optax expects [B, T, C] logits and paddings
        lp_btc = jnp.transpose(lp, (1, 0, 2)) if lp.ndim == 3 else lp
        B, T, C = lp_btc.shape
        t_idx = jnp.arange(T)[None, :]
        logitpad = (t_idx >= il[:, None]).astype(lp_btc.dtype)
        L = lab.shape[1]
        l_idx = jnp.arange(L)[None, :]
        labelpad = (l_idx >= ll[:, None]).astype(lp_btc.dtype)
        per_seq = optax.ctc_loss(lp_btc, logitpad, lab, labelpad,
                                 blank_id=blank)
        return _reduce(per_seq, reduction)
    return apply(_ctc, log_probs, labels, input_lengths, label_lengths,
                 op_name="ctc_loss")


# ---------------------------------------------------------------------------
# attention (tier-1 jnp path; the Pallas flash kernel replaces it on TPU)
# ---------------------------------------------------------------------------

@jax.named_scope(scopes.ATTENTION)
def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None,
                                 return_weights=False, scale=None,
                                 window=None):
    """[B, L, H, D] attention (paddle incubate layout); ``key`` and
    ``value`` may have fewer heads [B, Lk, Hk, D], ``Hk`` dividing ``H``
    (grouped-query attention: query head h reads key/value head
    ``h // (H / Hk)``; the kernel's index maps do it, XLA's path repeats
    them).  The Pallas
    flash-attention kernel (paddle_tpu.ops.pallas) replaces the jnp path
    when the tier is on (``ops.pallas.support.choose_kernel``), there is
    no ``attn_mask`` and the longer sequence has 512 positions or more,
    the crossover measured on the chip (``flash_attention_supported``;
    reference analog: bert_encoder_functor.cu fused attention).
    Counted at trace time: ``pallas.selected.flash_attention`` /
    ``attention.xla_path``.  ``scale`` multiplies the scores; left at
    None it is ``D ** -0.5`` (a family whose attention multiplier is
    another number hands it in, on either path).  ``window`` (with
    ``is_causal``): sliding-window attention, query t sees the keys s with
    ``t - window < s <= t``; the kernels skip the blocks below the window,
    XLA's path masks the band.  A windowed call runs under the scope
    ``window_attention`` inside this functional's own.

    ``return_weights=True`` forces the unfused path and returns
    ``(out, weights [B, H, Lq, Lk])`` — post-softmax probabilities, with
    dropout applied in training mode (matching the reference, which
    returns the dropped weights: nn/layer/transformer.py:412-431)."""
    if window is None:
        return _sdpa_call(query, key, value, attn_mask, dropout_p,
                          is_causal, training, return_weights, scale, None)
    if not is_causal or int(window) < 1:
        raise ValueError("scaled_dot_product_attention: window is a "
                         "positive count of positions under is_causal, got "
                         f"window={window!r}, is_causal={is_causal!r}")
    with jax.named_scope(scopes.WINDOW_ATTENTION):
        return _sdpa_call(query, key, value, attn_mask, dropout_p,
                          is_causal, training, return_weights, scale,
                          int(window))


def _sdpa_call(query, key, value, attn_mask, dropout_p, is_causal, training,
               return_weights, scale, window):
    """`scaled_dot_product_attention` under its scopes."""
    from ...ops.pallas import flash_attention, flash_attention_supported
    from ...ops.pallas.support import choose_kernel
    eff_dropout = dropout_p if training else 0.0
    supported = not return_weights and flash_attention_supported(
        tuple(query.shape), tuple(key.shape), as_array(query).dtype,
        attn_mask, eff_dropout, v_head_dim=value.shape[-1])
    if choose_kernel("attention", supported):
        if eff_dropout > 0.0:
            fdraw = stable_draw()  # in-trace + replay-stable seed
            return apply(
                lambda q, k, v: flash_attention(
                    q, k, v, causal=is_causal, scale=scale, window=window,
                    dropout_p=eff_dropout,
                    seed=jax.random.bits(fdraw.key(), (1, 1), jnp.uint32)
                    .astype(jnp.int32)),
                query, key, value, op_name="flash_attention")
        return apply(
            lambda q, k, v: flash_attention(q, k, v, causal=is_causal,
                                            scale=scale, window=window),
            query, key, value, op_name="flash_attention")

    use_dropout = dropout_p > 0.0 and training

    sdpa_draw = stable_draw() if use_dropout else None

    def _sdpa(q, k, v, *m):
        # key resolved in-trace (see dropout): static/jitted programs
        # fold the per-run key instead of a record-time constant
        dkey = sdpa_draw.key() if use_dropout else None
        mask = m[0] if m else None
        B, Lq, H, D = q.shape
        if k.shape[2] != H:
            if H % k.shape[2]:
                raise ValueError(f"{k.shape[2]} key/value heads do not "
                                 f"divide {H} query heads")
            k = jnp.repeat(k, H // k.shape[2], axis=2)
            v = jnp.repeat(v, H // v.shape[2], axis=2)
        qt = jnp.einsum("blhd,bshd->bhls", q, k) * (
            1.0 / math.sqrt(D) if scale is None else float(scale))
        if is_causal:
            causal = jnp.tril(jnp.ones((Lq, k.shape[1]), bool))
            if window is not None:      # the band: a query's last keys
                causal &= ~jnp.tril(jnp.ones_like(causal), -window)
            qt = jnp.where(causal[None, None], qt, -jnp.inf)
        if mask is not None:
            if mask.dtype == jnp.bool_:
                qt = jnp.where(mask, qt, -jnp.inf)
            else:
                qt = qt + mask
        w = jax.nn.softmax(qt, axis=-1)
        w_used = w
        if dkey is not None:
            mask = _u16_dropout_mask(dkey, w.shape, dropout_p, w.dtype)
            if mask is not None:
                w_used = w * mask
        out = jnp.einsum("bhls,bshd->blhd", w_used, v)
        if return_weights:
            # post-DROPOUT weights in training mode: the reference passes
            # weights through F.dropout before returning them
            # (nn/layer/transformer.py:412-431)
            return out, w_used
        return out

    args = [query, key, value] + ([attn_mask] if attn_mask is not None else [])
    return apply(_sdpa, *args, op_name="scaled_dot_product_attention")


@jax.named_scope(scopes.ATTN_GATE)
def attention_output_gate(out, gate, name=None):
    """``out * sigmoid(gate)``, elementwise: a gate on attention's output
    (Qiu et al. 2025, "Gated Attention for Large Language Models"), the
    heads' outputs [..., heads * head_dim] under the sigmoid of a
    projection of the same normed state, before the output projection.
    The sigmoid in float32, the result in ``out``'s type; under the scope
    ``attn_gate``."""
    return apply(
        lambda a, g: (a.astype(jnp.float32) * jax.nn.sigmoid(
            g.astype(jnp.float32))).astype(a.dtype),
        out, gate, op_name="attention_output_gate")


# ---------------------------------------------------------------------------
# misc (interpolate, pixel_shuffle, unfold, grid ops, sequence_mask)
# ---------------------------------------------------------------------------

def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW",
                name=None):
    def _interp(a):
        channels_last = not data_format.startswith("NC")
        a_ = a if channels_last else jnp.moveaxis(a, 1, -1)
        spatial = a_.shape[1:-1]
        if size is not None:
            out_sp = _norm_tuple(size, len(spatial))
        else:
            sf = scale_factor if isinstance(scale_factor, (list, tuple)) \
                else [scale_factor] * len(spatial)
            out_sp = tuple(int(s * f) for s, f in zip(spatial, sf))
        m = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
             "trilinear": "linear", "bicubic": "cubic", "area": "linear"}[mode]
        out = jax.image.resize(a_, (a_.shape[0], *out_sp, a_.shape[-1]),
                               method=m)
        return out if channels_last else jnp.moveaxis(out, -1, 1)
    return apply(_interp, x, op_name="interpolate")


upsample = interpolate


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    r = upscale_factor

    def _ps(a):
        N, C, H, W = a.shape
        out = a.reshape(N, C // (r * r), r, r, H, W)
        out = out.transpose(0, 1, 4, 2, 5, 3)
        return out.reshape(N, C // (r * r), H * r, W * r)
    return apply(_ps, x, op_name="pixel_shuffle")


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    k = _norm_tuple(kernel_sizes, 2)
    s = _norm_tuple(strides, 2)
    d = _norm_tuple(dilations, 2)
    p = _conv_padding(paddings, 2)

    def _unfold(a):
        N, C, H, W = a.shape
        patches = jax.lax.conv_general_dilated_patches(
            a, filter_shape=k, window_strides=s, padding=p, rhs_dilation=d,
            dimension_numbers=jax.lax.conv_dimension_numbers(
                a.shape, (1, C, *k), ("NCHW", "OIHW", "NCHW")))
        return patches.reshape(N, C * k[0] * k[1], -1)
    return apply(_unfold, x, op_name="unfold")


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", name=None):
    return _pad_op(x, pad, mode, value, data_format)


def sequence_mask(lengths, maxlen=None, dtype="int64", name=None):
    from ...core.dtype import convert_dtype
    d = convert_dtype(dtype)
    ml = maxlen or int(np.asarray(as_array(lengths)).max())
    return apply(lambda l: (jnp.arange(ml)[None, :] <
                            l[:, None]).astype(d),
                 lengths, op_name="sequence_mask", nondiff=True)


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    def _ls(t, *p):
        n = t.shape[-1]
        if p:
            return (1 - epsilon) * t + epsilon * p[0]
        return (1 - epsilon) * t + epsilon / n
    args = [label] + ([prior_dist] if prior_dist is not None else [])
    return apply(_ls, *args, op_name="label_smooth")


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW",
                   name=None):
    def _ts(a):
        NT, C, H, W = a.shape
        N = NT // seg_num
        v = a.reshape(N, seg_num, C, H, W)
        fold = int(C * shift_ratio)
        left = jnp.concatenate([v[:, 1:, :fold], jnp.zeros_like(
            v[:, :1, :fold])], axis=1)
        right = jnp.concatenate([jnp.zeros_like(v[:, :1, fold:2 * fold]),
                                 v[:, :-1, fold:2 * fold]], axis=1)
        rest = v[:, :, 2 * fold:]
        return jnp.concatenate([left, right, rest], axis=2).reshape(
            NT, C, H, W)
    return apply(_ts, x, op_name="temporal_shift")


def glu(x, axis=-1, name=None):
    return apply(lambda a: jax.nn.glu(a, axis=axis), x, op_name="glu")


def diag_embed(x, offset=0, dim1=-2, dim2=-1):
    def _de(a):
        n = a.shape[-1]
        out = jnp.zeros(a.shape + (n,), a.dtype)
        idx = jnp.arange(n)
        return out.at[..., idx, idx].set(a)
    return apply(_de, x, op_name="diag_embed")


# ---------------------------------------------------------------------------
# round-4 functional parity (reference: nn/functional full surface)
# ---------------------------------------------------------------------------

def log_sigmoid(x, name=None):
    """reference: activation.py log_sigmoid."""
    return apply(jax.nn.log_sigmoid, x, op_name="log_sigmoid",
                 cacheable=True)


def _thresholded_relu_fn(a, *, threshold):
    return jnp.where(a > threshold, a, 0.0)


def thresholded_relu(x, threshold=1.0, name=None):
    return apply(_thresholded_relu_fn, x, op_name="thresholded_relu",
                 threshold=float(threshold), cacheable=True)


def elu_(x, alpha=1.0, name=None):
    out = elu(x, alpha)
    x._rebind(out)
    return x


def relu_(x, name=None):
    out = relu(x)
    x._rebind(out)
    return x


def softmax_(x, axis=-1, dtype=None, name=None):
    out = softmax(x, axis, dtype)
    x._rebind(out)
    return x


def tanh_(x, name=None):
    from ...ops.math import tanh as _tanh
    out = _tanh(x)
    x._rebind(out)
    return x


def square_error_cost(input, label, name=None):
    """reference: loss.py square_error_cost — elementwise (x - y)^2."""
    return apply(lambda a, b: (a - b) ** 2, input, label,
                 op_name="square_error_cost")


def log_loss(input, label, epsilon=1e-4, name=None):
    """reference: loss.py log_loss — binary cross-entropy on
    probabilities."""
    def fn(p, y):
        return (-y * jnp.log(p + epsilon)
                - (1.0 - y) * jnp.log(1.0 - p + epsilon))
    return apply(fn, input, label, op_name="log_loss")


def dice_loss(input, label, epsilon=1e-5, name=None):
    """reference: loss.py dice_loss — 1 - dice coefficient over the
    class probabilities (input [N, ..., C] softmax outputs, label int)."""
    def fn(p, y):
        yf = jax.nn.one_hot(y.squeeze(-1), p.shape[-1], dtype=p.dtype)
        red = tuple(range(1, p.ndim))
        inter = jnp.sum(p * yf, axis=red)
        union = jnp.sum(p, axis=red) + jnp.sum(yf, axis=red)
        return jnp.mean(1.0 - (2.0 * inter + epsilon) / (union + epsilon))
    return apply(fn, input, label, op_name="dice_loss")


def npair_loss(anchor, positive, labels, l2_reg=0.002, name=None):
    """reference: loss.py npair_loss (Sohn 2016): softmax CE over
    anchor·positiveᵀ similarities + L2 on the embeddings."""
    def fn(a, p, y):
        sim = a @ p.T                                 # [B, B]
        lab = (y[:, None] == y[None, :]).astype(a.dtype)
        lab = lab / jnp.maximum(lab.sum(axis=1, keepdims=True), 1.0)
        logp = jax.nn.log_softmax(sim, axis=1)
        ce = -(lab * logp).sum(axis=1).mean()
        reg = l2_reg * ((a ** 2).sum(axis=1) + (p ** 2).sum(axis=1)
                        ).mean() * 0.25
        return ce + reg
    return apply(fn, anchor, positive, labels, op_name="npair_loss")


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    """Hierarchical sigmoid loss (reference: loss.py hsigmoid_loss /
    operators/hierarchical_sigmoid_op.cc).

    Default tree: complete binary tree over ``num_classes`` leaves (leaf
    of class c = node c + num_classes - 1, parent (i-1)//2, code = is-
    right-child) — the reference's non-custom-tree path.  Custom trees
    ride in ``path_table``/``path_code`` [N, L] (padded with -1)."""
    import numpy as np_

    if path_table is None:
        depth = max(int(np_.ceil(np_.log2(max(num_classes, 2)))), 1)
        tbl = np_.full((num_classes, depth), -1, np_.int64)
        code = np_.zeros((num_classes, depth), np_.float32)
        for c in range(num_classes):
            node = c + num_classes - 1
            path = []
            while node > 0:
                parent = (node - 1) // 2
                path.append((parent, float(node == 2 * parent + 2)))
                node = parent
            for d, (pn, bit) in enumerate(reversed(path)):
                tbl[c, d] = pn
                code[c, d] = bit
        la = as_array(label).reshape(-1)
        path_table = Tensor(jnp.asarray(tbl)[la])
        path_code = Tensor(jnp.asarray(code)[la])
    elif path_code is None:
        raise ValueError(
            "hsigmoid_loss: a custom path_table requires path_code")

    args = [input, label, path_table, path_code, weight] + (
        [bias] if bias is not None else [])

    def fn(x, y, tbl, code, w, *mb):
        valid = (tbl >= 0)
        t = jnp.maximum(tbl, 0)
        wn = w[t]                                 # [N, L, D]
        logits = jnp.einsum("nd,nld->nl", x, wn)
        if mb:
            logits = logits + mb[0][t]
        # BCE with the path code at every valid node
        ls = jax.nn.log_sigmoid(logits)
        lns = jax.nn.log_sigmoid(-logits)
        bce = -(code * ls + (1.0 - code) * lns)
        per_ex = (bce * valid).sum(axis=1)
        return per_ex[:, None]                     # [N, 1] like reference

    return apply(fn, *args, op_name="hsigmoid_loss")


def affine_grid(theta, out_shape, align_corners=True, name=None):
    """reference: vision.py affine_grid — sampling grid [N, H, W, 2] from
    2x3 affine matrices."""
    if hasattr(out_shape, "data"):
        out_shape = [int(v) for v in np_asarray(out_shape)]
    N, C, H, W = [int(v) for v in out_shape]

    def fn(th):
        if align_corners:
            ys = jnp.linspace(-1.0, 1.0, H)
            xs = jnp.linspace(-1.0, 1.0, W)
        else:
            ys = (jnp.arange(H) * 2 + 1) / H - 1.0
            xs = (jnp.arange(W) * 2 + 1) / W - 1.0
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        ones = jnp.ones_like(gx)
        base = jnp.stack([gx, gy, ones], axis=-1)      # [H, W, 3]
        return jnp.einsum("hwk,njk->nhwj", base, th)   # [N, H, W, 2]

    return apply(fn, theta, op_name="affine_grid")


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros",
                align_corners=True, name=None):
    """reference: vision.py grid_sample — sample NCHW input at normalized
    grid locations [N, H', W', 2] (x, y order)."""
    if mode not in ("bilinear", "nearest"):
        raise NotImplementedError(f"grid_sample mode {mode!r}")
    if padding_mode not in ("zeros", "border"):
        raise NotImplementedError(
            f"grid_sample padding_mode {padding_mode!r}")

    def fn(a, g):
        N, C, H, W = a.shape
        gx, gy = g[..., 0], g[..., 1]
        if align_corners:
            fx = (gx + 1.0) * (W - 1) / 2.0
            fy = (gy + 1.0) * (H - 1) / 2.0
        else:
            fx = ((gx + 1.0) * W - 1.0) / 2.0
            fy = ((gy + 1.0) * H - 1.0) / 2.0

        def gather(yi, xi):
            yi = jnp.clip(yi, 0, H - 1)
            xi = jnp.clip(xi, 0, W - 1)
            bidx = jnp.arange(N)[:, None, None]
            return a[bidx, :, yi, xi]              # [N, H', W', C]

        # zeros padding masks PER TAP (the rounded/nearest index for
        # 'nearest', each corner for 'bilinear') so boundary-straddling
        # samples keep their partial in-bounds contribution — reference
        # grid_sampler semantics
        inb_idx = lambda yy, xx: ((yy >= 0) & (yy <= H - 1)
                                  & (xx >= 0) & (xx <= W - 1))
        if mode == "nearest":
            yi = jnp.round(fy).astype(jnp.int32)
            xi = jnp.round(fx).astype(jnp.int32)
            out = gather(yi, xi)
            if padding_mode == "zeros":
                out = out * inb_idx(yi, xi)[..., None]
        else:
            y0 = jnp.floor(fy).astype(jnp.int32)
            x0 = jnp.floor(fx).astype(jnp.int32)
            wy = fy - y0
            wx = fx - x0
            vals = 0.0
            for dy, dx, wgt in (
                    (0, 0, (1 - wy) * (1 - wx)), (0, 1, (1 - wy) * wx),
                    (1, 0, wy * (1 - wx)), (1, 1, wy * wx)):
                yi, xi = y0 + dy, x0 + dx
                v = gather(yi, xi)
                if padding_mode == "zeros":
                    v = v * inb_idx(yi, xi)[..., None]
                vals = vals + v * wgt[..., None]
            out = vals
        return jnp.moveaxis(out, -1, 1)            # -> [N, C, H', W']

    return apply(fn, x, grid, op_name="grid_sample")


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW", name=None):
    os = _norm_tuple(output_size, 3)
    channels_last = not data_format.startswith("NC")

    def fn(a):
        if channels_last:                # NDHWC -> NCDHW
            a = jnp.moveaxis(a, -1, 1)
        N, C, D, H, W = a.shape
        if D % os[0] == 0 and H % os[1] == 0 and W % os[2] == 0:
            out = a.reshape(N, C, os[0], D // os[0], os[1], H // os[1],
                            os[2], W // os[2])
            out = out.mean(axis=(3, 5, 7))
            return jnp.moveaxis(out, 1, -1) if channels_last else out
        cells = jnp.zeros((N, C) + tuple(os), a.dtype)
        for i in range(os[0]):
            for j in range(os[1]):
                for k in range(os[2]):
                    blk = a[:, :,
                            (i * D) // os[0]:-(-(i + 1) * D // os[0]),
                            (j * H) // os[1]:-(-(j + 1) * H // os[1]),
                            (k * W) // os[2]:-(-(k + 1) * W // os[2])]
                    cells = cells.at[:, :, i, j, k].set(
                        blk.mean(axis=(2, 3, 4)))
        return jnp.moveaxis(cells, 1, -1) if channels_last else cells
    return apply(fn, x, op_name="adaptive_avg_pool3d")


def adaptive_max_pool3d(x, output_size, return_mask=False, name=None):
    if return_mask:
        raise NotImplementedError(
            "adaptive_max_pool3d: return_mask (argmax indices) is not "
            "implemented — dropping it silently would break "
            "reference-parity unpacking")
    os = _norm_tuple(output_size, 3)

    def fn(a):
        N, C, D, H, W = a.shape
        if D % os[0] == 0 and H % os[1] == 0 and W % os[2] == 0:
            out = a.reshape(N, C, os[0], D // os[0], os[1], H // os[1],
                            os[2], W // os[2])
            return out.max(axis=(3, 5, 7))
        cells = jnp.zeros((N, C) + tuple(os), a.dtype)
        for i in range(os[0]):
            for j in range(os[1]):
                for k in range(os[2]):
                    blk = a[:, :,
                            (i * D) // os[0]:-(-(i + 1) * D // os[0]),
                            (j * H) // os[1]:-(-(j + 1) * H // os[1]),
                            (k * W) // os[2]:-(-(k + 1) * W // os[2])]
                    cells = cells.at[:, :, i, j, k].set(
                        blk.max(axis=(2, 3, 4)))
        return cells
    return apply(fn, x, op_name="adaptive_max_pool3d")


def adaptive_max_pool1d(x, output_size, return_mask=False, name=None):
    if return_mask:
        raise NotImplementedError(
            "adaptive_max_pool1d: return_mask (argmax indices) is not "
            "implemented — dropping it silently would break "
            "reference-parity unpacking")
    os = int(output_size)

    def fn(a):
        N, C, L = a.shape
        if L % os == 0:
            return a.reshape(N, C, os, L // os).max(axis=3)
        return jnp.stack(
            [a[:, :, (i * L) // os:-(-(i + 1) * L // os)].max(axis=2)
             for i in range(os)], axis=-1)
    return apply(fn, x, op_name="adaptive_max_pool1d")


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCL", name=None):
    """reference: conv.py conv1d_transpose — via the 2-D kernel with a
    unit width axis."""
    channels_first = data_format.startswith("NC")
    # NCL -> NCLW (unit W after spatial); NLC -> NL1C (unit W axis 2,
    # keeping channels last)
    x4 = unsqueeze(x, -1 if channels_first else 2)
    w4 = unsqueeze(weight, -1)
    fmt = "NCHW" if channels_first else "NHWC"
    if output_size is not None:
        output_size = [_norm_tuple(output_size, 1)[0], 1]
    out = conv2d_transpose(
        x4, w4, bias, stride=(_norm_tuple(stride, 1)[0], 1),
        padding=(_norm_tuple(padding, 1)[0], 0),
        output_padding=(_norm_tuple(output_padding, 1)[0], 0),
        dilation=(_norm_tuple(dilation, 1)[0], 1), groups=groups,
        output_size=output_size, data_format=fmt)
    return squeeze(out, -1 if channels_first else 2)


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCDHW", name=None):
    """reference: conv.py conv3d_transpose (gradient-of-conv3d)."""
    n = 3
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    outpad = _norm_tuple(output_padding, n)
    if groups != 1:
        raise NotImplementedError("conv3d_transpose: groups > 1")
    channels_last = not data_format.startswith("NC")

    def fn(a, w):
        a_ = jnp.moveaxis(a, -1, 1) if channels_last else a
        pads = _conv_padding(padding, n)
        if isinstance(pads, str):
            raise ValueError(
                "string padding unsupported for conv_transpose")
        outpad_eff = list(outpad)
        if output_size is not None:
            os_ = _norm_tuple(tuple(output_size), n)
            for i in range(n):
                kk = (w.shape[2 + i] - 1) * dilation[i] + 1
                lo, hi = pads[i]
                base = (a_.shape[2 + i] - 1) * stride[i] - lo - hi + kk
                op = os_[i] - base
                if not 0 <= op < max(stride[i], 1) + 1:
                    raise ValueError(
                        f"conv3d_transpose: output_size[{i}]={os_[i]} "
                        f"not reachable (base {base}, stride {stride[i]})")
                outpad_eff[i] = op
        pad_list = []
        for i in range(n):
            kk = (w.shape[2 + i] - 1) * dilation[i] + 1
            lo, hi = pads[i]
            pad_list.append((kk - 1 - lo, kk - 1 - hi + outpad_eff[i]))
        w_t = jnp.swapaxes(jnp.flip(w, axis=(2, 3, 4)), 0, 1)
        dn = jax.lax.conv_dimension_numbers(
            a_.shape, w_t.shape, ("NCDHW", "OIDHW", "NCDHW"))
        out = jax.lax.conv_general_dilated(
            a_, w_t, window_strides=(1, 1, 1), padding=pad_list,
            lhs_dilation=stride, rhs_dilation=dilation,
            dimension_numbers=dn)
        return jnp.moveaxis(out, 1, -1) if channels_last else out

    out = apply(fn, x, weight, op_name="conv3d_transpose")
    if bias is not None:
        shape = [1] * 5
        shape[-1 if channels_last else 1] = -1
        out = out + bias.reshape(shape)
    return out


def np_asarray(x):
    import numpy as _np
    return _np.asarray(x.data if hasattr(x, "data") else x)


# ---------------------------------------------------------------------------
# RMSNorm, rotary positions, EVA attention (appended: the functions above
# keep their source lines, which the compile cache's key holds)
# ---------------------------------------------------------------------------

@jax.named_scope(scopes.RMS_NORM)
def rms_norm(x, weight=None, epsilon=1e-6, unit_offset=False, name=None):
    """``x / sqrt(mean(x^2) + epsilon) * weight`` over the last axis
    (Zhang & Sennrich 2019); ``unit_offset`` applies the weight as
    ``1 + weight``.  The mean of squares is taken in float32 whatever the
    input's type; the result has the weight's type (the input's without
    one), so that bfloat16 weights hand bfloat16 to the matmul that
    follows a float32 residual stream."""
    def _rms(a, *w):
        out = a.astype(jnp.float32)
        out = out * jax.lax.rsqrt(
            jnp.mean(jnp.square(out), axis=-1, keepdims=True) + epsilon)
        if not w:
            return out.astype(a.dtype)
        g = w[0].astype(jnp.float32)
        return (out * (1.0 + g if unit_offset else g)).astype(w[0].dtype)

    args = [x] if weight is None else [x, weight]
    return apply(_rms, *args, op_name="rms_norm")


@jax.named_scope(scopes.ROPE)
def rotary_embedding(x, theta=10000.0, position_ids=None, name=None,
                     interleaved=False):
    """Rotary position embedding (Su et al. 2021) of ``x`` [B, S, H, D]:
    pair i of the vector at position p turns by ``p * theta^(-2i/D)``.
    The pairing is rotate-half, dims (i, i + D/2), or with ``interleaved``
    neighbours, dims (2i, 2i + 1) (the GPT-J / DeepSeek ``rope_interleave``
    layout).  ``position_ids`` [S] or [B, S] defaults to 0..S-1.  Angles,
    cos and sin in float32; the result has the input's type."""
    def _rope(a, *pos):
        D = a.shape[-1]
        p = (pos[0] if pos else jnp.arange(a.shape[1])).astype(jnp.float32)
        freq = float(theta) ** (
            -jnp.arange(0, D, 2, dtype=jnp.float32) / D)
        ang = p[..., None] * freq                         # [(B,) S, D/2]
        cos = jnp.cos(ang)[..., None, :]
        sin = jnp.sin(ang)[..., None, :]
        if interleaved:
            a1 = a[..., 0::2].astype(jnp.float32)
            a2 = a[..., 1::2].astype(jnp.float32)
            return jnp.stack([a1 * cos - a2 * sin, a2 * cos + a1 * sin],
                             axis=-1).reshape(a.shape).astype(a.dtype)
        a1 = a[..., :D // 2].astype(jnp.float32)
        a2 = a[..., D // 2:].astype(jnp.float32)
        return jnp.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin],
                               axis=-1).astype(a.dtype)

    args = [x] if position_ids is None else [x, position_ids]
    return apply(_rope, *args, op_name="rotary_embedding")


@jax.named_scope(scopes.EVA_ATTENTION)
def eva_attention(query, key, value, mu, phi, window_size, chunk_size,
                  scale=None, name=None):
    """EVA attention (Zheng et al. 2023) in EvaByte's deterministic
    chunked form, [B, S, H, D] -> [B, S, H, D]: under one softmax, a query
    sees the exact keys of its own window of ``window_size`` up to itself
    and, for every ``chunk_size`` chunk of every earlier window, one
    summary pair pooled by a softmax inside the chunk against the per-head
    vectors ``mu`` / ``phi`` [H, D].  A row no longer than one window is
    causal attention.  The Pallas kernels (ops/pallas/eva_attention.py)
    run where the kernel tier is on and the shapes allow, the windowed XLA
    path elsewhere.  Counted at trace time:
    ``pallas.selected.eva_attention`` / ``eva_attention.xla_path``."""
    from ...ops.pallas import eva_attention as _eva
    from ...ops.pallas.support import choose_kernel
    kernels = choose_kernel("eva_attention", _eva.eva_attention_supported(
        tuple(query.shape), as_array(query).dtype, window_size, chunk_size))
    fn = _eva.eva_attention if kernels else _eva.eva_attention_xla
    return apply(
        lambda q, k, v, m, f: fn(q, k, v, m, f, window_size, chunk_size,
                                 scale),
        query, key, value, mu, phi, op_name="eva_attention")


# ---------------------------------------------------------------------------
# a routed mixture of experts; attention over a learned selection of keys
# ---------------------------------------------------------------------------

def moe_experts(x, router_weight, w_gate, w_up, w_down, top_k, first_expert=0,
                norm_topk_prob=True, name=None, scoring="softmax",
                router_bias=None, routed_scaling_factor=1.0, shared=None,
                train_router=True, gate_epsilon=None):
    """The part of a mixture-of-experts layer that the held experts give
    (ops/moe.py).  ``x`` [..., H] is the float32 normed stream; the router
    ``router_weight`` [H, E] spans all E experts and runs in float32; the
    expert weights are the held slice, stacked: ``w_gate`` / ``w_up``
    [held, H, F], ``w_down`` [held, F, H], experts ``first_expert ..`` of
    the E.  An expert is a SwiGLU, ``(silu(x Wg) * (x Wu)) Wd``, or, where
    ``w_gate`` is None, ``relu(x Wu)^2 Wd``: two products and no gate (the
    shared expert's gate is then None too).  Every assignment of a token to a held
    expert is computed (no capacity, no dropped token) through grouped
    matmuls; what the absent experts would add is left out.

    ``scoring``: ``"softmax"`` over the experts or ``"sigmoid"`` of each
    logit.  ``router_bias`` [E]: added to the scores for the SELECTION
    only, the gates are the chosen scores' (DeepSeek-V3's bias-corrected
    ``noaux_tc`` selection); it gets no gradient.  The gates are
    multiplied by ``routed_scaling_factor``.  ``gate_epsilon``: what the
    normalisation adds to the sum of a token's chosen scores (a family's
    own constant; None: ops/moe.py's default).  ``shared``: the three
    weights ``(gate [H, Fs], up [H, Fs], down [Fs, H])`` of a shared
    expert, one expert of the same form over every token whose result is added
    unscaled: every member of an expert-parallel group computes it alike,
    so a sum of the members' parts counts it once a member.
    ``train_router`` False holds the router still: the gates carry no
    gradient to ``router_weight`` or, through the router, to ``x`` (for a
    member that runs without its group: ops/moe.py).  Returns float32 of
    x's shape."""
    from ...ops.moe import moe_forward
    if shared is not None and (shared[0] is None) != (w_gate is None):
        raise ValueError("the shared expert has the experts' form: a gate "
                         "where they have one, None where they have none")
    s_gate, s_up, s_down = shared or (None, None, None)
    # the tensors that are there, by name: ``apply`` takes no None
    given = {k: v for k, v in dict(
        w_gate=w_gate, w_up=w_up, w_down=w_down, bias=router_bias,
        s_gate=s_gate, s_up=s_up, s_down=s_down).items() if v is not None}

    def fn(a, r, *values):
        t = dict(zip(given, values))
        return moe_forward(
            a, r, t.get("w_gate"), t["w_up"], t["w_down"], top_k=int(top_k),
            first=int(first_expert), norm_topk_prob=bool(norm_topk_prob),
            scoring=scoring, router_bias=t.get("bias"),
            scaling=float(routed_scaling_factor),
            shared=None if shared is None else
            (t.get("s_gate"), t["s_up"], t["s_down"]),
            train_router=bool(train_router), gate_epsilon=gate_epsilon)

    return apply(fn, x, router_weight, *given.values(), op_name="moe_experts")


def dsa_indexer(index_query, index_key, index_weight, topk, name=None):
    """The selection of DeepSeek Sparse Attention's indexer over one row
    a batch entry: ``index_query`` [B, T, J, d], ``index_key`` [B, T, d]
    (one key shared by the J heads), ``index_weight`` [B, T, J].  The
    score of key s for query t is ``sum_j w[t, j] relu(qI[t, j] . kI[s])``
    in float32; the ``topk`` largest among s <= t are kept (equal scores:
    the lower position; all of them while there are no more than
    ``topk``).  Returns ``(mask, lse)``: the selection as int8
    [B, keys, queries] for ``sparse_attention``, and the logsumexp of each
    query's selected scores [B, T] for ``dsa_indexer_loss``.  Neither
    carries a gradient: the indexer learns from its loss.
    Counted at trace time: ``pallas.selected.dsa_indexer`` /
    ``dsa_indexer.xla_path``."""
    from ...ops.pallas import sparse_attention as _sa
    from ...ops.pallas.support import choose_kernel
    kernels = choose_kernel("dsa_indexer", _sa.dsa_indexer_supported(
        tuple(index_query.shape), as_array(index_query).dtype))
    fn = _sa.dsa_select if kernels else _sa.dsa_select_xla
    return apply(lambda q, w, k: fn(q, w, k, int(topk)), index_query,
                 index_weight, index_key, op_name="dsa_indexer",
                 nondiff=True)


@jax.named_scope(scopes.SPARSE_ATTENTION)
def sparse_attention(query, key, value, mask, return_lse=False, name=None):
    """Grouped-query attention over a per-query selection of keys:
    ``query`` [B, T, A, D], ``key`` / ``value`` [B, T, KV, D] with A a
    multiple of KV (the repeat is never materialised), ``mask`` int8
    [B, keys, queries] from ``dsa_indexer`` (it holds the causal rule);
    scores are scaled by D^-1/2.  Returns [B, T, A, D], and with ``return_lse`` also the softmax's
    log-sum-exp rows [B, A, T] float32, detached.  Pallas kernels on a
    TPU for the shapes they support, a blocked XLA path elsewhere.
    Counted at trace time: ``pallas.selected.sparse_attention`` /
    ``sparse_attention.xla_path``."""
    from ...ops.pallas import sparse_attention as _sa
    from ...ops.pallas.support import choose_kernel
    kernels = choose_kernel("sparse_attention", _sa.sparse_attention_supported(
        tuple(query.shape), tuple(key.shape), as_array(query).dtype))
    fn = _sa.sparse_attention if kernels else _sa.sparse_attention_xla
    out, lse = apply(fn, query, key, value, mask, op_name="sparse_attention")
    return (out, lse) if return_lse else out


@jax.named_scope(scopes.DSA_INDEXER)
def dsa_indexer_loss(index_query, index_key, index_weight, mask, index_lse,
                     query, key, lse, name=None):
    """The indexer's training loss (the sparse training stage of the
    DeepSeek-V3.2-Exp report): mean over every query of the KL divergence
    from the main attention's probabilities over the selected keys,
    summed over the A heads, divided by A and detached, to the softmax of
    the index scores over the same keys.  ``mask`` and ``index_lse`` are
    ``dsa_indexer``'s, ``lse`` is ``sparse_attention``'s.  Gradients
    reach ``index_query``, ``index_key`` and ``index_weight`` only.
    Counted at trace time: ``pallas.selected.dsa_kl`` /
    ``dsa_indexer_loss.xla_path``."""
    from ...ops.pallas import sparse_attention as _sa
    from ...ops.pallas.support import choose_kernel
    kernels = choose_kernel(
        "dsa_indexer_loss",
        _sa.sparse_attention_supported(
            tuple(query.shape), tuple(key.shape), as_array(query).dtype)
        and _sa.dsa_indexer_supported(
            tuple(index_query.shape), as_array(index_query).dtype))
    fn = _sa.dsa_kl if kernels else _sa.dsa_kl_xla
    return apply(
        lambda qi, ki, wi, m, li, q, k, l: fn(qi, wi, ki, m, li, q, k, l),
        index_query, index_key, index_weight, mask, index_lse, query, key,
        lse, op_name="dsa_indexer_loss")


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------

@jax.named_scope(scopes.MLA_ATTENTION)
def mla_attention(q_nope, q_rope, k_nope, k_rope, value, name=None):
    """Causal multi-head latent attention (DeepSeek-V2, section 2.1) over
    its up-projected training form: a head's key is its own ``k_nope``
    [B, S, A, Dn] beside ONE rotated key a position that all heads share,
    ``k_rope`` [B, S, Dr]; ``q_nope`` [B, S, A, Dn], ``q_rope``
    [B, S, A, Dr] (rotated); ``value`` [B, S, A, Dv], whose width need not
    be the keys'.  Scores are scaled by ``(Dn + Dr)^-1/2``.  Returns
    [B, S, A, Dv].

    Where the kernel tier is on and the shapes allow, the flash kernels
    run over the two key parts (``flash_attention_shared_key``: the shared
    key is staged once a batch entry, never broadcast to the heads or
    joined to their keys in HBM); elsewhere XLA's path, which scores the
    two parts apart as well.  Counted at trace time:
    ``pallas.selected.mla_attention`` / ``mla_attention.xla_path``."""
    from ...ops.pallas import flash_attention_supported
    from ...ops.pallas.flash_attention import flash_attention_shared_key
    from ...ops.pallas.support import choose_kernel
    B, S, A, Dn = tuple(q_nope.shape)
    Dr, Dv = q_rope.shape[-1], value.shape[-1]
    if choose_kernel("mla_attention", flash_attention_supported(
            (B, S, A, Dn), (B, S, A, Dn), as_array(q_nope).dtype,
            v_head_dim=Dv, shared_key_dim=Dr)):
        return apply(flash_attention_shared_key, q_nope, q_rope, k_nope,
                     k_rope, value, op_name="mla_attention")

    scale = float(Dn + Dr) ** -0.5

    def _xla(qn, qr, kn, kr, v):
        s = (jnp.einsum("blhd,bshd->bhls", qn, kn,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("blhd,bsd->bhls", qr, kr,
                          preferred_element_type=jnp.float32)) * scale
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s,
                      -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bhls,bshd->blhd", w, v)

    return apply(_xla, q_nope, q_rope, k_nope, k_rope, value,
                 op_name="mla_attention")


# ---------------------------------------------------------------------------
# a looped model's exit
# ---------------------------------------------------------------------------

def _exit_log_probs(z):
    """``z`` [T, ...]: the gate's logits after each of T passes (the
    last row is not read: whoever has not left before the last pass
    leaves there).  -> ``log p`` [T, ...] of the exit distribution
    ``p_1 = l_1``, ``p_t = l_t prod_{j<t} (1 - l_j)``,
    ``p_T = prod_{j<T} (1 - l_j)`` with ``l = sigmoid(z)``, from
    ``log l = log_sigmoid(z)`` and ``log(1 - l) = log_sigmoid(-z)``: no
    ``log 0`` while ``z`` is finite."""
    z = z.astype(jnp.float32)
    T = z.shape[0]
    stay = jnp.cumsum(jax.nn.log_sigmoid(-z[:T - 1]), axis=0)
    before = jnp.concatenate([jnp.zeros_like(z[:1]), stay], axis=0)
    leave = jnp.concatenate([jax.nn.log_sigmoid(z[:T - 1]),
                             jnp.zeros_like(z[:1])], axis=0)
    return before + leave


# ---------------------------------------------------------------------------
# the parts of a state-space mixer (ops/ssm.py)
# ---------------------------------------------------------------------------

@jax.named_scope(scopes.SSM_CONV)
def causal_conv1d(x, weight, bias=None, activation=None, name=None, *,
                  first_channel=0, parts=None):
    """Depthwise causal convolution over time: x [B, T, C], ``weight``
    [K, C] (tap K - 1 meets the position itself, tap 0 the one K - 1
    back), ``bias`` [C]; ``activation`` None or ``"silu"``.  K shifted
    multiply-adds in float32; the result has x's type.

    By keyword, for a caller whose channels lie inside a wider array (a
    mixer's in-projection): ``first_channel``, where the C channels start
    in x [B, T, W >= first_channel + C], and ``parts``, the widths
    (summing to C) to return them in -> a tuple, one [B, T, width] a
    part.  On a TPU, for the shapes they take (the first channel and
    every part whole lane tiles, a part starting on a multiple of its own
    width, K <= 8, T a whole number of the kernel's blocks), two Pallas
    kernels that read the channels where they lie and write each part in
    rows (ops/pallas/causal_conv.py); XLA's slices and the form of
    ops/ssm.py elsewhere.  Counted at trace time:
    ``pallas.selected.causal_conv1d`` / ``causal_conv1d.xla_path``."""
    from ...ops.pallas import causal_conv as _kernels
    from ...ops.pallas.support import choose_kernel
    from ...ops.ssm import causal_conv1d as _conv
    first, C = int(first_channel), int(weight.shape[1])
    widths = None if parts is None else tuple(int(w) for w in parts)
    args = [x, weight] + ([] if bias is None else [bias])
    if choose_kernel("causal_conv1d", _kernels.causal_conv1d_supported(
            tuple(x.shape), tuple(weight.shape), as_array(x).dtype, first,
            widths or (C,), activation)):
        def fn(a, w, *b):
            out = _kernels.causal_conv1d(a, w, b[0] if b else None,
                                         activation, first, widths)
            return out if widths else out[0]
    else:
        def fn(a, w, *b):
            if (first, C) != (0, a.shape[2]):
                a = a[:, :, first:first + C]
            out = _conv(a, w, b[0] if b else None, activation)
            if widths is None:
                return out
            return tuple(jnp.split(out, np.cumsum(widths)[:-1], axis=2))
    return apply(fn, *args, op_name="causal_conv1d")


@jax.named_scope(scopes.SHORT_CONV_OP)
def gated_short_conv(bcz, weight, bias=None, name=None):
    """A gated short convolution, the token mixer of a convolution layer
    (LFM2's ``conv``) between its two projections: ``[B ; C ; z] = bcz``
    [batch, T, 3 H], thirds in that order; ``v = B * z``; ``c`` the
    depthwise causal convolution of v over time (``weight`` [K, H], tap
    K - 1 on the position itself, ``bias`` [H] or None, NO activation);
    ``y = C * c`` -> [batch, T, H] in bcz's type.  Float32 inside; a row
    starts from a zero state.

    On a TPU, without a bias and for the shapes they take (H whole lane
    tiles, K <= 8, T a whole number of the kernels' blocks), two Pallas
    kernels that read B, C and z out of ``bcz`` as the in-projection left
    it and write y, and in the backward ``d bcz`` once
    (ops/pallas/causal_conv.py); the form of ops/ssm.py elsewhere.
    Counted at trace time: ``pallas.selected.gated_short_conv`` /
    ``gated_short_conv.xla_path``."""
    from ...ops.pallas import causal_conv as _kernels
    from ...ops.pallas.support import choose_kernel
    from ...ops.ssm import gated_short_conv as _plain
    takes = bias is None and _kernels.gated_short_conv_supported(
        tuple(bcz.shape), tuple(weight.shape), as_array(bcz).dtype)
    if choose_kernel("gated_short_conv", takes):
        return apply(_kernels.gated_short_conv, bcz, weight,
                     op_name="gated_short_conv")
    args = (bcz, weight) if bias is None else (bcz, weight, bias)
    return apply(_plain, *args, op_name="gated_short_conv")


@jax.named_scope(scopes.SSM_SCAN)
def ssd_scan(x, dt, A, B, C, D, chunk_size=128, name=None):
    """Mamba-2's selective state-space scan (Dao & Gu 2024) in its
    chunked form.  Head h of group ``h // (H / G)`` keeps a state
    [P, N], zero at the start of every row:
    ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``.
    ``x`` [B, T, H, P]; ``dt`` [B, T, H] float32 and positive (after its
    softplus); ``A`` [H] float32, negative; ``B`` and ``C`` [B, T, G, N]
    in x's type; ``D`` [H] float32.  -> y [B, T, H, P] in x's type.
    Matrix products over chunks of ``chunk_size`` positions, the decays
    in float32, a hand-written backward that walks the chunks the other
    way (ops/ssm.py); T need not be a multiple of ``chunk_size``.  On a
    TPU, for the shapes they take (``chunk_size`` 128, a state of whole
    lane tiles, heads of 64 lanes or whole tiles, groups of a multiple of
    8 heads, taken 16 or 8 a grid step: a larger group, such as ONE group
    of 64, is walked in blocks whose dB and dC are summed after the
    kernel), two Pallas kernels that keep a chunk's matrices in VMEM
    (ops/pallas/ssd_scan.py), the XLA form elsewhere.
    Counted at trace time: ``pallas.selected.ssd_scan`` /
    ``ssd_scan.xla_path``."""
    from ...ops.pallas import ssd_scan as _kernels
    from ...ops.pallas.support import choose_kernel
    from ...ops.ssm import ssd_scan as _scan
    chunk = int(chunk_size)
    if choose_kernel("ssd_scan", _kernels.ssd_scan_supported(
            tuple(x.shape), tuple(B.shape), as_array(x).dtype, chunk)):
        fn = _kernels.ssd_scan
    else:
        fn = functools.partial(_scan, chunk=chunk)
    return apply(fn, x, dt, A, B, C, D, op_name="ssd_scan")


@jax.named_scope(scopes.SSM_GATE_NORM)
def gated_group_rms_norm(y, z, weight, groups, epsilon=1e-5, name=None):
    """``RMSNorm(y * silu(z)) * weight`` with the mean of squares taken
    over each of ``groups`` runs of the last axis (Mamba-2's gated norm,
    the gate before the norm), in float32; the result has the weight's
    type."""
    from ...ops.ssm import gated_group_rms_norm as _norm
    return apply(lambda a, g, w: _norm(a, g, w, int(groups), epsilon),
                 y, z, weight, op_name="gated_group_rms_norm")


@jax.named_scope(scopes.LOOP_EXIT)
def loop_exit_distribution(gate_logits, name=None):
    """The distribution over the pass a token leaves a looped model after
    (the LoopLM family's exit gate): ``gate_logits`` [T, ...] float32, one
    row a pass -> ``(p, log_p)``, each [T, ...] float32, ``p`` summing to 1
    over the first axis.  With ``lambda_t = sigmoid(gate_logits[t])``:
    ``p_1 = lambda_1``, ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` and
    ``p_T = prod_{j<T} (1 - lambda_j)``; the last row of the logits is not
    read.  The logarithms are sums of ``log_sigmoid``, so an entropy
    ``-sum p log_p`` has no ``0 * log 0`` at a saturated gate."""
    def _dist(z):
        log_p = _exit_log_probs(z)
        return jnp.exp(log_p), log_p
    return apply(_dist, gate_logits, op_name="loop_exit_distribution")


def loop_exit_loss(states, gate_logits, weight, bias, label, beta=0.1,
                   chunk: Optional[int] = None, ignore_index: int = -100,
                   name=None):
    """A looped model's training objective over its T exits (the LoopLM
    family's pre-training stage: the expected loss under the exit
    distribution, entropy-regularised towards a uniform prior over the
    exit step), mean over the tokens that are not ``ignore_index``:

        ``sum_t p_t * CE(states[t] @ weight + bias, label) - beta * H(p)``

    ``states`` [T, N, H] (exit t's state of each token), ``gate_logits``
    [T, N] (``loop_exit_distribution``'s input), ``weight`` [H, vocab] and
    ``bias`` [vocab] the one head of every exit, ``label`` [N].  Gradients
    reach ``p`` from the exits' losses and from the entropy, and the
    states and the head weighted by ``p``.

    The exits' losses come out of ONE pass of the chunked head over the T
    exits stacked to [T * N, H] (``linear_cross_entropy`` with
    ``token_weight`` = ``p_t`` over the number of kept tokens), so no
    [N, vocab] logits and no vector of losses is held for the backward
    pass, and the head's gradient is accumulated once: a pass an exit
    holds T float32 partial gradients of the head (2.0 GB more at
    2048 x 49,152 and T = 4, for the same step time; PERF.md, PR 37).
    ``chunk`` goes to that call as it is: ``None`` (the default) lets the
    head choose its rows as it does for any caller (1024, and ``dh`` 512
    rows a product for a float32 head over 32,768 columns or more;
    counted as ``linear_cross_entropy.rows.<rows>`` and
    ``.dh_rows.<rows>``), at four ``[rows, vocab]`` float32 arrays a
    chunk; an int is taken as given.
    The distribution, the entropy and the weights sit under the scope
    ``loop_exit``.  Device counters, float32: ``loop.exit_share`` [T] (the
    mean of ``p_t``) and ``loop.exit_entropy`` (the mean of H)."""
    from ...observability import device_counter
    from ...ops.manipulation import tile

    def _weights(z, lab):
        with jax.named_scope(scopes.LOOP_EXIT):
            log_p = _exit_log_probs(z)
            p = jnp.exp(log_p)
            keep = (lab != ignore_index)
            per = 1.0 / jnp.maximum(jnp.sum(keep), 1).astype(jnp.float32)
            kept = jnp.where(keep, per, 0.0)
            entropy = -jnp.sum(kept * jnp.sum(p * log_p, axis=0))
            device_counter(scopes.LOOP_EXIT_SHARE, jnp.sum(p * kept, axis=1))
            device_counter(scopes.LOOP_EXIT_ENTROPY, entropy)
            return p * kept, entropy

    w, entropy = apply(_weights, gate_logits, label,
                       op_name="loop_exit_weights")
    T, N = states.shape[0], states.shape[1]
    return linear_cross_entropy(
        states.reshape([T * N, states.shape[2]]), weight, bias,
        tile(label, [T]), chunk=chunk, ignore_index=ignore_index,
        token_weight=w.reshape([T * N])) - float(beta) * entropy


from ..decode import gather_tree  # noqa: F401,E402

from . import activation, common, conv, extension, loss, pooling  # noqa
