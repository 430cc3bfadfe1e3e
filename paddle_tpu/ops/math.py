"""Elementwise + linalg math ops (reference: python/paddle/tensor/math.py,
paddle/fluid/operators/elementwise/*, operators/matmul_v2_op.*).

Each public op wraps a pure jnp function through :func:`core.dispatch.apply`;
XLA fuses the elementwise zoo into surrounding matmuls on TPU, which replaces
the reference's hand-written fusion passes (ir/*_fuse_pass.cc)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.dispatch import apply, as_array
from ..core.tensor import Tensor


def _binop(jfn, name):
    def op(x, y, name=None):
        return apply(jfn, x, y, op_name=name, cacheable=True)
    op.__name__ = name
    return op


def _unop(jfn, name):
    def op(x, name=None):
        return apply(jfn, x, op_name=name, cacheable=True)
    op.__name__ = name
    return op


add = _binop(jnp.add, "add")
subtract = _binop(jnp.subtract, "subtract")
multiply = _binop(jnp.multiply, "multiply")
divide = _binop(jnp.divide, "divide")
floor_divide = _binop(jnp.floor_divide, "floor_divide")
remainder = _binop(jnp.remainder, "remainder")
mod = remainder
floor_mod = remainder
maximum = _binop(jnp.maximum, "maximum")
minimum = _binop(jnp.minimum, "minimum")
fmax = _binop(jnp.fmax, "fmax")
fmin = _binop(jnp.fmin, "fmin")
atan2 = _binop(jnp.arctan2, "atan2")
hypot = _binop(jnp.hypot, "hypot")

exp = _unop(jnp.exp, "exp")
expm1 = _unop(jnp.expm1, "expm1")
log = _unop(jnp.log, "log")
log2 = _unop(jnp.log2, "log2")
log10 = _unop(jnp.log10, "log10")
log1p = _unop(jnp.log1p, "log1p")
sqrt = _unop(jnp.sqrt, "sqrt")
rsqrt = _unop(jax.lax.rsqrt, "rsqrt")
square = _unop(jnp.square, "square")
abs = _unop(jnp.abs, "abs")
sign = _unop(jnp.sign, "sign")
neg = _unop(jnp.negative, "neg")
floor = _unop(jnp.floor, "floor")
ceil = _unop(jnp.ceil, "ceil")
round = _unop(jnp.round, "round")
trunc = _unop(jnp.trunc, "trunc")
frac = _unop(lambda a: a - jnp.trunc(a), "frac")
sin = _unop(jnp.sin, "sin")
cos = _unop(jnp.cos, "cos")
tan = _unop(jnp.tan, "tan")
asin = _unop(jnp.arcsin, "asin")
acos = _unop(jnp.arccos, "acos")
atan = _unop(jnp.arctan, "atan")
sinh = _unop(jnp.sinh, "sinh")
cosh = _unop(jnp.cosh, "cosh")
tanh = _unop(jnp.tanh, "tanh")
asinh = _unop(jnp.arcsinh, "asinh")
acosh = _unop(jnp.arccosh, "acosh")
atanh = _unop(jnp.arctanh, "atanh")
erf = _unop(jax.scipy.special.erf, "erf")
erfinv = _unop(jax.scipy.special.erfinv, "erfinv")
sigmoid = _unop(jax.nn.sigmoid, "sigmoid")
reciprocal = _unop(jnp.reciprocal, "reciprocal")
digamma = _unop(jax.scipy.special.digamma, "digamma")
lgamma = _unop(jax.scipy.special.gammaln, "lgamma")
isnan = _unop(jnp.isnan, "isnan")
isinf = _unop(jnp.isinf, "isinf")
isfinite = _unop(jnp.isfinite, "isfinite")
conj = _unop(jnp.conj, "conj")
real = _unop(jnp.real, "real")
imag = _unop(jnp.imag, "imag")
angle = _unop(jnp.angle, "angle")


def pow(x, y, name=None):
    return apply(jnp.power, x, y, op_name="pow")


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    """reference: operators/scale_op.cc semantics."""
    def _scale(a, s, b):
        out = a * s + b if bias_after_scale else (a + b) * s
        return out
    out = apply(_scale, x, scale, bias, op_name="scale")
    if act is not None:
        from ..nn import functional as F
        out = getattr(F, act)(out)
    return out


def increment(x, value=1.0, name=None):
    out = apply(lambda a: a + value, x, op_name="increment")
    x._rebind(out)
    return x


def clip(x, min=None, max=None, name=None):
    return apply(lambda a: jnp.clip(a, as_array(min) if min is not None else None,
                                    as_array(max) if max is not None else None),
                 x, op_name="clip")


def lerp(x, y, weight, name=None):
    return apply(lambda a, b, w: a + w * (b - a), x, y, weight, op_name="lerp")


def stanh(x, scale_a=0.67, scale_b=1.7159, name=None):
    return apply(lambda a: scale_b * jnp.tanh(scale_a * a), x, op_name="stanh")


def rad2deg(x, name=None):
    return apply(jnp.rad2deg, x, op_name="rad2deg")


def deg2rad(x, name=None):
    return apply(jnp.deg2rad, x, op_name="deg2rad")


def multiplex(inputs, index, name=None):
    def _mpx(idx, *xs):
        stacked = jnp.stack(xs, axis=0)
        return jnp.take_along_axis(
            stacked, idx.reshape(1, -1, *([1] * (stacked.ndim - 2))), axis=0
        )[0]
    return apply(_mpx, index, *inputs, op_name="multiplex")


# -- matmul family ---------------------------------------------------------

def matmul(x, y, transpose_x=False, transpose_y=False, name=None):
    """reference: operators/matmul_v2_op.* — maps straight onto the MXU."""
    def _matmul(a, b):
        if transpose_x:
            a = jnp.swapaxes(a, -1, -2) if a.ndim >= 2 else a
        if transpose_y:
            b = jnp.swapaxes(b, -1, -2) if b.ndim >= 2 else b
        return jnp.matmul(a, b)
    return apply(_matmul, x, y, op_name="matmul")


mm = matmul


def bmm(x, y, name=None):
    return apply(jnp.matmul, x, y, op_name="bmm")


def dot(x, y, name=None):
    return apply(lambda a, b: jnp.sum(a * b, axis=-1), x, y, op_name="dot")


def inner(x, y, name=None):
    return apply(jnp.inner, x, y, op_name="inner")


def outer(x, y, name=None):
    return apply(lambda a, b: jnp.outer(a, b), x, y, op_name="outer")


def addmm(input, x, y, beta=1.0, alpha=1.0, name=None):
    return apply(lambda i, a, b: beta * i + alpha * jnp.matmul(a, b),
                 input, x, y, op_name="addmm")


def kron(x, y, name=None):
    return apply(jnp.kron, x, y, op_name="kron")


def cross(x, y, axis=9, name=None):
    ax = axis if axis != 9 else None
    def _cross(a, b):
        axx = ax
        if axx is None:
            for i, d in enumerate(a.shape):
                if d == 3:
                    axx = i
                    break
        return jnp.cross(a, b, axis=axx)
    return apply(_cross, x, y, op_name="cross")


def einsum(equation, *operands):
    return apply(lambda *xs: jnp.einsum(equation, *xs), *operands,
                 op_name="einsum")


# -- cumulative ------------------------------------------------------------

def cumsum(x, axis=None, dtype=None, name=None):
    from ..core.dtype import convert_dtype
    d = convert_dtype(dtype)
    def _cumsum(a):
        if axis is None:
            a = a.reshape(-1)
            return jnp.cumsum(a, dtype=d)
        return jnp.cumsum(a, axis=axis, dtype=d)
    return apply(_cumsum, x, op_name="cumsum")


def cumprod(x, dim=None, dtype=None, name=None):
    from ..core.dtype import convert_dtype
    d = convert_dtype(dtype)
    return apply(lambda a: jnp.cumprod(a, axis=dim, dtype=d), x,
                 op_name="cumprod")


def cummax(x, axis=None, dtype="int64", name=None):
    def _cummax(a):
        ax = axis if axis is not None else 0
        arr = a.reshape(-1) if axis is None else a
        vals = jax.lax.associative_scan(jnp.maximum, arr, axis=ax)
        return vals
    return apply(_cummax, x, op_name="cummax")


def logcumsumexp(x, axis=None, dtype=None, name=None):
    def _lcse(a):
        arr = a.reshape(-1) if axis is None else a
        ax = 0 if axis is None else axis
        return jax.lax.cumlogsumexp(arr, axis=ax)
    return apply(_lcse, x, op_name="logcumsumexp")


def logaddexp(x, y, name=None):
    return apply(jnp.logaddexp, x, y, op_name="logaddexp")


def trace(x, offset=0, axis1=0, axis2=1, name=None):
    return apply(lambda a: jnp.trace(a, offset=offset, axis1=axis1,
                                     axis2=axis2), x, op_name="trace")


def nan_to_num(x, nan=0.0, posinf=None, neginf=None, name=None):
    return apply(lambda a: jnp.nan_to_num(a, nan=nan, posinf=posinf,
                                          neginf=neginf), x,
                 op_name="nan_to_num")


def add_n(inputs, name=None):
    """reference: operators/sum_op.cc — elementwise sum of a tensor list."""
    if not isinstance(inputs, (list, tuple)):
        inputs = [inputs]

    def fn(*arrs):
        out = arrs[0]
        for a in arrs[1:]:
            out = out + a
        return out

    return apply(fn, *inputs, op_name="add_n")


def mv(x, vec, name=None):
    """reference: operators/mv_op.cc — matrix @ vector."""
    return apply(lambda a, v: a @ v, x, vec, op_name="mv")


def tanh_(x, name=None):
    """Inplace tanh (reference inplace op tanh_)."""
    out = tanh(x)
    x._rebind(out)
    return x


def broadcast_shape(x_shape, y_shape):
    """reference: tensor/manipulation broadcast_shape."""
    return list(jnp.broadcast_shapes(tuple(x_shape), tuple(y_shape)))


def rank(input, name=None):
    """Tensor rank as a 0-d int tensor (tensor/attribute.py rank)."""
    from ..core.dispatch import as_array
    return Tensor(jnp.asarray(as_array(input).ndim, jnp.int32))


def shape(input, name=None):
    """Runtime shape as a 1-d int tensor (tensor/attribute.py shape)."""
    from ..core.dispatch import as_array
    return Tensor(jnp.asarray(as_array(input).shape, jnp.int32))
