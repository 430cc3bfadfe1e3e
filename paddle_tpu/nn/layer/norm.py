"""Normalisation layers (reference: python/paddle/nn/layer/norm.py;
batch_norm_op.cc, layer_norm_op.cc).

BatchNorm running stats are registered buffers; in eager training mode the
layer updates them in place.  Under jit, the functionalize pass captures
buffer writes and threads them through the compiled step (SURVEY §7
hard-parts: in-place semantics under functional XLA)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import autograd
from ...core.tensor import Tensor
from .. import functional as F
from .. import initializer as I
from ..layer_base import Layer


class _BatchNormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 use_global_stats=None, name=None):
        super().__init__()
        self._num_features = num_features
        self._momentum = momentum
        self._epsilon = epsilon
        self._data_format = data_format
        self._use_global_stats = use_global_stats
        self.weight = (None if weight_attr is False else
                       self.create_parameter(
                           [num_features], attr=weight_attr,
                           default_initializer=I.Constant(1.0)))
        self.bias = (None if bias_attr is False else
                     self.create_parameter([num_features], attr=bias_attr,
                                           is_bias=True))
        self.register_buffer("_mean", Tensor(jnp.zeros([num_features])))
        self.register_buffer("_variance", Tensor(jnp.ones([num_features])))

    def forward(self, x):
        training = self.training and not self._use_global_stats
        if training:
            # update running stats (in eager; functionalized under jit)
            ch_axis = (1 if self._data_format.startswith("NC")
                       and x.ndim > 1 else -1)
            axes = tuple(i for i in range(x.ndim)
                         if i != ch_axis % x.ndim)
            with autograd.no_grad():
                m = jnp.mean(x.data, axis=axes)
                v = jnp.var(x.data, axis=axes)
                mom = self._momentum
                self._mean.data = mom * self._mean.data + (1 - mom) * m
                self._variance.data = (mom * self._variance.data
                                       + (1 - mom) * v)
        return F.batch_norm(x, self._mean, self._variance, self.weight,
                            self.bias, training=training,
                            momentum=self._momentum, epsilon=self._epsilon,
                            data_format=self._data_format,
                            use_global_stats=self._use_global_stats)

    def extra_repr(self):
        return f"num_features={self._num_features}"


class BatchNorm1D(_BatchNormBase):
    pass


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCDHW",
                 use_global_stats=None, name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr,
                         bias_attr, "NCHW" if data_format == "NCDHW"
                         else data_format, use_global_stats, name)


class BatchNorm(_BatchNormBase):
    """Old-style paddle.nn.BatchNorm (fluid dygraph BatchNorm parity)."""

    def __init__(self, num_channels, act=None, momentum=0.9, epsilon=1e-5,
                 param_attr=None, bias_attr=None, dtype="float32",
                 data_layout="NCHW", in_place=False, moving_mean_name=None,
                 moving_variance_name=None, do_model_average_for_mean_and_var=True,
                 use_global_stats=False, trainable_statistics=False):
        super().__init__(num_channels, momentum, epsilon, param_attr,
                         bias_attr, data_layout,
                         use_global_stats or None)
        self._act = act

    def forward(self, x):
        out = super().forward(x)
        if self._act:
            out = getattr(F, self._act)(out)
        return out


class SyncBatchNorm(_BatchNormBase):
    """On TPU, batch stats sync falls out of SPMD compilation: under pjit
    the mean/var reductions become cross-replica automatically (reference's
    sync_batch_norm_op.cu is NCCL-based; no analog needed)."""

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        """Under the SPMD train step, batch norm statistics are computed
        over the GLOBAL (dp-sharded) batch by GSPMD, so conversion is the
        identity.  Under eager multi-process DataParallel there is no
        cross-process stat sync — warn so the silent-identity isn't
        mistaken for NCCL SyncBatchNorm."""
        import warnings
        from ...distributed.env import get_world_size
        if get_world_size() > 1:
            warnings.warn(
                "convert_sync_batchnorm: running stats are NOT synced "
                "across eager DataParallel processes; use the SPMD train "
                "step (batch sharded over 'dp') for global-batch BN "
                "statistics")
        return layer


class LayerNorm(Layer):
    """reference: nn/layer/norm.py LayerNorm → layer_norm_op.cc."""

    def __init__(self, normalized_shape, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self._normalized_shape = tuple(normalized_shape)
        self._epsilon = epsilon
        self.weight = (None if weight_attr is False else
                       self.create_parameter(
                           self._normalized_shape, attr=weight_attr,
                           default_initializer=I.Constant(1.0)))
        self.bias = (None if bias_attr is False else
                     self.create_parameter(self._normalized_shape,
                                           attr=bias_attr, is_bias=True))

    def forward(self, x):
        return F.layer_norm(x, self._normalized_shape, self.weight,
                            self.bias, self._epsilon)

    def extra_repr(self):
        return f"normalized_shape={list(self._normalized_shape)}"


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self._num_groups = num_groups
        self._epsilon = epsilon
        self._data_format = data_format
        self.weight = (None if weight_attr is False else
                       self.create_parameter(
                           [num_channels], attr=weight_attr,
                           default_initializer=I.Constant(1.0)))
        self.bias = (None if bias_attr is False else
                     self.create_parameter([num_channels], attr=bias_attr,
                                           is_bias=True))

    def forward(self, x):
        return F.group_norm(x, self._num_groups, self._epsilon, self.weight,
                            self.bias, self._data_format)


class InstanceNorm2D(Layer):
    def __init__(self, num_features, epsilon=1e-5, momentum=0.9,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self._epsilon = epsilon
        self.weight = (None if weight_attr is False else
                       self.create_parameter(
                           [num_features], attr=weight_attr,
                           default_initializer=I.Constant(1.0)))
        self.bias = (None if bias_attr is False else
                     self.create_parameter([num_features], attr=bias_attr,
                                           is_bias=True))

    def forward(self, x):
        return F.instance_norm(x, weight=self.weight, bias=self.bias,
                               eps=self._epsilon)


InstanceNorm1D = InstanceNorm2D
InstanceNorm3D = InstanceNorm2D


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=1e-4, beta=0.75, k=1.0,
                 data_format="NCHW", name=None):
        super().__init__()
        self.args = (size, alpha, beta, k, data_format)

    def forward(self, x):
        return F.local_response_norm(x, *self.args)


class SpectralNorm(Layer):
    def __init__(self, weight_shape, dim=0, power_iters=1, eps=1e-12,
                 name=None):
        super().__init__()
        self._dim = dim
        self._power_iters = power_iters
        self._eps = eps
        h = weight_shape[dim]
        w = 1
        for i, s in enumerate(weight_shape):
            if i != dim:
                w *= s
        self.weight_u = self.create_parameter(
            [h], default_initializer=I.Normal(0.0, 1.0))
        self.weight_u.stop_gradient = True
        self.weight_v = self.create_parameter(
            [w], default_initializer=I.Normal(0.0, 1.0))
        self.weight_v.stop_gradient = True

    def forward(self, weight):
        from ...core.dispatch import apply
        dim, iters, eps = self._dim, self._power_iters, self._eps

        def _sn(w, u, v):
            wm = jnp.moveaxis(w, dim, 0).reshape(w.shape[dim], -1)
            for _ in range(iters):
                v = wm.T @ u
                v = v / (jnp.linalg.norm(v) + eps)
                u = wm @ v
                u = u / (jnp.linalg.norm(u) + eps)
            sigma = u @ wm @ v
            return w / sigma
        return apply(_sn, weight, self.weight_u, self.weight_v,
                     op_name="spectral_norm")


class RMSNorm(Layer):
    """Root-mean-square norm over the last axis (Zhang & Sennrich 2019):
    ``x / sqrt(mean(x^2) + epsilon) * weight``.  ``unit_offset`` stores the
    gain as an offset from one (initialised 0, applied as ``1 + weight``),
    as EvaByte's ``norm_add_unit_offset`` does."""

    def __init__(self, hidden_size, epsilon=1e-6, unit_offset=False,
                 weight_attr=None, name=None):
        super().__init__()
        self._hidden_size = int(hidden_size)
        self._epsilon = epsilon
        self._unit_offset = bool(unit_offset)
        self.weight = self.create_parameter(
            [self._hidden_size], attr=weight_attr,
            default_initializer=I.Constant(0.0 if unit_offset else 1.0))

    def forward(self, x):
        return F.rms_norm(x, self.weight, self._epsilon, self._unit_offset)

    def extra_repr(self):
        return (f"hidden_size={self._hidden_size}, epsilon={self._epsilon}, "
                f"unit_offset={self._unit_offset}")
