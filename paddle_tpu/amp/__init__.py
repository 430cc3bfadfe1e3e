"""Automatic mixed precision.

Reference: python/paddle/amp/ (auto_cast.py:20, grad_scaler.py:20) backed by
C++ autocast hooks in the dygraph tracer (imperative/amp_auto_cast.h:31) and
static-mode decoration (fluid/contrib/mixed_precision/decorator.py:415).

TPU-first: the preferred low-precision dtype is **bfloat16** (MXU-native, no
loss scaling needed); float16 is supported for parity and engages the
GradScaler.  The cast hook lives at the shared dispatch point
(core/dispatch.py) so it applies identically in eager and traced modes —
the same design as the reference's single autocast hook in Tracer::TraceOp
(tracer.cc:160-163).
"""
from __future__ import annotations

import contextlib
import threading
import time

import jax.numpy as jnp
import numpy as np

from ..core.dtype import convert_dtype
from ..core.tensor import Tensor
from ..observability.compiles import setup_span
from ..utils import monitor

# op lists (reference: fluid/contrib/mixed_precision/fp16_lists.py)
WHITE_LIST = {
    "matmul", "mm", "bmm", "linear", "conv1d", "conv2d", "conv3d",
    "conv2d_transpose", "einsum", "scaled_dot_product_attention",
    "flash_attention", "eva_attention",
}
BLACK_LIST = {
    "exp", "log", "square", "mean", "sum", "softmax", "log_softmax",
    "cross_entropy", "nll_loss", "bce_with_logits", "binary_cross_entropy",
    "layer_norm", "batch_norm", "group_norm", "instance_norm", "norm",
    "logsumexp", "softmax_with_cross_entropy", "cosine_similarity",
    "kl_div", "sigmoid_focal_loss", "erf", "erfinv", "pow", "cumsum",
    "rms_norm",
}

_tls = threading.local()


def _state():
    if not hasattr(_tls, "amp"):
        _tls.amp = None
    return _tls.amp


class _AmpState:
    __slots__ = ("dtype", "level", "white", "black")

    def __init__(self, dtype, level, white, black):
        self.dtype = dtype
        self.level = level
        self.white = white
        self.black = black


def amp_active():
    return _state() is not None


def amp_cast_inputs(op_name: str, arrays):
    """Called from core.dispatch.apply for every op when AMP is on."""
    st = _state()
    if st is None:
        return arrays

    def _cast(a, dt):
        if hasattr(a, "dtype") and jnp.issubdtype(
                np.dtype(a.dtype), np.floating) and a.dtype != dt:
            if np.dtype(a.dtype) in (np.dtype(np.float16),
                                     np.dtype(jnp.bfloat16),
                                     np.dtype(np.float32)):
                return a.astype(dt)
        return a

    if op_name in st.black:
        return [_cast(a, jnp.float32) for a in arrays]
    if op_name in st.white or st.level == "O2":
        return [_cast(a, st.dtype) for a in arrays]
    return arrays


@contextlib.contextmanager
def auto_cast(enable=True, custom_white_list=None, custom_black_list=None,
              level="O1", dtype="bfloat16"):
    """paddle.amp.auto_cast parity (reference: amp/auto_cast.py:20).

    level O1: white-listed ops run in low precision; black-listed forced to
    float32.  level O2: everything except the black list runs low-precision.
    """
    prev = _state()
    if enable:
        white = set(WHITE_LIST)
        black = set(BLACK_LIST)
        if custom_white_list:
            white |= set(custom_white_list)
            black -= set(custom_white_list)
        if custom_black_list:
            black |= set(custom_black_list)
            white -= set(custom_black_list)
        _tls.amp = _AmpState(convert_dtype(dtype), level, white, black)
    else:
        _tls.amp = None
    try:
        yield
    finally:
        _tls.amp = prev


amp_guard = auto_cast  # fluid-era alias


def decorate(models=None, optimizers=None, level="O2", dtype="bfloat16",
             master_weight=None, save_dtype=None):
    """paddle.amp.decorate parity: cast model params to the AMP dtype
    (pure-fp16/bf16 mode) and enable optimizer master weights."""
    dt = convert_dtype(dtype)
    single = not isinstance(models, (list, tuple))
    ms = [models] if single else list(models)
    # the casts, an eager op a parameter: a set-up span and an always-on
    # counter, as parameter creation's
    t0 = time.perf_counter()
    with setup_span("setup.amp_decorate"):
        for m in ms:
            if m is not None:
                m.to(dtype=dt)
                # record the decorated dtype; jit.TrainStep(amp_level=...)
                # uses it when the caller opts into tracing under auto_cast
                m._amp_dtype = dt
    monitor.stat_add("setup.amp_decorate_s", time.perf_counter() - t0)
    if optimizers is not None:
        opts = ([optimizers] if not isinstance(optimizers, (list, tuple))
                else list(optimizers))
        for o in opts:
            o._multi_precision = True if master_weight is None else bool(
                master_weight)
        if single and not isinstance(optimizers, (list, tuple)):
            return models, optimizers
        return ms, opts
    return models if single else ms


class GradScaler:
    """Dynamic loss scaling (reference: amp/grad_scaler.py:20; static twin:
    check_finite_and_unscale + update_loss_scaling ops, operators/amp/).

    Needed only for float16; bfloat16 training normally runs unscaled."""

    def __init__(self, enable=True, init_loss_scaling=2.0 ** 15,
                 incr_ratio=2.0, decr_ratio=0.5, incr_every_n_steps=1000,
                 decr_every_n_nan_or_inf=1, use_dynamic_loss_scaling=True):
        self._enable = enable
        self._scale = float(init_loss_scaling)
        self._incr_ratio = incr_ratio
        self._decr_ratio = decr_ratio
        self._incr_every = incr_every_n_steps
        self._decr_every = decr_every_n_nan_or_inf
        self._dynamic = use_dynamic_loss_scaling
        self._good_steps = 0
        self._bad_steps = 0
        self._found_inf = False
        self._unscaled = {}  # id(optimizer) -> found_inf for this step

    def scale(self, var):
        if not self._enable:
            return var
        return var * self._scale

    def unscale_(self, optimizer):
        if not self._enable:
            return
        if id(optimizer) in self._unscaled:
            raise RuntimeError(
                "unscale_() has already been called on this optimizer "
                "since the last update()")
        if not self._unscaled:
            # first unscale of this step: recompute found_inf fresh so a
            # stale inf from a prior skipped-update iteration can't leak
            # into this step's decision
            self._found_inf = False
        inv = 1.0 / self._scale
        checks = []
        for p in optimizer._parameter_list or []:
            if p._grad_data is None:
                continue
            g = p._grad_data * inv
            checks.append(jnp.all(jnp.isfinite(g)))
            p._grad_data = g
        # one host sync for the whole param list, not one per param
        found = bool(not jnp.all(jnp.stack(checks))) if checks else False
        self._unscaled[id(optimizer)] = found
        self._found_inf = self._found_inf or found

    def step(self, optimizer):
        """Unscale (if not already) and apply the optimizer step unless inf/
        nan was found.  Call ``update()`` once per iteration afterwards
        (paddle 2.x flow); ``minimize`` does both."""
        if not self._enable:
            optimizer.step()
            return
        if id(optimizer) not in self._unscaled:
            self.unscale_(optimizer)
        # pop: the entry covers exactly one step, so the next iteration's
        # step() re-unscales even if the user skips update()
        if not self._unscaled.pop(id(optimizer)):
            optimizer.step()

    def minimize(self, optimizer, scaled_loss):
        scaled_loss.backward()
        self.step(optimizer)
        self.update()

    def update(self):
        self._unscaled.clear()
        if not (self._enable and self._dynamic):
            return
        if self._found_inf:
            self._bad_steps += 1
            self._good_steps = 0
            if self._bad_steps >= self._decr_every:
                self._scale = max(self._scale * self._decr_ratio, 1.0)
                self._bad_steps = 0
        else:
            self._good_steps += 1
            self._bad_steps = 0
            if self._good_steps >= self._incr_every:
                self._scale *= self._incr_ratio
                self._good_steps = 0
        self._found_inf = False

    def is_enable(self):
        return self._enable

    def is_use_dynamic_loss_scaling(self):
        return self._dynamic

    def get_init_loss_scaling(self):
        return self._scale

    def state_dict(self):
        self._sync_from_bound_step()
        return {"scale": self._scale, "good_steps": self._good_steps,
                "bad_steps": self._bad_steps}

    def load_state_dict(self, sd):
        self._scale = sd["scale"]
        self._good_steps = sd["good_steps"]
        self._bad_steps = sd["bad_steps"]
        # invalidate any compiled TrainStep's in-graph state so the next
        # step reinitialises from the loaded values
        step = getattr(self, "_bound_step", None)
        if step is not None:
            step._scaler_state = None

    def _sync_from_bound_step(self):
        """Pull the in-graph loss-scaling state from a TrainStep that
        threads this scaler through its compiled step (jit/train_step.py);
        one host sync, used at checkpoint time only."""
        step = getattr(self, "_bound_step", None)
        st = getattr(step, "_scaler_state", None)
        if st and "scale" in st:
            self._scale = float(st["scale"])
            self._good_steps = int(st["good"])
            self._bad_steps = int(st["bad"])
