"""paddle_tpu — a TPU-native deep-learning framework.

A from-scratch JAX/XLA/Pallas re-design of the reference framework's
capability surface (see /root/repo/SURVEY.md): dual-mode execution (eager
"dygraph" + traced/compiled "static"), an nn.Layer system, optimizers, AMP,
data loading, and first-class SPMD distribution (DP/ZeRO/TP/PP/SP) over
``jax.sharding.Mesh``.

Public API mirrors the reference's ``paddle.*`` 2.0 surface so users can
switch with minimal changes; internals are idiomatic JAX, not a port.
"""
from __future__ import annotations

import time as _time

_t_import = _time.perf_counter()     # setup.import_s, see the last lines
_t_import_wall = _time.time()        # the span setup.import, on jax's clock

__version__ = "0.1.0"

# PRNG impl: 'rbg' (XLA RngBitGenerator for bits, threefry for split/fold_in)
# is ~10x cheaper than threefry on the TPU VPU — measured 84 ms/step of pure
# mask generation on the BERT-base bench.  Must be configured before the
# first jax.random.key() (core.rng builds the global Generator at import).
import os as _os

if "JAX_DEFAULT_PRNG_IMPL" not in _os.environ:
    import jax as _jax

    # respect an explicit programmatic choice; only replace jax's built-in
    # default ('threefry2x32' never set by a user who wanted rbg semantics
    # would be indistinguishable — documented limitation)
    if _jax.config.jax_default_prng_impl == "threefry2x32":
        _jax.config.update("jax_default_prng_impl",
                           _os.environ.get("FLAGS_prng_impl", "rbg"))

# latency-hiding scheduler knob: XLA_FLAGS is parsed exactly once, at
# backend creation, so FLAGS_xla_latency_hiding must act HERE — before
# the first device query anywhere below (core/xla_env.py appends only
# the target platform's scheduler flags; unknown flags are fatal to
# XLA's parser, so a CPU process never gets TPU flags appended)
from .core import xla_env as _xla_env  # noqa: E402

# whether the caller made jax's backend before importing the package (then
# ``import jax`` and the client lie in setup.before_import_s); nothing
# above touches it
_backend_was_made = _xla_env._backend_initialized()
_xla_env.apply_latency_hiding_flags()

from .core import (Parameter, Tensor, enable_grad, get_default_dtype,  # noqa
                   get_flags, get_rng_state, grad, no_grad, seed,
                   set_default_dtype, set_flags, set_rng_state, to_tensor)
from .core.dtype import (bfloat16, bool_, complex64, complex128,  # noqa
                         float16, float32, float64, int8, int16, int32,
                         int64, uint8)

from . import ops  # noqa: E402
ops.monkey_patch_tensor()

# creation / random / manipulation / math / logic op surface at top level
from .ops import *  # noqa: F401,F403,E402
from .ops import linalg  # noqa: E402
from .ops.creation import to_tensor  # noqa: E402,F811

from .device import (device_count, get_device, is_compiled_with_cuda,  # noqa
                     is_compiled_with_tpu, is_compiled_with_xpu, set_device)
from .framework_io import load, save  # noqa: E402

from . import nn  # noqa: E402
from . import optimizer  # noqa: E402
from . import jit  # noqa: E402
from . import amp  # noqa: E402
from . import io  # noqa: E402
from . import metric  # noqa: E402
from . import distribution  # noqa: E402
from . import onnx  # noqa: E402
from . import vision  # noqa: E402
from . import text  # noqa: E402
from . import hapi  # noqa: E402
from .hapi import Model, summary  # noqa: E402
from . import distributed  # noqa: E402
from . import parallel  # noqa: E402
from . import static  # noqa: E402
from . import inference  # noqa: E402
from . import serving  # noqa: E402
from . import profiler  # noqa: E402
from . import observability  # noqa: E402
from . import utils  # noqa: E402
from . import quantization  # noqa: E402
from .parallel import DataParallel  # noqa: E402
from .optimizer import regularizer  # noqa: E402
from .nn.layer_base import ParamAttr  # noqa: E402

CPUPlace = "cpu"
TPUPlace = "tpu"

_static_mode = False


def disable_static(place=None):
    """Dygraph is the default mode; kept for API parity."""
    global _static_mode
    _static_mode = False


def enable_static():
    global _static_mode
    _static_mode = True


def in_dynamic_mode():
    return not _static_mode


def is_grad_enabled():
    from .core import autograd
    return autograd.grad_enabled()


# -- round-4 top-level parity (reference: paddle/__init__.py aliases) ----
from .framework_compat import (CPUPlace, CUDAPinnedPlace, CUDAPlace,  # noqa
                               TPUPlace, XPUPlace, create_parameter,
                               disable_dygraph, enable_dygraph, flops,
                               get_cuda_rng_state, get_cudnn_version,
                               in_dygraph_mode, set_cuda_rng_state,
                               set_printoptions)
from .hapi import callbacks  # noqa: E402,F401

# fleet telemetry: when the environment stages a spool dir (supervisors
# forward FLAGS_obs_spool_dir + a per-incarnation FLAGS_obs_role into
# every child they spawn), the exporter installs at import — a
# supervised child exports with zero code changes.  Unset (the normal
# case), this is one flag read.
from .core import flags as _flags  # noqa: E402

if _flags.get_flag("obs_spool_dir"):
    from .observability import export as _obs_export  # noqa: E402

    _obs_export.install_exporter()
from .ops.linalg import cholesky, histogram, inverse  # noqa: E402,F401
from .ops.manipulation import (crop_tensor, scatter_, shard_index,  # noqa
                               slice, squeeze_, strided_slice, unsqueeze_)
from .ops.math import (add_n, broadcast_shape, mv, rank, shape,  # noqa
                       tanh_)

# always-on set-up counter: this file's first line to its last, jax's
# own import included when it happens here; and the same stretch as the
# set-up span ``setup.import``, after the age the process had at the first
# line (``setup.before_import_s``)
utils.monitor.stat_set("setup.import_s", _time.perf_counter() - _t_import)
observability.compiles.import_done(_t_import_wall, _backend_was_made)
