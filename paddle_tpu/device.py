"""Device management.

TPU-native equivalent of the reference's Place / DeviceContextPool layer
(reference: paddle/fluid/platform/place.h:103, device_context.h:695).  On
TPU, XLA owns streams and contexts; what remains is device *selection* and
queries over ``jax.devices()``.
"""
from __future__ import annotations

import jax

from .core import xla_env
from .observability.compiles import setup_span

_current_device = None


def _devices(*kind):
    """``jax.devices``; the query that makes jax's backend is the set-up
    span ``setup.backend_init`` (where the caller made it first, that
    time lies in ``setup.before_import_s``)."""
    if xla_env._backend_initialized():
        return jax.devices(*kind)
    with setup_span("setup.backend_init"):
        return jax.devices(*kind)


def get_all_devices():
    return _devices()


def device_count(kind=None) -> int:
    return len(_devices(kind) if kind else _devices())


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    try:
        return any(d.platform == "tpu" for d in _devices())
    except RuntimeError:
        return False


def is_compiled_with_xpu() -> bool:
    return False


def set_device(device: str):
    """paddle.set_device parity: 'cpu' | 'tpu' | 'tpu:0' | 'gpu' (→ tpu).
    An accelerator that is not there raises — it is never quietly
    replaced by the CPU."""
    global _current_device
    kind = device.split(":")[0]
    idx = int(device.split(":")[1]) if ":" in device else 0
    if kind in ("gpu", "cuda", "tpu", "xpu"):
        # jax.devices raises RuntimeError when no TPU backend exists
        _current_device = _devices("tpu")[idx]
    elif kind == "cpu":
        _current_device = _devices("cpu")[idx]
    else:
        raise ValueError(f"unknown device {device!r}; expected 'cpu', "
                         f"'tpu' or 'tpu:<index>'")
    jax.config.update("jax_default_device", _current_device)
    return _current_device


def get_device() -> str:
    d = _current_device or _devices()[0]
    return f"{d.platform}:{d.id}"
