"""Device management.

TPU-native equivalent of the reference's Place / DeviceContextPool layer
(reference: paddle/fluid/platform/place.h:103, device_context.h:695).  On
TPU, XLA owns streams and contexts; what remains is device *selection* and
queries over ``jax.devices()``.
"""
from __future__ import annotations

import jax

_current_device = None


def get_all_devices():
    return jax.devices()


def device_count(kind=None) -> int:
    return len(jax.devices(kind) if kind else jax.devices())


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    try:
        return any(d.platform == "tpu" for d in jax.devices())
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
        _current_device = jax.devices("tpu")[idx]
    elif kind == "cpu":
        _current_device = jax.devices("cpu")[idx]
    else:
        raise ValueError(f"unknown device {device!r}; expected 'cpu', "
                         f"'tpu' or 'tpu:<index>'")
    jax.config.update("jax_default_device", _current_device)
    return _current_device


def get_device() -> str:
    d = _current_device or jax.devices()[0]
    return f"{d.platform}:{d.id}"
