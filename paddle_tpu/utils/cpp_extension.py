"""C++ custom-op extension path (compile + register native kernels).

Reference: ``python/paddle/utils/cpp_extension`` + the C++ registration
machinery in ``paddle/fluid/framework/custom_operator.cc`` and
``paddle/extension.h`` — users compile kernels against the framework ABI
and load them at runtime.

TPU-native redesign: the "framework ABI" is the **XLA FFI** (headers
shipped with jaxlib, ``jax.ffi.include_dir()``).  :func:`load` compiles
C++ sources declaring ``XLA_FFI_DEFINE_HANDLER_SYMBOL`` handlers into a
shared library, registers each exported handler as an XLA custom-call
target, and returns op callables built on ``jax.ffi.ffi_call`` — pure
jax functions that compose with jit/grad and can be wired through
:func:`paddle_tpu.utils.custom_op.register_custom_op` (including a
native backward as the custom-vjp pair).

Platform note (honest scope): FFI handlers are HOST kernels — they
register for the CPU platform.  Device-side custom kernels on TPU are
Pallas functions (`ops/pallas/`), which `register_custom_op` already
accepts as pure callables; there is no TPU device ABI for user C++ (the
reference's CUDA custom-op path has no TPU analog by construction).
"""
from __future__ import annotations

import ctypes
import os
import tempfile
from types import SimpleNamespace
from typing import Callable, Dict, Optional, Sequence, Union

import jax

from jax import ffi as _ffi
import numpy as np

__all__ = ["load", "get_build_directory", "CppExtension"]

_OutSpec = Union[str, Callable, jax.ShapeDtypeStruct,
                 Sequence[jax.ShapeDtypeStruct]]


def get_build_directory() -> str:
    """reference: cpp_extension.get_build_directory (PADDLE_EXTENSION_DIR).
    Honors $PADDLE_TPU_EXTENSION_DIR, else a per-user temp dir."""
    d = os.environ.get("PADDLE_TPU_EXTENSION_DIR")
    if not d:
        d = os.path.join(tempfile.gettempdir(),
                         f"paddle_tpu_extensions_{os.getuid()}")
    os.makedirs(d, exist_ok=True)
    return d


def _resolve_out(spec: _OutSpec, in_avals):
    if callable(spec) and not isinstance(spec, jax.ShapeDtypeStruct):
        return spec(*in_avals)
    if isinstance(spec, str):
        if not spec.startswith("like:"):
            raise ValueError(
                f"string out spec must be 'like:<input index>', got "
                f"{spec!r}")
        i = int(spec[5:])
        a = in_avals[i]
        return jax.ShapeDtypeStruct(a.shape, a.dtype)
    return spec


def _make_op(target: str, out: _OutSpec, vmap_method: Optional[str]):
    def op(*arrays, **attrs):
        avals = [jax.ShapeDtypeStruct(np.shape(a), a.dtype)
                 for a in arrays]
        out_aval = _resolve_out(out, avals)
        call = _ffi.ffi_call(target, out_aval, vmap_method=vmap_method)
        return call(*arrays, **attrs)

    op.__name__ = target.rsplit(".", 1)[-1]
    return op


def load(name: str, sources: Sequence[str],
         functions: Dict[str, dict],
         extra_cxx_cflags: Optional[Sequence[str]] = None,
         build_directory: Optional[str] = None,
         verbose: bool = False) -> SimpleNamespace:
    """Compile ``sources`` and register their FFI handlers as ops.

    reference: cpp_extension.load(name, sources, ...) — the JIT build
    path (setup()/CppExtension cover the ahead-of-time path).

    ``functions`` maps op name -> spec dict:
      - ``symbol``: the C symbol from XLA_FFI_DEFINE_HANDLER_SYMBOL
        (defaults to the op name);
      - ``out``: output aval — ``"like:<i>"`` (same shape/dtype as input
        i), a ``jax.ShapeDtypeStruct`` (or sequence for multi-output),
        or a callable ``(*in_avals) -> aval(s)``;
      - ``vmap_method``: forwarded to ``jax.ffi.ffi_call`` (default
        ``"sequential"`` so vmap works out of the box).

    Returns a namespace with one pure-jax callable per op, each usable
    directly, under jit/grad (via custom_vjp), or registered through
    ``register_custom_op``.
    """
    build_dir = build_directory or get_build_directory()
    os.makedirs(build_dir, exist_ok=True)

    srcs = [os.path.abspath(s) for s in sources]
    for s in srcs:
        if not os.path.exists(s):
            raise FileNotFoundError(f"cpp_extension.load: source {s}")
    # cache key = source CONTENTS + flags + FFI header identity: mtimes
    # lie (CI cache restores, tarballs), flag changes must rebuild, and
    # a jaxlib upgrade must not reuse a .so built against old headers
    import hashlib

    import jaxlib
    h = hashlib.sha1()
    h.update(getattr(jaxlib, "__version__", "?").encode())
    h.update(_ffi.include_dir().encode())
    for flag in (extra_cxx_cflags or []):
        h.update(flag.encode())
    for s in srcs:
        h.update(s.encode())
        with open(s, "rb") as f:
            h.update(f.read())
    so_path = os.path.join(build_dir,
                           f"lib{name}_{h.hexdigest()[:12]}.so")

    if not os.path.exists(so_path):
        from .native_build import build_shared_lib
        build_shared_lib(
            ["g++", "-O2", "-fPIC", "-shared", "-std=c++17",
             f"-I{_ffi.include_dir()}"] + list(extra_cxx_cflags or []),
            srcs, so_path, verbose=verbose, what="cpp_extension.load")

    lib = ctypes.CDLL(so_path)
    ns = {}
    for op_name, spec in functions.items():
        symbol = spec.get("symbol", op_name)
        target = f"{name}.{op_name}"
        handler = getattr(lib, symbol)
        _ffi.register_ffi_target(
            target, _ffi.pycapsule(handler), platform="cpu")
        ns[op_name] = _make_op(target, spec["out"],
                               spec.get("vmap_method", "sequential"))
    module = SimpleNamespace(**ns)
    module.__so_path__ = so_path
    return module


class CppExtension:
    """reference: cpp_extension.CppExtension (setuptools AOT path).
    The JIT :func:`load` covers this environment; building wheels of
    custom ops is out of scope here, so constructing one raises with
    the supported alternative spelled out."""

    def __init__(self, *a, **kw):
        raise NotImplementedError(
            "CppExtension/setup(): ahead-of-time wheel builds are not "
            "supported in this build — use paddle_tpu.utils."
            "cpp_extension.load(name, sources, functions) to JIT-compile "
            "and register XLA FFI kernels")
