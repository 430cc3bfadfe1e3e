"""XLA_FLAGS knobs that must precede backend initialisation.

XLA parses the ``XLA_FLAGS`` environment variable once, when the
backend client is created — and it aborts the process on flags its
build does not define (the CPU jaxlib, for example, knows the
``--xla_gpu_*`` family but dies on ``--xla_tpu_*``).  So anything that
wants to turn on the latency-hiding scheduler has to (a) run before
the first device query and (b) only append flags the target platform's
build actually defines.  ``paddle_tpu/__init__.py`` calls
:func:`apply_latency_hiding_flags` at import, gated on
``FLAGS_xla_latency_hiding`` (read from the environment — by the time
a ``set_flags()`` call could flip it, the backend usually exists).

Why this knob exists: the grad-comm stage (``distributed/grad_comm``)
emits each gradient bucket's collective dependent only on that
bucket's grads.  On TPU/GPU, XLA's latency-hiding scheduler is what
turns those into async start/done pairs hoisted across the remaining
backward compute — without it the compiler schedules collectives
roughly where they appear, and ``overlap='auto'`` falls back to the
explicit ppermute-chunked ring lowering instead.  On CPU there is no
such scheduler and nothing overlaps at all, so auto keeps the fused
per-bucket collectives (``overlap='ring'`` still forces the chunked
lowering for testing).  Path resolution asks
:func:`latency_hiding_active` — what actually reached ``XLA_FLAGS`` —
never the raw flag value.
"""
from __future__ import annotations

import os
from typing import List, Optional

__all__ = ["apply_latency_hiding_flags", "latency_hiding_active",
           "LATENCY_HIDING_FLAGS", "place_compile_cache"]

# per-platform scheduler flags — only ever appended for the platform
# the process is about to initialise, because an unknown flag in
# XLA_FLAGS is a FATAL parse error, not a warning
LATENCY_HIDING_FLAGS = {
    "tpu": ("--xla_tpu_enable_latency_hiding_scheduler=true",),
    "gpu": ("--xla_gpu_enable_latency_hiding_scheduler=true",),
    "cuda": ("--xla_gpu_enable_latency_hiding_scheduler=true",),
}


def _spec_present(*modules: str) -> bool:
    import importlib.util
    for mod in modules:
        try:
            if importlib.util.find_spec(mod) is not None:
                return True
        except (ImportError, ValueError):
            continue
    return False


def _target_platform() -> str:
    """The platform jax will initialise: the first entry of
    ``JAX_PLATFORMS`` when the user pinned one, else ``tpu`` when a
    libtpu is importable / ``gpu`` when a CUDA plugin is (the wheel's
    presence is what makes jax pick the backend), else ``cpu``."""
    plats = os.environ.get("JAX_PLATFORMS") or os.environ.get(
        "JAX_PLATFORM_NAME", "")
    first = plats.split(",")[0].strip().lower()
    if first:
        return first
    if _spec_present("libtpu"):
        return "tpu"
    if _spec_present("jax_cuda12_plugin", "jax_cuda11_plugin",
                     "jax_plugins.xla_cuda12"):
        return "gpu"
    return "cpu"


def latency_hiding_active(platform: str) -> bool:
    """Whether the latency-hiding scheduler flags for ``platform`` are
    actually IN ``XLA_FLAGS`` — the question grad_comm's overlap path
    resolution asks.  Deliberately not the raw ``FLAGS_xla_latency_
    hiding`` value: the knob can be requested and still never applied
    (set after backend init, or on a platform the detector missed), in
    which case compiling the fused path and calling its comm "hidden"
    would be a lie — the ring fallback is the right lowering then.
    Flags a user appended to ``XLA_FLAGS`` by hand count too."""
    current = os.environ.get("XLA_FLAGS", "")
    wanted = LATENCY_HIDING_FLAGS.get((platform or "").lower(), ())
    return bool(wanted) and all(f in current for f in wanted)


def _backend_initialized() -> bool:
    """Whether XLA has already parsed XLA_FLAGS (backend client
    exists) — appending after that is a silent no-op."""
    try:
        import jax._src.xla_bridge as _xb
        return bool(getattr(_xb, "_backends", None))
    except Exception:  # noqa: BLE001 - private API moved; assume early
        return False


def apply_latency_hiding_flags(platform: Optional[str] = None
                               ) -> List[str]:
    """Append the latency-hiding scheduler flags for ``platform``
    (auto-detected when None) to ``XLA_FLAGS`` — if
    ``FLAGS_xla_latency_hiding`` asks for it and the backend has not
    been created yet.  Returns the flags actually appended (empty when
    off, already present, unsupported platform, or too late).
    Idempotent: flags already in ``XLA_FLAGS`` are never duplicated."""
    from . import flags as _flags
    if not _flags.get_flag("xla_latency_hiding"):
        return []
    plat = (platform or _target_platform()).lower()
    wanted = LATENCY_HIDING_FLAGS.get(plat, ())
    if not wanted:
        return []
    current = os.environ.get("XLA_FLAGS", "")
    add = [f for f in wanted if f not in current]
    if not add:
        return []
    if _backend_initialized():
        import warnings
        warnings.warn(
            "FLAGS_xla_latency_hiding was requested after the jax "
            "backend initialised — XLA_FLAGS is parsed once at backend "
            "creation, so the latency-hiding scheduler flags cannot be "
            "applied to this process.  Set FLAGS_xla_latency_hiding=1 "
            "in the environment before the first jax device query "
            "(the supervisor's child_env is the right place for "
            "supervised training).", RuntimeWarning)
        return []
    os.environ["XLA_FLAGS"] = (current + " " + " ".join(add)).strip()
    return add


def place_compile_cache() -> str:
    """Decide where jax's persistent compilation cache lives, and return
    the directory.  For the entry points (``chip_smoke.py``,
    ``benchmark/run.py``, ``tools/serve.py``) to call before their first
    compile — never ``import paddle_tpu`` itself, a library does not
    start writing into its user's disk.

    ``JAX_COMPILATION_CACHE_DIR`` wins: jax reads it by itself and no
    other directory is set in code.  Without it the cache goes to
    ``<checkout>/.jax_cache`` — a fixed path, because the path is part
    of what a cache hit depends on (never a temp name, a pid, a time).

    Either way the cache key takes in the program's metadata (its scope
    names, and with them its source lines): an executable is loaded only
    for the program that made it, names included."""
    import jax
    # scopes are metadata, and jax's cache key leaves metadata out unless
    # asked: a program that differs from a cached one in its
    # ``jax.named_scope`` names alone would be answered with the old
    # executable and its old ``op_name``s, which every scope-reading
    # metric would then take for the program's own
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
