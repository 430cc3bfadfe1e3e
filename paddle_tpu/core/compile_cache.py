"""Persistent AOT executable cache — respawned replicas skip XLA.

Every cold start of a serving replica re-lowers and re-compiles every
batch bucket and decode variant, and that compile wall IS the
cold-start-to-first-token cost (seconds per variant vs milliseconds to
deserialize).  This module persists the compiled executables
themselves, keyed by the same structured signature the recompile-
attribution layer (:func:`observability.record_compile`) already
maintains, so the cache key and the compile cause are one vocabulary.

Design constraints:

- **We serialize ourselves** through the AOT ``lower().compile()`` +
  ``jax.experimental.serialize_executable`` path, routed via
  :mod:`paddle_tpu.core.jax_compat`, independent of whether jax's own
  persistent compilation cache is on (core/xla_env.py places that one).
- **Stamped invalidation.**  Each entry carries a version/topology
  stamp (jax, jaxlib, backend platform, device kind, device count,
  format version).  Any mismatch on load is a *reject* — counted as
  ``compile_cache.rejects``, never an exception on the serve path.
- **Device-fingerprint verification before first dispatch** (the
  load-path bugfix this subsystem ships with): a deserialized
  executable's input shardings must resolve onto the devices this
  process actually has.  A payload that deserializes but targets a
  different device set is rejected to a fresh compile instead of
  crashing (or silently corrupting) on first dispatch.
- **Single-process-topology scope.**  Entries are only written/read
  for single-device executables — the serving paths this cache exists
  for (Predictor buckets, GenerationEngine variants, the Executor's
  unsharded inference step).  Sharded train-step executables keep
  compiling fresh; their cost is amortized over hours, not paid per
  respawn.

Enabled by ``FLAGS_compile_cache_dir`` (empty = disabled, zero
filesystem traffic).  Stats: ``compile_cache.{hits,misses,rejects,
stores,errors}``; each event also emits a ``compile_cache`` tracer
event when observability is enabled.  ``explain_compiles()`` shows
loaded-vs-compiled per record via the ``cache=`` provenance field.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import tempfile
import threading
from typing import Callable, Optional, Tuple

__all__ = ["enabled", "cache_dir", "stamp", "cache_key", "load",
           "store", "cached_compile", "stats", "reset_stats"]

_FORMAT = 1                     # bump to invalidate every entry at once
_SUFFIX = ".xcache"

_lock = threading.Lock()
_stamp_cache: Optional[dict] = None


def enabled() -> bool:
    from . import flags
    return bool(flags.get_flag("compile_cache_dir"))


def cache_dir() -> str:
    from . import flags
    return str(flags.get_flag("compile_cache_dir"))


def _emit(event: str, **args) -> None:
    from . import obs_hook
    trc = obs_hook._tracer
    if trc is not None:
        trc.emit("compile_cache", event, args=args)


def _count(name: str) -> None:
    from ..utils import monitor
    monitor.stat_add(f"compile_cache.{name}")


def stamp() -> dict:
    """The version/topology stamp baked into every entry.  Any field
    changing between store and load rejects the entry: a jax/jaxlib
    upgrade, a backend flip (cpu<->tpu), a different chip generation,
    or a different device count all produce executables that must not
    be mixed."""
    global _stamp_cache
    if _stamp_cache is None:
        import jax
        import jaxlib
        devs = jax.devices()
        _stamp_cache = {
            "format": _FORMAT,
            "jax": jax.__version__,
            "jaxlib": getattr(jaxlib, "__version__", "unknown"),
            "backend": jax.default_backend(),
            "device_kind": devs[0].device_kind if devs else "none",
            "device_count": len(devs),
        }
    return dict(_stamp_cache)


def _freeze(v):
    """Deterministic, content-stable form of a signature value (same
    rules as the attribution layer: scalars verbatim, containers
    recursively frozen, everything else repr'd)."""
    if isinstance(v, (int, float, bool, str, type(None))):
        return v
    if isinstance(v, bytes):
        return v.hex()
    if isinstance(v, (tuple, list)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, (set, frozenset)):
        return tuple(sorted(_freeze(x) for x in v))
    if isinstance(v, dict):
        return tuple(sorted((str(k), _freeze(x)) for k, x in v.items()))
    return repr(v)


def cache_key(component: str, signature: dict) -> str:
    """Content hash of (component, frozen signature, stamp) — the file
    name under the cache dir.  The signature is the same ordered dict
    the caller hands ``record_compile``, extended with whatever
    identifies the *content* across processes (artifact digest, param
    fingerprint, program fingerprint) — process-local serials must NOT
    be in it."""
    frozen = (component,
              tuple((str(k), _freeze(v)) for k, v in signature.items()),
              tuple(sorted(stamp().items())))
    return hashlib.sha256(repr(frozen).encode()).hexdigest()


def _path_for(key: str) -> str:
    return os.path.join(cache_dir(), key + _SUFFIX)


def _device_fingerprint_ok(compiled) -> bool:
    """Verify the deserialized executable's devices are THIS process's
    devices before the first dispatch.  ``input_shardings`` resolves to
    concrete Device objects at deserialize time; if any of them is not
    in ``jax.devices()`` the executable would dispatch onto hardware we
    don't have — reject it instead."""
    import jax
    have = {(d.platform, d.id) for d in jax.devices()}
    try:
        in_sh, _ = compiled.input_shardings
        for sh in jax.tree_util.tree_leaves(in_sh):
            for d in getattr(sh, "device_set", ()):
                if (d.platform, d.id) not in have:
                    return False
    except Exception:
        return False        # no introspectable shardings: don't trust it
    return True


def _single_device(compiled):
    """The one device a single-device executable runs on, else None.
    Only those are cacheable (module docstring): judge the
    *executable*, not the process — a predictor bucket compiled for one
    device on a multi-device host is still safe."""
    import jax
    devs = {}
    in_sh, _ = compiled.input_shardings
    for sh in jax.tree_util.tree_leaves((in_sh, compiled.output_shardings)):
        for d in getattr(sh, "device_set", ()):
            devs[(d.platform, d.id)] = d
    if not devs and len(jax.devices()) == 1:
        return jax.devices()[0]
    return next(iter(devs.values())) if len(devs) == 1 else None


def load(component: str, signature: dict):
    """A cached executable for this signature, or None (miss/reject).
    Every failure mode — unreadable file, stamp mismatch, deserialize
    error, device-fingerprint mismatch — is a reject + None; the serve
    path never sees an exception from here."""
    if not enabled():
        return None
    path = _path_for(cache_key(component, signature))
    if not os.path.exists(path):
        _count("misses")
        return None
    try:
        with open(path, "rb") as f:
            entry = pickle.load(f)
    except Exception as e:              # torn write, foreign file
        _count("rejects")
        _emit("reject", component=component, why=f"unreadable: {e}")
        return None
    if entry.get("stamp") != stamp():
        # a stale stamp means the key hash collided across stamps only
        # if the dir was populated by a different process config under
        # the same key — possible when the stamp itself changed after
        # files were written (jax upgrade in place).  Reject cleanly.
        _count("rejects")
        _emit("reject", component=component, why="stamp mismatch",
              entry_stamp=entry.get("stamp"), want=stamp())
        return None
    import jax
    try:
        from . import jax_compat
        # load onto the one device it was compiled for; an id this
        # process does not have is a KeyError, i.e. a reject
        dev = {d.id: d for d in jax.devices()}[entry["device_id"]]
        compiled = jax_compat.deserialize_executable(
            entry["payload"], entry["in_tree"], entry["out_tree"], [dev])
    except Exception as e:              # incompatible payload
        _count("rejects")
        _emit("reject", component=component, why=f"deserialize: {e}")
        return None
    if not _device_fingerprint_ok(compiled):
        _count("rejects")
        _emit("reject", component=component, why="device fingerprint")
        return None
    _count("hits")
    _emit("hit", component=component)
    return compiled


def store(component: str, signature: dict, compiled) -> bool:
    """Serialize a freshly compiled executable under its key.  Atomic
    (tmp + rename) so concurrent replicas sharing one cache dir never
    read a torn entry; single-device executables only (see module
    docstring).  Failures count ``compile_cache.errors`` and return
    False — the executable itself is unaffected."""
    if not enabled():
        return False
    try:
        dev = _single_device(compiled)
        if dev is None:
            return False
        from . import jax_compat
        payload, in_tree, out_tree = jax_compat.serialize_executable(
            compiled)
        entry = {"stamp": stamp(), "component": component,
                 "signature": {str(k): _freeze(v)
                               for k, v in signature.items()},
                 "device_id": dev.id,
                 "payload": payload, "in_tree": in_tree,
                 "out_tree": out_tree}
        d = cache_dir()
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                pickle.dump(entry, f, protocol=pickle.HIGHEST_PROTOCOL)
            os.replace(tmp, _path_for(cache_key(component, signature)))
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
    except Exception as e:          # serialization gap, disk full, ...
        _count("errors")
        _emit("error", component=component, why=str(e))
        return False
    _count("stores")
    _emit("store", component=component)
    return True


def cached_compile(component: str, signature: dict,
                   build: Callable[[], object]
                   ) -> Tuple[object, Optional[str]]:
    """The one-call integration point for a compile site: try the
    cache, else ``build()`` (the site's ``lower().compile()`` thunk)
    and store the result.  Returns ``(executable, provenance)`` where
    provenance is ``"loaded"`` / ``"compiled"`` for the compile
    record's ``cache=`` field, or None when the cache is disabled
    (records then omit the field entirely)."""
    if not enabled():
        return build(), None
    hit = load(component, signature)
    if hit is not None:
        return hit, "loaded"
    compiled = build()
    store(component, signature, compiled)
    return compiled, "compiled"


def stats() -> dict:
    """Current ``compile_cache.*`` counters (0 when never touched)."""
    from ..utils import monitor
    return {k: monitor.get_stat(f"compile_cache.{k}")
            for k in ("hits", "misses", "rejects", "stores", "errors")}


def reset_stats() -> None:
    from ..utils import monitor
    for k in ("hits", "misses", "rejects", "stores", "errors"):
        monitor.stat_reset(f"compile_cache.{k}")
