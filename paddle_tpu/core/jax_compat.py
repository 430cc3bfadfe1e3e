"""The one seam around ``jax.experimental.serialize_executable``.

AOT executable serialization still lives under ``jax.experimental``,
so the persistent compile cache (core/compile_cache.py) reaches it
through these two functions and nothing else in the package does.
Every other jax call is spelled directly at its call site.
"""
from __future__ import annotations


def serialize_executable(compiled):
    """``(payload_bytes, in_tree, out_tree)`` for a ``lower().compile()``
    result.  The trees are picklable pytree defs; donation and static
    shapes ride the payload."""
    from jax.experimental.serialize_executable import serialize
    return serialize(compiled)


def deserialize_executable(payload, in_tree, out_tree, devices):
    """Rebuild a callable ``Compiled`` from :func:`serialize_executable`
    output, loaded onto exactly ``devices`` — left to its default jax
    loads onto every local device, and a single-device executable then
    fails its first dispatch on a multi-device host.  Raises on any
    incompatibility — callers (compile_cache) treat every failure as a
    cache reject and fall back to a fresh compile."""
    from jax.experimental.serialize_executable import deserialize_and_load
    return deserialize_and_load(payload, in_tree, out_tree,
                                execution_devices=list(devices))
