"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the reference and the
program both start from these, and neither takes anything from the other.
"""
import zlib

import jax
import jax.numpy as jnp

STD = 0.02


def maker(seed, shapes, round_to=None):
    """``shapes``: name -> (shape, base).  Returns a function that makes
    name -> float32 array (the same arrays at every call, so that nobody
    has to keep a copy), each leaf ``base + 0.02 * normal`` from a key
    folded from ``seed`` and the leaf's name.  ``round_to`` (e.g. bfloat16) rounds every value to
    that type and keeps float32 storage, so that a program that holds the
    weights in that type and a float32 reference start from the same
    numbers."""
    names = sorted(shapes)

    def gen(key):
        out = {}
        for n in names:
            shape, base = shapes[n]
            k = jax.random.fold_in(key, zlib.crc32(n.encode()) & 0x7FFFFFFF)
            w = base + STD * jax.random.normal(k, shape, jnp.float32)
            if round_to is not None:
                # not astype there and back: XLA folds that pair away
                # (xla_allow_excess_precision) and nothing gets rounded
                fi = jnp.finfo(round_to)
                w = jax.lax.reduce_precision(w, fi.nexp, fi.nmant)
            out[n] = w
        return out

    # any whole number up to a little over 2**31: fold it in two halves
    key = jax.random.fold_in(jax.random.key(int(seed) & 0xFFFFFFFF),
                             int(seed) >> 32)
    jitted = jax.jit(gen)
    return lambda: jitted(key)
