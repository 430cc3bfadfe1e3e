"""Counters the compiled step keeps on the device.

``utils.monitor`` counts what the host does; what a compiled step did
(how many assignments an expert got, which branch of a ``cond`` a chunk
took) is a value inside the executable, and only the program can hand
it out.  Traced code calls :func:`device_counter`; ``jit.TrainStep``
collects what one step emitted, keeps it in its device-resident carry
(``aux["counters"]``: for each name the newest step's value ``last``,
the sum ``total`` since the last read and the number ``steps`` in it)
and never fetches it.  :func:`read_device_counters` does, when somebody
asks: one ``device_get``, which waits for the newest dispatched step
exactly as reading the loss does, then moves the sums into
``utils.monitor``, the one place counters are read from, and zeroes
them in the carry.  The program never reads on its own.

The registry's names, for a counter ``name`` whose step value is
``[calls, ...]`` (the emissions of one step stacked in their order):

- ``name.steps``                 steps read so far (cumulative);
- ``name.total.<call>[.<i>...]`` the sum over those steps (cumulative);
- ``name.last.<call>[.<i>...]``  the newest step's value (a gauge).

A ``total`` of counts is int32 in the carry: it wraps after 2**31
counts between two reads, so read at least that often (the fullest
counter of the benchmark's cells adds 9,000 a step).
"""
from __future__ import annotations

import contextlib
import threading
import weakref
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import monitor

__all__ = ["device_counter", "collecting", "read_device_counters"]

# the most one emission may hold: a counter is a scalar or a small
# vector, not a way to export activations
MAX_VALUE_BYTES = 1024

_open = threading.local()           # .stack: the collectors open, innermost last
_carriers = weakref.WeakSet()       # the live steps whose carry holds counters
_published = set()                  # names that have reached the registry


class Collector:
    """What one traced region emitted: name -> blocks ``[k, ...]`` in
    order of emission."""

    def __init__(self):
        self._trace = jax.core.get_opaque_trace_state()
        self._blocks: Dict[str, list] = {}

    def add(self, name, block):
        """``block`` [k, ...]: k emissions of ``name``, already stacked."""
        if jax.core.get_opaque_trace_state() != self._trace:
            raise ValueError(
                f"device counter {name!r} was emitted inside the body of a "
                "lax.scan / lax.map / cond (or another nested trace): the "
                "collector cannot carry a value out of there; emit it from "
                "the level that calls the loop, as a vector over its "
                "iterations")
        held = self._blocks.setdefault(name, [])
        if held and (held[0].shape[1:], held[0].dtype) != (block.shape[1:],
                                                           block.dtype):
            raise ValueError(
                f"device counter {name!r}: emitted as "
                f"{held[0].dtype}{list(held[0].shape[1:])} and as "
                f"{block.dtype}{list(block.shape[1:])} in one step")
        held.append(block)

    def stacked(self):
        """name -> ``[calls, ...]``, the emissions in their order."""
        return {name: blocks[0] if len(blocks) == 1
                else jnp.concatenate(blocks)
                for name, blocks in self._blocks.items()}


def _stack():
    if not hasattr(_open, "stack"):
        _open.stack = []
    return _open.stack


@contextlib.contextmanager
def collect(active=True):
    """Collect the emissions of the code traced inside; yields the
    :class:`Collector`.  The caller hands ``stacked()`` out of its trace
    as an output (a tracer kept any other way is a leak).  Not
    ``active``: the collector is not opened, and stays empty."""
    collector = Collector()
    if not active:
        yield collector
        return
    stack = _stack()
    stack.append(collector)
    try:
        yield collector
    finally:
        stack.pop()


def collecting() -> bool:
    """Whether an emission would be kept: ask before computing one."""
    return bool(_stack())


def device_counter(name: str, value) -> None:
    """Emit ``value`` (int32 or float32; a scalar or a small vector, at
    most 1 KiB) under ``name`` from traced code.  The emissions of one
    name in one step are stacked in order ``[calls, ...]``.  A no-op
    where nothing collects (eager mode, the static Executor,
    ``eval_step``, ``SpmdTrainStep``)."""
    stack = _stack()
    if not stack:
        return
    value = jnp.asarray(value)
    if value.dtype not in (jnp.int32, jnp.float32):
        raise TypeError(f"device counter {name!r}: {value.dtype} values; "
                        "int32 (counts) or float32")
    if value.size * value.dtype.itemsize > MAX_VALUE_BYTES:
        raise ValueError(
            f"device counter {name!r}: a value of "
            f"{value.size * value.dtype.itemsize} bytes; {MAX_VALUE_BYTES} "
            "is the most (a counter is not a way to export activations)")
    stack[-1].add(name, value[None])


def re_emit(counted) -> None:
    """Emit again, outside, what a nested :func:`collect` handed out of
    its trace (``parallel.recompute``'s segment)."""
    for name, block in counted.items():
        _stack()[-1].add(name, block)


# -- the carry ----------------------------------------------------------------

def zero_carry(spec):
    """The carry before the first step.  ``spec``: name -> what a step
    emits (anything with ``shape`` and ``dtype``)."""
    return {name: {"last": jnp.zeros(s.shape, s.dtype),
                   "total": jnp.zeros(s.shape, s.dtype),
                   "steps": jnp.zeros((), jnp.int32)}
            for name, s in spec.items()}


def fold(carry, counted):
    """One step's emissions into the carry (traced)."""
    return {name: {"last": value,
                   "total": carry[name]["total"] + value,
                   "steps": carry[name]["steps"] + 1}
            for name, value in counted.items()}


def register(carrier) -> None:
    """``carrier.counter_carry()`` -> its carry (or None before the first
    step); :func:`read_device_counters` reads every live one."""
    _carriers.add(carrier)


# -- the reader ---------------------------------------------------------------

def _publish(name, record):
    _published.add(name)
    monitor.stat_add(f"{name}.steps", int(record["steps"]))
    total, last = np.asarray(record["total"]), np.asarray(record["last"])
    for index in np.ndindex(total.shape):
        key = ".".join(map(str, index))
        monitor.stat_add(f"{name}.total.{key}", total[index].item())
        monitor.stat_set(f"{name}.last.{key}", last[index].item())


def read(carriers):
    """Fetch the carries of ``carriers`` in one ``device_get``, add
    their sums to the registry, zero them in the carries."""
    carries = [c for c in (carrier.counter_carry() for carrier in carriers)
               if c]
    for carry, host in zip(carries, jax.device_get(carries)):
        for name, record in host.items():
            _publish(name, record)
            carry[name]["total"] = jnp.zeros_like(carry[name]["total"])
            carry[name]["steps"] = jnp.zeros_like(carry[name]["steps"])
    return registry_view()


def registry_view():
    """The registry's entries of every device counter read so far."""
    prefixes = tuple(name + "." for name in _published)
    return {key: value for key, value in monitor.all_stats().items()
            if key.startswith(prefixes)}


def read_device_counters():
    """Read the device counters of every live ``TrainStep`` into
    ``utils.monitor`` and return the registry's view of them (the
    module's docstring has the names).  Waits for the newest dispatched
    step; any number of readers may read in any order, because the
    registry is cumulative and a read zeroes what it took."""
    return read(list(_carriers))
