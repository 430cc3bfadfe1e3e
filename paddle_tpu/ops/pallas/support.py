"""Shared plumbing for the Pallas kernel tier.

Every kernel file (flash_attention, eva_attention, sparse_attention,
ssd_scan, causal_conv, moe_combine, fused_epilogue, fused_adam,
paged_attention, collective_matmul; attention_tiles holds the attention
family's shared tile mathematics and launches nothing) needs the same
five decisions made the same way:

- **backend**: the ``pltpu`` import, interpret mode when not on a real
  TPU;
- **activation**: the tier is ON when ``FLAGS_use_pallas_kernels`` is
  set AND either the backend is TPU or ``FLAGS_pallas_interpret``
  explicitly opts a CPU process into interpret-mode execution (tests,
  kernel_smoke — interpret mode is orders of magnitude slower than jnp,
  so it must never be the silent CPU default);
- **gates**: dtype and tile-alignment checks against the f32 (8, 128)
  sublane/lane tile;
- **the choice** between a kernel and its XLA form, for the functionals
  of ``nn.functional`` that have both (and the expert layer's sums in
  ``ops.moe``): `choose_kernel`, the one caller of `tier_enabled` on a
  benchmark cell's path;
- **observability**: every kernel SELECTION counts
  ``pallas.selected.<kernel>`` in monitor.  Selections happen at trace
  time (the kernel entry points run inside jitted programs, once per
  compile, then the baked executable dispatches without re-entering
  Python) — the counters say which kernels are compiled into the
  program, not how many times they executed; per-step volume belongs
  to the perf observatory.  "FLAGS off => zero selections" is the
  testable contract.

One place decides all five; the kernel files keep only their math.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...observability import scopes

__all__ = ["pltpu", "interpret_mode", "tier_enabled", "choose_kernel",
           "dtype_ok", "smem_scalar_spec", "count_kernel_selection",
           "kernel_selections", "block_rows", "name_residuals",
           "once_a_shape", "NEG_INF"]

NEG_INF = -1e30


def dot(a, b, dims):
    """MXU matmul with f32 accumulation.  Precision is explicit: the
    global jax_default_matmul_precision=highest (used by tests) is not
    lowerable by Mosaic for bf16 operands; bf16 x bf16 -> f32 is the
    MXU-native path."""
    prec = (jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16
            else jax.lax.Precision.HIGHEST)
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=prec)


def name_residuals(*values, names=(scopes.ATTN_OUT, scopes.ATTN_LSE)):
    """``values`` under their ``scopes.RESIDUALS`` names (by default the
    (out, lse) of an attention forward kernel), for the forward rule of a
    custom VJP to hand on as residuals: one named value each, so a
    checkpoint whose policy keeps the names (``parallel.recompute``) hands
    the backward rule what the forward pass produced and its replay runs
    no kernel for them.  Under no such policy a name is the identity and
    lowers to nothing."""
    return tuple(checkpoint_name(v, n) for v, n in zip(values, names,
                                                       strict=True))


def once_a_shape(*static_argnums):
    """Decorator of the kernel launchers: jax's tracing cache
    serves every call after the first with the same static shape, so a
    model's identical layers trace each kernel body once (36 kernel
    calls of the BERT cell cost its set-up 3.8 s of tracing and lowering
    without this: PERF.md, PR 27).  ``inline=True`` leaves no call in the
    program: every call site gets the equations under its own scope
    names.  The interpreter switch is an argument, and so part of the
    cache's key."""
    return functools.partial(jax.jit, static_argnums=static_argnums,
                             inline=True)


def interpret_mode() -> bool:
    """Pallas interpret mode: everywhere except a real TPU backend."""
    return jax.default_backend() != "tpu"


def tier_enabled() -> bool:
    """Should automatic paths (Executor fusion pass, fused Adam, the
    serving decode hook) select Pallas kernels right now?

    ``FLAGS_use_pallas_kernels`` is the master switch; off-TPU the tier
    additionally requires the explicit ``FLAGS_pallas_interpret`` opt-in
    — interpret mode exists for numerics tests, not for speed, so a CPU
    training run must never pay it by accident."""
    from ...core.flags import get_flag
    if not get_flag("use_pallas_kernels"):
        return False
    if jax.default_backend() == "tpu":
        return True
    return bool(get_flag("pallas_interpret"))


def dtype_ok(dtype) -> bool:
    """The two dtypes every tier kernel accumulates from (f32 math)."""
    return jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16))


def smem_scalar_spec():
    """(1, 1) scalar operand placed in SMEM."""
    return pl.BlockSpec((1, 1), lambda *_: (0, 0),
                        memory_space=pltpu.SMEM)


# selection counter: {kernel name: trace-time selections} (see module
# docstring — compiles, not executions).  Tests assert the OFF contract
# (flag off => no entry moves); chip_smoke.py prints the delta per phase.
kernel_selections: dict = {}


def count_kernel_selection(name: str) -> None:
    kernel_selections[name] = kernel_selections.get(name, 0) + 1
    from ...utils import monitor
    monitor.stat_add(f"pallas.selected.{name}")


def choose_kernel(functional: str, supported: bool) -> bool:
    """Kernel or XLA form, for one traced call of a functional that has
    both: the kernel where the tier is on (`tier_enabled`) and the
    mechanism's own gate took the call's shapes and dtype (``supported``:
    its ``*_supported``).  Who counts, one rule: a kernel's public entry
    counts its own selection (``pallas.selected.<kernel>``, as
    `flash_attention`, `sparse_attention`, `ssd_scan`, `causal_conv1d`,
    `moe_combine`, `fused_adam`, `fused_epilogue`, `paged_attention` and
    `collective_matmul` always have), so a direct call of it is counted too; the chooser counts the
    other side, ``<functional>.xla_path``.  Exactly one of the two moves
    a traced call."""
    if supported and tier_enabled():
        return True
    from ...utils import monitor
    monitor.stat_add(f"{functional}.xla_path")
    return False


def block_rows(m: int, preferred: int = 512) -> int:
    """Largest power-of-two row-block <= ``preferred`` that tiles ``m``
    (assumes ``m % 8 == 0``, the f32 sublane gate)."""
    bm = preferred
    while bm > 8 and m % bm:
        bm //= 2
    return max(min(bm, m), 1)
