"""Ring attention — sequence/context parallelism over an ICI ring.

The reference has NO long-context support (SURVEY §5.7: verified absent);
this is the capability-parity-plus item the TPU build adds natively.

Design (blockwise ring attention): the sequence is sharded over the 'sp'
mesh axis.  Each device holds its Q block permanently and circulates K/V
blocks around the ring with ``lax.ppermute`` (one hop per step, overlapping
the next hop's transfer with the current block's attention math).  Partial
attention results merge with the numerically-stable online-softmax
(log-sum-exp) rule, so the result is EXACTLY standard attention on the
full sequence.

Causal masking uses the *block* offset of the K/V shard currently held, so
each device does the same work pattern (no load imbalance beyond the mask).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec
from jax import shard_map

from ..core.dispatch import apply
from ..core.tensor import Tensor
from ..distributed.mesh import SP_AXIS, ensure_mesh


def _block_attn(q, k, v, scale, mask):
    """One Q-block × K-block attention with running-softmax stats.

    q: [B, Lq, H, D], k/v: [B, Lk, H, D]; returns (out_unnorm, lse, m) where
    out_unnorm = exp(s - m) @ v, m = rowmax, lse = log sum exp(s - m)."""
    s = jnp.einsum("blhd,bshd->bhls", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe)
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhls,bshd->blhd", p, v)
    return o, l, m_safe, m


def ring_attention_per_device(q, k, v, axis_name: str, is_causal: bool,
                              scale: Optional[float] = None):
    """Per-device ring attention body (call inside shard_map).

    q/k/v: local shards [B, L_local, H, D].  Returns [B, L_local, H, D]."""
    B, Lq, H, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    S = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % S) for i in range(S)]

    q_pos = my * Lq + jnp.arange(Lq)           # global positions of my Q

    def step(carry, r):
        k_blk, v_blk, o, l, m = carry
        src = (my - r) % S                      # whose K/V I hold at round r
        if is_causal:
            k_pos = src * Lq + jnp.arange(Lq)
            mask = (q_pos[:, None] >= k_pos[None, :])[None, None]
        else:
            mask = None
        o_b, l_b, m_safe_b, m_b = _block_attn(q, k_blk, v_blk, scale, mask)
        # online-softmax merge of (o, l, m) with block stats
        new_m = jnp.maximum(m, m_b)
        new_m_safe = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - new_m_safe, -jnp.inf))
        alpha = jnp.where(jnp.isfinite(m), alpha, 0.0)
        beta = jnp.exp(jnp.where(jnp.isfinite(m_b), m_safe_b - new_m_safe,
                                 -jnp.inf))
        beta = jnp.where(jnp.isfinite(m_b), beta, 0.0)
        # stats are [B, H, Lq, 1]; o is [B, Lq, H, D] → swap H/Lq axes
        o = (o * jnp.swapaxes(alpha, 1, 2)
             + o_b * jnp.swapaxes(beta, 1, 2))
        l = l * alpha + l_b * beta
        # rotate K/V to the next device (overlaps with next block's math)
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, o, l, new_m), None

    o0 = jnp.zeros((B, Lq, H, D), q.dtype)
    l0 = jnp.zeros((B, H, Lq, 1), q.dtype)
    m0 = jnp.full((B, H, Lq, 1), -jnp.inf, q.dtype)
    (_, _, o, l, m), _ = jax.lax.scan(
        step, (k, v, o0, l0, m0), jnp.arange(S))
    denom = jnp.swapaxes(jnp.maximum(l, 1e-20), 1, 2)  # → [B, Lq, H, 1]
    return o / denom


def _flash_eligible(q) -> bool:
    from ..ops.pallas.flash_attention import flash_attention_supported
    from ..ops.pallas.support import tier_enabled
    if not tier_enabled():
        return False
    shape = tuple(q.shape)  # the per-device local shard shape
    return flash_attention_supported(shape, shape, q.dtype)


def ring_attention_per_device_flash(q, k, v, axis_name: str, is_causal: bool,
                                    scale: Optional[float] = None):
    """Ring attention whose per-block math is the Pallas flash kernel.

    Each round attends my Q block against the circulating K/V block with
    the fused kernel (normalized output + logsumexp), then merges rounds
    with logsumexp weights.  Causality rides the kernel's *global position
    offsets*: q_off = my·L, k_off = src·L — rounds holding earlier shards
    are fully visible, later shards fully masked, the diagonal causal,
    all with one kernel (differentiable through the scan)."""
    from ..ops.pallas.flash_attention import flash_attention_block
    B, Lq, H, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    S = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % S) for i in range(S)]
    qt = jnp.swapaxes(q, 1, 2)                 # [B, H, L, D]
    q_off = (my * Lq).astype(jnp.float32).reshape(1, 1)

    def step(carry, r):
        k_blk, v_blk, o, lse = carry
        src = (my - r) % S
        if is_causal:
            k_off = (src * Lq).astype(jnp.float32).reshape(1, 1)
        else:
            # every position visible: put K "infinitely in the past"
            k_off = jnp.full((1, 1), -1e9, jnp.float32)
        o_b, lse_b = flash_attention_block(
            qt, jnp.swapaxes(k_blk, 1, 2), jnp.swapaxes(v_blk, 1, 2),
            q_off, k_off, scale)
        lse_new = jnp.logaddexp(lse, lse_b)               # [B, H, Lq]
        finite = jnp.isfinite(lse_new)
        w_old = jnp.where(finite, jnp.exp(lse - lse_new), 0.0)
        w_new = jnp.where(finite, jnp.exp(lse_b - lse_new), 0.0)
        o = o * w_old[..., None] + o_b.astype(jnp.float32) * w_new[..., None]
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, o, lse_new), None

    o0 = jnp.zeros((B, H, Lq, D), jnp.float32)
    lse0 = jnp.full((B, H, Lq), -jnp.inf, jnp.float32)
    (_, _, o, _), _ = jax.lax.scan(step, (k, v, o0, lse0), jnp.arange(S))
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)


def ring_attention(q, k, v, is_causal=True, mesh=None,
                   axis_name: str = SP_AXIS):
    """Tensor-level ring attention: q/k/v [B, L, H, D] with L sharded over
    the 'sp' axis.  Exact attention over the full sequence.  Per-block math
    uses the Pallas flash kernel when eligible (long local blocks)."""
    mesh = mesh or ensure_mesh()

    def _ra(qa, ka, va):
        n = mesh.shape[axis_name]
        local = qa.shape[1] // n
        use_flash = _flash_eligible(
            jax.ShapeDtypeStruct((qa.shape[0], local, qa.shape[2],
                                  qa.shape[3]), qa.dtype))
        body = (ring_attention_per_device_flash if use_flash
                else ring_attention_per_device)
        spec = PartitionSpec(None, axis_name, None, None)
        fn = shard_map(
            lambda a, b, c: body(a, b, c, axis_name, is_causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        return fn(qa, ka, va)

    return apply(_ra, q, k, v, op_name="ring_attention")


def reference_attention(q, k, v, is_causal=True):
    """Single-device oracle for tests."""
    def _attn(qa, ka, va):
        D = qa.shape[-1]
        s = jnp.einsum("blhd,bshd->bhls", qa, ka) / math.sqrt(D)
        if is_causal:
            L, Sk = qa.shape[1], ka.shape[1]
            mask = jnp.tril(jnp.ones((L, Sk), bool))
            s = jnp.where(mask[None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhls,bshd->blhd", w, va)
    return apply(_attn, q, k, v, op_name="reference_attention")
