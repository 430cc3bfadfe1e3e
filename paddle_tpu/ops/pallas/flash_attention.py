"""Flash attention as a Pallas TPU kernel (fwd + custom-vjp bwd).

The TPU-native replacement for the reference's fused attention CUDA path
(reference: paddle/fluid/operators/math/bert_encoder_functor.cu
MultiHeadGPUComputeFunctor, operators/fused/fused_attention_op.cu,
ir/multihead_matmul_fuse_pass.cc): one kernel keeps Q/K/V blocks in VMEM,
streams KV, and carries the online-softmax running max/sum so the [L, L]
score matrix never touches HBM.

Layout: [B, L, H, D] in (paddle layout), transposed once to [B, H, L, D]
around the kernel.  Forward saves per-row logsumexp for the
recompute-based backward (standard FlashAttention-2 dataflow).

Causal masking supports traced *global position offsets* for Q and K
(`q_off`/`k_off`, float32 [1,1] scalars): a Q/K pair is visible when
``q_off + i >= k_off + j``.  Offsets are what lets ring attention
(parallel/ring_attention.py) reuse this kernel for every ring round —
rounds holding earlier shards fully visible, later shards fully masked,
the diagonal round causal — with ONE kernel instead of a lax.switch
(which custom_vjp cannot differentiate through).

Interpret mode (CPU) runs the same kernels for tests.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...observability import scopes
from .support import (NEG_INF, dot as _dot, interpret_mode as _interpret,
                      pltpu, smem_scalar_spec as _smem_scalar_spec)


def flash_attention_supported(q_shape, k_shape, dtype, attn_mask=None,
                              dropout_p: float = 0.0,
                              block_q: int = 512, block_k: int = 512) -> bool:
    """Capability + profitability check: shapes/dtype the kernel handles
    AND where it beats XLA's fused attention (measured on v5e: flash wins
    ~30% at seq>=2048, XLA wins ~2% at seq 512 — the crossover is the
    FLAGS_pallas_attention_min_seqlen knob).  Attention dropout runs
    IN-KERNEL via the Pallas TPU PRNG (tile-seeded, regenerated in the
    backward) — but only on real TPUs (interpret mode has no PRNG)."""
    from ...core.flags import get_flag
    if attn_mask is not None:
        return False
    if dropout_p > 0.0 and _interpret():
        return False  # pltpu PRNG has no CPU interpreter lowering
    if len(q_shape) != 4:
        return False
    B, Lq, H, D = q_shape
    Lk = k_shape[1]
    if dtype not in (jnp.float32, jnp.bfloat16):
        return False
    min_len = get_flag("pallas_attention_dropout_min_seqlen"
                       if dropout_p > 0.0
                       else "pallas_attention_min_seqlen")
    if max(Lq, Lk) < min_len:
        return False
    # blocks must tile the sequence
    if Lq % min(block_q, Lq) or Lk % min(block_k, Lk):
        return False
    if D % 8:  # lane alignment of the head dim
        return False
    # whole-KV (and, in the dK/dV kernel, whole-Q) staging must fit VMEM
    # (~16 MB/core); beyond this the sequence belongs on the 'sp' ring
    itemsize = jnp.dtype(dtype).itemsize
    if max(Lq, Lk) * D * itemsize > 2 * 1024 * 1024:
        return False
    return True


def _mask_scores(s, causal, qi, j, q_off_ref, k_off_ref, block_q, block_k,
                 bq):
    if not causal:
        return s
    q_off = q_off_ref[0, 0]
    k_off = k_off_ref[0, 0]
    # int32 iota + cast: Mosaic's tpu.iota only produces integer vectors
    q_pos = (q_off + qi * block_q
             + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k),
                                        0).astype(jnp.float32))
    k_pos = (k_off + j * block_k
             + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k),
                                        1).astype(jnp.float32))
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _dropout_keep(seed_ref, qi, j, shape, dropout_p):
    """Tile keep-mask from the Pallas TPU PRNG, seeded on
    (user seed, b, h, q-block, k-block) so the backward kernels reproduce
    the forward's mask exactly.  prng_random_bits has int32 semantics on
    TPU: an arithmetic >>16 yields uniform [-32768, 32767], compared
    against the p-quantile threshold."""
    b = pl.program_id(0)
    h = pl.program_id(1)
    # Mosaic accepts at most 2 seed words: fold (b,h) and (qi,j) — the
    # 65599 strides keep tile seeds distinct for any h, j < 65599
    s1 = seed_ref[0, 0] ^ (b * 65599 + h)
    s2 = qi * 65599 + j
    pltpu.prng_seed(s1, s2)
    bits = pltpu.prng_random_bits(shape)
    v = jax.lax.shift_right_arithmetic(bits, 16)
    t = int(round(dropout_p * 65536.0)) - 32768
    return v >= t


def _apply_dropout(p, seed_ref, qi, j, dropout_p):
    """p (unnormalized probs) -> p * keep / (1 - p_q).  The softmax
    denominator keeps the UNdropped sum, which reproduces dropout applied
    to the normalized weights (out = sum(drop(w) v), w = p / l)."""
    if dropout_p <= 0.0:
        return p
    t = int(round(dropout_p * 65536.0))
    if t >= 65536:  # p ~ 1.0: everything drops
        return jnp.zeros_like(p)
    keep = _dropout_keep(seed_ref, qi, j, p.shape, dropout_p)
    inv_keep = 65536.0 / (65536 - t)
    return jnp.where(keep, p * inv_keep, 0.0)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_off_ref, k_off_ref, seed_ref, q_ref, k_ref, v_ref,
                o_ref, lse_ref, *, scale, block_k, seq_k, causal, block_q,
                aligned, dropout_p):
    qi = pl.program_id(2)
    q_raw = q_ref[0, 0]
    q = (q_raw.astype(jnp.float32) * scale).astype(q_raw.dtype)  # [BQ, D]
    bq, d = q.shape
    m = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)

    num_kv = seq_k // block_k
    if causal and aligned:
        # only blocks overlapping the causal triangle of this Q block
        num_kv = jnp.minimum(num_kv,
                             pl.cdiv((qi + 1) * block_q, block_k))

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :]   # [BK, D]
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
        s = _dot(q, k, ((1,), (1,)))                      # [BQ, BK] f32
        s = _mask_scores(s, causal, qi, j, q_off_ref, k_off_ref, block_q,
                         block_k, bq)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        # fully-masked rows: all s == NEG_INF makes s - m_new == 0; zero
        # those probabilities instead of attending uniformly
        p = jnp.where(s > 0.5 * NEG_INF, p, 0.0)
        alpha = jnp.exp(m - m_new)
        # denominator uses the UNdropped sum; only the value aggregation
        # sees the dropout mask (== dropout on normalized weights)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        u = _apply_dropout(p, seed_ref, qi, j, dropout_p)
        acc = acc * alpha + _dot(u.astype(v.dtype), v, ((1,), (0,)))
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, num_kv, body, (m, l, acc))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
    # lse block is (8, bq): positions on the LANE dim, replicated over 8
    # sublanes — the minimal Mosaic-legal tile.  A trailing unit dim
    # ([..., Lq, 1]) would make XLA tile-pad the HBM buffer 1 -> 128
    # lanes (128x memory — measured ~200 MB/layer residual at BERT-base
    # scale); the (bq, 1) -> (1, bq) relayout is a few hundred f32/block
    lse = jnp.where(l > 0, m + jnp.log(l_safe), NEG_INF)
    lse_ref[0, 0] = jnp.broadcast_to(lse.reshape(1, -1), (8, lse.shape[0]))


def _qkv_fwd_specs(block_q, Lk, D):
    return [
        _smem_scalar_spec(),
        _smem_scalar_spec(),
        _smem_scalar_spec(),
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, Lk, D), lambda b, h, i: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, Lk, D), lambda b, h, i: (b, h, 0, 0)),
    ]


def _fwd(q, k, v, q_off, k_off, seed, scale, causal, block_q, block_k,
         aligned, dropout_p=0.0):
    """q/k/v: [B, H, L, D] → (out [B,H,Lq,D], lse [B,H,Lq])."""
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    grid = (B, H, Lq // block_q)
    kernel = functools.partial(_fwd_kernel, scale=scale, block_k=block_k,
                               seq_k=Lk, causal=causal, block_q=block_q,
                               aligned=aligned, dropout_p=dropout_p)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=_qkv_fwd_specs(block_q, Lk, D),
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, 8, block_q), lambda b, h, i: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Lq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, 8, Lq), jnp.float32),
        ],
        interpret=_interpret(),
        name=scopes.FLASH_FWD,
    )(q_off, k_off, seed, q, k, v)
    # compact [B, H, Lq] is the residual / public lse shape; the 8-sublane
    # replication exists only at the kernel boundary
    return out, lse[:, :, 0, :]


# ---------------------------------------------------------------------------
# backward (recompute-based, FlashAttention-2 style)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_off_ref, k_off_ref, seed_ref, q_ref, k_ref, v_ref,
                   do_ref, lse_ref, delta_ref, dq_ref, *, scale, block_k,
                   seq_k, causal, block_q, aligned, dropout_p):
    qi = pl.program_id(2)
    q = q_ref[0, 0]                                       # [BQ, D]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0][0:1, :].reshape(-1, 1)            # [BQ, 1]
    delta = delta_ref[0, 0][0:1, :].reshape(-1, 1)
    bq, d = q.shape
    dq = jnp.zeros((bq, d), jnp.float32)

    num_kv = seq_k // block_k
    if causal and aligned:
        num_kv = jnp.minimum(num_kv,
                             pl.cdiv((qi + 1) * block_q, block_k))

    def body(j, dq):
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
        s = _dot(q, k, ((1,), (1,))) * scale
        s = _mask_scores(s, causal, qi, j, q_off_ref, k_off_ref, block_q,
                         block_k, bq)
        p = jnp.exp(s - lse)                              # [BQ, BK]
        p = jnp.where(s > 0.5 * NEG_INF, p, 0.0)
        u = _apply_dropout(p, seed_ref, qi, j, dropout_p)
        dp = _dot(do, v, ((1,), (1,)))
        # d s = p_norm * (keep_scale * dP - delta)  (see derivation in
        # _apply_dropout: the denominator is undropped)
        ds = (u * dp - p * delta) * scale
        return dq + _dot(ds.astype(k.dtype), k, ((1,), (0,)))

    dq = jax.lax.fori_loop(0, num_kv, body, dq)
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_off_ref, k_off_ref, seed_ref, q_ref, k_ref, v_ref,
                    do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *, scale,
                    block_q, seq_q, causal, block_k, aligned, dropout_p):
    kj = pl.program_id(2)
    k = k_ref[0, 0]                                       # [BK, D]
    v = v_ref[0, 0]
    bk, d = k.shape
    dk = jnp.zeros((bk, d), jnp.float32)
    dv = jnp.zeros((bk, d), jnp.float32)

    num_q = seq_q // block_q
    start = (kj * block_k) // block_q if (causal and aligned) else 0

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, 0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, 0, 0:1,
                      pl.ds(i * block_q, block_q)].reshape(-1, 1)
        delta = delta_ref[0, 0, 0:1,
                          pl.ds(i * block_q, block_q)].reshape(-1, 1)
        s = _dot(q, k, ((1,), (1,))) * scale
        # rows are q positions (loop index i), cols are this k block (kj)
        s = _mask_scores(s, causal, i, kj, q_off_ref, k_off_ref, block_q,
                         block_k, block_q)
        p = jnp.exp(s - lse)                              # [BQ, BK]
        p = jnp.where(s > 0.5 * NEG_INF, p, 0.0)
        # fwd tile (qi=i, j=kj): identical seed -> identical mask
        u = _apply_dropout(p, seed_ref, i, kj, dropout_p)
        dv = dv + _dot(u.astype(do.dtype), do, ((0,), (0,)))
        dp = _dot(do, v, ((1,), (1,)))
        ds = (u * dp - p * delta) * scale                 # [BQ, BK]
        dk = dk + _dot(ds.astype(q.dtype), q, ((0,), (0,)))
        return dk, dv

    dk, dv = jax.lax.fori_loop(start, num_q, body, (dk, dv))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _bwd(q, k, v, q_off, k_off, seed, out, lse, do, dlse, scale, causal,
         block_q, block_k, aligned, dropout_p=0.0):
    """Full backward.  The lse cotangent folds into delta: with
    P = exp(S - lse) row-normalized, dS = P * (dP_rows - delta + dlse)
    since d lse / dS = P."""
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                              # [B, H, Lq]
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    # 8-sublane replication at the kernel boundary (see _fwd_kernel note)
    lse8 = jnp.broadcast_to(lse[:, :, None, :], (B, H, 8, Lq))
    delta8 = jnp.broadcast_to(delta[:, :, None, :], (B, H, 8, Lq))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_k=block_k,
                          seq_k=Lk, causal=causal, block_q=block_q,
                          aligned=aligned, dropout_p=dropout_p),
        grid=(B, H, Lq // block_q),
        in_specs=_qkv_fwd_specs(block_q, Lk, D) + [
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, 8, block_q), lambda b, h, i: (b, h, 0, i)),
            pl.BlockSpec((1, 1, 8, block_q), lambda b, h, i: (b, h, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Lq, D), q.dtype),
        interpret=_interpret(),
        name=scopes.FLASH_BWD_DQ,
    )(q_off, k_off, seed, q, k, v, do, lse8, delta8)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, block_q=block_q,
                          seq_q=Lq, causal=causal, block_k=block_k,
                          aligned=aligned, dropout_p=dropout_p),
        grid=(B, H, Lk // block_k),
        in_specs=[
            _smem_scalar_spec(),
            _smem_scalar_spec(),
            _smem_scalar_spec(),
            pl.BlockSpec((1, 1, Lq, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, Lq, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 8, Lq), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 8, Lq), lambda b, h, j: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Lk, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Lk, D), v.dtype),
        ],
        interpret=_interpret(),
        name=scopes.FLASH_BWD_DKV,
    )(q_off, k_off, seed, q, k, v, do, lse8, delta8)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp cores over [B, H, L, D]
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash(q, k, v, q_off, k_off, seed, scale, causal, block_q, block_k,
           aligned, dropout_p):
    out, _ = _fwd(q, k, v, q_off, k_off, seed, scale, causal, block_q,
                  block_k, aligned, dropout_p)
    return out


def _flash_fwd(q, k, v, q_off, k_off, seed, scale, causal, block_q,
               block_k, aligned, dropout_p):
    out, lse = _fwd(q, k, v, q_off, k_off, seed, scale, causal, block_q,
                    block_k, aligned, dropout_p)
    return out, (q, k, v, q_off, k_off, seed, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, aligned, dropout_p, res,
               do):
    q, k, v, q_off, k_off, seed, out, lse = res
    dq, dk, dv = _bwd(q, k, v, q_off, k_off, seed, out, lse, do, None,
                      scale, causal, block_q, block_k, aligned, dropout_p)
    return (dq, dk, dv, jnp.zeros_like(q_off), jnp.zeros_like(k_off),
            None)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_with_lse(q, k, v, q_off, k_off, scale, block_q, block_k):
    """Position-masked attention returning (out, lse) — the ring-attention
    building block (both outputs differentiable; no dropout: ring rounds
    merge via logsumexp, which requires undropped weights)."""
    return _fwd(q, k, v, q_off, k_off, _zero_seed(), scale, True, block_q,
                block_k, False)


def _flash_with_lse_fwd(q, k, v, q_off, k_off, scale, block_q, block_k):
    out, lse = _fwd(q, k, v, q_off, k_off, _zero_seed(), scale, True,
                    block_q, block_k, False)
    return (out, lse), (q, k, v, q_off, k_off, out, lse)


def _flash_with_lse_bwd(scale, block_q, block_k, res, cts):
    q, k, v, q_off, k_off, out, lse = res
    do, dlse = cts
    dq, dk, dv = _bwd(q, k, v, q_off, k_off, _zero_seed(), out, lse, do,
                      dlse, scale, True, block_q, block_k, False)
    return dq, dk, dv, jnp.zeros_like(q_off), jnp.zeros_like(k_off)


_flash_with_lse.defvjp(_flash_with_lse_fwd, _flash_with_lse_bwd)


# ---------------------------------------------------------------------------
# public entries
# ---------------------------------------------------------------------------

def _zero_off():
    return jnp.zeros((1, 1), jnp.float32)


def _zero_seed():
    return jnp.zeros((1, 1), jnp.int32)


def flash_attention(q, k, v, causal: bool = False, scale=None,
                    block_q: int = 512, block_k: int = 512,
                    dropout_p: float = 0.0, seed=None):
    """q/k/v: [B, L, H, D] arrays → [B, Lq, H, D] attention output.

    ``dropout_p > 0`` applies attention-probability dropout IN-KERNEL
    (Pallas TPU PRNG, tile-seeded from ``seed`` so the backward
    regenerates the identical mask); pass a fresh int32 ``seed`` array
    ([1, 1]) per training step."""
    D = q.shape[-1]
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
    block_q = min(block_q, q.shape[1])
    block_k = min(block_k, k.shape[1])
    if dropout_p > 0.0 and _interpret():
        raise NotImplementedError(
            "flash_attention dropout needs the Pallas TPU PRNG (real TPU "
            "only); use scaled_dot_product_attention, whose dispatch "
            "falls back to the unfused path off-TPU")
    if seed is None:
        seed = _zero_seed()
    else:
        seed = jnp.asarray(seed, jnp.int32).reshape(1, 1)
    qt = jnp.swapaxes(q, 1, 2)      # [B, H, L, D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _flash(qt, kt, vt, _zero_off(), _zero_off(), seed, scale,
                 bool(causal), block_q, block_k, True,
                 float(dropout_p))
    return jnp.swapaxes(out, 1, 2)


def flash_attention_block(q_bhld, k_bhld, v_bhld, q_off, k_off, scale,
                          block_q: int = 512, block_k: int = 512):
    """Ring-attention building block: [B, H, L, D] layout, traced global
    position offsets (float32 [1,1] arrays), always position-masked.
    Returns (out normalized [B,H,L,D], lse [B,H,L]); fully-masked rows
    give out=0, lse≈-inf — ready for logsumexp merging across rounds."""
    block_q = min(block_q, q_bhld.shape[2])
    block_k = min(block_k, k_bhld.shape[2])
    return _flash_with_lse(q_bhld, k_bhld, v_bhld, q_off, k_off, scale,
                           block_q, block_k)


def mha_reference(q, k, v, causal=False, scale=None):
    """jnp oracle for tests ([B, L, H, D] layout)."""
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    s = jnp.einsum("blhd,bshd->bhls", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Lq, Lk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((Lq, Lk), bool))
        s = jnp.where(mask[None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhls,bshd->blhd", w, v.astype(jnp.float32)
                      ).astype(q.dtype)
