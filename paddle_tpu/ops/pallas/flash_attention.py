"""Flash attention as a Pallas TPU kernel (fwd + custom-vjp bwd).

The TPU-native replacement for the reference's fused attention CUDA path
(reference: paddle/fluid/operators/math/bert_encoder_functor.cu
MultiHeadGPUComputeFunctor, operators/fused/fused_attention_op.cu,
ir/multihead_matmul_fuse_pass.cc): one kernel keeps Q/K/V blocks in VMEM,
streams KV, and carries the online-softmax running max/sum so the [L, L]
score matrix never touches HBM.

Layout: [B, L, H, D] in and out (paddle layout).  The kernels take an
operand AS IT LIES, [B, L, H, D] under the name [B, L, H*D] with a head
chosen by the index map along the lane axis, or in a [B, H, L, D] copy
made once around the call (`_as_it_lies`):
- heads of 64 whose keys have a head each, in even number: everything as
  it lies, two neighbouring heads to a 128-lane block and a grid step,
  the body over each head's 64 lanes of its blocks in turn
  (`_each_of_a_pair`);
- heads whose D and Dv are multiples of 128, a key head each, through
  `flash_attention`: v, out, dO and dv as they lie (projections write
  and read them untouched), q, k, dq and dk in their copies: where an
  XLA fusion WRITES a [B, L, H, D] value (a rotation, a slice of a
  wider projection) its tiles hold a position's
  heads along the sublanes and want a `reshape` pass of their own to
  become [B, L, H*D], while the same fusion writes [B, H, L, D] order at
  no cost (`_QK_LIE_AT_128`, with the chip's table);
- every other call (96-wide heads, heads in groups, 64-wide heads in odd
  number, the shared-key entry, the ring's blocks, EVA's folded windows):
  everything in copies, as before, the parent's program.
One body and one arithmetic whatever the addressing (a body sees
[rows, width] under one or two unit dimensions); lse and delta are
[B, H, 8, L] throughout.

Keys and values may have fewer heads than the
queries (grouped-query attention: ``Hk`` divides ``H``, query head h
reads key/value head ``h // (H / Hk)``): the kernels' index maps send a
group's query heads to the one key/value head, which is never repeated
in HBM, and the backward walk emits each query head's part of dK and dV,
summed over the group in float32 outside (Nemotron-3-Nano, the
benchmark's: 32 query heads on 2).  The values may have a width of their own (``Dv``):
q, k, dq and dk are ``D`` wide, v, out, dO and dv ``Dv``.  A part of the
key may be one for all heads (``flash_attention_shared_key``: latent
attention's rotated key, [B, 1, L, Dr]); it is staged once a batch entry
and never broadcast.  JoyAI-LLM-Flash (the benchmark's) has D 128 + Dr
64 and Dv 128; published shapes that need ``Dv`` apart from ``D``: plain
heads of 192 with values of 128 (MiMo-V2-Flash), latent heads of 192 +
64 with values of 256 (GLM-5), 128 + 64 with 192 (GigaChat3.1), 64 + 64
with 128 (Mistral-Small-4).  Forward saves per-row logsumexp for the
recompute-based backward (standard FlashAttention-2 dataflow).  Inside,
every score tile is held transposed ([block_k, block_q]): the note and
the tile mathematics the family shares are in attention_tiles.py.

The backward is one kernel (PR 33): the dK/dV walk holds each pair's
``ds`` and its k block, so it makes dQ too, into a float32 VMEM
accumulator that holds the head's whole dQ; no second walk recomputes
the scores, p and dP (5 products a pair where two kernels ran 7).

Per call on one v5e, in the cells' steps (PERF.md, PR 33; the block
sweep is PR 25's): [8, 16, 2048, 96] bf16 causal takes 1.48 ms forward
and 2.90 ms backward (1.73 dq + 2.46 dk/dv before); [64, 12, 512, 64]
bf16 non-causal 0.90 and 1.46 ms (1.06 + 1.25); [2, 32, 8192, 128 + 64
shared] with 128-wide values, causal, 13.89 and 27.01 ms (16.74 +
22.39); [2, 16, 4096, 128] causal 1.381 and 2.487 ms with every operand
in a [B, H, L, D] copy, 1.392 and 2.499 with v, out, dO and dv as they
lie (a staged V column is 4,096 rows of 256 bytes at a stride of 4,096:
+0.6 %), 1.404 and 2.526 with q and k lying too (PERF.md section 6,
PR 44: 32 calls a step); [1, 16384, 32 on 4, 128] causal 19.08 and 34.01
ms (73.6 % of what the pair needs at the chip's peak), and under a
window of 2048, which runs 150 of the triangle's 528 blocks, 6.09 and
11.43 (52.4 % of the band's need; PERF.md section 5, PR 47).  Whether XLA's attention or this kernel is
taken at a length is a measured constant, `_KERNEL_FROM` below, beside the chip table it came
from: the kernel from 512 positions up, for every head size, mask and
dropout rate measured (PERF.md, PR 27).

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
from .attention_tiles import (BLOCK, block_loops, delta as _delta, dq_add,
                              dq_emit, dq_zero, kv_spans, lead,
                              mask_diagonal, mask_window, online_step, p_ds,
                              prescale, q_spans, rows, rows8, write_row8)
from .support import (NEG_INF, count_kernel_selection, dot as _dot,
                      interpret_mode as _interpret, name_residuals,
                      once_a_shape as _once_a_shape, pltpu,
                      smem_scalar_spec as _smem_scalar_spec)


# XLA's attention or the kernel?  One attention call, forward + backward
# under jax.jit, [B, L, H, D] in and out (the layout changes around the
# kernel and the delta reduction inside the timing), bf16, non-causal,
# 32,768 tokens x 768 hidden on one v5e (PERF.md, PR 27); ms, XLA / kernel:
#
#      L   D = 64         D = 96        D = 128       D = 64, dropout 0.1
#    128   2.21 / 7.08    1.22 / 4.95   1.26 / 2.87   2.88 / 7.11
#    256   3.67 / 5.32    2.75 / 3.84   2.42 / 2.04   5.09 / 5.41
#    384   5.08 / 5.17    3.69 / 3.71   3.12 / 1.88   7.10 / 5.39
#    512   6.41 / 4.94    4.61 / 3.57   3.83 / 1.89   9.26 / 5.74
#   1024  13.14 / 8.18    9.06 / 5.88   6.88 / 3.70
#
# A causal mask costs the kernel 0.1 to 0.5 ms more below 1024, XLA
# nothing, and moves no row to the other side.  From 512 up the kernel
# wins in every column, at 128 XLA does; between them it depends on the
# head size.  A step is harder on the kernel than this sweep: in the
# BERT cell ([64, 512, 12, 64], 12 layers) the step fell by 8.4 ms where
# the sweep's 1.47 ms a layer promise 17.6, because XLA lays the
# projections around the kernel out less well (0.7 ms a layer that no
# sweep of one call sees).  That margin takes D = 64 and 96 at 384 and
# D = 128 at 256 to XLA's side and leaves D = 128 at 384 and dropout at
# 384 as the only entries under 512 on the kernel's, lengths no cell and
# hardly a model runs: one length decides, with dropout (XLA writes its
# masks to HBM) and without.
_KERNEL_FROM = 512


def flash_attention_supported(q_shape, k_shape, dtype, attn_mask=None,
                              dropout_p: float = 0.0,
                              block_q: int | None = None,
                              block_k: int | None = None,
                              v_head_dim: int | None = None,
                              shared_key_dim: int = 0) -> bool:
    """Capability + profitability check: shapes/dtype the kernel handles
    AND where it is taken over XLA's fused attention (from `_KERNEL_FROM`
    positions up, the crossover measured above).  Attention dropout runs
    IN-KERNEL via the Pallas TPU PRNG (tile-seeded, regenerated in the
    backward) — but only on real TPUs (interpret mode has no PRNG).
    ``v_head_dim``: the values' width where it is not the keys';
    ``shared_key_dim``: the width of a key part all heads share, beside
    the ``D`` of the shapes (`flash_attention_shared_key`).

    Head counts: ``q_shape`` [B, Lq, H, D] and ``k_shape`` [B, Lk, Hk, D]
    with ``Hk`` dividing ``H`` (``Hk == H``: plain multi-head attention;
    fewer: grouped-query attention, query head h on key/value head
    ``h // (H / Hk)``; the values have the keys' head count).  The
    shared-key call (``shared_key_dim``) takes ``Hk == H`` only."""
    if attn_mask is not None:
        return False
    if dropout_p > 0.0 and _interpret():
        return False  # pltpu PRNG has no CPU interpreter lowering
    if len(q_shape) != 4:
        return False
    B, Lq, H, D = q_shape
    Lk, Hk = k_shape[1], k_shape[2]
    if H % Hk or (shared_key_dim and Hk != H):
        return False
    Dv = D if v_head_dim is None else v_head_dim
    if dtype not in (jnp.float32, jnp.bfloat16):
        return False
    if max(Lq, Lk) < _KERNEL_FROM:
        return False
    # blocks must tile the sequence
    bq, bk = _resolve_blocks(block_q, block_k, Lq, Lk)
    if Lq % bq or Lk % bk:
        return False
    if D % 8 or Dv % 8 or shared_key_dim % 8:  # lane alignment
        return False
    # A head's K and V are staged whole (in the dK/dV kernel its Q and dO):
    # 4 MiB a head (`_STAGED_DEFAULT`: K and V of 2 MiB each, the rule this
    # gate has always had) inside Mosaic's default scoped VMEM.  Only the
    # shared-key call goes further, to the 5 MiB that latent attention
    # stages at 8192 (`_STAGED_SHARED_KEY`), under a stated larger limit
    # (`_staging`), and the plain call to the 8 MiB of 16384 x 128 bf16
    # (`_STAGED_PLAIN`); beyond that the sequence belongs on the 'sp' ring
    L = max(Lq, Lk)
    staged = _staged_bytes(L, D + shared_key_dim, Dv, dtype)
    if staged <= _STAGED_DEFAULT:
        return True
    if shared_key_dim:
        return staged <= _STAGED_SHARED_KEY
    # a plain call over the default: the form that was compiled and
    # measured, heads one lane tile wide in a two-byte type
    return (D == Dv == 128 and jnp.dtype(dtype).itemsize == 2
            and staged <= _STAGED_PLAIN)


# Bytes of one head's K and V (or Q and dO) as staged: both at ``D`` = 128
# fill the 2 MiB each that the gate has always admitted (8192 x 128 bf16),
# inside Mosaic's default scoped VMEM of 16 MiB with their double buffers.
# Latent attention at 8192 stages k 2 MiB + the 64 lanes all heads share
# 1 MiB (2 as laid out) + v 2 MiB a head, 12 MiB double-buffered before the
# tiles: those calls state a limit of their own, of the chip's 128 MiB.
# That one shape is what was compiled for the chip and measured there
# (tests/test_chip_compile.py; PERF.md, PR 32), so the gate admits more
# than the default for the shared-key call up to its bytes, and (PR 47)
# for the plain call of 128-wide two-byte heads up to 8 MiB
# (`_STAGED_PLAIN`: 16384 x 128 bf16, Trinity-Mini's rows, and the
# shorter ones of that form; the readings are at the end of this note).
# A plain call of wider heads or of float32 over the default (8192 x 192
# bf16, 6144 x 128 f32) stays XLA's or the ring's: compiled for a v5e
# their walks take 55 to 76 MB of scoped VMEM, over the stated limit,
# where 16384 x 128 bf16 takes 38.  The backward walk that
# makes dQ too holds a head's dQ block and its float32 accumulator
# besides, and `_staging` counts that call as VMEM lays it out (a width
# under 128 lanes takes 128): at 8192 x 128, q and dO 8 MiB and dQ 4
# double-buffered, the accumulator 4, lse and delta 1, about 17 MiB
# before the tiles, and at 8192 x 64 still 15 (64 lanes as 128), so both
# state the limit.  8192 x 64 is a cell's shape since PR 43 (granite-4.0-h:
# [1, 8192, 32 heads on 8, 64], causal, scale 1/64): compiled for a v5e the
# walk takes 15,888,384 bytes of scoped VMEM, the 15 MiB of this budget,
# and the forward 5,869,568 on the defaults; on the chip the layer's pair
# reads 4.05 + 8.27 ms in the step (PERF.md section 5).  4096 x 128 (8.5
# MiB) and the other cells' plain shapes stay on the defaults; the
# shared-key walk about 30 MiB of the 48 (q 4 + qr 4 + dO 4, dQ 4 + dQr 4,
# the accumulators 4 + 2, lse and delta 1).  16384 x 128 bf16 (PR 47,
# [1, 16384, 32 on 4, 128] causal): by this count q and dO 16 MiB and dQ 8
# double-buffered, the accumulator 8, lse and delta 2, about 34 MiB before
# the tiles; compiled for a v5e (tests/test_chip_compile.py) the walk takes
# 37,662,720 bytes of scoped VMEM and under a window of 2048 38,739,968
# (the second mask's tiles), the forward 2,469,888 and 2,949,120: under
# the 48 MiB both; on the chip the full pair reads 19.08 + 34.01 ms in the
# step and a window pair 6.09 + 11.43 (PERF.md section 5).  A window call
# stages the head's whole row as the full call does: it fits, a group's
# eight query heads share one staging of K and V (a DMA a 256 grid
# steps), and staging the `window + block` rows a step can see instead
# would move 1.3 MB a step (ROADMAP R0 keeps that form for rows no chip
# stages whole).
_STAGED_DEFAULT = 4 * 1024 * 1024
_STAGED_SHARED_KEY = 5 * 1024 * 1024
_STAGED_PLAIN = 8 * 1024 * 1024
_VMEM_LIMIT_STAGED = 48 * 1024 * 1024


def _staged_bytes(L, D, Dv, dtype):
    return L * (D + Dv) * jnp.dtype(dtype).itemsize


def _staging(L, D, Dv, dtype, dq_widths=()):
    """``compiler_params`` of a call that stages ``L`` rows a head: None
    (Mosaic's defaults, the path every shape up to ``_STAGED_DEFAULT``
    has always taken) or the larger scoped-VMEM limit.  ``dq_widths``: of
    the backward walk that makes dQ too, the widths of its dQ blocks (the
    staged q's: ``D``, and a shared part's beside it).  That walk holds
    q, dO and the head's dQ blocks [L, width], double-buffered alike and
    each width laid out in whole 128-lane tiles, and the float32
    accumulators once (four bytes an element, so two beside blocks
    that are held twice)."""
    if dq_widths:
        lanes = 2 * sum(map(_lanes, dq_widths)) + _lanes(Dv)
        held = (L * lanes * jnp.dtype(dtype).itemsize
                + L * sum(dq_widths) * 2)
    else:
        held = _staged_bytes(L, D, Dv, dtype)
    if held <= _STAGED_DEFAULT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_STAGED)


def _lanes(width):
    """``width`` as VMEM lays a block's last dimension out."""
    return -(-width // 128) * 128


# ---------------------------------------------------------------------------
# blocks from the shape
# ---------------------------------------------------------------------------

def _resolve_blocks(block_q, block_k, Lq, Lk):
    """(block_q, block_k) of both kernels, from the static shapes:
    ``BLOCK`` where left at None (explicit ones are for tests and the
    ring path), the whole of a shorter sequence.  One pair for both,
    because the dropout tile seeds are block indices."""
    return min(block_q or BLOCK, Lq), min(block_k or BLOCK, Lk)


def _count_blocks(Lq, Lk, block_q, block_k, causal, aligned, window=None):
    """Trace-time counters, once per kernel traced: block iterations per
    (batch, head) that run without a mask (``blocks_full``) and with one
    (``blocks_masked``).  The backward kernel walks the forward's (q
    block, k block) pairs, by columns.  A windowed call counts its own
    beside them: ``window_blocks_full`` / ``window_blocks_masked`` (what
    it runs, in the two counters above as well) and
    ``window_blocks_skipped`` (the blocks of the causal triangle that lie
    wholly below its window)."""
    from ...utils import monitor
    num_q, num_kv = Lq // block_q, Lk // block_k

    def run(window):
        spans = [span for qi in range(num_q) for span in kv_spans(
            qi, block_q, block_k, num_kv, minimum=min, window=window)]
        return (sum(hi - lo for lo, hi, mask in spans if mask is None),
                sum(hi - lo for lo, hi, mask in spans if mask))

    if not causal:
        full, masked = num_q * num_kv, 0
    elif not aligned:
        full, masked = 0, num_q * num_kv
    else:
        full, masked = run(window)
    monitor.stat_add("pallas.flash.blocks_full", full)
    monitor.stat_add("pallas.flash.blocks_masked", masked)
    if window is not None:
        monitor.stat_add("pallas.flash.window_blocks_full", full)
        monitor.stat_add("pallas.flash.window_blocks_masked", masked)
        monitor.stat_add("pallas.flash.window_blocks_skipped",
                         sum(run(None)) - full - masked)


def _mask_scores(s, q_off_ref, k_off_ref, qi, j, block_q, block_k):
    """Position mask of the ring path: visibility depends on the traced
    global offsets, so every block is masked."""
    q_off = q_off_ref[0, 0]
    k_off = k_off_ref[0, 0]
    # int32 iota + cast: Mosaic's tpu.iota only produces integer vectors
    k_pos = (k_off + j * block_k
             + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                        0).astype(jnp.float32))
    q_pos = (q_off + qi * block_q
             + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                        1).astype(jnp.float32))
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _dropout_keep(seed_ref, qi, j, shape, dropout_p, of_pair=None):
    """Tile keep-mask from the Pallas TPU PRNG, seeded on
    (user seed, b, h, q-block, k-block) so the backward kernel reproduces
    the forward's mask exactly (both hold the tile as
    [block_k, block_q]).  prng_random_bits has int32 semantics on
    TPU: an arithmetic >>16 yields uniform [-32768, 32767], compared
    against the p-quantile threshold."""
    b = pl.program_id(0)
    h = pl.program_id(1)
    if of_pair is not None:         # the grid's second axis counts pairs
        h = 2 * h + of_pair
    # Mosaic accepts at most 2 seed words: fold (b,h) and (qi,j) — the
    # 65599 strides keep tile seeds distinct for any h, j < 65599
    s1 = seed_ref[0, 0] ^ (b * 65599 + h)
    s2 = qi * 65599 + j
    pltpu.prng_seed(s1, s2)
    bits = pltpu.prng_random_bits(shape)
    v = jax.lax.shift_right_arithmetic(bits, 16)
    t = int(round(dropout_p * 65536.0)) - 32768
    return v >= t


def _dropout(seed_ref, qi, j, dropout_p, of_pair=None):
    """Tile (``qi``, ``j``)'s hook for `online_step` and `p_ds`, None with
    dropout off: p (unnormalized probs) -> p * keep / (1 - p_q).  The
    softmax denominator keeps the UNdropped sum, which reproduces dropout
    applied to the normalized weights (out = sum(drop(w) v), w = p / l)."""
    if dropout_p <= 0.0:
        return None
    t = int(round(dropout_p * 65536.0))
    if t >= 65536:  # p ~ 1.0: everything drops
        return jnp.zeros_like

    def drop(p):
        keep = _dropout_keep(seed_ref, qi, j, p.shape, dropout_p, of_pair)
        return jnp.where(keep, p * (65536.0 / (65536 - t)), 0.0)
    return drop


# the masks of `attention_tiles.kv_spans` under a window: a block at its
# lower edge may hold none of a later query's keys
_AT_THE_WINDOW = ("window", "both")


def scores(k, q, mask, qi, j, q_off_ref, k_off_ref, block_q, block_k,
           shared=None, window=None):
    """[BK, BQ] f32 scores of (pre-scaled) q block ``qi`` against k block
    ``j`` under the block's mask.  ``shared``: the block's rows of the
    key part all heads share and the (pre-scaled) query part that meets
    it, ``(kr [BK, Dr], qr [BQ, Dr])``; their product joins the heads'."""
    s = _dot(k, q, ((1,), (1,)))
    if shared is not None:
        s = s + _dot(shared[0], shared[1], ((1,), (1,)))
    if mask == "diagonal":
        return mask_diagonal(s, qi, j, block_q, block_k)
    if mask in _AT_THE_WINDOW:
        return mask_window(s, qi, j, block_q, block_k, window,
                           diagonal=mask == "both")
    if mask == "positions":
        return _mask_scores(s, q_off_ref, k_off_ref, qi, j, block_q,
                            block_k)
    return s


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_off_ref, k_off_ref, seed_ref, q_ref, k_ref, v_ref, *rest,
                scale, block_k, seq_k, causal, block_q, aligned, dropout_p,
                shared, window=None, of_pair=None):
    if shared:
        qr_ref, kr_ref, o_ref, lse_ref = rest
        qr = prescale(qr_ref[lead(qr_ref)], scale)        # [BQ, Dr]
    else:
        o_ref, lse_ref = rest
    qi = pl.program_id(2)
    q = prescale(q_ref[lead(q_ref)], scale)               # [BQ, D]
    bq = q.shape[0]
    m = jnp.full((1, bq), NEG_INF, jnp.float32)
    l = jnp.zeros((1, bq), jnp.float32)
    acc = jnp.zeros((v_ref.shape[-1], bq), jnp.float32)   # out^T
    num_kv = seq_k // block_k

    def body(j, carry, mask):
        k = rows(k_ref, j, block_k)                       # [BK, D]
        v = rows(v_ref, j, block_k)
        s = scores(k, q, mask, qi, j, q_off_ref, k_off_ref, block_q,
                   block_k, (rows(kr_ref, j, block_k), qr)
                   if shared else None, window)           # [BK, BQ]
        return online_step(carry, s, v,
                           may_hide_query=(mask == "positions"
                                           or mask in _AT_THE_WINDOW),
                           drop=_dropout(seed_ref, qi, j, dropout_p,
                                         of_pair))

    m, l, acc = block_loops(body, (m, l, acc), num_kv, causal, aligned,
                            kv_spans(qi, block_q, block_k, num_kv,
                                     window=window))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[lead(o_ref)] = (acc / l_safe).T.astype(o_ref.dtype)
    write_row8(lse_ref, jnp.where(l > 0, m + jnp.log(l_safe), NEG_INF))


def _windowed(window, causal, aligned):
    """``window`` as the kernels' static: None, or a positive count of
    positions under the aligned causal schedule."""
    if window is None:
        return None
    if not causal or int(window) < 1:
        raise ValueError("flash attention: a window is a positive count of "
                         f"positions under causal=True, got {window!r}")
    if not aligned:
        raise NotImplementedError(
            "a sliding window is part of the aligned causal schedule: the "
            "ring's blocks (traced offsets) take none")
    return int(window)


def _kv_head(h, group):
    """The key/value head that query head ``h`` reads, ``group`` query
    heads to one (1: its own, and the index map is what it always was)."""
    return h if group == 1 else h // group


# How a kernel finds a head's rows, from the static shapes alone (the
# body sees [rows, width] either way, `attention_tiles.lead`):
# - as it lies: the operand is [B, L, H * width], which is
#   [B, L, H, width] as the projections leave it under another name; a
#   head is the lane block ``h``: block (1, rows, width) at
#   (b, row block, h).  A lane block is whole lane tiles: a head whose D
#   and Dv are multiples of 128, or two neighbouring heads of 64 where
#   keys and queries have a head each, one grid step a pair
#   (`_each_of_a_pair`).  Such an operand keeps that one form from the
#   public entry to the kernels (the custom-vjp cores, their residuals,
#   `delta`, a group's sum), so no [B, L, H, width] value is laid out for
#   the kernels' sake.
# - transposed: the operand is a [B, H, L, width] copy, block
#   (1, 1, rows, width) at (b, h, row block, 0).
# ``lies`` = ``(H, whether q and k lie too)`` says which: None, every
# operand transposed (every other width, heads in groups, the shared-key
# call, what a caller builds itself: EVA's folded windows, the ring's
# blocks); else v, out, dO and dv lie and `_qk(lies)` answers for q, k,
# dq and dk.

# Do q and k (and dq, dk) of 128-wide heads lie too?  Not where something
# rotates or slices them on their way here: XLA writes such a fusion's
# result straight into [B, H, L, D] order (the transposed addressing then
# costs q and k nothing), while its [B, L, H, D] tiles want a `reshape`
# pass of their own to become [B, L, H*D].  v, out, dO and dv come from
# and go to projections untouched, in every cell.  The Ouro cell's step
# ([2, 4096, 16, 128] causal, 32 calls, q and k out of a rotation), ms on
# one v5e (PERF.md section 6, PR 44):
#
#                            step      attention scope   rope    kernels
#   all transposed (PR 43)   1,024.13  149.11            28.90   123.78
#   all as they lie          1,032.00  130.06            45.16   125.74
#   q, k in their copies     1,007.05  138.26            26.20   124.49
#
# A call cannot see who wrote its operands, so one answer serves every
# 128-wide call.  Heads of 64 in pairs lie whole: BERT's come straight
# from projections, and a [B, H, L, 64] copy is laid out as 128 lanes,
# twice its bytes.
_QK_LIE_AT_128 = False


def _as_it_lies(q_shape, k_shape, v_shape):
    """``(H, whether q and k lie too)`` where a call's [B, L, H, ...]
    operands are taken as they lie, else None.  Keys with a head each
    only: where a group of query heads shares one, k and v are a fraction
    of q and only out and dO could lie, and the chip read Nemotron's step
    (32 on 2 at 8192) 1.1 ms SLOWER for it (PERF.md section 6, PR 44)."""
    H, Hk, D, Dv = q_shape[2], k_shape[2], q_shape[3], v_shape[3]
    if H != Hk:
        return None
    if D == Dv == 64 and H % 2 == 0:
        return H, True
    if D % 128 == 0 and Dv % 128 == 0:
        return H, _QK_LIE_AT_128
    return None


def _qk(lies):
    """``lies`` as it holds for q, k, dq and dk."""
    return lies if lies and lies[1] else None


def _heads_a_step(lies, D):
    """Heads whose lanes are one block: two of 64 as they lie."""
    return 2 if lies and D == 64 else 1


class _Part:
    """``size`` entries from ``start`` along ``axis`` of a kernel's ref, as
    the body indexes a ref: the part's place joins the index of each load
    and store (a ref sliced ahead of them would have to be whole tiles;
    a load or a store at half a lane tile need not)."""

    def __init__(self, ref, axis, start, size):
        self.ref, self.axis, self.start, self.size = ref, axis, start, size
        self.dtype = ref.dtype
        self.shape = ref.shape[:axis] + (size,) + ref.shape[axis + 1:]

    def _at(self, index):
        index = index if isinstance(index, tuple) else (index,)
        if index == (Ellipsis,):
            index = ()
        index = list(index) + [slice(None)] * (len(self.shape) - len(index))
        along = index[self.axis]
        index[self.axis] = (pl.ds(self.start, self.size)
                            if along == slice(None) else self.start + along)
        return tuple(index)

    def __getitem__(self, index):
        return self.ref[self._at(index)]

    def __setitem__(self, index, value):
        self.ref[self._at(index)] = value


def _each_of_a_pair(kernel, scratch=0):
    """``kernel`` for a grid step that holds two neighbouring 64-wide heads
    in its 128-lane blocks: the body over each head in turn, on its part
    of the step's refs: the head's lanes of a [1, rows, 128] block (of the
    ``scratch`` accumulators [.., 128, block] at the end, where they are
    sublanes), its row of a [1, 2, 8, L] block.  The same arithmetic on
    the same 64-wide tiles as a head a step."""
    def both(*refs, **static):
        blocks = len(refs) - scratch
        for r in range(2):
            def part(ref):
                if len(ref.shape) == 2:                   # a scalar in SMEM
                    return ref
                if len(ref.shape) == 3:
                    return _Part(ref, 2, 64 * r, 64)
                return _Part(ref, 1, r, 1)
            kernel(*map(part, refs[:blocks]),
                   *(_Part(acc, 1, 64 * r, 64) for acc in refs[blocks:]),
                   of_pair=r, **static)
    return both


def _head_rows(lies, rows, width, at):
    """The block of ``rows`` positions of one head, ``width`` wide;
    ``at(b, h, j)`` -> (batch entry, head, row block)."""
    if not lies:
        return pl.BlockSpec((1, 1, rows, width),
                            lambda b, h, j: (*at(b, h, j), 0))

    def along_lanes(b, h, j):
        entry, head, block = at(b, h, j)
        return entry, block, head
    return pl.BlockSpec((1, rows, width), along_lanes)


def _dims(q, k, v, lies):
    """-> (B, H, Hk, Lq, Lk, D, Dv) of operands in either form."""
    if _qk(lies):
        H, Hk, Lq, Lk = lies[0], lies[0], q.shape[1], k.shape[1]
        D = q.shape[2] // H
    else:
        H, Hk, Lq, Lk, D = (q.shape[1], k.shape[1], q.shape[2], k.shape[2],
                            q.shape[3])
    return (q.shape[0], H, Hk, Lq, Lk, D,
            v.shape[2] // H if lies else v.shape[3])


def _qkv_fwd_specs(block_q, Lk, D, Dv, Dr=0, group=1, lies=None):
    """In-specs of the forward kernel; with ``Dr`` also the
    query part [B, H, Lq, Dr] that meets the shared key [B, 1, Lk, Dr],
    which is staged once a batch entry: its block index does not move
    with the head.  ``group`` query heads read one key/value head: its
    block index does not move inside a group, so it is staged once a
    group."""
    shared = [
        pl.BlockSpec((1, 1, block_q, Dr), lambda b, h, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, Lk, Dr), lambda b, h, i: (b, 0, 0, 0)),
    ] if Dr else []

    def kv_head(b, h, i):
        return b, _kv_head(h, group), 0
    return [
        _smem_scalar_spec(),
        _smem_scalar_spec(),
        _smem_scalar_spec(),
        _head_rows(_qk(lies), block_q, D, lambda b, h, i: (b, h, i)),
        _head_rows(_qk(lies), Lk, D, kv_head),
        _head_rows(lies, Lk, Dv, kv_head),
    ] + shared


def _shared_width(shared):
    return shared[0].shape[-1] if shared else 0


def _fwd(q, k, v, q_off, k_off, seed, scale, causal, blocks, aligned,
         dropout_p=0.0, shared=None, lies=None, window=None):
    """q [B, H, L, D], k [B, Hk, Lk, D], v [B, Hk, Lk, Dv] → (out
    [B,H,Lq,Dv], lse [B,H,Lq]); with ``lies`` q, k, v and out are
    [B, L, H * ...] (the lse [B, H, Lq] either way).  ``shared``:
    ``(qr [B, H, Lq, Dr], kr [B, 1, Lk, Dr])``, a key part all heads
    share and the query part that meets it."""
    _, _, _, Lq, Lk, _, _ = _dims(q, k, v, lies)
    window = _windowed(window, causal, aligned)
    _count_blocks(Lq, Lk, *blocks, causal, aligned, window)
    return _fwd_call(q, k, v, q_off, k_off, seed, scale, causal, blocks,
                     aligned, dropout_p, _interpret(), lies, window,
                     shared=shared)


@_once_a_shape(6, 7, 8, 9, 10, 11, 12, 13)
def _fwd_call(q, k, v, q_off, k_off, seed, scale, causal, blocks, aligned,
              dropout_p, interpret, lies, window, shared=None):
    B, H, Hk, Lq, Lk, D, Dv = _dims(q, k, v, lies)
    Dr = _shared_width(shared)
    block_q, block_k = blocks
    kernel = functools.partial(_fwd_kernel, scale=scale, block_k=block_k,
                               seq_k=Lk, causal=causal, block_q=block_q,
                               aligned=aligned, dropout_p=dropout_p,
                               shared=bool(shared), window=window)
    group, n = H // Hk, _heads_a_step(lies, D)
    if n == 2:
        kernel, H, D, Dv = _each_of_a_pair(kernel), H // 2, 2 * D, 2 * Dv
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, Lq // block_q),
        in_specs=_qkv_fwd_specs(block_q, Lk, D, Dv, Dr, group, lies),
        out_specs=[
            _head_rows(lies, block_q, Dv, lambda b, h, i: (b, h, i)),
            pl.BlockSpec((1, n, 8, block_q), lambda b, h, i: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Lq, H * Dv) if lies
                                 else (B, H, Lq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H * n, 8, Lq), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_staging(Lk, D + Dr, Dv, q.dtype),
        name=scopes.FLASH_FWD,
    )(q_off, k_off, seed, q, k, v, *(shared or ()))
    return out, lse[:, :, 0, :]       # the compact [B, H, Lq]


# ---------------------------------------------------------------------------
# backward (recompute-based, FlashAttention-2 style)
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(q_off_ref, k_off_ref, seed_ref, q_ref, k_ref, v_ref,
                    *rest, scale, block_q, seq_q, causal, block_k, aligned,
                    dropout_p, shared, n_dq, window=None, of_pair=None):
    """The backward walk: k block ``kj`` of a head against the q blocks it
    sees, for its dK and dV.  Where the call asks for dQ too (``rest``
    then ends in ``n_dq`` dq outputs and as many accumulators, see
    `_bwd_dkv_call`), each pair's ``ds`` also meets the k block, and the
    head's whole dQ^T adds up in float32 over the k blocks in VMEM: no
    second walk recomputes the scores, p and dP for it."""
    if shared:
        qr_ref, kr_ref, *rest = rest
        kr = kr_ref[lead(kr_ref)]                         # [BK, Dr]
    do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *rest = rest
    if shared:
        dkr_ref, *rest = rest
    dq_refs, dq_accs = rest[:n_dq], rest[n_dq:]
    kj = pl.program_id(2)
    k = k_ref[lead(k_ref)]                                # [BK, D]
    v = v_ref[lead(v_ref)]
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    # this head's part of the shared key's gradient
    dkr = jnp.zeros(kr.shape, jnp.float32) if shared else None
    num_q = seq_q // block_q

    if dq_accs:
        @pl.when(kj == 0)
        def _():
            dq_zero(dq_accs)

    def body(i, carry, mask):
        dk, dv, dkr = carry
        q = prescale(rows(q_ref, i, block_q), scale)      # [BQ, D]
        qr = prescale(rows(qr_ref, i, block_q), scale) if shared else None
        do = rows(do_ref, i, block_q)
        cols = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        lse = lse_ref[0, 0, 0:1, cols]                    # [1, BQ]
        delta = delta_ref[0, 0, 0:1, cols]
        s = scores(k, q, mask, i, kj, q_off_ref, k_off_ref, block_q,
                   block_k, (kr, qr) if shared else None, window)
        # fwd tile (qi=i, j=kj): identical seed -> identical mask; a
        # windowed row sees its own key, so its lse is finite and a key
        # outside the window gets p = exp(-1e30 - lse) = 0 unguarded
        u, ds = p_ds(s, lse, do, v, delta, may_hide_query=mask == "positions",
                     drop=_dropout(seed_ref, i, kj, dropout_p, of_pair))
        dv = dv + _dot(u.astype(do.dtype), do, ((1,), (0,)))
        # against the pre-scaled q: dk needs no scale of its own
        ds = ds.astype(q.dtype)
        dk = dk + _dot(ds, q, ((1,), (0,)))
        if shared:
            dkr = dkr + _dot(ds, qr, ((1,), (0,)))
        # q block i's dq^T [D, BQ], and under it the shared part's
        dq_add(dq_accs, (k, kr) if shared else (k,), i, ds)
        return dk, dv, dkr

    dk, dv, dkr = block_loops(body, (dk, dv, dkr), num_q, causal, aligned,
                              q_spans(kj, block_q, block_k, num_q, window))
    dk_ref[lead(dk_ref)] = dk.astype(dk_ref.dtype)
    dv_ref[lead(dv_ref)] = dv.astype(dv_ref.dtype)
    if shared:
        dkr_ref[lead(dkr_ref)] = dkr

    if dq_accs:
        @pl.when(kj == pl.num_programs(2) - 1)
        def _():
            dq_emit(dq_refs, dq_accs, num_q, block_q, scale)


def bwd_dkv(q, k, v, q_off, k_off, seed, do, lse8, delta8, scale, causal,
            blocks, aligned, dropout_p, shared=None, with_dq=False,
            lies=None, window=None):
    """``with_dq``: what the caller needs of the walk.  `_bwd` takes dQ
    from it; EVA's windows (eva_attention.py) take dK and dV alone, their
    dQ comes with the summaries' gradients from a kernel of their own."""
    _, _, _, Lq, Lk, _, _ = _dims(q, k, v, lies)
    window = _windowed(window, causal, aligned)
    _count_blocks(Lq, Lk, *blocks, causal, aligned, window)
    return _bwd_dkv_call(q, k, v, q_off, k_off, seed, do, lse8, delta8,
                         scale, causal, blocks, aligned, dropout_p,
                         _interpret(), with_dq, lies, window, shared=shared)


@_once_a_shape(9, 10, 11, 12, 13, 14, 15, 16, 17)
def _bwd_dkv_call(q, k, v, q_off, k_off, seed, do, lse8, delta8, scale,
                  causal, blocks, aligned, dropout_p, interpret, with_dq,
                  lies, window, shared=None):
    """-> (dk, dv), each QUERY head's part [B, H, Lk, ...] (k and v may
    have fewer heads, a group of query heads on each: `_bwd` sums the
    parts), with ``shared`` also each head's float32 part of the
    shared key's gradient [B, H, Lk, Dr], with ``with_dq`` then dq (and
    with both dqr).  A head's dQ block is its whole [Lq, D] under an index
    that does not move with the k block: it goes to HBM once a (batch,
    head), from float32 accumulators [Lq / block_q, D, block_q] that are
    zeroed at the head's first k block.  With ``lies`` q, k, v and dO come
    and dk, dv and dq go [B, L, H * ...]; what belongs to a shared part
    keeps [B, H, L, Dr]."""
    B, H, Hk, Lq, Lk, D, Dv = _dims(q, k, v, lies)
    Dr = _shared_width(shared)
    block_q, block_k = blocks
    group, n = H // Hk, _heads_a_step(lies, D)
    if n == 2:
        H, D, Dv = H // 2, 2 * D, 2 * Dv
    # (width, as it lies) of the dQ blocks: a shared part's stays [B, H, L, Dr]
    dq_blocks = ([(D, _qk(lies))] + ([(Dr, None)] if shared else [])
                 if with_dq else [])
    dq_widths = [w for w, _ in dq_blocks]
    kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, block_q=block_q, seq_q=Lq,
        causal=causal, block_k=block_k, aligned=aligned,
        dropout_p=dropout_p, shared=bool(shared), n_dq=len(dq_widths),
        window=window)
    if n == 2:
        kernel = _each_of_a_pair(kernel, scratch=len(dq_widths))

    def whole(width, lies=lies):
        return _head_rows(lies, Lq, width, lambda b, h, j: (b, h, 0))

    def part(width, lies=lies):
        return _head_rows(lies, block_k, width, lambda b, h, j: (b, h, j))

    def kv_rows(width, lies=lies):
        return _head_rows(lies, block_k, width,
                          lambda b, h, j: (b, _kv_head(h, group), j))

    def shape(L, width, lies=lies):
        return (B, L, H * width) if lies else (B, H, L, width)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, Lk // block_k),
        in_specs=[
            _smem_scalar_spec(),
            _smem_scalar_spec(),
            _smem_scalar_spec(),
            whole(D, _qk(lies)),
            kv_rows(D, _qk(lies)),
            kv_rows(Dv),
        ] + ([whole(Dr, None), pl.BlockSpec((1, 1, block_k, Dr),
                                             lambda b, h, j: (b, 0, j, 0))]
             if shared else []) + [
            whole(Dv),
            pl.BlockSpec((1, n, 8, Lq), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, n, 8, Lq), lambda b, h, j: (b, h, 0, 0)),
        ],
        out_specs=[part(D, _qk(lies)), part(Dv)]
        + ([part(Dr, None)] if shared else [])
        + [whole(*block) for block in dq_blocks],
        out_shape=[
            jax.ShapeDtypeStruct(shape(Lk, D, _qk(lies)), k.dtype),
            jax.ShapeDtypeStruct(shape(Lk, Dv), v.dtype),
        ] + ([jax.ShapeDtypeStruct((B, H, Lk, Dr), jnp.float32)]
             if shared else [])
        + [jax.ShapeDtypeStruct(shape(Lq, *block), q.dtype)
           for block in dq_blocks],
        scratch_shapes=[pltpu.VMEM((Lq // block_q, w, block_q), jnp.float32)
                        for w in dq_widths],
        interpret=interpret,
        compiler_params=_staging(Lq, D + Dr, Dv, q.dtype, dq_widths),
        name=scopes.FLASH_BWD_DKV,
    )(q_off, k_off, seed, q, k, v, *(shared or ()), do, lse8, delta8)
    return tuple(out)


def _bwd(q, k, v, q_off, k_off, seed, out, lse, do, dlse, scale, causal,
         blocks, aligned, dropout_p=0.0, shared=None, lies=None, window=None):
    """Full backward, one kernel -> (dq, dk, dv), or with ``shared``
    ((dq, dqr), (dk, each head's part of dkr), dv).  The lse cotangent
    folds into delta: with P = exp(S - lse) row-normalized,
    dS = P * (dP_rows - delta + dlse) since d lse / dS = P."""
    from ...utils import monitor
    if lies:        # [B, Lq, H] -> [B, H, Lq]: 1 / Dv of a tensor moves
        by_head = do.shape[:2] + (lies[0], -1)
        delta = jnp.swapaxes(
            _delta(do.reshape(by_head), out.reshape(by_head)), 1, 2)
    else:
        delta = _delta(do, out)                           # [B, H, Lq]
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    monitor.stat_add("pallas.flash.bwd_fused")
    dk, dv, *rest = bwd_dkv(q, k, v, q_off, k_off, seed, do, rows8(lse),
                            rows8(delta), scale, causal, blocks, aligned,
                            dropout_p, shared=shared, with_dq=True,
                            lies=lies, window=window)
    if not shared:
        if lies:                # keys with a head each: the parts are it
            return rest[0], dk, dv
        return rest[0], _sum_groups(dk, k), _sum_groups(dv, v)
    dkr, dq, dqr = rest
    return (dq, dqr), (dk, dkr), dv


def _sum_groups(parts, like):
    """The query heads' parts [B, H, Lk, D] of a key/value gradient, summed
    in float32 over each group that shares a head of ``like``
    [B, Hk, Lk, D]; with a head each they are the gradient."""
    Hk = like.shape[1]
    if parts.shape[1] == Hk:
        return parts
    B, H, Lk, D = parts.shape
    return jnp.sum(parts.reshape(B, Hk, H // Hk, Lk, D), 2,
                   dtype=jnp.float32).astype(parts.dtype)


# ---------------------------------------------------------------------------
# custom-vjp cores over [B, H, L, D], or [B, L, H*D] as it lies
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(6, 7, 8, 9, 10, 11, 12))
def _flash(q, k, v, q_off, k_off, seed, scale, causal, blocks, aligned,
           dropout_p, lies, window):
    """q, k, v and out [B, H, L, ...], or with ``lies`` [B, L, H * ...]."""
    out, _ = _fwd(q, k, v, q_off, k_off, seed, scale, causal, blocks,
                  aligned, dropout_p, lies=lies, window=window)
    return out


def _flash_fwd(q, k, v, q_off, k_off, seed, scale, causal, blocks, aligned,
               dropout_p, lies, window):
    out, lse = name_residuals(*_fwd(q, k, v, q_off, k_off, seed, scale,
                                    causal, blocks, aligned, dropout_p,
                                    lies=lies, window=window))
    return out, (q, k, v, q_off, k_off, seed, out, lse)


def _flash_bwd(scale, causal, blocks, aligned, dropout_p, lies, window, res,
               do):
    q, k, v, q_off, k_off, seed, out, lse = res
    dq, dk, dv = _bwd(q, k, v, q_off, k_off, seed, out, lse, do, None,
                      scale, causal, blocks, aligned, dropout_p, lies=lies,
                      window=window)
    return (dq, dk, dv, jnp.zeros_like(q_off), jnp.zeros_like(k_off),
            None)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_with_lse(q, k, v, q_off, k_off, scale, blocks):
    """Position-masked attention returning (out, lse) — the ring-attention
    building block (both outputs differentiable; no dropout: ring rounds
    merge via logsumexp, which requires undropped weights)."""
    return _fwd(q, k, v, q_off, k_off, zero_seed(), scale, True, blocks,
                False)


def _flash_with_lse_fwd(q, k, v, q_off, k_off, scale, blocks):
    out, lse = name_residuals(*_fwd(q, k, v, q_off, k_off, zero_seed(),
                                    scale, True, blocks, False))
    return (out, lse), (q, k, v, q_off, k_off, out, lse)


def _flash_with_lse_bwd(scale, blocks, res, cts):
    q, k, v, q_off, k_off, out, lse = res
    do, dlse = cts
    dq, dk, dv = _bwd(q, k, v, q_off, k_off, zero_seed(), out, lse, do,
                      dlse, scale, True, blocks, False)
    return dq, dk, dv, jnp.zeros_like(q_off), jnp.zeros_like(k_off)


_flash_with_lse.defvjp(_flash_with_lse_fwd, _flash_with_lse_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_shared_key(q, qr, k, kr, v, scale, blocks):
    """Causal attention whose key is a head's own ``k`` [B, H, L, D]
    beside ``kr`` [B, 1, L, Dr], one for all heads; ``qr`` [B, H, L, Dr]
    meets it.  The shared part enters the kernels once a batch entry."""
    out, _ = _fwd(q, k, v, zero_off(), zero_off(), zero_seed(), scale,
                  True, blocks, True, shared=(qr, kr))
    return out


def _flash_shared_key_fwd(q, qr, k, kr, v, scale, blocks):
    out, lse = name_residuals(*_fwd(
        q, k, v, zero_off(), zero_off(), zero_seed(), scale, True,
        blocks, True, shared=(qr, kr)))
    return out, (q, qr, k, kr, v, out, lse)


def _flash_shared_key_bwd(scale, blocks, res, do):
    q, qr, k, kr, v, out, lse = res
    (dq, dqr), (dk, dkr), dv = _bwd(
        q, k, v, zero_off(), zero_off(), zero_seed(), out, lse, do, None,
        scale, True, blocks, True, shared=(qr, kr))
    # the heads' parts add up in float32
    dkr = jnp.sum(dkr, axis=1, keepdims=True).astype(kr.dtype)
    return dq, dqr, dk, dkr, dv


_flash_shared_key.defvjp(_flash_shared_key_fwd, _flash_shared_key_bwd)


# ---------------------------------------------------------------------------
# public entries
# ---------------------------------------------------------------------------

def zero_off():
    return jnp.zeros((1, 1), jnp.float32)


def zero_seed():
    return jnp.zeros((1, 1), jnp.int32)


def _count_addressing(lies):
    """One count a call of a public entry, at trace time: which way its
    kernels find a head (``flash_attention.as_it_lies`` /
    ``flash_attention.transposed``)."""
    from ...utils import monitor
    monitor.stat_add("flash_attention.as_it_lies" if lies
                     else "flash_attention.transposed")
    return lies


def _to_kernels(x, lies):
    """[B, L, H, D] as the kernels take it: the same bytes under the name
    [B, L, H * D], or a [B, H, L, D] copy."""
    return x.reshape(*x.shape[:2], -1) if lies else jnp.swapaxes(x, 1, 2)


def _from_kernels(out, lies):
    """The kernels' result as [B, L, H, Dv]."""
    if lies:
        return out.reshape(*out.shape[:2], lies[0], -1)
    return jnp.swapaxes(out, 1, 2)


def flash_attention(q, k, v, causal: bool = False, scale=None,
                    block_q: int | None = None, block_k: int | None = None,
                    dropout_p: float = 0.0, seed=None,
                    window: int | None = None):
    """q: [B, L, H, D], k: [B, Lk, Hk, D], v: [B, Lk, Hk, Dv] (``Dv`` may
    differ from ``D``; ``Hk`` divides ``H``, query head h reads key/value
    head ``h // (H / Hk)``) → [B, Lq, H, Dv] attention output; the default
    ``scale`` is ``D ** -0.5``.

    ``block_q`` / ``block_k`` left at None are chosen from the static
    shapes (`_resolve_blocks`).

    ``window`` (with ``causal``): a sliding window, query t sees the keys
    s with ``t - window < s <= t`` (its own position and the ``window -
    1`` before it).  Blocks that lie wholly below a q block's window are
    skipped in the forward and in the backward walk, those that straddle
    its lower edge take a second mask; None is plain causal attention,
    the same program as before the argument existed.

    ``dropout_p > 0`` applies attention-probability dropout IN-KERNEL
    (Pallas TPU PRNG, tile-seeded from ``seed`` so the backward
    regenerates the identical mask); pass a fresh int32 ``seed`` array
    ([1, 1]) per training step."""
    D = q.shape[-1]
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
    blocks = _resolve_blocks(block_q, block_k, q.shape[1], k.shape[1])
    if dropout_p > 0.0 and _interpret():
        raise NotImplementedError(
            "flash_attention dropout needs the Pallas TPU PRNG (real TPU "
            "only); use scaled_dot_product_attention, whose dispatch "
            "falls back to the unfused path off-TPU")
    if seed is None:
        seed = zero_seed()
    else:
        seed = jnp.asarray(seed, jnp.int32).reshape(1, 1)
    count_kernel_selection("flash_attention")
    lies = _count_addressing(_as_it_lies(q.shape, k.shape, v.shape))
    q, k, v = (_to_kernels(q, _qk(lies)), _to_kernels(k, _qk(lies)),
               _to_kernels(v, lies))
    out = _flash(q, k, v, zero_off(), zero_off(), seed, scale,
                 bool(causal), blocks, True, float(dropout_p), lies,
                 _windowed(window, causal, True))
    return _from_kernels(out, lies)


def flash_attention_shared_key(q, q_shared, k, k_shared, v, scale=None,
                               block_q: int | None = None,
                               block_k: int | None = None):
    """Causal attention over keys in two parts: a head's own ``k``
    [B, L, H, D] and ``k_shared`` [B, L, Dr], one key part a position for
    all heads (latent attention's rotated key); ``q`` [B, L, H, D] and
    ``q_shared`` [B, L, H, Dr] meet them, ``v`` is [B, L, H, Dv].  The
    score is ``scale * (q . k + q_shared . k_shared)``, ``scale`` by
    default ``(D + Dr) ** -0.5``.  The shared part is never broadcast to
    the heads: the kernels stage it once a batch entry.  -> [B, L, H, Dv].
    """
    D, Dr = q.shape[-1], q_shared.shape[-1]
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(D + Dr)
    blocks = _resolve_blocks(block_q, block_k, q.shape[1], k.shape[1])
    count_kernel_selection("mla_attention")     # and the kernels it runs on
    count_kernel_selection("flash_attention")
    # [B, H, L, D] copies throughout: v is a slice that a fusion writes,
    # and with v, out, dO and dv lying the JoyAI cell's step read 1.1 ms
    # slower and 304 MB larger (PERF.md section 6, PR 44)
    _count_addressing(None)
    out = _flash_shared_key(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(q_shared, 1, 2),
        jnp.swapaxes(k, 1, 2), k_shared[:, None], jnp.swapaxes(v, 1, 2),
        scale, blocks)
    return jnp.swapaxes(out, 1, 2)


def flash_attention_block(q_bhld, k_bhld, v_bhld, q_off, k_off, scale,
                          block_q: int = BLOCK, block_k: int = BLOCK):
    """Ring-attention building block: [B, H, L, D] layout, traced global
    position offsets (float32 [1,1] arrays), always position-masked.
    Returns (out normalized [B,H,L,D], lse [B,H,L]); fully-masked rows
    give out=0, lse≈-inf — ready for logsumexp merging across rounds."""
    blocks = _resolve_blocks(block_q, block_k, q_bhld.shape[2],
                             k_bhld.shape[2])
    _count_addressing(None)
    return _flash_with_lse(q_bhld, k_bhld, v_bhld, q_off, k_off, scale,
                           blocks)


def mha_reference(q, k, v, causal=False, scale=None, window=None):
    """jnp oracle for tests ([B, L, H, D] layout; v may be [.., Dv]; k
    and v may have fewer heads, each repeated over its group; ``window``:
    of the causal keys, a query's last ``window`` positions)."""
    D = q.shape[-1]
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    s = jnp.einsum("blhd,bshd->bhls", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Lq, Lk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((Lq, Lk), bool))
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((Lq, Lk), bool), -window)
        s = jnp.where(mask[None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhls,bshd->blhd", w, v.astype(jnp.float32)
                      ).astype(q.dtype)
