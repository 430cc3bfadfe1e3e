"""Pallas TPU kernels — the hand-fused native tier.

These are the TPU analog of the reference's hand-written CUDA fusions
(reference: operators/math/bert_encoder_functor.cu multi-head attention,
operators/fused/, ir/*_fuse_pass.cc): where XLA's automatic fusion is not
enough (attention's softmax-rescale dataflow, the matmul-epilogue chains
the cost model ranks, the optimizer's multi-pass update), we write the
kernel by hand against the MXU/VMEM model.  Selection is behind
FLAGS_use_pallas_kernels with per-op capability checks (plus the
FLAGS_pallas_interpret opt-in off TPU); every kernel has an
interpret-mode path so the same code runs (slowly) on CPU in tests.

The tier:

- ``flash_attention``        — online-softmax attention, fwd + bwd; the
  values may be narrower than the keys and a part of the key may be one
  for all heads, staged once a row (latent attention's 128 + 64 / 128,
  behind ``F.mla_attention``); a sliding ``window`` under the causal
  mask skips the blocks below it in both walks; a head's K and V are
  staged whole, up to 8 MiB a head (over 4 MiB under a stated scoped-VMEM
  limit: the shared-key call to 5, 128-wide bfloat16 heads to 16,384 rows);
- ``eva_attention`` (module) — EVA's windowed attention over exact keys
  and chunk summaries under one softmax, fwd + bwd, behind
  ``F.eva_attention``;
- ``sparse_attention`` (module) — grouped-query attention over a learned
  per-query selection of keys (a mask shared by the heads), the
  indexer's scores, top-k threshold and KL loss (five kernels; the loss
  makes its gradient with its value, in one), fwd + bwd, behind
  ``F.dsa_indexer`` / ``F.sparse_attention`` / ``F.dsa_indexer_loss``;
- ``ssd_scan`` (module) — Mamba-2's chunked state-space scan, fwd + bwd:
  a chunk's decay matrices stay in VMEM and the state rides a scratch
  along the chunk axis, behind ``F.ssd_scan``;
- ``causal_conv`` (module)   — a mixer's depthwise causal convolution,
  fwd + bwd: its channels read out of the in-projection's rows where they
  lie, x, B and C written apart as the scan's kernels take them, the
  taps shifted along the sublanes in VMEM, behind ``F.causal_conv1d``;
- ``moe_combine`` (module)   — an expert layer's sums over a token's held
  slots, the buffer's rows streamed once in token order and summed on the
  MXU under scalar-prefetched (token block, row tile) pairs, behind
  ``ops.moe`` (the forward's gate-weighted sum and the transpose of the
  gather to the experts);
- ``fused_linear_epilogue``  — matmul + bias/gelu/relu/residual/
  layer_norm epilogues off the cost model's ranked fusion candidates
  (selected by the static Executor's fusion pass);
- ``fused_adam_update``      — one-pass Adam over the donated
  ``_ExecState`` param/slot pairs;
- ``paged_attention_decode`` — gather-free paged decode attention
  behind ``ops.attention.register_paged_attention_kernel``.

The three attention modules share their tile mathematics (the
online-softmax step, a pair's p and dS, the dQ walk's accumulators, the
8-sublane rows of lse and delta) through ``attention_tiles.py``, which
launches nothing.  Shared backend/gate/counter plumbing lives in
``support.py``, and so does the one choice between a kernel and its XLA
form (``choose_kernel``) that every kernel-backed functional makes.
"""
from .flash_attention import (flash_attention, flash_attention_supported,
                              mha_reference)
from .fused_adam import fused_adam_supported, fused_adam_update
from .fused_epilogue import (fused_epilogue_supported,
                             fused_linear_epilogue, reference_epilogue)
from .paged_attention import paged_attention_decode, paged_decode_supported
from .support import kernel_selections

__all__ = ["flash_attention", "flash_attention_supported", "mha_reference",
           "fused_adam_supported", "fused_adam_update",
           "fused_epilogue_supported", "fused_linear_epilogue",
           "reference_epilogue", "paged_attention_decode",
           "paged_decode_supported", "kernel_selections"]
