"""The fixed vocabulary of ``jax.named_scope`` names the program puts into
its executables' ``op_name`` metadata (constants only).

Three kinds, all short (a scope string is repeated on every instruction
under it, and the step executables are tens of megabytes):

- phases of a compiled train step, set by ``jit.TrainStep`` and the
  static ``Executor`` around the code that is there.  jax itself wraps
  the differentiated ``loss`` phase as ``jvp(loss)`` (forward) and
  ``transpose(jvp(loss))`` (backward), and marks what ``jax.checkpoint``
  replays as ``rematted_computation``;
- components: every ``nn.Layer`` runs its ``forward`` under
  ``"<attribute name>:<ClassName>"`` (``blocks.3:Block``, ``q:Linear``),
  and the composite functional entry points under their own name;
- kernels: every ``pl.pallas_call`` carries ``name=`` and sits under a
  scope of the same name.

PERF.md (section 3) lists which benchmark metric reads which name.

Two more vocabularies live here because the same tools read them: the
``jax.ad_checkpoint.checkpoint_name`` names of values a rematerialisation
policy may keep (``RESIDUALS``), and the names of the counters a compiled
step keeps on the device (``DEVICE_COUNTERS``; ``device_counters.py`` says
how they reach ``utils.monitor``).  They are names of values, not scopes.
"""
from __future__ import annotations

# -- phases ----------------------------------------------------------------
LOSS = "loss"                 # model forward + loss_fn
UNSCALE = "unscale"           # loss-scaling: unscale grads, find non-finite
GRAD_CLIP = "grad_clip"       # gradient transform and clip
OPTIMIZER = "optimizer"       # the parameter update
SCALER = "scaler"             # loss-scaling: skip the update, move the scale
PHASES = (LOSS, UNSCALE, GRAD_CLIP, OPTIMIZER, SCALER)

# -- composite functional entry points --------------------------------------
ATTENTION = "scaled_dot_product_attention"
LINEAR_CROSS_ENTROPY = "linear_cross_entropy"
GELU = "gelu"
LAYER_NORM = "layer_norm"
EMBEDDING = "embedding"
DROPOUT = "dropout"
EVA_ATTENTION = "eva_attention"
EVA_POOL = "eva_pool"         # chunk summaries, inside eva_attention
RMS_NORM = "rms_norm"
ROPE = "rope"
MOE = "moe"                   # the routed expert layer, every part of it
MOE_ROUTER = "moe_router"     # router matmul, softmax, top-k (float32)
MOE_DISPATCH = "moe_dispatch"  # sort, gather to the experts, combine back
MOE_EXPERTS = "moe_experts"   # the grouped products over the held experts
DSA_INDEXER = "dsa_indexer"   # index scores and the indexer's KL loss
DSA_SELECT = "dsa_select"     # the top-k threshold a query and the mask
SPARSE_ATTENTION = "sparse_attention"
QK_NORM = "qk_norm"           # RMSNorm per head on q and k
MOE_SHARED = "moe_shared"     # the shared expert: one expert of the layer's
                              # form (SwiGLU or relu^2) over every token
MLA_ATTENTION = "mla_attention"  # latent attention: one rotated key a row
                              # beside the heads' own, the flash kernels
MTP = "mtp"                   # a multi-token-prediction module, its head too
LOOP_STACK = "loop_stack"     # one pass of a looped stack (nn.LoopedStack):
                              # every block application sits under it
LOOP_EXIT = "loop_exit"       # a looped model's exit: the gate, the exit
                              # distribution, its entropy, the weighting
                              # (the head stays under linear_cross_entropy)
SSM = "ssm"                   # a state-space mixer (nn.Mamba2Mixer), every
                              # part of it, its two projections too
SSM_CONV = "ssm_conv"         # the causal convolution: taps, bias, silu
                              # (the kernels conv_fwd / conv_bwd, or XLA's
                              # slices and shifted multiply-adds)
SSM_SCAN = "ssm_scan"         # softplus, the decays, the chunked scan, D x
SSM_GATE_NORM = "ssm_gate_norm"  # the gate and the group norm
FFN = "ffn"                   # a gated feed-forward layer (nn.GatedFFN):
                              # its two projections, the gate's activation
                              # and the product
WINDOW_ATTENTION = "window_attention"  # a sliding-window call of
                              # scaled_dot_product_attention, inside its
                              # scope: the kernels or the banded XLA form
ATTN_GATE = "attn_gate"       # the sigmoid gate on attention's output
                              # (F.attention_output_gate)
SHORT_CONV = "short_conv"     # a gated short-convolution mixer
                              # (nn.ShortConv), every part of it, its two
                              # projections too
SHORT_CONV_OP = "short_conv_op"  # the gates and the taps alone,
                              # C * conv(B * z) (F.gated_short_conv: the
                              # kernels short_conv_fwd / short_conv_bwd, or
                              # XLA's slices, products and shifted
                              # multiply-adds)
FUNCTIONALS = (ATTENTION, LINEAR_CROSS_ENTROPY, GELU, LAYER_NORM, EMBEDDING,
               DROPOUT, EVA_ATTENTION, EVA_POOL, RMS_NORM, ROPE, MOE,
               MOE_ROUTER, MOE_DISPATCH, MOE_EXPERTS, DSA_INDEXER, DSA_SELECT,
               SPARSE_ATTENTION, QK_NORM, MOE_SHARED, MLA_ATTENTION, MTP,
               LOOP_STACK, LOOP_EXIT, SSM, SSM_CONV, SSM_SCAN, SSM_GATE_NORM,
               FFN, WINDOW_ATTENTION, ATTN_GATE, SHORT_CONV, SHORT_CONV_OP)

# -- Pallas kernels ----------------------------------------------------------
FLASH_FWD = "flash_fwd"
FLASH_BWD_DKV = "flash_bwd_dkv"  # the backward walk: dk, dv and, but for
                              # EVA's windows, dq (benchmark/scope_reduce.py
                              # reads this name, so it stays)
EPILOGUE_FWD = "epilogue_fwd"
EPILOGUE_BWD = "epilogue_bwd"
FUSED_ADAM = "fused_adam"
PAGED_ATTENTION = "paged_attention"
COLLECTIVE_MATMUL_CHUNK = "collective_matmul_chunk"
EVA_FWD = "eva_fwd"
EVA_BWD_DQ = "eva_bwd_dq"     # dq, and the summaries' dk~ / dv~
DSA_SCORES = "dsa_scores"     # index scores, [keys, queries] tiles
DSA_THRESHOLD = "dsa_threshold"  # the topk-th largest score a query
DSA_KL = "dsa_kl"             # head-summed probabilities, the KL term and,
                              # differentiated, its gradient to qI, w and kI
SPARSE_FWD = "sparse_fwd"
SPARSE_BWD_DKV = "sparse_bwd_dkv"  # the backward walk: dk, dv and dq; the
                              # name holds all of the backward, as
                              # FLASH_BWD_DKV does
SSD_FWD = "ssd_fwd"           # the state-space scan, chunk by chunk with
                              # the state in VMEM (ops/pallas/ssd_scan.py)
SSD_BWD = "ssd_bwd"           # its backward, the chunks the other way
CONV_FWD = "conv_fwd"         # a mixer's causal convolution out of the
                              # in-projection's rows, x, B and C written
                              # apart (ops/pallas/causal_conv.py)
CONV_BWD = "conv_bwd"         # its backward, the taps transposed
MOE_COMBINE = "moe_combine"   # an expert layer's sums over a token's held
                              # slots, rows in token order (under
                              # MOE_DISPATCH, forward and backward)
SHORT_CONV_FWD = "short_conv_fwd"  # a gated short convolution out of the
                              # in-projection's rows: B * z, the taps, times
                              # C (ops/pallas/causal_conv.py)
SHORT_CONV_BWD = "short_conv_bwd"  # its backward: the in-projection's
                              # cotangent d[B ; C ; z] written once
KERNELS = (FLASH_FWD, FLASH_BWD_DKV, EPILOGUE_FWD, EPILOGUE_BWD,
           FUSED_ADAM, PAGED_ATTENTION, COLLECTIVE_MATMUL_CHUNK, EVA_FWD,
           EVA_BWD_DQ, DSA_SCORES, DSA_THRESHOLD, DSA_KL, SPARSE_FWD,
           SPARSE_BWD_DKV, SSD_FWD, SSD_BWD, MOE_COMBINE, CONV_FWD,
           CONV_BWD, SHORT_CONV_FWD, SHORT_CONV_BWD)


# -- values named for a rematerialisation policy -----------------------------
# What a kernel's backward rule takes and only its forward kernel can
# regenerate; ``parallel.recompute`` keeps these across its replay.
ATTN_OUT = "attn_out"         # an attention kernel's output, [B, H, L, D]
ATTN_LSE = "attn_lse"         # its log-sum-exp rows, [B, H, L] float32
# dsa_kl's gradient up to the loss's cotangent, float32: the forward pass
# makes it with the value, and nothing but the backward rule reads it
DSA_KL_DQ = "dsa_kl_dq"       # to the index queries, [B, J, d, T]
DSA_KL_DW = "dsa_kl_dw"       # to the head weights, [B, J, T]
DSA_KL_DK = "dsa_kl_dk"       # to the index key, [B, T, d]
DSA_KL_GRADS = (DSA_KL_DQ, DSA_KL_DW, DSA_KL_DK)
# what the sparse-attention kernels take besides: kept, a replayed block
# runs nothing that only they read (the selection; v's projection, the
# rotation of q and k, the layout copies)
SPARSE_Q = "sparse_q"         # q as the kernels take it, [B, A, T, D]
SPARSE_K = "sparse_k"         # k, [B, KV, T, D]
SPARSE_V = "sparse_v"         # v, [B, KV, T, D]
SPARSE_MASK = "sparse_mask"   # the selection, [B, keys, queries] int8
SPARSE_OPERANDS = (SPARSE_Q, SPARSE_K, SPARSE_V, SPARSE_MASK)
RESIDUALS = (ATTN_OUT, ATTN_LSE) + DSA_KL_GRADS + SPARSE_OPERANDS


# -- counters the compiled step keeps on the device ----------------------------
# Emitted a call of ``ops/moe.py::moe_forward`` (one expert layer), int32;
# a step's value is the calls stacked in their order.
MOE_EXPERT_LOAD = "moe.expert_load"   # [held]: assignments each held expert got
MOE_CHUNK_ASSIGNMENTS = "moe.chunk_assignments"  # [chunks]: held assignments
                              # of each chunk, what its buffer is chosen by
MOE_FULL_BUFFER_CHUNKS = "moe.full_buffer_chunks"  # chunks that took the
                              # full buffer (their load was over the small one)
MOE_FULLEST_EXPERT_LOAD = "moe.fullest_expert_load"  # the largest of
                              # MOE_EXPERT_LOAD: a maximum does not survive
                              # the sum over steps that a read returns
# Emitted a call of ``F.loop_exit_loss`` (one looped model's objective),
# float32: parts of a loss, means over the step's kept tokens.
LOOP_EXIT_SHARE = "loop.exit_share"   # [passes]: the mean of p_t, the
                              # probability of leaving after pass t
LOOP_EXIT_ENTROPY = "loop.exit_entropy"  # the mean entropy of that
                              # distribution, in nats
# Emitted a call of ``nn.Mamba2Mixer`` (one state-space layer), float32
# scalars: whether the recurrence carries anything.
SSM_STATE_SHARE = "ssm.state_share"   # RMS of the state's part S_t C_t of
                              # the scan's output over the RMS of all of it
                              # (S_t C_t + D x_t): near 0 the scan is D x
SSM_MEAN_DECAY = "ssm.mean_decay"     # the mean of exp(dt A) over tokens and
                              # heads: near 1 the state never moves, near 0
                              # it forgets inside a chunk
DEVICE_COUNTERS = (MOE_EXPERT_LOAD, MOE_CHUNK_ASSIGNMENTS,
                   MOE_FULL_BUFFER_CHUNKS, MOE_FULLEST_EXPERT_LOAD,
                   LOOP_EXIT_SHARE, LOOP_EXIT_ENTROPY, SSM_STATE_SHARE,
                   SSM_MEAN_DECAY)
