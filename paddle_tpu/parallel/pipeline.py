"""Pipeline parallelism.

Reference: PipelineTrainer + SectionWorker (reference: trainer.h:328,
section_worker.cc:115-165 — F-then-B and 1F1B microbatch schedules;
program splitting in fluid/optimizer.py:3954 `_split_program`; inter-stage
tensors via send_v2/recv_v2 collective ops).

TPU-native design: stages are SPMD over a 'pp' mesh axis.  Each device
executes the SAME stage function with ITS stage's parameters (stage params
stacked on a leading axis and sharded over 'pp'); activations move between
neighbouring stages with ``lax.ppermute`` (the send_v2/recv_v2 analog, but
compiler-scheduled over ICI).  The fill-drain schedule is a ``lax.scan``
over M + S - 1 ticks, so forward AND backward pipeline in one compiled
program — differentiating the scan yields the reverse schedule
automatically.

Activation-memory discipline (measured in tests/test_pipeline_memory.py):
differentiating the scan stores residuals for every tick, so per-device
backward memory is O(M) in the microbatch count — what each tick STORES is
the lever.  With ``remat=True`` (default) the stage/embed/head bodies are
``jax.checkpoint``-ed, so a tick stores only its carry (ONE microbatch
activation at the stage boundary) and recomputes layer internals in the
backward: O(M · |mb activation|) total, a factor-of-depth below the
unrematted scan's O(M · |all layer internals|).  This is the same
recompute-in-backward trade the reference's 1F1B + recompute combination
makes (section_worker.cc:128-165 interleaves backward to hold O(S)
in-flight microbatches; its per-microbatch store is the full section's
internals unless recompute is also on — for stages deeper than ~2 layers
and the usual M ≈ 2S, rematted-scan stores LESS than unrematted 1F1B).

Memory/layout discipline (round-3 redesign):
- the microbatch INPUT stream is sharded over 'pp' round-robin (microbatch
  t lives on rank t mod S); each tick the owner psum-broadcasts one
  microbatch to stage 0 — per-device input storage is O(batch/S), and the
  in-flight state is O(microbatch), never O(batch);
- the OUTPUT stream is collected the same way (each rank keeps the
  microbatches it owns), so outputs are born 'pp'-sharded instead of
  being psum-replicated;
- with a 'dp' axis in the mesh the batch dim of every stream is
  additionally dp-sharded: each data-parallel group runs its own pipeline
  (the reference's dp x pp grid, fleet meta-parallel);
- optionally non-uniform FIRST/LAST stages: an embedding applied at
  injection (stage 0) and a head applied at collection (stage S-1) — the
  reference's first/last section programs with their own params.

Requirement (same as the reference's middle sections): the S repeated
stages must be shape-uniform — activation shape in == out.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec
from jax import shard_map

from ..core import autograd
from ..core.tensor import Tensor
from ..distributed.mesh import DP_AXIS, PP_AXIS, ensure_mesh
from ..jit.bind import bind, param_list
from ..nn.layer_base import Layer


class PipelineStage(Layer):
    """Marker container for one stage (uniform structure across stages)."""

    def __init__(self, block: Layer):
        super().__init__()
        self.block = block

    def forward(self, x):
        return self.block(x)


class Pipeline(Layer):
    """A sequence of shape-uniform stages.

    Eager/single-chip: runs stages sequentially (reference F-then-B
    degenerate case).  Use :func:`pipelined_fn` to obtain the SPMD
    microbatched execution over the 'pp' mesh axis.
    """

    def __init__(self, stages: Sequence[Layer], num_microbatches: int = 1):
        super().__init__()
        from ..nn.layer.container import LayerList
        self.stages = LayerList(list(stages))
        self.num_microbatches = num_microbatches

    def forward(self, x):
        for s in self.stages:
            x = s(x)
        return x


def stack_stage_params(stages: Sequence[Layer]):
    """Stack per-stage parameter arrays along a new leading 'stage' axis.

    All stages must have identical parameter structure (the reference makes
    the same uniformity assumption when splitting programs into sections).
    Returns (stacked_arrays: list, n_params_per_stage)."""
    per_stage = [[p.data for p in param_list(s)] for s in stages]
    n = len(per_stage[0])
    for ps in per_stage:
        assert len(ps) == n, "pipeline stages must be structurally uniform"
    stacked = [jnp.stack([ps[i] for ps in per_stage], axis=0)
               for i in range(n)]
    return stacked, n


def _apply_layer(template: Layer, p_arrs, x):
    with autograd.no_grad():
        with bind(template, list(p_arrs)):
            out = template(Tensor(x))
    return out.data if isinstance(out, Tensor) else out


def pipelined_fn(stage_layer: Layer, n_stages: int, num_microbatches: int,
                 mesh=None, pp_axis: str = PP_AXIS,
                 dp_axis: Optional[str] = None,
                 embed_layer: Optional[Layer] = None,
                 head_layer: Optional[Layer] = None,
                 remat: bool = True):
    """Build a pure function running ``stage_layer`` as an S-stage pipeline.

    Returns ``fn(stacked_params, x[, embed_params][, head_params])``:
    ``stacked_params`` are stage params stacked on axis 0 (sharded over
    'pp'); ``x`` is the batch [B, ...] (dp-sharded when ``dp_axis`` is in
    the mesh), split into ``num_microbatches`` (a multiple of S).
    ``embed_layer``/``head_layer`` make the first/last stages non-uniform
    (their params ride replicated).  Output: [B, ...] after embed → S
    stages → head.

    ``remat=True`` checkpoints the stage/embed/head bodies so the scan's
    backward stores one microbatch boundary activation per tick instead of
    every layer internal (see module docstring; the reference's recompute
    + 1F1B combination, section_worker.cc + recompute_optimizer.py).
    """
    mesh = mesh or ensure_mesh()
    S = n_stages
    M = num_microbatches
    # round-robin stream layout [S, Q]; when S doesn't divide M the tail
    # slots are zero-padding that is never injected or collected
    Q = (M + S - 1) // S
    template = stage_layer
    n_params = len(param_list(template))
    n_embed = len(param_list(embed_layer)) if embed_layer else 0
    n_head = len(param_list(head_layer)) if head_layer else 0
    use_dp = dp_axis is not None and dp_axis in mesh.shape

    def per_device(*args):
        stage_local = args[:n_params]          # [1, ...] my stage's params
        my_stream = args[n_params][0]          # [Q, mb, ...] my microbatches
        rest = args[n_params + 1:]
        e_params = rest[:n_embed]
        h_params = rest[n_embed:n_embed + n_head]
        my_params = [a[0] for a in stage_local]
        idx = jax.lax.axis_index(pp_axis)
        T = M + S - 1

        def inject(t):
            """Owner rank (t mod S) broadcasts microbatch t to the ring;
            storage stays sharded, the wire carries ONE microbatch."""
            slot = t // S
            cand = jax.lax.dynamic_index_in_dim(my_stream, slot, 0,
                                                keepdims=False)
            mine = (idx == t % S)
            masked = jnp.where(mine, cand,
                               jnp.zeros_like(cand)
                               if jnp.issubdtype(cand.dtype, jnp.floating)
                               else cand * 0)
            return jax.lax.psum(masked, pp_axis)

        maybe_remat = jax.checkpoint if remat else (lambda f: f)
        stage_apply = maybe_remat(
            lambda p, a: _apply_layer(template, p, a))
        embed_apply = maybe_remat(
            lambda p, a: _apply_layer(embed_layer, p, a))
        head_apply = maybe_remat(
            lambda p, a: _apply_layer(head_layer, p, a))

        def first_stage_in(mb_in):
            if embed_layer is not None:
                return embed_apply(e_params, mb_in)
            return mb_in

        def last_stage_out(y):
            if head_layer is not None:
                return head_apply(h_params, y)
            return y

        # probe shapes (abstract): activation and collected-output element
        act0 = jax.eval_shape(
            lambda m: first_stage_in(m),
            jax.ShapeDtypeStruct(my_stream.shape[1:], my_stream.dtype))
        y0 = jax.eval_shape(
            lambda a: stage_apply(my_params, a), act0)
        out0 = jax.eval_shape(lambda a: last_stage_out(a), y0)

        def tick(carry, t):
            buf, out_stream = carry
            mb_in = inject(jnp.clip(t, 0, M - 1))
            cand_act = first_stage_in(mb_in)
            inp = jnp.where(idx == 0, cand_act, buf)
            y = stage_apply(my_params, inp)
            nxt = jax.lax.ppermute(
                y, pp_axis, [(i, (i + 1) % S) for i in range(S)])
            # collect: last stage's tick-t output is microbatch t-(S-1);
            # its owner rank stores it (stream stays 'pp'-sharded)
            tp = t - (S - 1)
            tq = jnp.clip(tp, 0, M - 1)
            h_out = last_stage_out(y)
            yb = jax.lax.psum(
                jnp.where(idx == S - 1, h_out, jnp.zeros_like(h_out)),
                pp_axis)
            write = (tp >= 0) & (idx == tq % S)
            updated = jax.lax.dynamic_update_index_in_dim(
                out_stream, yb, tq // S, 0)
            out_stream = jnp.where(write, updated, out_stream)
            return (nxt, out_stream), None

        buf0 = jnp.zeros(act0.shape, act0.dtype)
        outs0 = jnp.zeros((Q,) + out0.shape, out0.dtype)
        (_, out_stream), _ = jax.lax.scan(tick, (buf0, outs0),
                                          jnp.arange(T))
        return out_stream[None]                # [1, Q, mb, ...]

    stream_spec = PartitionSpec(pp_axis, None,
                                dp_axis if use_dp else None)
    in_specs = tuple([PartitionSpec(pp_axis)] * n_params
                     + [stream_spec]
                     + [PartitionSpec()] * (n_embed + n_head))
    out_specs = stream_spec

    def fn(stacked_params, x, embed_params=(), head_params=()):
        B = x.shape[0]
        assert B % M == 0, f"batch {B} not divisible by microbatches {M}"
        mb = B // M
        if Q * S != M:  # pad the stream's tail slots (never injected)
            pad = jnp.zeros((Q * S - M, mb, *x.shape[1:]), x.dtype)
            xp = jnp.concatenate(
                [x.reshape(M, mb, *x.shape[1:]), pad], axis=0)
        else:
            xp = x.reshape(M, mb, *x.shape[1:])
        # round-robin stream layout: stream[r, q] = microbatch q*S + r
        xs = xp.reshape(Q, S, mb, *x.shape[1:]).swapaxes(0, 1)
        sm = shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
        out = sm(*stacked_params, xs, *embed_params, *head_params)
        # [S, Q, mb, ...] -> [B, ...] undoing the round-robin layout
        out = out.swapaxes(0, 1)               # [Q, S, mb, ...]
        out = out.reshape(Q * S * mb, *out.shape[3:])
        return out[:M * mb]

    return fn


def pipeline_train_fn(stage_layer: Layer, head_fn: Callable, n_stages: int,
                      num_microbatches: int, mesh=None,
                      pp_axis: str = PP_AXIS, dp_axis=None,
                      embed_layer=None, head_layer=None, remat: bool = True):
    """fn(stacked_params, x, y, ...) -> scalar loss, for use inside
    jax.value_and_grad.  ``head_fn(out_arrays, y)`` computes the loss from
    pipeline output (pure jnp)."""
    fwd = pipelined_fn(stage_layer, n_stages, num_microbatches, mesh,
                       pp_axis, dp_axis=dp_axis, embed_layer=embed_layer,
                       head_layer=head_layer, remat=remat)

    def fn(stacked_params, x, y, embed_params=(), head_params=()):
        out = fwd(stacked_params, x, embed_params, head_params)
        return head_fn(out, y)

    return fn
