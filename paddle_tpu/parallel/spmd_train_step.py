"""SpmdTrainStep — the multi-chip training step.

TPU-native replacement for the reference's entire multi-device execution
stack: ParallelExecutor SSA graphs + per-grad allreduce insertion
(reference: multi_devices_graph_pass.cc:484,724; details/
all_reduce_op_handle.cc), fleet GraphExecutionOptimizer, and the sharding
meta-optimizer (sharding_optimizer.py:33).

One jit'd step over a ``Mesh`` with explicit in/out shardings:
- batch sharded over 'dp'  → gradient psum falls out of GSPMD (the DDP
  Reducer's fused allreduce, reducer.cc, becomes compiler-scheduled)
- ZeRO stage 1: optimizer slots sharded over 'dp'
- ZeRO stage 2: grads constrained to 'dp' shardings before the update, so
  XLA reduce-scatters gradients, updates shard-locally, and all-gathers
  the new params (the reference's broadcast+reduce choreography,
  sharding_optimizer.py:103-171, becomes three compiler-inserted
  collectives)
- ZeRO stage 3: params themselves sharded over 'dp'.  Params whose dim 0
  is not divisible by dp are stored *padded* to the next multiple (the
  reference pads to numel, meta_optimizers/sharding/shard.py) and sliced
  back inside the trace, so odd vocab sizes and bias vectors still shard.
- TP: params carrying placements (parallel/tp_layers.py) partition their
  matmuls over 'mp'.
- strategy.gradient_merge → in-step microbatch accumulation;
  strategy.amp (float16) → in-graph dynamic loss scaling;
  strategy.recompute → jax.checkpoint over the loss (rematerialised
  backward, recompute_optimizer.py:18);
  strategy.grad_comm (and its alias strategy.fp16_allreduce ==
  grad_comm.dtype='bf16') → the explicit gradient-collective stage
  (distributed/grad_comm.py): grads bucketed and quantised to the wire
  dtype, reduced inside a shard_map over 'dp' with per-bucket
  latency-vs-bandwidth algorithm selection
  (fp16_allreduce_optimizer.py:18; bf16 instead of fp16 because bf16
  shares f32's exponent range — no loss-scale overflow on the wire — and
  is the TPU-native half type.  The error-feedback residual carry lives
  on the static Executor path).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..distributed.mesh import DP_AXIS, ensure_mesh
from ..distributed.strategy import DistributedStrategy
from ..jit.train_step import TrainStep
from .tp_layers import get_placement


def _numel(shape):
    n = 1
    for d in shape:
        n *= d
    return n


def _shardable(shape, n):
    return len(shape) > 0 and shape[0] % n == 0 and shape[0] >= n


class SpmdTrainStep(TrainStep):
    """TrainStep + mesh shardings.  ``strategy`` controls ZeRO stage etc."""

    # the carry's shardings are laid out a key in ``_build``: device
    # counters are not collected here (``device_counter`` is a no-op)
    _collects_counters = False

    def __init__(self, model, loss_fn, optimizer, mesh=None,
                 strategy: Optional[DistributedStrategy] = None,
                 n_inputs: int = 1, donate: bool = True, scaler=None,
                 accumulate_steps: Optional[int] = None):
        strategy = strategy or DistributedStrategy()
        from ..distributed.strategy import validate_toggles
        validate_toggles(strategy)
        if accumulate_steps is None:
            accumulate_steps = (strategy.gradient_merge_configs.k_steps
                                if strategy.gradient_merge else 1)
        amp_level = None
        if strategy.amp:
            c = strategy.amp_configs
            if scaler is None and c.dtype == "float16":
                from ..amp import GradScaler
                scaler = GradScaler(
                    init_loss_scaling=c.init_loss_scaling,
                    incr_ratio=c.incr_ratio, decr_ratio=c.decr_ratio,
                    incr_every_n_steps=c.incr_every_n_steps,
                    decr_every_n_nan_or_inf=c.decr_every_n_nan_or_inf,
                    use_dynamic_loss_scaling=c.use_dynamic_loss_scaling)
            # wire the autocast itself, not just the scaler — bf16 O1/O2
            # previously compiled with no cast at all (silent no-op)
            amp_level = "O2" if c.use_pure_fp16 else "O1"
            model._amp_dtype = c.dtype
        super().__init__(model, loss_fn, optimizer, n_inputs, donate,
                         scaler=scaler, accumulate_steps=accumulate_steps,
                         recompute=strategy.recompute, amp_level=amp_level)
        self.mesh = mesh or ensure_mesh()
        self.strategy = strategy
        # explicit gradient-collective stage (distributed/grad_comm.py):
        # strategy.grad_comm knobs, with strategy.fp16_allreduce as the
        # backward-compatible alias for a bf16 wire
        from ..distributed import grad_comm as _gc
        self._grad_comm = _gc.resolve(strategy)
        self._comm_plan = None
        if self._grad_comm is not None:
            zero3 = (strategy.sharding
                     and strategy.sharding_configs.stage >= 3)
            msg = _gc.incompatibility(
                self._grad_comm, self.mesh.shape,
                sharded_params=(["<ZeRO-3 stage-3 params>"] if zero3
                                else ()))
            if msg is not None:
                raise NotImplementedError(msg)
            if (self._grad_comm.error_feedback
                    and self._grad_comm.source == "grad_comm"
                    and self._grad_comm.dtype != "fp32"):
                import warnings
                warnings.warn(
                    "grad_comm.error_feedback: the per-device residual "
                    "carry lives in the static Executor's donated state; "
                    "SpmdTrainStep reduces without error feedback.  Use "
                    "the static path (fleet + Executor) for EF, or set "
                    "error_feedback=False to silence this.")
        # -- ZeRO-3 padding plan (reference: sharding/shard.py pads numel) --
        self._padded = {}
        if (strategy.sharding and strategy.sharding_configs.stage >= 3
                and DP_AXIS in self.mesh.shape):
            dp = self.mesh.shape[DP_AXIS]
            min_numel = strategy.sharding_configs.min_shard_numel
            for i, p in enumerate(self._params):
                shp = p.shape_tuple
                if (get_placement(p) is None and len(shp) > 0
                        and _numel(shp) >= min_numel and shp[0] % dp != 0):
                    pad_d0 = -(-shp[0] // dp) * dp
                    self._padded[i] = (shp[0], pad_d0)
        self._p_store = None       # padded/sharded master copies
        self._store_dirty = False
        self._seen_pdata = {}      # padded idx -> p.data identity at encode

    # -- sharding rules ----------------------------------------------------
    def _dp_size(self) -> int:
        return self.mesh.shape.get(DP_AXIS, 1)

    def _stage3_sharded(self, i, p) -> bool:
        if not (self.strategy.sharding
                and self.strategy.sharding_configs.stage >= 3
                and DP_AXIS in self.mesh.shape
                and get_placement(p) is None):
            return False
        if i in self._padded:
            return True
        shp = p.shape_tuple
        return (_numel(shp) >= self.strategy.sharding_configs.min_shard_numel
                and _shardable(shp, self._dp_size()))

    def _param_spec(self, i, p) -> PartitionSpec:
        pl = get_placement(p)
        if pl is not None:
            return pl
        if self._stage3_sharded(i, p):
            return PartitionSpec(DP_AXIS)
        return PartitionSpec()

    def _slot_spec(self, i, p, slot_shape) -> PartitionSpec:
        pl = get_placement(p)
        if pl is not None and tuple(slot_shape) == p.shape_tuple:
            return pl
        stored_shape = self._stored_shape(i, p)
        if (self.strategy.sharding
                and self.strategy.sharding_configs.stage >= 1
                and DP_AXIS in self.mesh.shape
                and tuple(slot_shape) == stored_shape
                and _shardable(slot_shape, self._dp_size())):
            return PartitionSpec(DP_AXIS)
        return PartitionSpec()

    def _stored_shape(self, i, p):
        if i in self._padded:
            return (self._padded[i][1],) + p.shape_tuple[1:]
        return p.shape_tuple

    def _ns(self, spec) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    # -- ZeRO-3 padded store ----------------------------------------------
    def _encode_param(self, i, arr):
        if i in self._padded:
            d0, pad_d0 = self._padded[i]
            widths = [(0, pad_d0 - d0)] + [(0, 0)] * (arr.ndim - 1)
            arr = jnp.pad(arr, widths)
        return arr

    def _decode_params(self, p_list):
        if not self._padded:
            return p_list
        out = []
        for i, a in enumerate(p_list):
            if i in self._padded:
                a = jax.lax.slice_in_dim(a, 0, self._padded[i][0], axis=0)
            out.append(a)
        return out

    def _encode_and_demote(self, i):
        """Encode padded param i into its dp-sharded store form, then
        demote ``p.data`` to a host mirror — keeping the original full
        device array alive would erase the stage-3 memory saving."""
        import weakref

        import numpy as _np
        p = self._params[i]
        stored = jax.device_put(self._encode_param(i, p.data),
                                self._ns(self._param_spec(i, p)))
        host = _np.asarray(p.data)
        p.data = host
        p._param_owner_step = weakref.ref(self)  # state_dict auto-sync
        self._seen_pdata[i] = host
        return stored

    def _param_arrays(self):
        if not self._padded:
            return super()._param_arrays()
        if self._p_store is None:
            store = list(p.data for p in self._params)
            for i in self._padded:
                store[i] = self._encode_and_demote(i)
            self._p_store = tuple(store)
        else:
            # rebuild the tuple each call: non-padded entries read p.data
            # fresh (honors external set_state_dict), padded entries are
            # re-encoded only when p.data changed identity since encode
            store = list(self._p_store)
            for i, p in enumerate(self._params):
                if i not in self._padded:
                    store[i] = p.data
                elif p.data is not self._seen_pdata.get(i):
                    store[i] = self._encode_and_demote(i)
            self._p_store = tuple(store)
        return self._p_store

    def _writeback_params(self, new_p):
        if not self._padded:
            return super()._writeback_params(new_p)
        self._p_store = tuple(new_p)
        for i, (p, arr) in enumerate(zip(self._params, new_p)):
            if i not in self._padded:
                p.data = arr
        self._store_dirty = True

    def sync_params(self):
        """Materialise padded ZeRO-3 shards back into model params.

        Under stage 3 with padding, ``p.data`` is not refreshed per step
        (doing so would keep a gathered full copy alive and erase the
        memory saving); call this before ``state_dict()``/checkpointing."""
        if self._p_store is not None and self._store_dirty:
            for i in self._padded:
                d0, _ = self._padded[i]
                self._params[i].data = self._p_store[i][:d0]
                self._seen_pdata[i] = self._params[i].data
            self._store_dirty = False

    # -- ZeRO-2: reduce-scatter grads + sharded update --------------------
    def _grad_transform(self, grads):
        if not (self.strategy.sharding
                and self.strategy.sharding_configs.stage >= 2
                and DP_AXIS in self.mesh.shape):
            return grads
        n = self._dp_size()
        out = []
        for p, g in zip(self._params, grads):
            if get_placement(p) is None and _shardable(g.shape, n):
                # constraining the grad to 'dp' makes XLA lower the grad
                # psum as reduce-scatter, run the optimizer shard-local,
                # and all-gather the updated params — ZeRO-2 dataflow
                out.append(jax.lax.with_sharding_constraint(
                    g, self._ns(PartitionSpec(DP_AXIS))))
            else:
                out.append(g)
        return out

    # -- grad_comm: explicit bucketed/quantized grad reduction ------------
    def _wrap_loss_and_grad(self, fn):
        cfg = self._grad_comm
        if cfg is None:
            return fn
        mesh = self.mesh
        dp = self._dp_size()
        if dp <= 1:
            return fn  # nothing crosses a wire
        from ..distributed import grad_comm as _gc
        shapes = [self._stored_shape(i, p)
                  for i, p in enumerate(self._params)]
        plan = _gc.plan_reduction(shapes, dp=dp, cfg=cfg)
        self._comm_plan = plan

        def wrapped(p_cur, b_cur, mb_inputs, mb_labels, kidx):
            def local(ins, labs, k):
                # decorrelate per-shard dropout masks
                k = k * dp + jax.lax.axis_index(DP_AXIS)
                # differentiate w.r.t. a device-VARYING copy of the params:
                # grads stay local (no compiler-inserted f32 psum for the
                # invariant cotangent) so the ONLY reduction is ours below
                p_var = [jax.lax.pcast(a, DP_AXIS, to="varying")
                         for a in p_cur]
                loss, new_b, grads, _ = fn(p_var, b_cur, ins, labs, k)
                # bucketed quantize → reduce → dequantize: the wire
                # carries the plan's dtype (bf16 subsumes the old
                # fp16_allreduce cast/recast pair,
                # fp16_allreduce_optimizer.py:18); residual-less — the
                # error-feedback carry lives on the Executor path.
                # The overlap lowering follows the plan's resolved
                # path (strategy.grad_comm.overlap), same as the
                # Executor — ring/none/xla are numerics-compatible
                grads, _ = _gc.reduce_gradients(
                    grads, plan=plan, axis_name=DP_AXIS, residuals=None)
                loss = jax.lax.pmean(loss, DP_AXIS)
                new_b = jax.tree.map(
                    lambda a: jax.lax.pmean(a, DP_AXIS), new_b)
                return loss, new_b, grads, {}

            from jax import shard_map
            P = PartitionSpec
            # check_vma off: the int8 route's all_to_all/all_gather
            # results are replicated by construction, which the static
            # replication checker cannot infer
            return shard_map(
                local, mesh=mesh,
                in_specs=(P(DP_AXIS), P(DP_AXIS), P()),
                out_specs=P(), check_vma=False)(mb_inputs, mb_labels,
                                                kidx)

        return wrapped

    def _build(self, training: bool):
        step_fn = self._make_step_fn()
        p_specs = tuple(self._ns(self._param_spec(i, p))
                        for i, p in enumerate(self._params))
        b_specs = tuple(self._ns(PartitionSpec())
                        for _ in self._bnames)
        state = self._opt_state or self.optimizer.functional_init(
            list(self._param_arrays()))
        s_specs = [
            {k: self._ns(self._slot_spec(i, p, v.shape))
             for k, v in slots.items()}
            for i, (p, slots) in enumerate(zip(self._params, state))]
        scalar = self._ns(PartitionSpec())
        aux_specs = {k: scalar for k in self._aux_keys()}
        batch_spec = self._ns(PartitionSpec(DP_AXIS))
        jitted = jax.jit(
            step_fn,
            in_shardings=(p_specs, b_specs, s_specs, aux_specs, scalar,
                          None, None),
            out_shardings=(scalar, p_specs, b_specs, s_specs, aux_specs),
            donate_argnums=(0, 1, 2, 3) if self._donate else (),
        )
        return _ShardBatch(jitted, batch_spec, self.n_inputs)


class _ShardBatch:
    """Callable shim: places batch arrays with dp sharding, then calls the
    jitted step (jit infers shardings for key/inputs/labels from committed
    device placement)."""

    def __init__(self, jitted, batch_spec, n_inputs):
        self._jitted = jitted
        self._spec = batch_spec
        self.n_inputs = n_inputs

    def lower(self, *args):
        return self._jitted.lower(*args)

    def __call__(self, p_arr, b_arr, opt_state, aux, lr, inputs, labels):
        put = lambda a: jax.device_put(a, self._spec)
        inputs = tuple(put(a) for a in inputs)
        labels = tuple(put(a) for a in labels)
        return self._jitted(p_arr, b_arr, opt_state, aux, lr, inputs,
                            labels)
