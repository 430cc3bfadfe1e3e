"""TrainStep: whole-training-step compilation.

The TPU-native analog of the reference's CompiledProgram/ParallelExecutor
fast path (reference: fluid/compiler.py, parallel_executor.cc:619): forward,
backward, gradient clip, and optimizer update are traced into ONE XLA
executable with donated buffers, so the MXU never waits on Python between
micro-steps.  Under a `Mesh` (paddle_tpu.distributed) the same step is
pjit-sharded for DP/TP/PP hybrid execution.

Also compiled in-graph (zero host syncs per step):
- **dynamic loss scaling** (``scaler=``): scale the loss, unscale grads,
  detect non-finite grads, skip the update and adjust the scale — the
  reference's check_finite_and_unscale + update_loss_scaling ops
  (operators/amp/check_finite_and_unscale_op.cu, update_loss_scaling_op.cu)
  as a handful of fused scalar ops.
- **gradient accumulation** (``accumulate_steps=k``): a lax.scan over k
  microbatches accumulating f32 grads, one optimizer update — the
  reference's gradient-merge meta-optimizer
  (fleet/meta_optimizers/gradient_merge_optimizer.py:18,
  grad_merge_all_reduce_op_handle.cc) without the extra memory pass.
- **device counters** (``observability.device_counter``): what the model
  emitted while the step ran stays in the carry (``aux["counters"]``),
  summed from step to step, until ``device_counters()`` reads it.  A
  step that emits nothing has no such entry and is the same program.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.extend.core import jaxpr_as_fun

from ..core import autograd, rng
from ..core.tensor import Tensor
from ..observability import compiles, device_counters, scopes, span
from ..utils import monitor
from .bind import bind, buffer_arrays, buffer_names, param_list

_as_arr = lambda x: x.data if isinstance(x, Tensor) else jnp.asarray(x)


def _select(pred, when_true, when_false):
    """Per-leaf scalar select over matching pytrees."""
    return jax.tree.map(lambda a, b: jnp.where(pred, a, b),
                        when_true, when_false)


class TrainStep:
    """Compile `loss = loss_fn(model(*inputs), *labels)` + optimizer update.

    Usage::

        step = TrainStep(model, loss_fn, opt)       # loss_fn(outputs, labels)
        loss = step(x, y)                            # one fused XLA call

    ``loss_fn`` receives (model_output, *labels) as Tensors inside the trace.
    Model parameters / optimizer slots / buffers live as device arrays
    between calls and are donated each step (no copies).

    ``scaler``: a paddle_tpu.amp.GradScaler whose dynamic-loss-scaling state
    is threaded through the compiled step (fp16 path; bf16 needs none).
    ``accumulate_steps``: microbatch gradient accumulation inside the step
    (the global batch you pass is split into this many microbatches).
    """

    # whether the step collects ``observability.device_counter`` emissions
    # (SpmdTrainStep fixes the carry's shardings by key and does not)
    _collects_counters = True

    def __init__(self, model, loss_fn: Callable, optimizer,
                 n_inputs: int = 1, donate: bool = False, scaler=None,
                 accumulate_steps: int = 1, amp_level: Optional[str] = None,
                 recompute: bool = False):
        # donate=False by default: eager user code may alias param arrays
        # (e.g. state_dict sharing); SpmdTrainStep/bench enable donation.
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.n_inputs = n_inputs
        self._params = param_list(model)
        self._bnames = buffer_names(model)
        self._compiled: Dict[Any, Callable] = {}
        self._opt_state = None
        self._donate = donate
        self.scaler = (scaler if scaler is not None
                       and getattr(scaler, "_enable", True) else None)
        self.accumulate_steps = int(accumulate_steps)
        # amp_level: re-enter auto_cast(level, model's decorated dtype)
        # inside the compiled trace (the reference's train-loop
        # `with amp.auto_cast(...)`); None = trace ops at their natural
        # dtypes (pure-bf16 after amp.decorate O2)
        self.amp_level = amp_level
        # recompute: rematerialise the forward during backward instead of
        # storing activations — the reference's recompute meta-optimizer
        # (fleet/meta_optimizers/recompute_optimizer.py:18) as jax.checkpoint
        # over the whole loss (checkpoints=[] edge: keep only the inputs)
        self._recompute = bool(recompute)
        self._scaler_state = None
        self._lr_value = None
        self._lr_device = None
        self._buffer_objs = None
        # name -> what a step emits; None until the first build has looked
        self._counter_spec = None
        if self.scaler is not None:
            # let scaler.state_dict()/load_state_dict() see the in-graph
            # state (checkpoint correctness)
            self.scaler._bound_step = self
        # let optimizer.state_dict()/set_state_dict() see / resync the
        # in-graph step counter (checkpoint correctness)
        optimizer._bound_train_step = self

    # -- hooks for subclasses ---------------------------------------------
    def _grad_transform(self, grads: List[jnp.ndarray]) -> List[jnp.ndarray]:
        """Applied to (unscaled) grads before the optimizer update.
        SpmdTrainStep overrides this for ZeRO-2 grad sharding."""
        return grads

    def _decode_params(self, p_list):
        """Stored form -> model-shaped arrays (inside the trace).
        SpmdTrainStep overrides this to un-pad ZeRO-3 padded shards."""
        return p_list

    def _wrap_loss_and_grad(self, fn):
        """Wrap the per-microbatch (b_cur, inputs, labels, kidx) ->
        (loss, new_buffers, grads, counted) function.  SpmdTrainStep
        overrides this for fp16_allreduce (shard_map with
        reduced-precision grad psum)."""
        return fn

    def _value_and_grad(self, loss_of, p_list):
        """Differentiate ``loss_of`` (returns (scaled_loss, (loss, new_b,
        counted))) w.r.t. the stored param list, honoring ``recompute``."""
        if self._recompute:
            loss_of = jax.checkpoint(loss_of)
        return jax.value_and_grad(loss_of, has_aux=True)(p_list)

    def _param_arrays(self):
        """Stored param arrays fed to the compiled step (subclasses may
        keep a padded/sharded store distinct from ``p.data``)."""
        return tuple(p.data for p in self._params)

    def _writeback_params(self, new_p):
        for p, arr in zip(self._params, new_p):
            p.data = arr

    def sync_params(self):
        """Materialise any step-held authoritative weights into the model
        (no-op here; ZeRO-3 padded / LocalSGD subclasses override).  Layer
        .state_dict() calls this via the ``_param_owner_step`` hook."""

    # -- the compiled step -------------------------------------------------
    def _make_step_fn(self):
        model, loss_fn, opt = self.model, self.loss_fn, self.optimizer
        params_meta = self._params
        bnames = self._bnames
        K = self.accumulate_steps
        scaler = self.scaler
        grad_transform = self._grad_transform
        collects = self._collects_counters
        if scaler is not None:
            sc = dict(incr_ratio=scaler._incr_ratio,
                      decr_ratio=scaler._decr_ratio,
                      incr_every=scaler._incr_every,
                      decr_every=scaler._decr_every,
                      dynamic=scaler._dynamic)

        def step_fn(p_arr, b_arr, opt_state, aux, lr, inputs, labels):
            compiles.claim("train_step.call")   # a recompile's owner
            # aux carries everything that changes per step but lives on
            # device: the RNG base key, the effective step counter, and the
            # loss-scaling state.  Keeping these in-graph means __call__
            # performs ZERO host->device uploads per step (each tiny
            # upload is a synchronous transfer that serialises the
            # dispatch pipeline).
            key = jax.random.wrap_key_data(aux["key"])
            # 'step' counts only applied updates (non-finite-grad steps
            # don't advance Adam bias correction — reference GradScaler
            # semantics where optimizer.step() is skipped); 'draw' advances
            # every call so RNG draws are never reused after a skip
            attempt = aux["step"] + 1
            draw = aux["draw"] + 1
            step_i = attempt.astype(jnp.float32)
            key = jax.random.fold_in(key, draw)
            scale = aux["scale"] if scaler is not None else None

            amp_level = self.amp_level

            def amp_scope():
                if amp_level is None:
                    return contextlib.nullcontext()
                from ..amp import auto_cast
                return auto_cast(level=amp_level,
                                 dtype=getattr(model, "_amp_dtype",
                                               "bfloat16"))

            def loss_and_grad(p_cur, b_cur, mb_inputs, mb_labels, kidx):
                def loss_of(p_list):
                    k_mb = jax.random.fold_in(key, kidx)
                    p_model = self._decode_params(p_list)
                    with autograd.no_grad(), rng.seed_scope(k_mb), \
                            amp_scope(), jax.named_scope(scopes.LOSS):
                        with bind(model, p_model, list(b_cur)) as res, \
                                device_counters.collect(collects) as counted:
                            out = model(*[Tensor(a) for a in mb_inputs])
                            lab = [Tensor(a) for a in mb_labels]
                            loss_t = loss_fn(out, *lab)
                        # new_buffers is populated on bind-context exit
                        new_b = tuple(
                            _as_arr(res.new_buffers.get(n, old))
                            for n, old in zip(bnames, b_cur))
                        # what the model emitted leaves the differentiated
                        # trace as an output, like the loss
                        counted = counted.stacked()
                    loss = loss_t.data
                    scaled = loss * scale if scaler is not None else loss
                    return scaled, (loss, new_b, counted)

                (_, (loss, new_b, counted)), grads = self._value_and_grad(
                    loss_of, list(p_cur))
                return loss, new_b, grads, counted

            loss_and_grad = self._wrap_loss_and_grad(loss_and_grad)

            if K <= 1:
                loss, new_b, grads, counted = loss_and_grad(
                    p_arr, b_arr, inputs, labels, 0)
            else:
                # gradient merge: scan over K microbatches, f32 accumulators
                mb_in = tuple(a.reshape(K, a.shape[0] // K, *a.shape[1:])
                              for a in inputs)
                mb_lab = tuple(a.reshape(K, a.shape[0] // K, *a.shape[1:])
                               for a in labels)

                def mb_body(carry, xs):
                    b_cur, g_acc, l_acc = carry
                    idx, ins, labs = xs
                    loss, new_b, grads, counted = loss_and_grad(
                        p_arr, b_cur, ins, labs, idx)
                    g_acc = [ga + g.astype(jnp.float32)
                             for ga, g in zip(g_acc, grads)]
                    return (new_b, g_acc, l_acc + loss), counted

                g0 = [jnp.zeros(p.shape, jnp.float32) for p in p_arr]
                (new_b, g_acc, l_sum), counted = jax.lax.scan(
                    mb_body, (tuple(b_arr), g0, jnp.zeros((), jnp.float32)),
                    (jnp.arange(K), mb_in, mb_lab))
                loss = l_sum / K
                grads = [g / K for g in g_acc]
                # a step's count is the sum over its micro-batches (what
                # they emit is only known once the body is traced, so it
                # leaves the scan stacked, not in the carry)
                counted = {n: jnp.sum(v, 0) for n, v in counted.items()}

            if scaler is not None:
                with jax.named_scope(scopes.UNSCALE):
                    inv = 1.0 / scale
                    grads = [g * inv for g in grads]
                    finite = jnp.all(jnp.stack(
                        [jnp.all(jnp.isfinite(g)) for g in grads]))
                    found_inf = jnp.logical_not(finite)

            with jax.named_scope(scopes.GRAD_CLIP):
                grads = grad_transform(grads)
            with jax.named_scope(scopes.OPTIMIZER):
                new_p, new_s = opt.functional_update(
                    list(p_arr), grads, opt_state, lr, step_i,
                    params_meta=params_meta)

            new_aux = dict(aux)
            new_aux["draw"] = draw
            if counted:
                # this step's own; ``_carry_counters`` folds it into the
                # carry's sums
                new_aux["counters"] = counted
            if scaler is not None:
                with jax.named_scope(scopes.SCALER):
                    # skip the update on non-finite grads (reference:
                    # check_finite_and_unscale) ...
                    new_p = _select(found_inf, list(p_arr), new_p)
                    new_s = _select(found_inf, opt_state, new_s)
                    # ... and adjust the scale in-graph (update_loss_scaling)
                    good, bad = aux["good"], aux["bad"]
                    if sc["dynamic"]:
                        good = jnp.where(found_inf, 0, good + 1)
                        bad = jnp.where(found_inf, bad + 1, 0)
                        dec = bad >= sc["decr_every"]
                        new_scale = jnp.where(
                            dec, jnp.maximum(scale * sc["decr_ratio"], 1.0),
                            scale)
                        bad = jnp.where(dec, 0, bad)
                        inc = good >= sc["incr_every"]
                        new_scale = jnp.where(
                            inc, new_scale * sc["incr_ratio"], new_scale)
                        good = jnp.where(inc, 0, good)
                    else:
                        new_scale = scale
                    new_aux.update(scale=new_scale, good=good, bad=bad,
                                   found_inf=found_inf,
                                   step=jnp.where(found_inf, aux["step"],
                                                  attempt))
            else:
                new_aux["step"] = attempt
            return loss, tuple(new_p), new_b, new_s, new_aux

        return step_fn

    def _build(self, training: bool):
        donate = (0, 1, 2, 3) if self._donate else ()
        return jax.jit(self._make_step_fn(), donate_argnums=donate)

    def _carry_counters(self, jitted, args):
        """The step that ``__call__`` runs, from ``_build``'s: where the
        model emits device counters, ``aux["counters"]`` comes in as the
        carry (``last`` / ``total`` / ``steps`` a name) and goes out with
        this step's emissions folded in; where it emits none, ``jitted``
        itself.

        What a step emits is known once it is traced, and the carry has
        to be an argument before that.  So ``jitted`` is traced here
        without the carry (jit keeps that trace: where nothing is
        emitted, the call that follows finds it), and the carrying step
        is that trace's jaxpr with the fold behind it, under the same
        name and with the same donation: the model's Python runs once
        either way."""
        if not self._collects_counters:
            return jitted
        aux = {k: v for k, v in args[3].items() if k != "counters"}
        traced = jitted.trace(*args[:3], aux, *args[4:])
        spec = {name: jax.ShapeDtypeStruct(v.shape, v.dtype) for name, v
                in traced.out_info[4].get("counters", {}).items()}
        if self._counter_spec not in (None, spec):
            # the model emits something else in this mode: what the old
            # carry holds goes to the registry, and both modes build anew
            self.device_counters()
            self._scaler_state.pop("counters", None)
            self._compiled.clear()
        self._counter_spec = spec
        if not spec:
            return jitted
        device_counters.register(self)
        body = jaxpr_as_fun(traced.jaxpr)
        out_tree = jax.tree.structure(traced.out_info)

        def step_fn(p_arr, b_arr, opt_state, aux, lr, inputs, labels):
            compiles.claim("train_step.call")
            aux = dict(aux)
            carry = aux.pop("counters")
            loss, new_p, new_b, new_s, new_aux = jax.tree.unflatten(
                out_tree, body(*jax.tree.leaves(
                    (p_arr, b_arr, opt_state, aux, lr, inputs, labels))))
            new_aux["counters"] = device_counters.fold(
                carry, new_aux["counters"])
            return loss, new_p, new_b, new_s, new_aux

        return jax.jit(step_fn,
                       donate_argnums=(0, 1, 2, 3) if self._donate else ())

    def counter_carry(self):
        """The device counters' part of the carry (None before the first
        step, or where the model emits none)."""
        return (self._scaler_state or {}).get("counters")

    def device_counters(self):
        """Read this step's device counters into ``utils.monitor``
        (``observability.read_device_counters`` for one step): one
        ``device_get`` that waits for the newest dispatched step, as
        reading the loss does.  Returns the registry's view."""
        return device_counters.read([self])

    def _aux_keys(self):
        """Static key set of the aux carry (no side effects — used to
        build shardings without consuming RNG state)."""
        keys = ["step", "draw", "key"]
        if self.scaler is not None:
            keys += ["scale", "good", "bad", "found_inf"]
        return keys

    def _init_scaler_state(self):
        """Device-resident per-step carry: step/draw counters, RNG base
        key, and (when a scaler is bound) the dynamic loss-scaling state.
        The applied-step counter seeds from the optimizer's host count so a
        set_state_dict before the first step is honored."""
        aux = {"step": jnp.asarray(self.optimizer._step_count, jnp.int32),
               "draw": jnp.asarray(0, jnp.int32),
               "key": jax.random.key_data(rng.next_key())}
        if self.scaler is not None:
            aux.update(
                scale=jnp.asarray(self.scaler._scale, jnp.float32),
                good=jnp.asarray(self.scaler._good_steps, jnp.int32),
                bad=jnp.asarray(self.scaler._bad_steps, jnp.int32),
                found_inf=jnp.asarray(False))
        if self._counter_spec:
            aux["counters"] = device_counters.zero_carry(self._counter_spec)
        return aux

    @property
    def loss_scale(self) -> Optional[float]:
        """Current loss scale (host sync; for logging/checkpoint only)."""
        if self._scaler_state is None or "scale" not in self._scaler_state:
            return None
        return float(self._scaler_state["scale"])

    def __call__(self, *batch):
        assert len(batch) >= self.n_inputs, (
            f"TrainStep expects at least {self.n_inputs} input(s)")
        if self._opt_state is None:
            # one-off set-up (counted as setup.opt_state_init_s), kept out
            # of the step's own python below
            self._opt_state = self.optimizer.functional_init(
                list(self._param_arrays()))
        # always-on host-step counters: time in this method outside the
        # compiled call (train_step.python_ns over train_step.calls)
        t0 = time.perf_counter_ns()
        with span("train_step.prepare"):
            inputs = tuple(_as_arr(b) for b in batch[:self.n_inputs])
            labels = tuple(_as_arr(b) for b in batch[self.n_inputs:])
            if self.accumulate_steps > 1:
                bs = inputs[0].shape[0]
                if bs % self.accumulate_steps:
                    raise ValueError(
                        f"batch size {bs} is not divisible by "
                        f"accumulate_steps={self.accumulate_steps}")
            p_arr = self._param_arrays()
            b_arr = tuple(buffer_arrays(self.model))
            if self._scaler_state is None:
                self._scaler_state = self._init_scaler_state()
            self.optimizer._step_count += 1
            lr_val = float(self.optimizer.get_lr())
            if lr_val != self._lr_value:
                # upload the lr only when the schedule moves it (every
                # host->device transfer stalls the dispatch pipeline)
                self._lr_value = lr_val
                self._lr_device = jnp.asarray(lr_val, jnp.float32)
            training = self.model.training
            compiled = self._compiled.get(training)
        t1 = time.perf_counter_ns()
        first = None
        if compiled is None:
            # one-off: the step is traced here (``_carry_counters``), where
            # it used to be traced inside its first call below, so this is
            # kept out of the step's own python as that was.  The set-up
            # span ``train_step.first_call`` owns what jax traces, lowers,
            # loads and compiles from here through the return of the first
            # compiled call (``observability.setup_report``)
            first = compiles.begin_setup("train_step.first_call")
            with compiles.setup_span("train_step.build"):
                compiled = self._carry_counters(
                    self._build(training),
                    (p_arr, b_arr, self._opt_state, self._scaler_state,
                     self._lr_device, inputs, labels))
            self._compiled[training] = compiled
            if self._counter_spec:
                self._scaler_state.setdefault(
                    "counters",
                    device_counters.zero_carry(self._counter_spec))
            t0 += time.perf_counter_ns() - t1
            t1 = time.perf_counter_ns()
        try:
            with span("train_step.execute"):
                loss, new_p, new_b, new_s, new_sc = compiled(
                    p_arr, b_arr, self._opt_state, self._scaler_state,
                    self._lr_device, inputs, labels)
        finally:
            if first is not None:
                compiles.first_call_done("train_step", first)
        t2 = time.perf_counter_ns()
        with span("train_step.writeback"):
            # write back (device-side aliasing, no host copies)
            self._writeback_params(new_p)
            if self._buffer_objs is None:
                buffers = dict(self.model.named_buffers())
                self._buffer_objs = [buffers[n] for n in self._bnames]
            for b, arr in zip(self._buffer_objs, new_b):
                b.data = arr
            self._opt_state = new_s
            self._scaler_state = new_sc
            out = Tensor(loss)
        monitor.stat_add("train_step.python_ns",
                         t1 - t0 + time.perf_counter_ns() - t2)
        monitor.stat_add("train_step.calls")
        return out

    def eval_step(self, *batch):
        """Forward-only compiled step (no param update)."""
        inputs = tuple(_as_arr(b) for b in batch[:self.n_inputs])
        labels = tuple(_as_arr(b) for b in batch[self.n_inputs:])
        model, loss_fn = self.model, self.loss_fn
        bnames = self._bnames

        key = ("eval", model.training)
        compiled = self._compiled.get(key)
        first = None
        if compiled is None:
            first = compiles.begin_setup("eval_step.first_call")

            def eval_fn(p_arr, b_arr, key_data, inputs, labels):
                compiles.claim("eval_step.call")
                k = jax.random.wrap_key_data(key_data)
                p_model = self._decode_params(list(p_arr))
                with autograd.no_grad(), rng.seed_scope(k):
                    with bind(model, p_model, list(b_arr)):
                        out = model(*[Tensor(a) for a in inputs])
                        lab = [Tensor(a) for a in labels]
                        loss_t = loss_fn(out, *lab)
                out_arr = jax.tree.map(
                    lambda t: t.data if isinstance(t, Tensor) else t, out,
                    is_leaf=lambda x: isinstance(x, Tensor))
                return loss_t.data, out_arr
            compiled = jax.jit(eval_fn)
            self._compiled[key] = compiled
        try:
            p_arr = self._param_arrays()
            b_arr = tuple(buffer_arrays(self.model))
            key_data = jax.random.key_data(rng.next_key())
            loss, out = compiled(p_arr, b_arr, key_data, inputs, labels)
        finally:
            if first is not None:
                compiles.first_call_done("eval_step", first)
        return Tensor(loss), jax.tree.map(Tensor, out)
