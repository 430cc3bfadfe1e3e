"""Optimizers.

TPU-native replacement for the reference's optimizer-op zoo (reference:
paddle/fluid/operators/optimizers/ — sgd_op, momentum_op, adam_op, lamb_op,
lars_momentum_op...; python façade python/paddle/optimizer/).

Design: every optimizer defines two PURE functions over arrays —
``init_slots`` and ``update_param`` — shared by:
- eager ``.step()`` (reads ``param.grad``, writes ``param.data``), and
- the jit path (``paddle_tpu.jit.TrainStep`` tree-maps them inside one
  compiled XLA program, where the whole update fuses into a handful of
  kernels — the analog of the reference's fused optimizer kernels).
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core import autograd
from ..core.tensor import Parameter, Tensor
from ..observability import scopes
from ..observability.compiles import setup_span
from ..utils import monitor
from .clip import ClipGradBase
from .lr import LRScheduler
from .regularizer import L1Decay, L2Decay, WeightDecayRegularizer


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None,
                 weight_decay=None, grad_clip=None, name=None,
                 multi_precision=False):
        if parameters is not None:
            parameters = list(parameters)
            if parameters and isinstance(parameters[0], dict):
                # param groups: flatten (kept simple; per-group lr TODO)
                flat = []
                for grp in parameters:
                    flat.extend(grp["params"])
                parameters = flat
        self._parameter_list: Optional[List[Parameter]] = parameters
        self._learning_rate = learning_rate
        self._grad_clip: Optional[ClipGradBase] = grad_clip
        if isinstance(weight_decay, (int, float)):
            weight_decay = L2Decay(float(weight_decay))
        self._weight_decay: Optional[WeightDecayRegularizer] = weight_decay
        self._multi_precision = multi_precision
        self._slots: Dict[int, Dict[str, Any]] = {}
        self._step_count = 0

    # -- lr ---------------------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._learning_rate, LRScheduler):
            return self._learning_rate()
        return float(self._learning_rate)

    def set_lr(self, value: float):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError(
                "cannot set_lr when learning rate is an LRScheduler")
        self._learning_rate = float(value)

    @property
    def _lr_scheduler(self):
        return (self._learning_rate
                if isinstance(self._learning_rate, LRScheduler) else None)

    # -- pure per-param update (override these two) -----------------------
    def init_slots(self, p: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        return {}

    def update_param(self, p, g, slots, lr, step):
        raise NotImplementedError

    # -- regularization ----------------------------------------------------
    def _apply_decay(self, param: Parameter, g):
        """Param-level regularizer wins over optimizer-level
        (reference: fluid/regularizer.py append_regularization_ops)."""
        reg = getattr(param, "regularizer", None) or self._weight_decay
        if reg is not None and not self._decoupled():
            g = reg(param.data, g)
        return g

    def _decoupled(self) -> bool:
        return False  # AdamW overrides

    def _decoupled_decay(self, p, lr, param_name=None):
        """Decoupled (AdamW-style) decay applied to the param array right
        before the main update; base optimizers are a no-op."""
        return p

    def _param_lr_ratio(self, param) -> float:
        return 1.0  # AdamW lr_ratio overrides

    # -- eager step --------------------------------------------------------
    def step(self):
        assert self._parameter_list is not None, (
            "optimizer constructed without parameters; pass parameters= "
            "or use the functional interface")
        self._step_count += 1
        # clip raw grads first, THEN regularize — matching the reference's
        # apply_gradients order (python/paddle/optimizer/optimizer.py:746-757)
        # and this file's functional_update.
        from ..core.selected_rows import SelectedRows
        pg = [(p, p._grad_data) for p in self._parameter_list
              if p.trainable and p._grad_data is not None]
        if self._grad_clip is not None:
            pg = self._grad_clip(pg)  # SelectedRows-aware (clip.py)
        # weight decay skips SelectedRows (regularizing only touched rows
        # would bias the decay; the reference's sparse tables decay via
        # table-side accessors instead)
        pg = [(p, g if isinstance(g, SelectedRows)
               else self._apply_decay(p, g)) for p, g in pg]
        lr = self.get_lr()
        for p, g in pg:
            slots = self._slots.get(id(p))
            if slots is None:
                slots = self.init_slots(p.data)
                if (self._multi_precision
                        and p.data.dtype in (jnp.bfloat16, jnp.float16)):
                    slots["master"] = p.data.astype(jnp.float32)
                self._slots[id(p)] = slots
            plr = lr * getattr(p, "optimize_attr", {}).get("learning_rate", 1.0)
            plr = plr * self._param_lr_ratio(p)
            if isinstance(g, SelectedRows):
                g = g.merge()
                if "master" not in slots and self._sparse_supported():
                    # true SelectedRows semantics: only touched rows (and
                    # their optimizer slots) are updated
                    p.data, new_slots = self._sparse_update_param(
                        p.data, g, slots, plr, self._step_count)
                    self._slots[id(p)] = new_slots
                    continue
                g = g.to_dense()  # optimizers without a sparse kernel
            if "master" in slots:
                master = self._decoupled_decay(slots["master"], plr, p.name)
                new_master, new_slots = self.update_param(
                    master, g.astype(jnp.float32),
                    {k: v for k, v in slots.items() if k != "master"},
                    plr, self._step_count)
                new_slots["master"] = new_master
                p.data = new_master.astype(p.data.dtype)
            else:
                pdata = self._decoupled_decay(p.data, plr, p.name)
                p.data, new_slots = self.update_param(
                    pdata, g, slots, plr, self._step_count)
            self._slots[id(p)] = new_slots

    # -- sparse (SelectedRows) updates -------------------------------------
    def _sparse_supported(self) -> bool:
        """Whether this optimizer has a row-wise SelectedRows kernel
        (reference: sgd_op.h SelectedRows branch, adam_op.h lazy_mode)."""
        return False

    def _sparse_update_param(self, p, sr, slots, lr, step):
        raise NotImplementedError

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        """Static mode: attach this optimizer to the loss's Program
        (static.Executor compiles backward + update in-graph).  Eager:
        backward + step (reference: optimizer.py minimize)."""
        from ..static.program import Variable
        if isinstance(loss, Variable):
            loss.program._optimizer = (self, loss, parameters, no_grad_set)
            return None, None
        loss.backward()
        self.step()
        return None, [(p, p.grad) for p in (self._parameter_list or [])]

    def clear_grad(self, set_to_zero=False):
        for p in self._parameter_list or []:
            p.clear_grad()

    clear_gradients = clear_grad

    # -- functional interface (used by jit.TrainStep) ----------------------
    def functional_init(self, param_arrays: Sequence[jnp.ndarray]):
        t0 = time.perf_counter()
        states = []
        with setup_span("setup.opt_state_init"):
            for p in param_arrays:
                s = self.init_slots(p)
                if (self._multi_precision
                        and p.dtype in (jnp.bfloat16, jnp.float16)):
                    s["master"] = p.astype(jnp.float32)
                states.append(s)
        # always-on set-up counter (eager ops, one or more a leaf)
        monitor.stat_add("setup.opt_state_init_s",
                         time.perf_counter() - t0)
        return states

    def functional_update(self, param_arrays, grad_arrays, states, lr,
                          step, params_meta=None):
        """Pure: returns (new_params, new_states). ``lr``/``step`` may be
        traced scalars.  params_meta: optional list of Parameters for
        regularizer / per-param lr metadata."""
        meta = params_meta or [None] * len(param_arrays)
        if self._grad_clip is not None:
            with jax.named_scope(scopes.GRAD_CLIP):
                pg = self._grad_clip(list(zip(meta, grad_arrays)))
            grad_arrays = [g for _, g in pg]
        new_ps, new_ss = [], []
        for p, g, s, m in zip(param_arrays, grad_arrays, states, meta):
            if m is not None:
                reg = getattr(m, "regularizer", None) or self._weight_decay
                if reg is not None and not self._decoupled():
                    g = reg(p, g)
                plr = lr * getattr(m, "optimize_attr", {}).get("learning_rate", 1.0)
                plr = plr * self._param_lr_ratio(m)
            elif self._weight_decay is not None and not self._decoupled():
                g = self._weight_decay(p, g)
                plr = lr
            else:
                plr = lr
            pname = m.name if m is not None else None
            if "master" in s:
                sub = {k: v for k, v in s.items() if k != "master"}
                master = self._decoupled_decay(s["master"], plr, pname)
                new_master, ns = self.update_param(
                    master, g.astype(jnp.float32), sub, plr, step)
                ns["master"] = new_master
                new_ps.append(new_master.astype(p.dtype))
            else:
                p_in = self._decoupled_decay(p, plr, pname)
                np_, ns = self.update_param(p_in, g, s, plr, step)
                new_ps.append(np_)
            new_ss.append(ns)
        return new_ps, new_ss

    # -- state dict --------------------------------------------------------
    def _effective_step(self):
        """Applied-update count.  A compiled TrainStep tracks this on
        device (skipped non-finite steps don't advance it); fall back to
        the host counter otherwise."""
        step = getattr(self, "_bound_train_step", None)
        aux = getattr(step, "_scaler_state", None)
        if aux and "step" in aux:
            return int(aux["step"])
        return self._step_count

    def state_dict(self):
        out = {"step": self._effective_step(), "slots": {}}
        if self._parameter_list:
            for i, p in enumerate(self._parameter_list):
                s = self._slots.get(id(p))
                if s:
                    out["slots"][str(i)] = {k: np.asarray(v)
                                            for k, v in s.items()}
        # static path: slots live in the Executor's device-resident
        # state, not in self._slots — read them through the provider the
        # Executor registered (keys are positions in
        # program.parameters(); set_state_dict routes them back via
        # _static_pending_slots).  Only when no eager slots exist: the
        # two index spaces (parameter_list vs program.parameters())
        # differ, and a mixed eager+static optimizer checkpoint would
        # silently cross-wire moments — eager slots win, as before.
        prov = getattr(self, "_static_state_provider", None)
        if prov is not None and not out["slots"]:
            st = prov()
            if st is not None:
                out["slots"].update(st.export_slots())
        if self._lr_scheduler is not None:
            out["lr_scheduler"] = self._lr_scheduler.state_dict()
        return out

    def set_state_dict(self, state):
        self._step_count = state.get("step", 0)
        # resync any compiled TrainStep: preserve its in-graph scaler
        # values, then drop the aux carry so the next step reinitialises
        # from the newly loaded counters
        step = getattr(self, "_bound_train_step", None)
        if step is not None:
            if step.scaler is not None:
                step.scaler._sync_from_bound_step()
            step._scaler_state = None
        slots = state.get("slots", {})
        if self._parameter_list:
            for i, p in enumerate(self._parameter_list):
                if str(i) in slots:
                    self._slots[id(p)] = {
                        k: jnp.asarray(v) for k, v in slots[str(i)].items()}
        elif slots:
            # static path (no parameter list): slot keys are positions in
            # program.parameters().  Stash them for the Executor to load
            # into its device-resident state, and drop any live state's
            # slots so the next run reinitialises from the checkpoint
            self._static_pending_slots = dict(slots)
            prov = getattr(self, "_static_state_provider", None)
            st = prov() if prov is not None else None
            if st is not None:
                st.opt_state = None
        if self._lr_scheduler is not None and "lr_scheduler" in state:
            self._lr_scheduler.set_state_dict(state["lr_scheduler"])


class SGD(Optimizer):
    """reference: operators/optimizers/sgd_op.cc."""

    def update_param(self, p, g, slots, lr, step):
        return p - lr * g.astype(p.dtype), slots

    def _sparse_supported(self):
        return True

    def _sparse_update_param(self, p, sr, slots, lr, step):
        """Row-wise scatter update (reference: sgd_op.h SelectedRows
        kernel): untouched rows are never read or written."""
        return p.at[sr.rows].add(-lr * sr.values.astype(p.dtype)), slots


class Momentum(Optimizer):
    """reference: operators/optimizers/momentum_op.h."""

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def init_slots(self, p):
        return {"velocity": jnp.zeros_like(
            p, dtype=jnp.float32 if self._multi_precision else p.dtype)}

    def update_param(self, p, g, slots, lr, step):
        g = g.astype(p.dtype)
        v = self._momentum * slots["velocity"] + g
        if self._use_nesterov:
            new_p = p - lr * (g + self._momentum * v)
        else:
            new_p = p - lr * v
        return new_p, {"velocity": v}


class Adam(Optimizer):
    """reference: operators/optimizers/adam_op.h."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._lazy = bool(lazy_mode)

    def _sparse_supported(self):
        return self._lazy

    def _sparse_update_param(self, p, sr, slots, lr, step):
        """lazy_mode Adam (reference: adam_op.h lazy_mode branch): moments
        and params update ONLY on touched rows; untouched rows keep stale
        moments — the documented lazy semantics for huge embeddings."""
        b1, b2, eps = self._beta1, self._beta2, self._eps
        rows = sr.rows
        g = sr.values.astype(slots["m"].dtype)
        m_r = b1 * slots["m"][rows] + (1 - b1) * g
        v_r = b2 * slots["v"][rows] + (1 - b2) * g * g
        step_f = jnp.asarray(step, jnp.float32)
        mhat = m_r / (1 - b1 ** step_f)
        vhat = v_r / (1 - b2 ** step_f)
        upd = lr * mhat / (jnp.sqrt(vhat) + eps)
        return (p.at[rows].add(-upd.astype(p.dtype)),
                {"m": slots["m"].at[rows].set(m_r),
                 "v": slots["v"].at[rows].set(v_r)})

    def init_slots(self, p):
        dt = jnp.float32 if p.dtype in (jnp.bfloat16, jnp.float16) else p.dtype
        return {"m": jnp.zeros_like(p, dtype=dt),
                "v": jnp.zeros_like(p, dtype=dt)}

    def update_param(self, p, g, slots, lr, step):
        b1, b2, eps = self._beta1, self._beta2, self._eps
        g = g.astype(slots["m"].dtype)
        m = b1 * slots["m"] + (1 - b1) * g
        v = b2 * slots["v"] + (1 - b2) * g * g
        # bias correction with traced-friendly power
        step_f = jnp.asarray(step, jnp.float32)
        mhat = m / (1 - b1 ** step_f)
        vhat = v / (1 - b2 ** step_f)
        upd = lr * mhat / (jnp.sqrt(vhat) + eps)
        return (p - upd.astype(p.dtype)), {"m": m, "v": v}


class AdamW(Adam):
    """Decoupled weight decay (reference: adamw — python/paddle/optimizer/
    adamw.py; decay applied directly to the param, not the grad)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, lazy_mode, multi_precision, name)
        self._coeff = (weight_decay.coeff
                       if isinstance(weight_decay, L2Decay)
                       else float(weight_decay))
        self._apply_decay_param_fun = apply_decay_param_fun
        self._lr_ratio = lr_ratio

    def _decoupled(self):
        return True

    def _param_lr_ratio(self, param):
        if self._lr_ratio is None or param is None:
            return 1.0
        return float(self._lr_ratio(param))

    def _decoupled_decay(self, p, lr, param_name=None):
        fn = self._apply_decay_param_fun
        if fn is not None and param_name is not None and not fn(param_name):
            return p
        return p - lr * self._coeff * p


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def init_slots(self, p):
        return {"m": jnp.zeros_like(p), "inf": jnp.zeros_like(p)}

    def update_param(self, p, g, slots, lr, step):
        b1, b2, eps = self._beta1, self._beta2, self._eps
        m = b1 * slots["m"] + (1 - b1) * g
        u = jnp.maximum(b2 * slots["inf"], jnp.abs(g))
        step_f = jnp.asarray(step, jnp.float32)
        new_p = p - (lr / (1 - b1 ** step_f)) * m / (u + eps)
        return new_p, {"m": m, "inf": u}


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None,
                 initial_accumulator_value=0.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._eps = epsilon
        self._init_acc = initial_accumulator_value

    def init_slots(self, p):
        return {"moment": jnp.full_like(p, self._init_acc)}

    def update_param(self, p, g, slots, lr, step):
        mom = slots["moment"] + g * g
        return p - lr * g / (jnp.sqrt(mom) + self._eps), {"moment": mom}


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._eps, self._rho = epsilon, rho

    def init_slots(self, p):
        return {"avg_sq_grad": jnp.zeros_like(p),
                "avg_sq_update": jnp.zeros_like(p)}

    def update_param(self, p, g, slots, lr, step):
        rho, eps = self._rho, self._eps
        asg = rho * slots["avg_sq_grad"] + (1 - rho) * g * g
        upd = g * jnp.sqrt(slots["avg_sq_update"] + eps) / jnp.sqrt(asg + eps)
        asu = rho * slots["avg_sq_update"] + (1 - rho) * upd * upd
        return p - lr * upd, {"avg_sq_grad": asg, "avg_sq_update": asu}


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._rho, self._eps = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def init_slots(self, p):
        s = {"mean_square": jnp.zeros_like(p),
             "momentum": jnp.zeros_like(p)}
        if self._centered:
            s["mean_grad"] = jnp.zeros_like(p)
        return s

    def update_param(self, p, g, slots, lr, step):
        rho, eps = self._rho, self._eps
        ms = rho * slots["mean_square"] + (1 - rho) * g * g
        out = dict(slots, mean_square=ms)
        if self._centered:
            mg = rho * slots["mean_grad"] + (1 - rho) * g
            denom = jnp.sqrt(ms - mg * mg + eps)
            out["mean_grad"] = mg
        else:
            denom = jnp.sqrt(ms + eps)
        mom = self._momentum * slots["momentum"] + lr * g / denom
        out["momentum"] = mom
        return p - mom, out


class Lamb(Optimizer):
    """reference: operators/optimizers/lamb_op.h (large-batch)."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, parameters=None,
                 grad_clip=None, exclude_from_weight_decay_fn=None,
                 name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._wd = lamb_weight_decay
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def init_slots(self, p):
        return {"m": jnp.zeros_like(p), "v": jnp.zeros_like(p)}

    def update_param(self, p, g, slots, lr, step):
        b1, b2, eps = self._beta1, self._beta2, self._eps
        m = b1 * slots["m"] + (1 - b1) * g
        v = b2 * slots["v"] + (1 - b2) * g * g
        step_f = jnp.asarray(step, jnp.float32)
        mhat = m / (1 - b1 ** step_f)
        vhat = v / (1 - b2 ** step_f)
        r = mhat / (jnp.sqrt(vhat) + eps) + self._wd * p
        w_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
        trust = jnp.where(
            (w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return p - lr * trust * r, {"m": m, "v": v}


class LarsMomentum(Optimizer):
    """reference: operators/optimizers/lars_momentum_op.cc."""

    def __init__(self, learning_rate=0.001, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, parameters=None, grad_clip=None,
                 epsilon=0, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._lars_wd = lars_weight_decay
        self._eps = epsilon

    def init_slots(self, p):
        return {"velocity": jnp.zeros_like(p)}

    def update_param(self, p, g, slots, lr, step):
        w_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
        local_lr = jnp.where(
            (w_norm > 0) & (g_norm > 0),
            lr * self._lars_coeff * w_norm
            / (g_norm + self._lars_wd * w_norm + self._eps), lr)
        v = (self._momentum * slots["velocity"]
             + local_lr * (g + self._lars_wd * p))
        return p - v, {"velocity": v}


class Ftrl(Optimizer):
    """reference: operators/optimizers/ftrl_op.h (FTRL-Proximal,
    McMahan et al.; linear/squared accumulators, soft-threshold on the
    linear term).  ``lr_power`` follows the reference's sign convention
    (-0.5 means accum^0.5 in the denominators)."""

    def __init__(self, learning_rate=0.001, l1=0.0, l2=0.0,
                 lr_power=-0.5, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay,
                         grad_clip, name)
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def init_slots(self, p):
        return {"squared": jnp.zeros_like(p), "linear": jnp.zeros_like(p)}

    def update_param(self, p, g, slots, lr, step):
        sq, lin = slots["squared"], slots["linear"]
        new_sq = sq + g * g
        if self._lr_power == -0.5:
            sigma = (jnp.sqrt(new_sq) - jnp.sqrt(sq)) / lr
            denom = jnp.sqrt(new_sq) / lr
        else:
            sigma = (new_sq ** -self._lr_power
                     - sq ** -self._lr_power) / lr
            denom = new_sq ** -self._lr_power / lr
        new_lin = lin + g - sigma * p
        x = self._l1 * jnp.sign(new_lin) - new_lin
        y = denom + 2.0 * self._l2
        new_p = jnp.where(jnp.abs(new_lin) > self._l1, x / y,
                          jnp.zeros_like(p))
        return new_p, {"squared": new_sq, "linear": new_lin}


class Dpsgd(Optimizer):
    """reference: operators/optimizers/dpsgd_op.h — differentially
    private SGD: whole-gradient L2 clip to ``clip`` plus one shared
    Gaussian noise draw scaled by 1/batch_size.

    Divergence (documented): the reference seeds from time() when
    seed==0, which cannot exist inside a compiled step — seed=0 here is
    simply the literal seed, with the step index folded in so every
    step draws fresh noise."""

    def __init__(self, learning_rate=0.001, clip=10.0, batch_size=16.0,
                 sigma=1.0, seed=0, parameters=None, name=None):
        super().__init__(learning_rate, parameters, None, None, name)
        self._clip, self._batch = clip, batch_size
        self._sigma, self._seed = sigma, seed
        self._next_noise_id = 0

    def init_slots(self, p):
        # per-parameter noise id: the Gaussian-mechanism analysis needs
        # INDEPENDENT noise per tensor — a (seed, step)-only key would
        # hand every parameter the same draw
        nid = self._next_noise_id
        self._next_noise_id += 1
        return {"noise_id": jnp.asarray(nid, jnp.int32)}

    def update_param(self, p, g, slots, lr, step):
        norm = jnp.sqrt(jnp.sum(g.astype(jnp.float32) ** 2))
        scale = jnp.where(norm > self._clip, norm / self._clip, 1.0)
        key = jax.random.fold_in(jax.random.PRNGKey(self._seed),
                                 jnp.asarray(step, jnp.int32))
        key = jax.random.fold_in(key, slots["noise_id"])
        noise = self._sigma * jax.random.normal(key, (), jnp.float32)
        upd = g / scale.astype(g.dtype) + (noise / self._batch).astype(
            g.dtype)
        return p - lr * upd, {"noise_id": slots["noise_id"]}


class ProximalGD(Optimizer):
    """reference: operators/optimizers/proximal_gd_op.h — plain GD step
    followed by the L1 soft-threshold / L2 shrink proximal map."""

    def __init__(self, learning_rate=0.001, l1=0.0, l2=0.0,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay,
                         grad_clip, name)
        self._l1, self._l2 = l1, l2

    def init_slots(self, p):
        return {}

    def _prox(self, prox_param, lr):
        if self._l1 > 0:
            return (jnp.sign(prox_param)
                    * jnp.maximum(jnp.abs(prox_param) - lr * self._l1, 0.0)
                    / (1.0 + lr * self._l2))
        return prox_param / (1.0 + lr * self._l2)

    def update_param(self, p, g, slots, lr, step):
        return self._prox(p - lr * g, lr), slots


class ProximalAdagrad(ProximalGD):
    """reference: operators/optimizers/proximal_adagrad_op.h — Adagrad
    step (accumulated g^2 scaling) followed by the same proximal map.

    Divergence (documented): the reference divides by sqrt(moment) with
    no epsilon, so an element whose accumulated g^2 is still zero (dead
    unit, untouched row) becomes 0/0 = NaN and is destroyed; here a
    zero accumulator takes a zero step instead."""

    def init_slots(self, p):
        return {"moment": jnp.zeros_like(p)}

    def update_param(self, p, g, slots, lr, step):
        mom = slots["moment"] + g * g
        safe = jnp.where(mom > 0, mom, 1.0)
        step_v = jnp.where(mom > 0, lr * g / jnp.sqrt(safe), 0.0)
        return self._prox(p - step_v, lr), {"moment": mom}


class DecayedAdagrad(Optimizer):
    """reference: operators/optimizers/decayed_adagrad_op.h — Adagrad
    with an exponentially decayed accumulator."""

    def __init__(self, learning_rate=0.001, decay=0.95, epsilon=1e-6,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay,
                         grad_clip, name)
        self._decay, self._eps = decay, epsilon

    def init_slots(self, p):
        return {"moment": jnp.zeros_like(p)}

    def update_param(self, p, g, slots, lr, step):
        mom = self._decay * slots["moment"] + (1 - self._decay) * g * g
        return p - lr * g / (jnp.sqrt(mom) + self._eps), {"moment": mom}
