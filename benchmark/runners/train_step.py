"""Runner for ``jit.TrainStep``: Layer -> ``amp.decorate`` O2 ->
``TrainStep(donate=True)`` (copy of ``chip_smoke.py::phase_train``'s
construction).  One object is built, checked and timed.
"""
import jax

import check

EXECUTABLE = "jit_step_fn"


def build(cell, cfg, model_mod, theta0, mix):
    """``theta0``: reference leaf name -> float32 array.  Returns the
    state the other functions take."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer.clip import ClipGradByGlobalNorm

    opt_cfg = cell["optimizer"]
    if opt_cfg["name"] != "adamw":
        raise ValueError(f"train_step runner builds AdamW, cell asks for "
                         f"{opt_cfg['name']!r}")
    paddle.seed(0)      # the program's own init is overwritten below
    model, loss_fn = model_mod.build(cfg, cell["model_args"])
    opt = optimizer.AdamW(
        learning_rate=opt_cfg["lr"], beta1=opt_cfg["beta1"],
        beta2=opt_cfg["beta2"], epsilon=opt_cfg["eps"],
        parameters=model.parameters(),
        weight_decay=opt_cfg["weight_decay"],
        grad_clip=ClipGradByGlobalNorm(opt_cfg["clip_global_norm"]),
        multi_precision=cell["dtype"] != "float32")
    if cell["dtype"] != "float32":
        model, opt = amp.decorate(model, opt, level="O2",
                                  dtype=cell["dtype"])
    names = model_mod.param_map(cfg, cell["model_args"])
    leaves = {}
    for pname, p in model.named_parameters():
        key = check.key_of(*names[pname])
        want = check.take(theta0, key)
        if tuple(p.data.shape) != tuple(want.shape):
            raise ValueError(f"{pname}: program has {tuple(p.data.shape)}, "
                             f"reference {key} has {tuple(want.shape)}")
        p.data = want.astype(p.data.dtype)
        leaves[id(p)] = key
    if sorted(leaves.values()) != sorted(check.expanded_keys(theta0)):
        raise ValueError("program and reference disagree on the leaves")
    step = TrainStep(model, loss_fn, opt, n_inputs=1, donate=True)
    return {"step": step,
            "leaf_names": [leaves[id(p)] for p in step._params]}


def feed(state, ids, labels):
    """Put one host batch on the device."""
    return jax.device_put((ids, labels))


def dispatch(state, fed):
    """Dispatch one step; returns the loss on the device without waiting
    for it."""
    return state["step"](*fed).data


def moments(state):
    """leaf -> Adam's first moment."""
    return {n: s["m"] for n, s in zip(state["leaf_names"],
                                      state["step"]._opt_state)}


def params_f32(state):
    """leaf -> the float32 parameter the optimizer holds (the master copy
    under O2, the parameter itself in float32)."""
    step = state["step"]
    return {n: s.get("master", p.data) for n, s, p in zip(
        state["leaf_names"], step._opt_state, step._params)}


def close(state):
    state.clear()
