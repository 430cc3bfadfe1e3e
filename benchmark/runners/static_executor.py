"""Runner for the static path: a ``paddle.static`` Program through
``Executor`` with default flags (copy of ``chip_smoke.py::phase_static``'s
construction).  One Executor and one Program are built, checked and
timed.
"""
import jax

import check

EXECUTABLE = "jit_train_fn"


def build(cell, cfg, model_mod, theta0, mix):
    """``theta0``: reference leaf name -> float32 array."""
    import paddle_tpu as paddle

    if cell["dtype"] != "float32":
        raise ValueError("the static runner builds a float32 program")
    paddle.enable_static()
    paddle.seed(0)      # the program's own init is overwritten below
    prog, loss, leaves, constant_feeds = model_mod.build(
        cfg, cell["model_args"], mix["batch"], mix["seq"],
        cell["optimizer"])
    if sorted(leaves) != sorted(check.expanded_keys(theta0)):
        raise ValueError("program and reference disagree on the leaves")
    for leaf, p in leaves.items():
        want = check.take(theta0, leaf)
        if tuple(p.shape) != tuple(want.shape):
            raise ValueError(f"{leaf}: program has {tuple(p.shape)}, "
                             f"reference {tuple(want.shape)}")
        p.data = want
    return {"exe": paddle.static.Executor(), "prog": prog, "loss": loss,
            "constant_feeds": jax.device_put(constant_feeds),
            "leaf_of": {id(p): leaf for leaf, p in leaves.items()}}


def feed(state, ids, labels):
    """Put one host batch on the device."""
    return jax.device_put((ids, labels))


def dispatch(state, fed):
    """Dispatch one step; returns the loss on the device without waiting
    for it."""
    out = state["exe"].run(state["prog"],
                           feed={"ids": fed[0], "labels": fed[1],
                                 **state["constant_feeds"]},
                           fetch_list=[state["loss"]], return_numpy=False)
    return out[0].data


def _exec_state(state):
    return state["exe"]._states[state["prog"]._serial]


def moments(state):
    """leaf -> Adam's first moment."""
    st = _exec_state(state)
    return {state["leaf_of"][id(st.params[i])]: s["m"]
            for i, s in zip(st.t_idx, st.opt_state)}


def params_f32(state):
    """leaf -> the float32 parameter as the Executor holds it."""
    st = _exec_state(state)
    return {state["leaf_of"][id(p)]: a
            for p, a in zip(st.params, st.p_arrays)}


def close(state):
    import paddle_tpu as paddle
    state["exe"].close()
    state.clear()
    paddle.disable_static()
    paddle.static.reset_default_programs()
