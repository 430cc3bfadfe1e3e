"""At tiny widths on the CPU: each cell's program, built in float32,
gives the plain reference's loss to 1e-6; a bfloat16 cast of the weights
breaks that tolerance; and the control (the reference one precision
lower, in the program's place) fails the rehearsal's limits."""
import copy
import os

import numpy as np
import pytest

import run as harness

CELLS = sorted(f[:-5] for f in os.listdir(
    os.path.join(harness.HERE, "workloads")))
# tighter than the 1e-5 one might pick: at these widths bfloat16 moves the
# loss at seeded weights by only 3e-6, and float32 agrees to 1e-7
TOL = 1e-6


def _parts(cell_name):
    cell, cfg, mix, model_mod, ref, runner = harness.load_parts(
        cell_name, rehearse=True)
    cell = copy.deepcopy(cell)
    cell["dtype"] = "float32"
    ring, theta0 = harness.seeded_inputs(cell, cfg, mix, ref, seed=11)
    return cell, cfg, mix, model_mod, ref, runner, ring, theta0


@pytest.mark.parametrize("cell_name", CELLS)
def test_program_forward_loss_equals_the_reference(cell_name):
    import jax
    import jax.numpy as jnp
    cell, cfg, mix, model_mod, ref, runner, ring, theta0 = _parts(cell_name)
    ids, labels = ring[0]
    with jax.default_matmul_precision("highest"):
        want = float(ref.loss(theta0(), jnp.asarray(ids),
                              jnp.asarray(labels), cfg, cell["model_args"]))
        # weights held in bfloat16, so that the forward pass runs in it
        cast = {n: a.astype(jnp.bfloat16) for n, a in theta0().items()}
        low = float(ref.loss(cast, jnp.asarray(ids), jnp.asarray(labels),
                             cfg, cell["model_args"]))
    state = runner.build(cell, cfg, model_mod, theta0(), mix)
    try:
        # the loss a step returns is the forward loss at the weights it
        # was given
        got = float(runner.dispatch(state, runner.feed(state, ids, labels)))
    finally:
        runner.close(state)
    assert abs(got - want) / want < TOL
    assert abs(low - want) / want > TOL


@pytest.mark.parametrize("cell_name", CELLS)
def test_the_control_fails_the_rehearsal_limits(cell_name):
    import check
    cell, cfg, mix, model_mod, ref, runner = harness.load_parts(
        cell_name, rehearse=True)
    ring, theta0 = harness.seeded_inputs(cell, cfg, mix, ref, seed=12)
    want = harness.follow_reference(check, ref, cell, cfg, mix, ring, theta0)
    low = harness.follow_reference(check, ref, cell, cfg, mix, ring, theta0,
                                   cell["check"]["control"])
    numbers = check.compare(low, want)
    assert cell["check"]["limits"], "the rehearsal has no limits"
    assert check.verdict(numbers, cell["check"]["limits"],
                         log=lambda m: None) is False
    same = check.compare(want, want)
    assert all(np.isclose(v[0], 0.0) for v in same.values())
