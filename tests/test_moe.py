"""The routed expert layer (``nn.MoELayer``, ``F.moe_experts``,
ops/moe.py) against the plain reference of benchmark/reference/keye_vl2.py:
forward and gradients, a chip's share of the experts, the shares adding
up to the uncut layer, and imbalance without a dropped token."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import nn
from paddle_tpu.ops import moe as moe_ops
from paddle_tpu.utils import monitor

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from reference import keye_vl2 as ref  # noqa: E402

H, FF, E, K = 32, 16, 16, 4
CFG = {"num_experts_per_tok": K}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _weights(seed, held=E):
    ks = jax.random.split(jax.random.key(seed), 5)
    return {"router.w": jax.random.normal(ks[0], (H, E)),
            "experts.gate": 0.3 * jax.random.normal(ks[1], (E, H, FF)),
            "experts.up": 0.3 * jax.random.normal(ks[2], (E, H, FF)),
            "experts.down": 0.3 * jax.random.normal(ks[3], (E, FF, H)),
            "x": jax.random.normal(ks[4], (2, 24, H))}


def _slice(w, held):
    lo, hi = held.start, held.stop
    return {n: (a[lo:hi] if n.startswith("experts.") else a)
            for n, a in w.items()}


def _program(w, held):
    return moe_ops.moe_forward(
        w["x"], w["router.w"], w["experts.gate"], w["experts.up"],
        w["experts.down"], top_k=K, first=held.start)


def _reference(w, held):
    rows = [ref.moe(x, w, CFG, tuple(held), lambda a: a)[0] for x in w["x"]]
    return jnp.stack(rows)


@pytest.mark.parametrize("held", [range(0, 16), range(4, 8), range(12, 16)],
                         ids=["all", "4to7", "12to15"])
def test_forward_and_gradients_match_the_reference(held):
    w = _slice(_weights(0), held)
    np.testing.assert_allclose(_program(w, held), _reference(w, held),
                               rtol=2e-5, atol=2e-5)

    def loss(fn, w):
        return jnp.sum(fn(w, held) * jnp.cos(jnp.arange(H)))

    got = jax.grad(lambda w: loss(_program, w))(w)
    want = jax.grad(lambda w: loss(_reference, w))(w)
    for n in want:
        np.testing.assert_allclose(got[n], want[n], rtol=2e-4, atol=2e-5,
                                   err_msg=n)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips of four experts each: their parts, summed, are what the
    reference gives for the whole 16-expert layer."""
    w = _weights(1)
    whole = _reference(w, range(E))
    parts = sum(_program(_slice(w, range(lo, lo + 4)), range(lo, lo + 4))
                for lo in range(0, E, 4))
    np.testing.assert_allclose(parts, whole, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", ["all_to_one", "none_held"])
def test_imbalance_drops_nothing_and_stays_finite(case):
    """Every token to the same four experts (one held expert gets all of
    them, the others none), and a share none of whose experts is chosen."""
    w = _weights(2)
    router = jnp.zeros((H, E)).at[:, 4:8].set(1.0)
    # positive inputs: the four favoured experts win for every token
    w = {**w, "router.w": router, "x": jnp.abs(w["x"]) + 0.1}
    held = range(4, 6) if case == "all_to_one" else range(8, 12)
    ws = _slice(w, held)
    out, grads = jax.value_and_grad(
        lambda w: jnp.sum(_program(w, held) ** 2))(ws)
    assert np.isfinite(out)
    assert all(np.all(np.isfinite(g)) for g in grads.values())
    np.testing.assert_allclose(_program(ws, held), _reference(ws, held),
                               rtol=2e-5, atol=2e-5)
    if case == "none_held":
        assert float(jnp.max(jnp.abs(_program(ws, held)))) == 0.0


@pytest.mark.parametrize("case", ["spread", "two_a_token", "four_a_token"])
def test_each_buffer_gives_what_the_full_one_gives(case):
    """``_chunk`` takes the small buffer where the chunk's held
    assignments fit it and the full one where they do not; either way the
    result and the gradients are the full buffer's."""
    w = _weights(3)
    held = range(4, 8)
    if case != "spread":            # every token to experts 4..5 or 4..7
        top = 6 if case == "two_a_token" else 8
        w = {**w, "router.w": jnp.zeros((H, E)).at[:, 4:top].set(1.0),
             "x": jnp.abs(w["x"]) + 0.1}
    ws = _slice(w, held)
    x = ws["x"][0]
    n = x.shape[0]
    gates, ids = moe_ops.moe_route(x, ws["router.w"], K)
    local = jnp.where((ids >= 4) & (ids < 8), ids - 4, 4)
    small = moe_ops._small_buffer(n, K, len(held), E)
    assert small == int(2.25 * n) < n * K
    load = int(jnp.sum(local < 4))
    assert (load > small) == (case == "four_a_token"), (load, small)
    if case == "two_a_token":
        assert load == 2 * n

    def run(fn):
        return jax.value_and_grad(
            lambda x, g, wg, wu, wd: jnp.sum(
                fn(x, g, local, wg, wu, wd) * jnp.cos(jnp.arange(H))),
            argnums=(0, 1, 2, 3, 4))(
                x, gates, ws["experts.gate"], ws["experts.up"],
                ws["experts.down"])

    got, got_grads = run(functools.partial(moe_ops._chunk, small))
    want, want_grads = run(moe_ops.moe_experts)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for g, wnt in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, wnt, rtol=1e-5, atol=1e-6)
    # most experts held: no buffer smaller than the full one, no branch
    assert moe_ops._small_buffer(n, K, 12, E) is None


def test_layer_and_counters():
    paddle.seed(0)
    monitor.stat_reset()
    layer = nn.MoELayer(H, FF, E, K, held=range(4, 8))
    assert layer.w_gate.shape == [4, H, FF]
    assert layer.router_weight.shape == [H, E]
    x = paddle.randn([2, 8, H])
    out = layer(x)
    assert out.shape == [2, 8, H] and out.dtype == paddle.float32
    stats = monitor.all_stats()
    assert (stats["moe.experts_held"], stats["moe.experts_total"],
            stats["moe.top_k"]) == (4, E, K)
    assert stats["moe.ragged_dot_path"] >= 1
    again = F.moe_experts(x, layer.router_weight, layer.w_gate, layer.w_up,
                          layer.w_down, K, first_expert=4)
    np.testing.assert_allclose(out.numpy(), again.numpy())
    with pytest.raises(ValueError):
        nn.MoELayer(H, FF, E, K, held=range(14, 18))
