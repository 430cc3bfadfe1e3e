"""The routed expert layer (``nn.MoELayer``, ``F.moe_experts``,
ops/moe.py) against the plain reference of benchmark/reference/keye_vl2.py:
forward and gradients, a chip's share of the experts, the shares adding
up to the uncut layer, and imbalance without a dropped token.  Every
comparison runs in both forms of the sums over a token's slots: XLA's
gather by ``pos`` and the ``moe_combine`` kernel over the rows in token
order (interpret mode), at a width and a sequence the kernel takes: two
token blocks a chunk."""
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

H, FF, E, K, SEQ = 128, 32, 16, 4, 256
CFG = {"num_experts_per_tok": K}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(params=["xla", "kernel"])
def form(request):
    """Which form a chunk with a small buffer takes; the value is a check
    that the calls since took it and not the other (``took(False)``: that
    no small buffer was traced at all)."""
    if request.param == "kernel":
        request.getfixturevalue("kernels_on")
    monitor.stat_reset()
    stat = {"xla": "moe_combine.xla_path",
            "kernel": "pallas.selected.moe_combine"}

    def took(it=True):
        stats = monitor.all_stats()
        other = stat["xla" if request.param == "kernel" else "kernel"]
        assert other not in stats, stats
        assert (stat[request.param] in stats) == it, stats

    return took


def _weights(seed, held=E):
    ks = jax.random.split(jax.random.key(seed), 5)
    return {"router.w": jax.random.normal(ks[0], (H, E)),
            "experts.gate": 0.3 * jax.random.normal(ks[1], (E, H, FF)),
            "experts.up": 0.3 * jax.random.normal(ks[2], (E, H, FF)),
            "experts.down": 0.3 * jax.random.normal(ks[3], (E, FF, H)),
            # half the scale at four times the width of 32: the router's
            # logits and the experts' hidden units as wide as they were
            "x": 0.5 * jax.random.normal(ks[4], (2, SEQ, H))}


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
def test_forward_and_gradients_match_the_reference(held, form):
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
    # every expert held: one buffer, the full one, and no choice to make
    form(len(held) < E)


def test_the_shares_add_up_to_the_uncut_layer(form):
    """Four chips of four experts each: their parts, summed, are what the
    reference gives for the whole 16-expert layer."""
    w = _weights(1)
    whole = _reference(w, range(E))
    parts = sum(_program(_slice(w, range(lo, lo + 4)), range(lo, lo + 4))
                for lo in range(0, E, 4))
    np.testing.assert_allclose(parts, whole, rtol=2e-5, atol=2e-5)
    form()


@pytest.mark.parametrize("case", ["all_to_one", "none_held"])
def test_imbalance_drops_nothing_and_stays_finite(case, form):
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
    form()


@pytest.mark.parametrize("case", ["spread", "two_a_token", "four_a_token",
                                  "all_four_of_half", "gateless",
                                  "router_still"])
def test_each_buffer_gives_what_the_full_one_gives(case, form):
    """``_chunk`` takes the small buffer where the chunk's held
    assignments fit it and the full one where they do not; either way the
    result and the five gradients are the full buffer's, which sums in
    XLA's form always.  ``all_four_of_half``: the first token block's
    tokens hold all four of their slots and the second block's none, so
    that block owns no row; ``gateless``: experts of the form without a
    gate; ``router_still``: the gates are constants of the backward
    pass."""
    w = _weights(3)
    held = range(4, 8)
    if case in ("two_a_token", "four_a_token"):
        # every token to experts 4..5 or 4..7
        top = 6 if case == "two_a_token" else 8
        w = {**w, "router.w": jnp.zeros((H, E)).at[:, 4:top].set(1.0),
             "x": jnp.abs(w["x"]) + 0.1}
    elif case == "all_four_of_half":
        # the router reads one feature: up for the first half of the
        # row (experts 4..7), down for the second (0..3)
        w = {**w, "router.w": jnp.zeros((H, E)).at[0, 4:8].set(10.0)
             .at[0, :4].set(-10.0),
             "x": w["x"].at[:, :, 0].set(
                 jnp.where(jnp.arange(SEQ) < SEQ // 2, 1.0, -1.0))}
    ws = _slice(w, held)
    x = ws["x"][0]
    n = x.shape[0]
    gates, ids = moe_ops.moe_route(x, ws["router.w"], K)
    local = jnp.where((ids >= 4) & (ids < 8), ids - 4, 4)
    small = moe_ops._small_buffer(n, K, len(held), E)
    assert small == int(2.25 * n) < n * K
    load = jnp.sum(local < 4, 1)
    assert (int(jnp.sum(load)) > small) == (case == "four_a_token")
    if case == "two_a_token":
        assert int(jnp.sum(load)) == 2 * n
    if case == "all_four_of_half":
        assert load.tolist() == [K] * (n // 2) + [0] * (n // 2)
    w_gate = None if case == "gateless" else ws["experts.gate"]

    def run(fn):
        def loss(x, g, wg, wu, wd):
            if case == "router_still":
                g = jax.lax.stop_gradient(g)
            return jnp.sum(fn(x, g, local, wg, wu, wd)
                           * jnp.cos(jnp.arange(H)))
        return jax.value_and_grad(
            loss, argnums=(0, 1, 3, 4) if w_gate is None else range(5))(
                x, gates, w_gate, ws["experts.up"], ws["experts.down"])

    got, got_grads = run(functools.partial(moe_ops._chunk, small))
    form()
    want, want_grads = run(moe_ops.moe_experts)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for g, wnt in zip(got_grads, want_grads, strict=True):
        np.testing.assert_allclose(g, wnt, rtol=1e-5, atol=5e-6)
    assert np.any(np.asarray(got_grads[1])) == (case != "router_still")
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
    # XLA's form, the CPU's: a token-major pass gathers a row a slot
    assert (stats["moe.small_buffer_rows"], stats["moe.full_buffer_rows"],
            stats["moe.token_major_rows"]) == (int(2.25 * 8), 8 * K, 8 * K)
    again = F.moe_experts(x, layer.router_weight, layer.w_gate, layer.w_up,
                          layer.w_down, K, first_expert=4)
    np.testing.assert_allclose(out.numpy(), again.numpy())
    with pytest.raises(ValueError):
        nn.MoELayer(H, FF, E, K, held=range(14, 18))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("weighted", [True, False], ids=["gated", "plain"])
@pytest.mark.parametrize("rows,held,tokens", [
    (576, 300, "spread"),       # a tile's worth of places past the rows
    (512, 512, "spread"),       # a buffer filled to its last row
    (512, 0, "spread"),         # a chunk that holds nothing
    (768, 700, "three"),        # every row on three tokens: no bound on
                                # how many rows a token has
    (1024, 1000, "last_two"),   # the first token block owns no row
], ids=["part", "full", "none", "three_tokens", "last_two_tokens"])
def test_the_kernel_sums_each_tokens_rows(rows, held, tokens, weighted, dtype):
    """``moe_combine`` alone, in interpret mode, against a scatter-add in
    float64: rows in token order, the places past the held ones marked
    with token n and filled with NaN, which the kernel must not read."""
    from paddle_tpu.ops.pallas import moe_combine as mc
    n, width = SEQ, H
    assert mc.moe_combine_supported(n, width, dtype)
    rng = np.random.default_rng(rows + held)
    padded = mc.padded_rows(rows)
    lo, hi = {"spread": (0, n), "three": (5, 8), "last_two": (n - 2, n)}[tokens]
    tok = np.concatenate([np.sort(rng.integers(lo, hi, held)),
                          np.full(padded - held, n)]).astype(np.int32)
    x = rng.standard_normal((padded, width)).astype(np.float32)
    x[held:] = np.nan
    x = jnp.asarray(x).astype(dtype)
    w = rng.uniform(0, 1, padded).astype(np.float32)
    got = mc.moe_combine(x, jnp.asarray(tok), jnp.asarray(w) if weighted
                         else None, n, jnp.float32 if weighted else dtype)
    assert got.dtype == (jnp.float32 if weighted else dtype)
    want = np.zeros((n, width))
    np.add.at(want, tok[:held], (w[:held, None] if weighted else 1.0)
              * np.asarray(x.astype(jnp.float32), np.float64)[:held])
    # gated: float32 to the last bits; plain: rounded to the rows' type
    tol = 1e-6 if weighted or dtype == jnp.float32 else 2 ** -8
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)), want,
                               rtol=tol, atol=tol * max(1.0, np.abs(want).max()))
