"""Nemotron-3-Nano's three kinds of block as the program builds them
(benchmark/models/nemotron_h.py over ``nn.Mamba2Mixer``, ``nn.MoELayer``
with ``relu2`` experts, grouped-query ``F.scaled_dot_product_attention``)
against benchmark/reference/nemotron_h.py on seeded weights, at the
cell's rehearsal widths in float32; the expert form on the one expert
layer; a chip's share of an ``E`` layer; the whole step through
``TrainStep`` (the harness's rehearsal)."""
import argparse
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

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCH)
import run as harness  # noqa: E402

CELL = "nemotron_3_nano_30b_a3b.train_bf16_b2_s8192"


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def parts():
    """The rehearsal's model (pattern MEM*E at tiny widths, float32) with
    seeded weights laid into it, and the same weights as the reference's
    leaves."""
    import check
    cell, cfg, mix, model_mod, ref, _ = harness.load_parts(CELL,
                                                           rehearse=True)
    _, theta0 = harness.seeded_inputs({**cell, "dtype": "float32"}, cfg,
                                      mix, ref, seed=5)
    theta = theta0()
    paddle.seed(0)
    model, _ = model_mod.build(cfg, cell["model_args"])
    names = model_mod.param_map(cfg, cell["model_args"])
    for pname, p in model.named_parameters():
        p.data = check.take(theta, check.key_of(*names[pname]))
    return cfg, ref, model, theta


def _close(got, want, what, tol=2e-4):
    scale = max(float(jnp.max(jnp.abs(want))), 1e-12)
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * scale,
                               err_msg=what)


# Float32 at precision "highest" on both sides; the same sums in another
# order (the chunked scan against the recurrence, grouped products
# against one expert at a time, XLA's attention over all queries against
# 512 at a time): differences of 1e-6 to 5e-5 of the largest element were
# read (PR 39); 2e-4 leaves four times that, far under what a missing
# term, a dropped tap or a mis-grouped head gives (order 1e-1).
@pytest.mark.parametrize("index", [0, 1, 3], ids=["M", "E", "attention"])
def test_a_block_is_the_references(parts, index):
    cfg, ref, model, theta = parts
    kind, nth = ref.blocks_of(cfg)[index]
    blk = model.blocks[index]
    assert blk.kind == kind
    leaves = ref._of_kind(theta, kind, nth)
    x = 0.5 * jax.random.normal(jax.random.key(index), (2, 48, 64))
    ct = jnp.cos(jnp.arange(64.0))
    pnames = [n for n, _ in blk.named_parameters()]
    from models import nemotron_h as model_mod  # the leaves' names
    to_leaf = model_mod._LEAVES[kind]

    def program(x, *values):
        for (_, p), v in zip(blk.named_parameters(), values):
            p.data = v
        return blk(paddle.to_tensor(x)).data

    def reference(x, *values):
        p = {to_leaf[n]: v for n, v in zip(pnames, values)}
        return ref.block(kind, x, p, cfg, ref.held_ids(cfg), lambda a: a)[0]

    values = [leaves[to_leaf[n]] for n in pnames]
    wrt = tuple(range(len(values) + 1))
    got, got_g = jax.value_and_grad(
        lambda *a: jnp.sum(program(*a) * ct), wrt)(x, *values)
    want, want_g = jax.value_and_grad(
        lambda *a: jnp.sum(reference(*a) * ct), wrt)(x, *values)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    for name, g, w in zip(["x"] + pnames, got_g, want_g):
        if "router" in name:        # held still: no gradient on either side
            assert not np.any(np.asarray(w)) and not np.any(np.asarray(g))
            continue
        _close(g, w, name)


def test_the_sixteen_shares_add_up_to_the_uncut_layer(parts):
    """Four holders of four of the rehearsal's 16 experts (the cell:
    sixteen of eight of 128): their routed parts and ONE shared expert are
    what the reference gives for the whole layer."""
    cfg, ref, _, theta = parts
    E, per = cfg["published"]["n_routed_experts"], cfg["n_routed_experts"]
    H, Fw = cfg["hidden_size"], cfg["moe_intermediate_size"]
    ks = jax.random.split(jax.random.key(3), 3)
    p = dict(ref._of_kind(theta, "e", 0))
    p["experts.up"] = 0.3 * jax.random.normal(ks[0], (E, H, Fw))
    p["experts.down"] = 0.3 * jax.random.normal(ks[1], (E, Fw, H))
    b = jax.random.normal(ks[2], (2, 24, H))
    ident = lambda a: a  # noqa: E731
    whole = jnp.stack([ref.routed(row, p, cfg, tuple(range(E)), ident)[0]
                       + ref.shared(row, p, ident) for row in b])

    def share(first, shared):
        return moe_ops.moe_forward(
            b, p["router.w"], None, p["experts.up"][first:first + per],
            p["experts.down"][first:first + per],
            top_k=cfg["num_experts_per_tok"], first=first,
            scoring="sigmoid", router_bias=p["router.bias"],
            scaling=cfg["routed_scaling_factor"],
            shared=(None, p["shared.up.w"], p["shared.down.w"])
            if shared else None, train_router=False)

    holders = range(0, E, per)
    assert len(holders) * per == E
    once = jnp.stack([ref.shared(row, p, ident) for row in b])
    routed = sum(share(first, False) for first in holders)
    np.testing.assert_allclose(routed + once, whole, rtol=2e-5, atol=2e-5)
    with_shared = sum(share(first, True) for first in holders)
    np.testing.assert_allclose(with_shared - (len(holders) - 1) * once,
                               whole, rtol=2e-5, atol=5e-5)


def test_relu2_experts_have_no_gate_leaf_and_run_no_gate_product():
    monitor.stat_reset()
    layer = nn.MoELayer(32, 16, 16, 4, held=range(4, 8), scoring="sigmoid",
                        selection_bias=True, shared_width=32,
                        expert_form="relu2")
    assert {n for n, _ in layer.named_parameters()} == {
        "router_weight", "router_bias", "w_up", "w_down", "shared_up",
        "shared_down"}
    assert layer.w_gate is None and layer.shared_gate is None
    x = paddle.randn([2, 8, 32])
    out = layer(x)
    assert tuple(out.shape) == (2, 8, 32) and out.dtype == paddle.float32
    assert monitor.all_stats()["moe.gateless_experts"] == 1
    # two grouped products an expert where the SwiGLU runs three
    def products(layer):
        text = str(jax.make_jaxpr(lambda a: layer(paddle.to_tensor(a)).data)(
            x.data))
        return text.count("ragged_dot")
    gated = nn.MoELayer(32, 16, 16, 4, held=range(4, 8))
    two, three = products(layer), products(gated)
    assert two and two * 3 == three * 2, (two, three)
    with pytest.raises(ValueError, match="expert_form"):
        nn.MoELayer(32, 16, 16, 4, expert_form="gelu")
    with pytest.raises(ValueError, match="shared expert"):
        F.moe_experts(x, layer.router_weight, None, layer.w_up, layer.w_down,
                      4, 4, shared=(layer.w_up[0], layer.shared_up,
                                    layer.shared_down))


def test_swiglu_experts_are_the_parents_bit_for_bit():
    """The expert layer's SwiGLU path with the form as an argument gives
    the bits of the parent's expressions (ops/moe.py at PR 38:
    ``silu(g.astype(f32)) * u.astype(f32)`` over the grouped products,
    ``silu(x Wg) * (x Wu)`` in the shared expert), in float32 and in
    bfloat16."""
    ks = jax.random.split(jax.random.key(4), 5)
    for dtype in (jnp.float32, jnp.bfloat16):
        g, u = (jax.random.normal(k, (24, 16)).astype(dtype) for k in ks[:2])
        np.testing.assert_array_equal(
            moe_ops._hidden(g, u),
            jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32))
        x = jax.random.normal(ks[2], (24, 32)).astype(dtype)
        wg, wu = (0.2 * jax.random.normal(k, (32, 16)).astype(dtype)
                  for k in ks[3:])
        wd = wu.T

        def dot(a, w):
            return jnp.dot(a, w, preferred_element_type=jnp.float32)

        parent = dot((jax.nn.silu(dot(x, wg)) * dot(x, wu)).astype(dtype), wd)
        np.testing.assert_array_equal(
            moe_ops._shared_expert(x, wg, wu, wd), parent)
    # and the form without a gate is not the gated one with a gate of ones
    assert not np.allclose(moe_ops._hidden(None, u), moe_ops._hidden(g, u))


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "swiglu"])
def test_a_width_padded_with_zeros_changes_nothing(gated, monkeypatch):
    """An expert width that is no multiple of 256 runs padded with hidden
    units of zeros (ops/moe.py: XLA's grouped-matmul kernel tiles such a
    width badly): the layer's value and every gradient are what the
    unpadded products give, and a width that is such a multiple comes back
    as it is."""
    ks = jax.random.split(jax.random.key(9), 6)
    H, Fw, held, E = 32, 24, 4, 16
    x = jax.random.normal(ks[0], (2, 12, H))
    router = jax.random.normal(ks[1], (H, E))
    wg = 0.3 * jax.random.normal(ks[2], (held, H, Fw)) if gated else None
    wu = 0.3 * jax.random.normal(ks[3], (held, H, Fw))
    wd = 0.3 * jax.random.normal(ks[4], (held, Fw, H))
    ct = jax.random.normal(ks[5], (2, 12, H))

    def layer(x, wg, wu, wd):
        return jnp.sum(ct * moe_ops.moe_forward(x, router, wg, wu, wd,
                                                top_k=8, first=0))

    wrt = (0, 1, 2, 3) if gated else (0, 2, 3)
    assert moe_ops._padded_width(wg, wu, wd)[1].shape == (held, H, 256)
    got, got_g = jax.value_and_grad(layer, wrt)(x, wg, wu, wd)
    monkeypatch.setattr(moe_ops, "_WIDTH_MULTIPLE", 1)
    assert moe_ops._padded_width(wg, wu, wd)[1] is wu
    want, want_g = jax.value_and_grad(layer, wrt)(x, wg, wu, wd)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for g, w in zip(got_g, want_g):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
    monkeypatch.undo()
    wide = jnp.zeros((held, H, 768))      # keye_vl2's and joyai's width
    assert moe_ops._padded_width(None, wide, wide.swapaxes(1, 2))[1] is wide


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_grouped_key_value_heads_are_the_repeated_ones(causal):
    """Two key/value heads under eight query heads through sdpa's XLA
    path: the value and every gradient of ``jnp.repeat`` to eight heads
    (whose transpose sums a group's parts)."""
    ks = jax.random.split(jax.random.key(6), 4)
    q = jax.random.normal(ks[0], (2, 40, 8, 16))
    k, v = (jax.random.normal(kk, (2, 40, 2, 16)) for kk in ks[1:3])
    ct = jax.random.normal(ks[3], (2, 40, 8, 16))

    def sdpa(q, k, v):
        return F.scaled_dot_product_attention(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
            is_causal=causal).data

    def repeated(q, k, v):
        return sdpa(q, jnp.repeat(k, 4, axis=2), jnp.repeat(v, 4, axis=2))

    got, got_g = jax.value_and_grad(
        lambda *a: jnp.sum(sdpa(*a) * ct), (0, 1, 2))(q, k, v)
    want, want_g = jax.value_and_grad(
        lambda *a: jnp.sum(repeated(*a) * ct), (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for g, w in zip(got_g, want_g):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="do not divide"):
        sdpa(q, k[:, :, :1].repeat(3, axis=2), v[:, :, :1].repeat(3, axis=2))


def test_grouped_heads_through_the_flash_kernels(kernels_on):
    """The kernels' index maps (interpret mode): 4 query heads on 2
    key/value heads, forward and gradients against the oracle that
    repeats them; the gate says which head counts it takes."""
    from paddle_tpu.ops.pallas import flash_attention as fa_pkg  # noqa: F401
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    ks = jax.random.split(jax.random.key(8), 4)
    q = jax.random.normal(ks[0], (1, 512, 4, 64))
    k, v = (jax.random.normal(kk, (1, 512, 2, 64)) for kk in ks[1:3])
    ct = jax.random.normal(ks[3], (1, 512, 4, 64))
    assert fa.flash_attention_supported(q.shape, k.shape, jnp.float32)
    assert not fa.flash_attention_supported(q.shape, (1, 512, 3, 64),
                                            jnp.float32)
    assert not fa.flash_attention_supported(q.shape, k.shape, jnp.float32,
                                            shared_key_dim=64)

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, block_q=128,
                                  block_k=128)

    got, got_g = jax.value_and_grad(
        lambda *a: jnp.sum(kernel(*a) * ct), (0, 1, 2))(q, k, v)
    want, want_g = jax.value_and_grad(
        lambda *a: jnp.sum(fa.mha_reference(*a, causal=True) * ct),
        (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for g, w in zip(got_g, want_g):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


def test_the_whole_step_follows_the_reference_through_trainstep():
    """``run.py --rehearse``: the model through ``TrainStep``, ``amp`` O2,
    AdamW and per-block recompute over two steps against the float32
    reference, under the rehearsal's limits."""
    args = argparse.Namespace(workload=CELL, seed=7, seconds=0.5, trace=0,
                              keep_trace=None)
    assert harness.run_cell(args, rehearse=True)["correct"] is True
