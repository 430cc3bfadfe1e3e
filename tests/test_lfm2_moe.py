"""LFM2-24B-A2B as the program builds it (benchmark/models/lfm2_moe.py over
``nn.ShortConv``, ``nn.GroupedQueryAttention``, ``nn.GatedFFN``,
``nn.MoELayer``) against benchmark/reference/lfm2_moe.py on seeded weights
at the cell's rehearsal widths: the loss with every gradient leaf and one
AdamW step in float32, the whole step through ``TrainStep`` in bfloat16
O2 (the harness's rehearsal); the gated short convolution's kernels
(interpret mode) against the plain form, forward and every gradient; the
router's selection bias and the public epsilon of its gates; the chip's
share of an expert layer tied to the uncut model; and the convolution
and router calls of the cells that were there traced to the programs
they were."""
import argparse
import hashlib
import importlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import nn
from paddle_tpu.observability import scopes
from paddle_tpu.ops import moe
from paddle_tpu.ops.ssm import gated_short_conv as plain_short_conv
from paddle_tpu.utils import monitor

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCH)
import run as harness  # noqa: E402

CELL = "lfm2_24b_a2b.train_bf16_b4_s8192"
cc = importlib.import_module("paddle_tpu.ops.pallas.causal_conv")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def parts():
    """The rehearsal's cell (a dense conv layer, then an attention layer
    and three conv layers with experts: a whole period behind a dense
    layer, at tiny widths, rows of 64) in float32: its files, its seeded
    weights as the reference's leaves, and a batch."""
    cell, cfg, mix, model_mod, ref, runner = harness.load_parts(
        CELL, rehearse=True)
    cell = {**cell, "dtype": "float32"}
    ring, theta0 = harness.seeded_inputs(cell, cfg, mix, ref, seed=5)
    return cell, cfg, mix, model_mod, ref, runner, ring, theta0


@pytest.fixture(scope="module")
def reference(parts):
    """The reference's loss and gradients on the first batch, float32."""
    _, cfg, _, _, ref, _, ring, theta0 = parts
    ids, labels = (jnp.asarray(a) for a in ring[0])
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda theta: ref.loss(theta, ids, labels, cfg, {})))(theta0())


def _laid_in(model_mod, cfg, theta):
    """The program's model with the reference's leaves laid into it."""
    import check
    paddle.seed(0)
    model, loss_fn = model_mod.build(cfg, {})
    names = model_mod.param_map(cfg, {})
    for pname, p in model.named_parameters():
        p.data = check.take(theta, check.key_of(*names[pname]))
    return model, loss_fn, names


def _close(got, want, what, tol=2e-4):
    scale = max(float(jnp.max(jnp.abs(want))), 1e-12)
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * scale,
                               err_msg=what)


# ------------------------------------------- the model against the reference
def _loss_and_gradients(parts, reference, program_cfg=None):
    """-> (the program's loss and gradients keyed as the reference's
    leaves, the reference's) on the first batch, float32."""
    import check
    _, cfg, _, model_mod, _, _, ring, theta0 = parts
    theta = theta0()
    model, loss_fn, names = _laid_in(model_mod, program_cfg or cfg, theta)
    ids, labels = (jnp.asarray(a) for a in ring[0])
    params = list(model.named_parameters())

    def program(values):
        for (_, p), v in zip(params, values):
            p.data = v
        return loss_fn(model(paddle.to_tensor(ids)),
                       paddle.to_tensor(labels)).data

    got, grads = jax.jit(jax.value_and_grad(program))(
        [p.data for _, p in params])
    got_g = {check.key_of(*names[n]): g for (n, _), g in zip(params, grads)}
    want, want_g = reference
    return (got, got_g), (want, {k: check.take(want_g, k) for k in got_g})


def _worst(got_g, want_g):
    """The largest difference of a leaf's gradients over that leaf's
    largest element, over the leaves with a gradient."""
    return max(float(jnp.max(jnp.abs(got_g[k] - w)))
               / float(jnp.max(jnp.abs(w)))
               for k, w in want_g.items() if float(jnp.max(jnp.abs(w))) > 0)


# Float32 at precision "highest" on both sides; the same sums in another
# order (XLA's attention over all queries against 128 at a time, the
# grouped products against an expert at a time, a fused in-projection
# against its halves): 2e-4 of a leaf's largest element, granite's and
# trinity's limit, far under what a missing term gives
# (`test_each_part_matters`).
def test_the_loss_and_every_gradient_are_the_references(parts, reference):
    """The whole model and the chunked tied head in float32: the loss to
    1e-5 and every leaf's gradient to 2e-4 of its largest element.  The
    routers are held still, so ``router.w`` and the selection bias have
    none, on both sides."""
    (got, got_g), (want, want_g) = _loss_and_gradients(parts, reference)
    # the dense conv layer 7, a conv expert layer 10, the attention expert
    # layer 13, the embedding and the final norm
    assert sorted(got_g) == sorted(want_g)
    assert len(got_g) == 7 + 3 * 10 + 13 + 2
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for k, w in want_g.items():
        still = ".router." in k
        assert (float(jnp.max(jnp.abs(w))) > 0) != still, k
        _close(got_g[k], w, k)


@pytest.mark.parametrize("change", [
    {"rope_parameters": {"rope_theta": 100.0, "rope_type": "default"}},
    {"norm_topk_prob": False},
    {"layer_types": ["conv", "conv", "conv", "conv", "full_attention"]},
], ids=["another_theta", "gates_not_normalised",
        "the_attention_layer_elsewhere"])
def test_each_part_matters(parts, reference, change):
    """A program built with another rotary base, gates left as the
    scores, or its one attention layer in another place (the stacked
    leaves of the two kinds then land in other layers) fails the
    comparison the true one passes at 2e-4 by a hundred times."""
    cfg = parts[1]
    (got, got_g), (want, want_g) = _loss_and_gradients(
        parts, reference, {**cfg, **change})
    off = max(_worst(got_g, want_g), abs(float(got - want)) / float(want))
    assert off > 2e-2, (change, off)


def test_one_adamw_step_in_float32(parts):
    """The harness's comparison (benchmark/check.py) with the cell in
    float32: the first two losses, the first gradient as the optimizer got
    it and the parameters' change over two AdamW steps with the clip;
    granite's limits (float32 against float32 at "highest": a hundred
    times under the bfloat16 rehearsal's)."""
    import check
    cell, cfg, mix, model_mod, ref, runner, ring, theta0 = parts
    want = harness.follow_reference(check, ref, cell, cfg, mix, ring, theta0)
    state = runner.build(cell, cfg, model_mod, theta0(), mix)
    got = harness.follow_program(check, runner, state, cell, ring, theta0)
    numbers = {k: v[0] for k, v in check.compare(got, want).items()}
    runner.close(state)
    assert numbers["loss_gap"] < 1e-6, numbers
    assert numbers["grad_norm_gap"] < 2e-5, numbers
    assert numbers["update_norm_gap"] < 1e-3, numbers
    assert numbers["grad_diff"] < 2e-5, numbers


def test_the_whole_step_follows_the_reference_through_trainstep():
    """``run.py --rehearse``: the model through ``TrainStep``, ``amp`` O2
    (bfloat16), AdamW and per-block recompute over two steps against the
    float32 reference, under the rehearsal's limits (the cell's file says
    what each was set from)."""
    args = argparse.Namespace(workload=CELL, seed=7, seconds=0.5, trace=0,
                              keep_trace=None)
    assert harness.run_cell(args, rehearse=True)["correct"] is True


# ------------------------------------------------------------- the share --
def _expert_layer(E, H, Fw, seed=3):
    keys = jax.random.split(jax.random.key(seed), 5)
    return {"router.w": 0.5 * jax.random.normal(keys[0], (H, E)),
            "router.bias": 0.02 * jax.random.normal(keys[1], (E,)),
            "experts.gate": 0.2 * jax.random.normal(keys[2], (E, H, Fw)),
            "experts.up": 0.2 * jax.random.normal(keys[3], (E, H, Fw)),
            "experts.down": 0.2 * jax.random.normal(keys[4], (E, Fw, H))}


def _member(cfg, share, E, held, gate_epsilon):
    """The program's expert layer as models/lfm2_moe.py builds it, told
    which experts it holds, with ``share``'s leaves."""
    H, Fw = share["experts.gate"].shape[1:]
    layer = nn.MoELayer(
        H, Fw, E, cfg["num_experts_per_tok"], held=held,
        norm_topk_prob=cfg["norm_topk_prob"], scoring="sigmoid",
        selection_bias=True,
        routed_scaling_factor=cfg["routed_scaling_factor"],
        train_router=False, gate_epsilon=gate_epsilon)
    for name, leaf in (("router_weight", "router.w"),
                       ("router_bias", "router.bias"),
                       ("w_gate", "experts.gate"), ("w_up", "experts.up"),
                       ("w_down", "experts.down")):
        getattr(layer, name).data = share[leaf]
    return layer


def test_the_eight_members_parts_add_up_to_the_uncut_layer(parts):
    """The share tied to the model (the model-configs guide, section 4):
    one expert layer's feed-forward part over the published 64 experts, 8
    members holding experts 0-7 ... 56-63, at the rehearsal's widths.
    Each member's part, by the program's ``nn.MoELayer`` told which
    experts it holds, is the reference's for that share; the eight parts
    add up to what the uncut reference gives for the whole layer (there
    is no shared expert to count once)."""
    _, cfg, _, model_mod, ref, _, _, _ = parts
    E, H, Fw = 64, cfg["hidden_size"], cfg["moe_intermediate_size"]
    cfg = {**cfg, "num_experts": 8,
           "published": {**cfg["published"], "num_experts": E}}
    whole = _expert_layer(E, H, Fw)
    b = jax.random.normal(jax.random.key(4), (96, H))
    want, chosen = ref.feed_forward(b, whole, {**cfg, "num_experts": E},
                                    tuple(range(E)))
    assert chosen.shape == (96, cfg["num_experts_per_tok"])
    total, seen = 0.0, 0
    for first in range(0, E, 8):
        held = range(first, first + 8)
        share = {k: (v[first:first + 8] if k.startswith("experts.") else v)
                 for k, v in whole.items()}
        part, _ = ref.feed_forward(b, share, cfg, tuple(held))
        layer = _member(cfg, share, E, held, model_mod.GATE_EPSILON)
        _close(layer(paddle.to_tensor(b)).data, part, f"members {held}")
        seen += int(jnp.sum((chosen >= first) & (chosen < first + 8)))
        total = total + part
    assert seen == chosen.size
    assert float(jnp.max(jnp.abs(want))) > 0.1
    _close(total, want, "the members' parts")


# ------------------------------------------------------------ the router --
def test_the_selection_reads_the_bias_and_the_gates_do_not():
    """Expert 7 has the lowest score of eight and a bias that lifts it
    into every token's two: it is chosen, and its gate is its own score
    over the chosen two's, the bias nowhere in it."""
    x = jnp.ones((5, 8), jnp.float32)
    logits = jnp.linspace(1.0, -1.0, 8)
    router_w = jnp.tile(logits[None, :] / 8.0, (8, 1))
    bias = jnp.zeros(8).at[7].set(10.0)
    gates, ids = moe.moe_route(x, router_w, 2, True, "sigmoid", bias)
    s = jax.nn.sigmoid(logits)
    assert sorted(np.asarray(ids[0]).tolist()) == [0, 7]
    by_id = dict(zip(np.asarray(ids[0]).tolist(), np.asarray(gates[0])))
    np.testing.assert_allclose(by_id[7], s[7] / (s[0] + s[7]), rtol=1e-6)
    np.testing.assert_allclose(by_id[0], s[0] / (s[0] + s[7]), rtol=1e-6)
    _, unbiased = moe.moe_route(x, router_w, 2, True, "sigmoid", None)
    assert sorted(np.asarray(unbiased[0]).tolist()) == [0, 1]


@pytest.mark.parametrize("public", [True, False],
                         ids=["the_public_epsilon", "the_default_floor"])
def test_the_gates_normalise_over_the_public_epsilon(parts, public):
    """Where a token's four scores are of the epsilon's own size
    (sigmoid(-14) = 8.3e-7 each) the public code's ``sum + 1e-6`` is
    seen: each gate is 0.192, not 0.25.  The program computes that value
    (`models/lfm2_moe.py::GATE_EPSILON` through ``nn.MoELayer``), as the
    reference does; the argument left out is the floor the other
    families' cells run (1e-20: 0.25)."""
    _, cfg, _, model_mod, ref, _, _, _ = parts
    E, H, Fw = 16, cfg["hidden_size"], cfg["moe_intermediate_size"]
    share = _expert_layer(E, H, Fw)
    share["router.w"] = jnp.full((H, E), -14.0 / H)
    share["router.bias"] = jnp.zeros((E,))
    b = jnp.ones((6, H), jnp.float32)
    epsilon = model_mod.GATE_EPSILON if public else None
    score = float(jax.nn.sigmoid(-14.0))
    the_public_gate = score / (4 * score + 1e-6)
    assert 0.19 < the_public_gate < 0.195
    gates, _ = moe.moe_route(b, share["router.w"], 4, True, "sigmoid",
                             share["router.bias"], gate_epsilon=epsilon)
    np.testing.assert_allclose(gates, the_public_gate if public else 0.25,
                               rtol=1e-3)
    got = _member(cfg, share, E, range(E), epsilon)(paddle.to_tensor(b)).data
    want, _ = ref.feed_forward(b, share, {**cfg, "num_experts": E},
                               tuple(range(E)))
    assert float(jnp.max(jnp.abs(want))) > 1e-3
    if public:
        _close(got, want, "the layer at the public epsilon")
    else:
        _close(got, want * (0.25 / the_public_gate),
               "the layer at the floor", tol=2e-3)


# ------------------------------------------------- the operator's kernels --
def _operands(B, T, H, K, dtype, seed=0):
    k = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(k[0], (B, T, 3 * H), jnp.float32).astype(dtype),
            (0.5 + 0.3 * jax.random.normal(k[1], (K, H))).astype(dtype),
            jax.random.normal(k[2], (B, T, H), jnp.float32).astype(dtype))


def _value_and_grads(fn, x, w, ct):
    def loss(x, w):
        return jnp.sum(fn(x, w).astype(jnp.float32)
                       * ct.astype(jnp.float32))
    return fn(x, w), jax.grad(loss, (0, 1))(x, w)


# (B, T, H, K): two rows (a row's start inside the batch: zeros before
# it, not the row before); 1024 rows of 256 lanes in float32 are two T
# blocks of the forward kernel and four of the backward's, 128 lanes one
# slab and 256 two; 48 rows one short block
@pytest.mark.parametrize("B,T,H,K,dtype", [
    (2, 256, 256, 3, jnp.float32), (1, 1024, 256, 3, jnp.float32),
    (1, 1024, 256, 3, jnp.bfloat16), (1, 512, 128, 4, jnp.bfloat16),
    (2, 48, 128, 2, jnp.float32)],
    ids=["two_rows", "t_blocks", "t_blocks_bf16", "four_taps_bf16",
         "a_short_block"])
def test_the_kernels_are_the_plain_form(kernels_on, B, T, H, K, dtype):
    """``short_conv_fwd`` and ``short_conv_bwd`` in interpret mode against
    ops/ssm.py's slices, products and shifted multiply-adds: y, dB, dC,
    dz and the taps' gradient.  Both forms compute in float32 and round
    once, so bfloat16 agrees to a rounding of the result."""
    x, w, ct = _operands(B, T, H, K, dtype)
    assert cc.gated_short_conv_supported(x.shape, w.shape, dtype)
    before = dict(monitor.all_stats())
    y, (dx, dw) = _value_and_grads(
        lambda x, w: F.gated_short_conv(paddle.Tensor(x),
                                        paddle.Tensor(w)).data, x, w, ct)
    stats = monitor.all_stats()
    assert stats["pallas.selected.gated_short_conv"] > before.get(
        "pallas.selected.gated_short_conv", 0)
    assert stats.get("gated_short_conv.xla_path", 0) == before.get(
        "gated_short_conv.xla_path", 0)
    want_y, (want_dx, want_dw) = _value_and_grads(plain_short_conv, x, w, ct)
    tol = 2e-6 if dtype == jnp.float32 else 1e-2
    f32 = jnp.float32
    _close(y.astype(f32), want_y.astype(f32), "y", tol)
    for i, name in enumerate(("dB", "dC", "dz")):
        _close(dx[..., i * H:(i + 1) * H].astype(f32),
               want_dx[..., i * H:(i + 1) * H].astype(f32), name, tol)
    _close(dw.astype(f32), want_dw.astype(f32), "the taps' gradient", tol)


def test_the_operator_is_the_gates_round_the_taps():
    """The plain form against the layer's equations written out: tap
    K - 1 on the position itself, zeros before the row's start, no
    activation."""
    x, w, _ = _operands(2, 16, 8, 3, jnp.float32)
    B, C, z = x[..., :8], x[..., 8:16], x[..., 16:]
    v = np.asarray(B * z)
    want = np.zeros((2, 16, 8), np.float32)
    for t in range(16):
        for k in range(3):
            if t - 2 + k >= 0:
                want[:, t] += np.asarray(w[k]) * v[:, t - 2 + k]
    np.testing.assert_allclose(plain_short_conv(x, w), np.asarray(C) * want,
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("shape,w_shape,dtype", [
    ((1, 256, 3 * 192), (3, 192), jnp.float32),      # no whole lane tiles
    ((1, 250, 3 * 128), (3, 128), jnp.float32),      # no whole T block
    ((1, 256, 3 * 128), (9, 128), jnp.float32),      # nine taps
    ((1, 256, 4 * 128), (3, 128), jnp.float32),      # not three parts of H
    ((1, 256, 3 * 128), (3, 128), jnp.float16)],
    ids=["lanes", "rows", "taps", "width", "dtype"])
def test_a_call_outside_the_gate_runs_the_plain_form(kernels_on, shape,
                                                     w_shape, dtype):
    assert not cc.gated_short_conv_supported(shape, w_shape, dtype)
    if shape[2] != 3 * w_shape[1]:
        return
    before = monitor.all_stats().get("gated_short_conv.xla_path", 0)
    x, w = jnp.ones(shape, dtype), jnp.ones(w_shape, dtype)
    y = F.gated_short_conv(paddle.Tensor(x), paddle.Tensor(w)).data
    assert y.shape == shape[:2] + (w_shape[1],) and y.dtype == dtype
    assert monitor.all_stats()["gated_short_conv.xla_path"] == before + 1


# ------------------------------------------------------------- the layer --
@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
def test_the_mixer_is_two_projections_round_the_operator(kernels_on, bias):
    """``nn.ShortConv`` against its equations, under its two scopes; with
    ``bias`` the projections and the convolution have one each and the
    call takes the plain form (the kernels have no bias)."""
    paddle.seed(1)
    H, T = 128, 64
    layer = nn.ShortConv(H, taps=3, bias=bias)
    names = sorted(n for n, _ in layer.named_parameters())
    assert names == sorted(
        ["in_proj.weight", "conv_weight", "out_proj.weight"]
        + (["in_proj.bias", "conv_bias", "out_proj.bias"] if bias else []))
    layer.conv_weight.data = layer.conv_weight.data + 0.5
    if bias:
        for p in (layer.in_proj.bias, layer.conv_bias, layer.out_proj.bias):
            p.data = p.data + 0.1
    h = jax.random.normal(jax.random.key(2), (2, T, H))
    bcz = h @ layer.in_proj.weight.data + (layer.in_proj.bias.data
                                           if bias else 0.0)
    want = plain_short_conv(bcz, layer.conv_weight.data,
                            layer.conv_bias.data if bias else None) \
        @ layer.out_proj.weight.data + (layer.out_proj.bias.data
                                        if bias else 0.0)
    before = dict(monitor.all_stats())
    _close(layer(paddle.to_tensor(h)).data, want, "the mixer", 1e-5)
    took = "gated_short_conv.xla_path" if bias \
        else "pallas.selected.gated_short_conv"
    assert monitor.all_stats()[took] == before.get(took, 0) + 1
    text = jax.jit(lambda a: layer(paddle.Tensor(a)).data).lower(
        h).as_text(debug_info=True)
    assert {scopes.SHORT_CONV, scopes.SHORT_CONV_OP} <= set(
        scopes.FUNCTIONALS)
    assert f"{scopes.SHORT_CONV}/{scopes.SHORT_CONV_OP}/" in text
    assert f"{scopes.SHORT_CONV}/out_proj:Linear" in text


# ----------------------------------- the cells that were there, unchanged --
# Digests of ``str(jax.make_jaxpr(...))`` (addresses dropped) made at this
# PR's parent, 3361c73, before the gated kernels, ``_t_block``'s
# ``arrays`` and ``gate_epsilon`` existed, under the jax that
# pyproject.toml pins; the same script on this tree gives the same four.
# A PR that changes the kernels or the router on purpose reads the
# failure's digest and moves them.
_PARENTS = {
    "nemotron_conv": "2d19a534ab0282a1", "granite_conv": "a43303222d1aed0e",
    "sigmoid_router": "1baa397144734276", "softmax_router": "a40d793ee8aeccc3"}


@pytest.mark.parametrize("case", sorted(_PARENTS))
def test_the_other_cells_calls_trace_to_the_parents_jaxpr(case):
    """The Mamba cells' convolution at their shapes (forward and
    backward kernels) and an expert layer behind a sigmoid router with a
    bias (JoyAI's, Nemotron's, Trinity's) or a softmax router (Keye's),
    ``gate_epsilon`` left out."""
    def x(*shape, dtype=jnp.bfloat16):
        return jnp.zeros(shape, dtype)

    def conv(widths):
        def loss(a, w, b):
            outs = cc.causal_conv1d(a, w, b, "silu", 4096, widths)
            return sum(jnp.sum(o.astype(jnp.float32) ** 2) for o in outs)
        return jax.value_and_grad(loss, (0, 1, 2))

    def routed(scoring):
        def loss(a, r, wg, wu, wd, *b):
            return jnp.sum(moe.moe_forward(
                a, r, wg, wu, wd, top_k=4, first=0, scoring=scoring,
                router_bias=b[0] if b else None, scaling=2.5,
                train_router=False) ** 2)
        return jax.value_and_grad(loss, (0, 2, 3, 4))

    f32 = jnp.float32
    experts = (x(2, 256, 128, dtype=f32), x(128, 16, dtype=f32),
               x(4, 128, 256), x(4, 128, 256), x(4, 256, 128))
    fn, args = {
        "nemotron_conv": (conv((4096, 1024, 1024)),
                          (x(2, 8192, 10304), x(4, 6144), x(6144))),
        "granite_conv": (conv((4096, 128, 128)),
                         (x(1, 8192, 8512), x(4, 4352), x(4352))),
        "sigmoid_router": (routed("sigmoid"), experts + (x(16, dtype=f32),)),
        "softmax_router": (routed("softmax"), experts),
    }[case]
    # the digests were made without the tests' matmul precision
    with jax.default_matmul_precision(None):
        text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(fn)(*args)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == _PARENTS[case]
