"""Latent attention, the sigmoid router with its selection bias, the
shared expert and the MTP module against the plain reference of
benchmark/reference/joyai_llm_flash.py: the flash kernels with keys wider
than values (interpret mode), ``F.mla_attention`` / ``nn.MLAttention`` on
both paths, interleaved RoPE, the router's variants, a chip's share of
the experts adding up to the uncut layer with the shared expert counted
once, and the whole model through ``TrainStep`` bf16 O2."""
import importlib
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
from reference import joyai_llm_flash as ref  # noqa: E402

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
CELL = "joyai_llm_flash.train_bf16_b2_s8192"


def ident(a):
    return a


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


# ----------------------------------------- kernels: keys wider than values --
def _qkv(seed, B, L, H, D, Dv, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (B, L, H, D), dtype),
            jax.random.normal(ks[1], (B, L, H, D), dtype),
            jax.random.normal(ks[2], (B, L, H, Dv), dtype),
            jax.random.normal(ks[3], (B, L, H, Dv), jnp.float32))


@pytest.mark.parametrize("blocks", [(64, 128), (128, 64), (256, 256)],
                         ids=["q64k128", "q128k64", "one_block"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_kernels_with_a_value_width_of_their_own(causal, blocks):
    """Dqk 48, Dv 32, K and V walked in several blocks: the forward and
    all three gradients against plain jax.numpy."""
    q, k, v, w = _qkv(0, 2, 256, 2, 48, 32)

    def both(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * w), (0, 1, 2))(q, k, v)

    got = both(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal, block_q=blocks[0], block_k=blocks[1]))
    want = both(lambda q, k, v: fa.mha_reference(q, k, v, causal=causal))
    assert got[1][0].shape == q.shape and got[1][2].shape == v.shape
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, r, name in zip(got[1], want[1], "qkv"):
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-5, err_msg=name)


def test_flash_kernels_in_bfloat16_at_the_cells_head_shape():
    """192-wide keys, 128-wide values, bfloat16, one head."""
    q, k, v, w = _qkv(1, 1, 256, 1, 192, 128, jnp.bfloat16)

    def both(fn):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * w), (0, 1, 2))(q, k, v)

    got = both(lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                                  block_q=128, block_k=128))
    want = both(lambda q, k, v: fa.mha_reference(q, k, v, causal=True))
    for g, r in zip(got[1], want[1]):
        err = jnp.linalg.norm((g - r).astype(jnp.float32))
        assert err < 0.03 * jnp.linalg.norm(r.astype(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("blocks", [(64, 128), (128, 64), (64, 64),
                                    (256, 256)],
                         ids=["q64k128", "q128k64", "q64k64", "one_block"])
def test_flash_kernels_take_a_key_part_that_the_heads_share(blocks, dtype):
    """The rotated key enters once a batch entry, never broadcast: the
    forward and all five gradients against the joined, broadcast form in
    plain jax.numpy; the shared part's gradient is the heads' sum.  The
    backward is one kernel: the dK/dV walk makes dQ and dQr too."""
    B, L, H, D, Dr, Dv = 2, 256, 3, 32, 16, 24
    ks = jax.random.split(jax.random.key(13), 6)
    q = jax.random.normal(ks[0], (B, L, H, D), dtype)
    qr = jax.random.normal(ks[1], (B, L, H, Dr), dtype)
    k = jax.random.normal(ks[2], (B, L, H, D), dtype)
    kr = jax.random.normal(ks[3], (B, L, Dr), dtype)
    v = jax.random.normal(ks[4], (B, L, H, Dv), dtype)
    w = jax.random.normal(ks[5], (B, L, H, Dv))

    def joined(q, qr, k, kr, v):
        kk = jnp.concatenate(
            [k, jnp.broadcast_to(kr[:, :, None], (B, L, H, Dr))], -1)
        return fa.mha_reference(jnp.concatenate([q, qr], -1), kk, v,
                                causal=True)

    def both(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
            (0, 1, 2, 3, 4))

    kernels = both(lambda *a: fa.flash_attention_shared_key(
        *a, block_q=blocks[0], block_k=blocks[1]))
    fused = monitor.get_stat("pallas.flash.bwd_fused")
    text = str(jax.make_jaxpr(kernels)(q, qr, k, kr, v))
    assert monitor.get_stat("pallas.flash.bwd_fused") == fused + 1
    assert text.count("name=flash_bwd_dkv") == 1
    assert "flash_bwd_dq" not in text
    got, want = kernels(q, qr, k, kr, v), both(joined)(q, qr, k, kr, v)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, r, name in zip(got[1], want[1], ("q", "qr", "k", "kr", "v")):
        assert g.shape == r.shape and g.dtype == dtype, name
        if dtype == jnp.float32:
            np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-5,
                                       err_msg=name)
        else:
            err = jnp.linalg.norm((g - r).astype(jnp.float32))
            assert err < 0.03 * jnp.linalg.norm(r.astype(jnp.float32)), name


def test_the_gate_takes_the_value_width_and_the_staging_budget():
    bf = jnp.bfloat16
    mla = (2, 8192, 32, 128)        # a head's own key lanes, beside 64 shared
    assert fa.flash_attention_supported(mla, mla, bf, v_head_dim=128,
                                        shared_key_dim=64)
    assert not fa.flash_attention_supported(mla, mla, bf, v_head_dim=100,
                                            shared_key_dim=64)
    # under 512 positions XLA's attention is taken, whatever the widths
    short = (2, 256, 32, 128)
    assert not fa.flash_attention_supported(short, short, bf, v_head_dim=128,
                                            shared_key_dim=64)
    # what a head stages decides, keys and values together
    assert fa._staged_bytes(8192, 128 + 64, 128, bf) == 5 * 2 ** 20
    assert fa._staging(8192, 192, 128, bf).vmem_limit_bytes > 16 * 2 ** 20
    for shape in ((8, 2048, 16, 96), (64, 512, 12, 64), (1, 8192, 32, 128)):
        assert fa.flash_attention_supported(shape, shape, bf)
        assert fa._staging(shape[1], shape[3], shape[3], bf) is None
    # since PR 47 a plain call of 128-wide bfloat16 heads stages up to
    # 8 MiB (16384 rows, Trinity-Mini's) under the stated limit; twice the
    # row does not
    long = (1, 16384, 8, 128)
    assert fa.flash_attention_supported(long, long, bf)
    assert fa._staging(16384, 128, 128, bf).vmem_limit_bytes > 16 * 2 ** 20
    longer = (1, 32768, 8, 128)
    assert not fa.flash_attention_supported(longer, longer, bf)


@pytest.mark.parametrize("shape,dtype,kwargs", [
    ((1, 8192, 8, 192), jnp.bfloat16, {"v_head_dim": 128}),
    ((1, 8192, 8, 192), jnp.bfloat16, {}),
    ((1, 24576, 8, 128), jnp.bfloat16, {}),
    ((1, 6144, 8, 128), jnp.float32, {}),
    ((1, 8192, 8, 192), jnp.bfloat16, {"v_head_dim": 256,
                                       "shared_key_dim": 64}),
], ids=["keys192_values128", "heads192", "24576x128", "6144x128_f32",
        "glm5_at_8192"])
def test_only_the_measured_shared_key_shape_stages_over_the_default(
        shape, dtype, kwargs):
    """More than 4 MiB a head is admitted for the shared-key call up to
    the 5 MiB that was compiled and measured (JoyAI's cell) and, since
    PR 47, for a plain call of 128-wide two-byte heads up to 8 MiB
    (Trinity-Mini's cell: 16384 rows): wider heads and float32 of that
    size stay XLA's or the ring's, as before (compiled for a v5e their
    walks take 55 to 76 MB of scoped VMEM, over the stated 48 MiB)."""
    assert fa._staged_bytes(shape[1], shape[3] + kwargs.get(
        "shared_key_dim", 0), kwargs.get("v_head_dim", shape[3]),
        dtype) > fa._STAGED_DEFAULT
    assert not fa.flash_attention_supported(shape, shape, dtype, **kwargs)


@pytest.mark.parametrize("rows", [12288, 16384])
def test_a_plain_call_of_128_wide_heads_stages_up_to_8_mib(rows):
    shape = (1, rows, 32, 128)
    kv = (1, rows, 4, 128)
    assert fa._STAGED_DEFAULT < fa._staged_bytes(
        rows, 128, 128, jnp.bfloat16) <= fa._STAGED_PLAIN
    assert fa.flash_attention_supported(shape, kv, jnp.bfloat16)
    assert not fa.flash_attention_supported(shape, kv, jnp.float32)


def test_a_value_width_of_its_own_is_admitted_under_the_default():
    # plain heads of 192 with values of 128 (MiMo-V2-Flash's) at 4096
    shape = (1, 4096, 8, 192)
    assert fa.flash_attention_supported(shape, shape, jnp.bfloat16,
                                        v_head_dim=128)
    assert fa._staging(4096, 192, 128, jnp.bfloat16) is None


# ------------------------------------------------------------------- RoPE --
def test_interleaved_rope_pairs_neighbours():
    x = jax.random.normal(jax.random.key(2), (2, 24, 3, 16))
    got = F.rotary_embedding(paddle.to_tensor(x), 3.2e7,
                             interleaved=True).data
    want = jnp.stack([ref.rope(r, 3.2e7) for r in x])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the public code's form: permute to halves, rotate (i, i + D/2); the
    # same vector up to that permutation, so q . k is the same
    perm = jnp.concatenate([jnp.arange(0, 16, 2), jnp.arange(1, 16, 2)])
    halves = F.rotary_embedding(paddle.to_tensor(x[..., perm]), 3.2e7).data
    np.testing.assert_allclose(halves, got[..., perm], rtol=1e-5, atol=1e-6)
    # rotate-half is untouched
    plain = F.rotary_embedding(paddle.to_tensor(x), 3.2e7).data
    assert not np.allclose(plain, got)


# ------------------------------------------------------- latent attention --
ACFG = {"hidden_size": 32, "num_attention_heads": 2, "q_lora_rank": 24,
        "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "rms_norm_eps": 1e-6, "rope_theta": 3.2e7,
        "intermediate_size": 48, "moe_intermediate_size": 16,
        "published": {"n_routed_experts": 8}, "n_routed_experts": 8,
        "num_experts_per_tok": 2, "first_k_dense_replace": 1,
        "num_hidden_layers": 2, "vocab_size": 64}


def _attention_weights(seed):
    z = ref.sizes(ACFG)
    out = {}
    for i, (n, (shape, base)) in enumerate(
            sorted(ref._attention_shapes(z).items())):
        out[n] = base + 0.3 * jax.random.normal(
            jax.random.fold_in(jax.random.key(seed), i), shape)
    return out


def _attention_layer(p):
    layer = nn.MLAttention(32, 2, 24, 16, 16, 8, 16, rope_theta=3.2e7)
    for name in ("q_a", "q_b", "kv_a", "kv_b", "o"):
        getattr(layer, name).weight.data = p[name + ".w"]
    layer.q_norm.weight.data = p["q_norm.g"]
    layer.kv_norm.weight.data = p["kv_norm.g"]
    return layer


def _reference_branch(a, p):
    return jnp.stack([ref.latent_attention(r, p, ACFG, ident) @ p["o.w"]
                      for r in a])


def test_the_latent_attention_layer_matches_the_reference():
    p = _attention_weights(3)
    a = jax.random.normal(jax.random.key(4), (2, 40, 32))
    monitor.stat_reset()
    got = _attention_layer(p)(paddle.to_tensor(a)).data
    assert monitor.all_stats()["mla_attention.xla_path"] == 1
    np.testing.assert_allclose(got, _reference_branch(a, p), rtol=2e-4,
                               atol=2e-5)


def test_the_kernel_path_matches_the_reference_with_its_gradients(kernels_on):
    """512 positions, so that ``F.mla_attention`` takes the flash kernels
    (interpret mode): the branch and its gradients to the input and to
    every weight of the layer."""
    p = _attention_weights(5)
    a = jax.random.normal(jax.random.key(6), (1, 512, 32))
    w = jnp.cos(jnp.arange(32.0))

    def program(a, p):
        # the eager tape off: jax differentiates, as under TrainStep
        with paddle.no_grad():
            return jnp.sum(_attention_layer(p)(paddle.to_tensor(a)).data * w)

    monitor.stat_reset()
    got = jax.value_and_grad(program, (0, 1))(a, p)
    stats = monitor.all_stats()
    assert stats["pallas.selected.mla_attention"] == 1
    assert "mla_attention.xla_path" not in stats
    want = jax.value_and_grad(
        lambda a, p: jnp.sum(_reference_branch(a, p) * w), (0, 1))(a, p)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-4)
    np.testing.assert_allclose(got[1][0], want[1][0], rtol=2e-3, atol=2e-4)
    for n in want[1][1]:
        np.testing.assert_allclose(got[1][1][n], want[1][1][n], rtol=2e-3,
                                   atol=2e-4, err_msg=n)


# ------------------------------------------- the router and the experts --
H, FF, E, K, HELD = 32, 16, 256, 8, 16
MCFG = {"num_experts_per_tok": K, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "train_router": True}


def _moe_weights(seed):
    ks = jax.random.split(jax.random.key(seed), 9)
    return {"router.w": jax.random.normal(ks[0], (H, E)),
            "router.bias": 0.2 * jax.random.normal(ks[1], (E,)),
            "experts.gate": 0.3 * jax.random.normal(ks[2], (E, H, FF)),
            "experts.up": 0.3 * jax.random.normal(ks[3], (E, H, FF)),
            "experts.down": 0.3 * jax.random.normal(ks[4], (E, FF, H)),
            "shared.gate.w": 0.3 * jax.random.normal(ks[5], (H, FF)),
            "shared.up.w": 0.3 * jax.random.normal(ks[6], (H, FF)),
            "shared.down.w": 0.3 * jax.random.normal(ks[7], (FF, H)),
            "x": jax.random.normal(ks[8], (2, 24, H))}


def _slice(w, held):
    return {n: (a[held.start:held.stop] if n.startswith("experts.") else a)
            for n, a in w.items()}


def _program(w, held, shared=True, train_router=True):
    return moe_ops.moe_forward(
        w["x"], w["router.w"], w["experts.gate"], w["experts.up"],
        w["experts.down"], top_k=K, first=held.start, scoring="sigmoid",
        router_bias=w["router.bias"], scaling=2.5,
        shared=(w["shared.gate.w"], w["shared.up.w"], w["shared.down.w"])
        if shared else None, train_router=train_router)


def _reference(w, held, shared=True, train_router=True):
    cfg = {**MCFG, "train_router": train_router}
    rows = [ref.routed(x, w, cfg, tuple(held), ident)[0]
            + (ref.shared(x, w, ident) if shared else 0.0) for x in w["x"]]
    return jnp.stack(rows)


def test_the_sigmoid_router_selects_on_score_plus_bias():
    w = _moe_weights(7)
    x = w["x"].reshape(-1, H)
    gates, ids = moe_ops.moe_route(x, w["router.w"], K, True, "sigmoid",
                                   w["router.bias"], 2.5)
    want_gates, want_ids = ref.route(x, w, MCFG, ident)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(gates, want_gates, rtol=1e-5)
    np.testing.assert_allclose(jnp.sum(gates, -1), 2.5, rtol=1e-5)
    # the gates are the scores', not score + bias: without the bias another
    # set may be chosen, but a token's chosen scores give the same weights
    s = jax.nn.sigmoid(x @ w["router.w"])
    picked = jnp.take_along_axis(s, ids, -1)
    np.testing.assert_allclose(
        gates, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    plain, plain_ids = moe_ops.moe_route(x, w["router.w"], K, True, "sigmoid")
    assert not np.array_equal(plain_ids, ids)
    np.testing.assert_allclose(jnp.sum(plain, -1), 1.0, rtol=1e-5)


def test_equal_selection_values_keep_the_lower_expert():
    x = jnp.ones((3, H))
    router = jnp.zeros((H, E))              # every score is 1/2
    bias = jnp.zeros((E,)).at[jnp.array([5, 9, 200])].set(0.1)
    _, ids = moe_ops.moe_route(x, router, 4, True, "sigmoid", bias, 1.0)
    np.testing.assert_array_equal(ids[0], [5, 9, 200, 0])


def test_softmax_scoring_is_what_it_was():
    w = _moe_weights(8)
    x = w["x"].reshape(-1, H)
    gates, ids = moe_ops.moe_route(x, w["router.w"], K)
    probs = jax.nn.softmax(x @ w["router.w"], -1)
    vals, want_ids = jax.lax.top_k(probs, K)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(gates, vals / vals.sum(-1, keepdims=True),
                               rtol=1e-6)
    with pytest.raises(ValueError, match="scoring"):
        moe_ops.moe_route(x, w["router.w"], K, scoring="tanh")


@pytest.mark.parametrize("held", [range(0, 16), range(48, 64),
                                  range(240, 256)],
                         ids=["0to15", "48to63", "240to255"])
def test_the_layer_and_its_gradients_match_the_reference(held):
    w = _slice(_moe_weights(9), held)
    np.testing.assert_allclose(_program(w, held), _reference(w, held),
                               rtol=2e-5, atol=2e-5)

    def loss(fn, w):
        return jnp.sum(fn(w, held) * jnp.cos(jnp.arange(H)))

    got = jax.grad(lambda w: loss(_program, w))(w)
    want = jax.grad(lambda w: loss(_reference, w))(w)
    for n in want:
        np.testing.assert_allclose(got[n], want[n], rtol=2e-4, atol=2e-5,
                                   err_msg=n)
    # only the selection reads the bias
    assert not np.any(np.asarray(got["router.bias"]))
    assert np.any(np.asarray(got["shared.down.w"]))


@pytest.mark.parametrize("held", [range(0, 16), range(240, 256)],
                         ids=["0to15", "240to255"])
def test_a_router_held_still_gets_no_gradient_and_hands_none_on(held):
    """``train_router=False``: the same result; the router's weight gets
    a zero gradient, the stream only what the experts hand it, and every
    other leaf what the reference gives under its ``train_router`` false
    (the cell's configuration: one member without its group)."""
    w = _slice(_moe_weights(13), held)
    np.testing.assert_allclose(_program(w, held, train_router=False),
                               _program(w, held), rtol=0, atol=0)

    def loss(fn, w):
        return jnp.sum(fn(w, held, train_router=False)
                       * jnp.cos(jnp.arange(H)))

    got = jax.grad(lambda w: loss(_program, w))(w)
    want = jax.grad(lambda w: loss(_reference, w))(w)
    assert not np.any(np.asarray(got["router.w"]))
    assert not np.any(np.asarray(want["router.w"]))
    for n in want:
        np.testing.assert_allclose(got[n], want[n], rtol=2e-4, atol=2e-5,
                                   err_msg=n)
    trained = jax.grad(lambda w: jnp.sum(
        _program(w, held) * jnp.cos(jnp.arange(H))))(w)
    assert np.any(np.asarray(trained["router.w"]))
    assert not np.allclose(trained["x"], got["x"])
    for n in ("experts.gate", "experts.down", "shared.up.w"):
        np.testing.assert_allclose(trained[n], got[n], rtol=1e-6, atol=1e-7,
                                   err_msg=n)


def test_the_shares_add_up_with_the_shared_expert_counted_once():
    """Sixteen holders of sixteen of the 256 experts: their routed parts
    and ONE shared expert are what the reference gives for the whole
    layer; each holder's own result carries the shared expert, as every
    member of the group computes it."""
    w = _moe_weights(10)
    whole = _reference(w, range(E))
    holders = [range(lo, lo + HELD) for lo in range(0, E, HELD)]
    routed = sum(_program(_slice(w, h), h, shared=False) for h in holders)
    once = jnp.stack([ref.shared(x, w, ident) for x in w["x"]])
    np.testing.assert_allclose(routed + once, whole, rtol=2e-5, atol=2e-5)
    with_shared = sum(_program(_slice(w, h), h) for h in holders)
    np.testing.assert_allclose(with_shared - (len(holders) - 1) * once,
                               whole, rtol=2e-5, atol=5e-5)


def test_the_layer_holds_the_variants_as_parameters():
    monitor.stat_reset()
    layer = nn.MoELayer(H, FF, E, K, held=range(16, 32), scoring="sigmoid",
                        selection_bias=True, routed_scaling_factor=2.5,
                        shared_width=FF)
    names = {n for n, _ in layer.named_parameters()}
    assert names == {"router_weight", "router_bias", "w_gate", "w_up",
                     "w_down", "shared_gate", "shared_up", "shared_down"}
    assert tuple(layer.router_bias.shape) == (E,)
    out = layer(paddle.randn([2, 8, H]))
    assert tuple(out.shape) == (2, 8, H) and out.dtype == paddle.float32
    stats = monitor.all_stats()
    assert stats["moe.scoring_sigmoid"] == 1
    assert stats["moe.shared_experts"] == 1
    plain = nn.MoELayer(H, FF, 16, 4)
    assert {n for n, _ in plain.named_parameters()} == {
        "router_weight", "w_gate", "w_up", "w_down"}
    with pytest.raises(ValueError, match="scoring"):
        nn.MoELayer(H, FF, 16, 4, scoring="tanh")


# ------------------------------------------------------ the whole model --
def _follow(seed, overrides=None, mix_overrides=None):
    """The cell at its rehearsal sizes through the harness's own two
    followers: the float32 reference and the TrainStep bf16 O2 program."""
    import check
    import run as harness
    cell, cfg, mix, model_mod, ref_mod, runner = harness.load_parts(
        CELL, rehearse=True)
    cfg = {**cfg, **(overrides or {})}
    mix = {**mix, **(mix_overrides or {})}
    ring, theta0 = harness.seeded_inputs(cell, cfg, mix, ref_mod, seed)
    want = harness.follow_reference(check, ref_mod, cell, cfg, mix, ring,
                                    theta0)
    state = runner.build(cell, cfg, model_mod, theta0(), mix)
    try:
        got = harness.follow_program(check, runner, state, cell, ring, theta0)
    finally:
        runner.close(state)
    return {k: v[0] for k, v in check.compare(got, want).items()}, got, want


def test_the_model_trains_through_trainstep_as_the_reference_does():
    """Loss of both steps, the first gradient as the optimizer gets it and
    the update, leaf by leaf: bfloat16 against float32 at tiny widths."""
    numbers, got, want = _follow(11)
    assert numbers["loss_gap"] < 1e-4
    assert numbers["grad_norm_gap"] < 0.03
    assert numbers["update_norm_gap"] < 0.02
    assert numbers["grad_diff"] < 0.03
    # two terms: more than the cross-entropy of a uniform guess alone
    assert want["losses"][0] > 1.25 * np.log(16160)
    # the selection bias gets no gradient, in either; nor does a router
    # held still, as the cell's configuration has it
    for side in (got, want):
        zero = [n for n, g in side["grad_norms"].items() if g == 0.0]
        assert sorted(zero) == [
            "layers.router.bias[0]", "layers.router.bias[1]",
            "layers.router.w[0]", "layers.router.w[1]", "mtp.router.bias",
            "mtp.router.w"]
    # the embedding and the head are each one leaf with two uses
    assert "tok" in want["grad_norms"] and "head.w" in want["grad_norms"]


def test_the_model_on_the_kernel_path_agrees_too(kernels_on):
    """Rows of 512, so that every block's attention runs the flash kernels
    (interpret mode) inside the recomputed step."""
    monitor.stat_reset()
    numbers, _, _ = _follow(12, {"num_hidden_layers": 2},
                            {"batch": 1, "seq": 512})
    stats = monitor.all_stats()
    # a dense layer, an expert layer and the module's block
    assert stats["pallas.selected.mla_attention"] >= 3
    assert stats["recompute.kept.attn_out"] == 3
    assert stats["recompute.kept.attn_lse"] == 3
    assert numbers["loss_gap"] < 1e-4
    assert numbers["grad_norm_gap"] < 0.03
    assert numbers["update_norm_gap"] < 0.02
    assert numbers["grad_diff"] < 0.03
