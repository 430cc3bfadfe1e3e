"""EvaByte's layers: EVA attention (XLA path and the kernels in interpret
mode) against an oracle that builds every query's key set literally,
RMSNorm and rotary positions against closed forms, the multi-byte loss,
the kernels' block counters, and the whole model through ``TrainStep``
against the benchmark's plain reference.
"""
import importlib
import math
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
from paddle_tpu.core.flags import set_flags
from paddle_tpu.utils import monitor

eva = importlib.import_module("paddle_tpu.ops.pallas.eva_attention")

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


# (window, chunk, seq, kernel block): four windows of one block; three
# windows of two blocks; a row inside one window
SHAPES = [(16, 4, 64, None), (32, 8, 96, 16), (64, 4, 48, None)]


def _inputs(seq, heads=2, dim=16, batch=1, seed=0, dtype=jnp.float32):
    r = np.random.RandomState(seed)
    q, k, v = (jnp.asarray(r.randn(batch, seq, heads, dim), dtype)
               for _ in range(3))
    mu, phi = (jnp.asarray(r.randn(heads, dim), dtype) for _ in range(2))
    return q, k, v, mu, phi


def oracle(q, k, v, mu, phi, window, chunk):
    """Every query's key set built literally, in float64 numpy."""
    q, k, v, mu, phi = (np.asarray(a, np.float64) for a in (q, k, v, mu, phi))
    B, S, H, D = q.shape
    scale = D ** -0.5
    W = min(window, S)

    def softmax(x):
        e = np.exp(x - x.max())
        return e / e.sum()

    out = np.zeros_like(q)
    for b in range(B):
        for h in range(H):
            for t in range(S):
                keys, values = [], []
                for m in range((t // W) * W, t + 1):      # the exact keys
                    keys.append(k[b, m, h])
                    values.append(v[b, m, h])
                for c0 in range(0, (t // W) * W, chunk):  # earlier windows
                    kc, vc = k[b, c0:c0 + chunk, h], v[b, c0:c0 + chunk, h]
                    keys.append(softmax(scale * kc @ mu[h]) @ kc)
                    values.append(softmax(scale * kc @ phi[h]) @ vc)
                p = softmax(scale * np.stack(keys) @ q[b, t, h])
                out[b, t, h] = p @ np.stack(values)
    return out


def oracle_jax(q, k, v, mu, phi, window, chunk):
    """The same key sets as dense masks, differentiable."""
    B, S, H, D = q.shape
    scale = D ** -0.5
    W = min(window, S)
    pos = jnp.arange(S)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    exact = (pos[:, None] // W == pos[None] // W) & (pos[None] <= pos[:, None])
    s = jnp.where(exact, s, -jnp.inf)
    if S > W:
        kc = k.reshape(B, S // chunk, chunk, H, D)
        vc = v.reshape(B, S // chunk, chunk, H, D)
        wk = jax.nn.softmax(scale * jnp.einsum("bnchd,hd->bnch", kc, mu), 2)
        wv = jax.nn.softmax(scale * jnp.einsum("bnchd,hd->bnch", kc, phi), 2)
        ks = jnp.einsum("bnch,bnchd->bnhd", wk, kc)
        vs = jnp.einsum("bnch,bnchd->bnhd", wv, vc)
        t = jnp.einsum("bqhd,bnhd->bhqn", q, ks) * scale
        seen = (jnp.arange(S // chunk)[None] * chunk) // W < pos[:, None] // W
        s = jnp.concatenate([s, jnp.where(seen, t, -jnp.inf)], -1)
    p = jax.nn.softmax(s, -1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p[..., :S], v)
    if S > W:
        out = out + jnp.einsum("bhqn,bnhd->bqhd", p[..., S:], vs)
    return out


@pytest.mark.parametrize("window,chunk,seq,block", SHAPES)
@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_eva_attention_outputs_and_gradients(path, window, chunk, seq,
                                             block):
    args = _inputs(seq)
    assert eva.eva_attention_supported(args[0].shape, jnp.float32, window,
                                       chunk)
    if path == "xla":
        attend = eva.eva_attention_xla
    else:
        attend = lambda *a: eva.eva_attention(*a, block=block)
    got = attend(*args, window, chunk)
    np.testing.assert_allclose(np.asarray(got),
                               oracle(*args, window, chunk), atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(oracle_jax(*args, window, chunk)),
        oracle(*args, window, chunk), atol=2e-6)
    weight = jnp.asarray(np.random.RandomState(1).randn(*got.shape),
                         jnp.float32)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a, window, chunk) * weight)

    want = jax.grad(loss(oracle_jax), (0, 1, 2, 3, 4))(*args)
    have = jax.grad(loss(attend), (0, 1, 2, 3, 4))(*args)
    for name, a, b in zip(("q", "k", "v", "mu", "phi"), have, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=name)
    if seq > window:
        assert float(jnp.abs(want[3]).max()) > 1e-3   # mu, phi do get one


def test_the_kernels_in_bfloat16_follow_the_xla_path():
    args = _inputs(64, dtype=jnp.bfloat16)
    got = eva.eva_attention(*args, 16, 4)
    want = oracle(*(a.astype(jnp.float32) for a in args), 16, 4)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=3e-2)


def test_a_row_that_does_not_split_is_refused():
    args = _inputs(40)
    assert not eva.eva_attention_supported(args[0].shape, jnp.float32, 16, 4)
    with pytest.raises(ValueError, match="does not split"):
        eva.eva_attention_xla(*args, 16, 4)


@pytest.mark.parametrize("tier", ["xla", "kernels"])
def test_inside_one_window_it_is_causal_attention(tier, request):
    if tier == "kernels":
        request.getfixturevalue("kernels_on")
    q, k, v, mu, phi = (paddle.to_tensor(np.asarray(a))
                        for a in _inputs(32, seed=3))
    for window in (32, 2048):
        got = F.eva_attention(q, k, v, mu, phi, window, 16)
        want = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        np.testing.assert_allclose(np.asarray(got.data),
                                   np.asarray(want.data), atol=2e-6)


def test_the_functional_takes_the_kernels_only_where_the_tier_is_on(
        kernels_on):
    args = [paddle.to_tensor(np.asarray(a)) for a in _inputs(64)]
    stats = lambda: monitor.all_stats().get("pallas.eva.blocks_local", 0)
    n = stats()
    F.eva_attention(*args, 16, 4)
    assert stats() > n
    set_flags({"pallas_interpret": False})
    n = stats()
    F.eva_attention(*args, 16, 4)
    assert stats() == n


def test_block_counters_for_the_cell_shape():
    """8192 positions, windows of 2048, blocks of 512: a (batch, head)
    walks 10 exact-key blocks a window and, in window w, w summary blocks
    a query block; the forward and the dq kernel count once each."""
    q = jax.ShapeDtypeStruct((1, 8192, 2, 128), jnp.bfloat16)
    vec = jax.ShapeDtypeStruct((2, 128), jnp.bfloat16)
    names = ("pallas.eva.blocks_local", "pallas.eva.blocks_summary")

    def traced(fn):
        before = [monitor.all_stats().get(n, 0) for n in names]
        jax.eval_shape(fn, q, q, q, vec, vec)
        return [monitor.all_stats().get(n, 0) - b
                for n, b in zip(names, before)]

    fwd = lambda *a: eva.eva_attention(*a, 2048, 16)
    assert traced(fwd) == [40, 24]
    assert traced(jax.grad(
        lambda *a: jnp.sum(fwd(*a).astype(jnp.float32)),
        (0, 1, 2, 3, 4))) == [80, 48]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_windows_ask_the_flash_walk_for_dk_and_dv_alone(dtype):
    """EVA's backward borrows the flash family's dK/dV walk over folded
    windows and makes its dQ with the summaries' gradients: it never
    counts ``pallas.flash.bwd_fused``, and the call it makes traces the
    kernel without dQ (two outputs, no accumulator) and gets bit for bit
    the dK and dV of the walk that makes dQ too."""
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    args = _inputs(64, dtype=dtype)
    grad = jax.grad(lambda *a: jnp.sum(eva.eva_attention(
        *a, 16, 4).astype(jnp.float32)), (0, 1, 2, 3, 4))
    fused = monitor.all_stats().get("pallas.flash.bwd_fused", 0)
    text = str(jax.make_jaxpr(grad)(*args))
    assert monitor.all_stats().get("pallas.flash.bwd_fused", 0) == fused
    from paddle_tpu.observability import scopes
    assert [k for k in scopes.KERNELS
            if re.search(rf"\bname={k}\b", text)] == [
        "flash_bwd_dkv", "eva_fwd", "eva_bwd_dq"]

    # the same walk over one folded window, asked both ways
    q, k, v = (jnp.swapaxes(a, 1, 2)[:, :, :16] for a in args[:3])
    do = (q * 0.5 + 0.25).astype(dtype)
    zero, seed = fa.zero_off(), fa.zero_seed()
    out, lse = fa._fwd(q, k, v, zero, zero, seed, 0.25, True, (8, 8), True)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), -1)
    rows8 = [jnp.broadcast_to(a[:, :, None, :], a.shape[:2] + (8, 16))
             for a in (lse, delta)]

    def walk(with_dq):
        return lambda q, k, v, do: fa.bwd_dkv(
            q, k, v, zero, zero, seed, do, *rows8, 0.25, True, (8, 8), True,
            0.0, with_dq=with_dq)

    # the launcher is inlined: its one equation is the kernel's call
    call, = (e for e in jax.make_jaxpr(walk(False))(q, k, v, do).eqns
             if e.primitive.name == "pallas_call")
    assert len(call.outvars) == 2
    assert call.params["grid_mapping"].num_scratch_operands == 0
    alone, with_dq = walk(False)(q, k, v, do), walk(True)(q, k, v, do)
    assert len(alone) == 2 and len(with_dq) == 3
    for a, b in zip(alone, with_dq):
        assert a.dtype == dtype
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


# ----------------------------------------------------- norm and rotary --
@pytest.mark.parametrize("unit_offset", [False, True])
def test_rms_norm_closed_form(unit_offset):
    r = np.random.RandomState(0)
    x = r.randn(3, 5, 8).astype(np.float32)
    layer = nn.RMSNorm(8, epsilon=1e-5, unit_offset=unit_offset)
    np.testing.assert_array_equal(np.asarray(layer.weight.data),
                                  np.full(8, 0.0 if unit_offset else 1.0))
    g = r.randn(8).astype(np.float32)
    layer.weight.data = jnp.asarray(g)
    want = (x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5)
            * (1 + g if unit_offset else g))
    np.testing.assert_allclose(np.asarray(layer(paddle.to_tensor(x)).data),
                               want, rtol=1e-5, atol=1e-6)
    # float32 statistics and the weight's type out, whatever comes in
    layer.weight.data = jnp.asarray(g, jnp.bfloat16)
    out = layer(paddle.to_tensor(x)).data
    assert out.dtype == jnp.bfloat16
    g16 = np.asarray(jnp.asarray(g, jnp.bfloat16), np.float32)
    want = (x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5)
            * (1 + g16 if unit_offset else g16))
    np.testing.assert_allclose(np.asarray(out, np.float32), want, rtol=1e-2)


def test_rotary_embedding_closed_form():
    r = np.random.RandomState(0)
    x = r.randn(2, 7, 3, 8).astype(np.float32)
    got = np.asarray(F.rotary_embedding(paddle.to_tensor(x), 100.0).data)
    for p in range(7):
        for i in range(4):
            ang = p * 100.0 ** (-2 * i / 8)
            a, b = x[:, p, :, i], x[:, p, :, i + 4]
            np.testing.assert_allclose(
                got[:, p, :, i], a * math.cos(ang) - b * math.sin(ang),
                rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(
                got[:, p, :, i + 4], b * math.cos(ang) + a * math.sin(ang),
                rtol=1e-5, atol=1e-6)
    # position 0 is left alone; a rotation keeps every pair's length
    np.testing.assert_allclose(got[:, 0], x[:, 0], rtol=1e-6)
    np.testing.assert_allclose((got ** 2).sum(-1), (x ** 2).sum(-1),
                               rtol=1e-5)
    # explicit positions: the same rotation wherever the vector sits
    shifted = np.asarray(F.rotary_embedding(
        paddle.to_tensor(x), 100.0,
        position_ids=paddle.to_tensor(np.arange(7) + 5)).data)
    np.testing.assert_allclose(
        shifted[:, 0], np.asarray(F.rotary_embedding(
            paddle.to_tensor(np.repeat(x[:, :1], 7, 1)), 100.0).data)[:, 5],
        rtol=1e-5, atol=1e-6)


# ------------------------------------------------ the model, end to end --
@pytest.fixture(scope="module")
def harness():
    sys.path.insert(0, BENCH)
    import run
    return run


CELL = "evabyte.train_bf16_b1_s8192"


def _float32_cell(harness):
    import copy
    cell, cfg, mix, model_mod, ref, runner = harness.load_parts(
        CELL, rehearse=True)
    cell = copy.deepcopy(cell)
    cell["dtype"] = "float32"
    ring, theta0 = harness.seeded_inputs(cell, cfg, mix, ref, seed=5)
    return cell, cfg, mix, model_mod, ref, runner, ring, theta0


@pytest.mark.parametrize("tier", ["xla", "kernels"])
def test_evabyte_through_trainstep_against_the_reference(harness, tier,
                                                         request):
    """Float32 at the rehearsal's widths: the loss to 1e-6 and the first
    gradient leaf by leaf (from Adam's first moment, before any clip: the
    clip is set out of reach)."""
    if tier == "kernels":
        request.getfixturevalue("kernels_on")
    import check
    cell, cfg, mix, model_mod, ref, runner, ring, theta0 = _float32_cell(
        harness)
    cell["optimizer"]["clip_global_norm"] = 1e9
    ids, labels = ring[0]
    want_loss, want_grad = jax.value_and_grad(ref.loss)(
        theta0(), jnp.asarray(ids), jnp.asarray(labels), cfg, {})
    state = runner.build(cell, cfg, model_mod, theta0(), mix)
    try:
        got_loss = float(runner.dispatch(state, runner.feed(state, ids,
                                                            labels)))
        moments = runner.moments(state)
    finally:
        runner.close(state)
    assert abs(got_loss - float(want_loss)) / float(want_loss) < 1e-6
    assert sorted(moments) == sorted(check.expanded_keys(want_grad))
    for leaf, m in moments.items():
        got = np.asarray(m) / (1 - cell["optimizer"]["beta1"])
        want = np.asarray(check.take(want_grad, leaf))
        np.testing.assert_allclose(
            got, want, rtol=2e-4, atol=2e-5 * float(np.abs(want).max()),
            err_msg=leaf)


def test_the_multi_byte_loss_drops_exactly_the_pairs_past_the_row(harness):
    """Labels at t + j >= S never reach the loss: with V = 3 and logits
    that put everything on class 0, the loss is the share of kept pairs
    whose label is not 0, times the constant gap."""
    cell, cfg, mix, model_mod, ref, runner, ring, theta0 = _float32_cell(
        harness)
    P, S, B = cfg["num_pred_heads"], mix["seq"], mix["batch"]
    model, loss_fn = model_mod.build(cfg, {})
    r = np.random.RandomState(0)
    out = paddle.to_tensor(r.randn(B, S, cfg["hidden_size"])
                           .astype(np.float32))
    labels = r.randint(0, cfg["vocab_size"], (B, S)).astype(np.int32)
    z = np.asarray(out.data) @ np.asarray(model.head.weight.data)
    logp = np.asarray(jax.nn.log_softmax(
        jnp.asarray(z).reshape(B, S, P, cfg["vocab_size"]), -1))
    total, pairs = 0.0, 0
    for b in range(B):
        for t in range(S):
            for j in range(P):
                if t + j < S:
                    total -= float(logp[b, t, j, labels[b, t + j]])
                    pairs += 1
    assert pairs == B * (P * S - P * (P - 1) // 2)
    got = float(loss_fn(out, paddle.to_tensor(labels)).data)
    assert got == pytest.approx(total / pairs, rel=1e-5)
    assert float(ref.multi_byte_loss(jnp.asarray(z), jnp.asarray(labels),
                                     P)) == pytest.approx(got, rel=1e-5)


def test_the_new_functionals_run_under_their_scopes():
    """``op_name``s of a jitted block carry ``rms_norm``, ``rope``,
    ``eva_attention`` and, inside it, ``eva_pool`` (observability/
    scopes.py): the benchmark's EVA metrics read them."""
    from paddle_tpu.observability import scopes
    q, k, v, mu, phi = _inputs(64)

    def block(q, k, v, mu, phi, g):
        q = F.rms_norm(paddle.to_tensor(q), paddle.to_tensor(g), 1e-5, True)
        q = F.rotary_embedding(q, 1e5)
        return F.eva_attention(q, paddle.to_tensor(k), paddle.to_tensor(v),
                               paddle.to_tensor(mu), paddle.to_tensor(phi),
                               16, 4).data

    text = jax.jit(block).lower(q, k, v, mu, phi,
                                jnp.zeros(16)).compile().as_text()
    for scope in (scopes.RMS_NORM, scopes.ROPE, scopes.EVA_ATTENTION):
        assert f"/{scope}/" in text, scope
    assert f"/{scopes.EVA_ATTENTION}/{scopes.EVA_POOL}/" in text
