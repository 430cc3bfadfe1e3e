"""DeepSeek-Sparse-Attention over grouped-query heads as Keye-VL-2.0 has
it: the indexer's selection (``F.dsa_indexer``), the attention under it
(``F.sparse_attention``) and the indexer's loss (``F.dsa_indexer_loss``)
against the plain reference of benchmark/reference/keye_vl2.py, the
Pallas kernels in interpret mode against the XLA path, and the whole
model through ``TrainStep`` against the reference's loss and gradients.
"""
import copy
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
from paddle_tpu.utils import monitor

sa = importlib.import_module("paddle_tpu.ops.pallas.sparse_attention")

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCH)
from reference import keye_vl2 as ref  # noqa: E402

T, A, KV, D, J, DI = 128, 4, 2, 16, 8, 8


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(seed=0, batch=2, seq=T, weight=0.3):
    ks = jax.random.split(jax.random.key(seed), 6)
    return dict(
        q=jax.random.normal(ks[0], (batch, seq, A, D)),
        k=jax.random.normal(ks[1], (batch, seq, KV, D)),
        v=jax.random.normal(ks[2], (batch, seq, KV, D)),
        qI=jax.random.normal(ks[3], (batch, seq, J, DI)),
        kI=jax.random.normal(ks[4], (batch, seq, DI)),
        w=weight * jax.random.normal(ks[5], (batch, seq, J)))


def _program(x, topk):
    """-> (out [B, T, A * D], the indexer's loss) through the functionals."""
    t = {n: paddle.to_tensor(np.asarray(a)) for n, a in x.items()}
    return _program_arrays(topk, **{n: a.data for n, a in t.items()})


def _program_arrays(topk, q, k, v, qI, kI, w):
    as_t = paddle.Tensor
    mask, idx_lse = F.dsa_indexer(as_t(qI), as_t(kI), as_t(w), topk)
    out, lse = F.sparse_attention(as_t(q), as_t(k), as_t(v), mask,
                                  return_lse=True)
    kl = F.dsa_indexer_loss(as_t(qI), as_t(kI), as_t(w), mask, idx_lse,
                            as_t(q), as_t(k), lse)
    B, S = q.shape[:2]
    return out.data.reshape(B, S, A * D), kl.data


def _reference(topk, q, k, v, qI, kI, w):
    rows = [ref.sparse_attention(q[b], k[b], v[b], qI[b], w[b], kI[b], topk,
                                 lambda a: a) for b in range(q.shape[0])]
    return (jnp.stack([o for o, _ in rows]),
            jnp.mean(jnp.stack([kl for _, kl in rows])))


def _scalar(fn, topk):
    def f(x):
        out, kl = fn(topk, **x)
        return jnp.sum(out * jnp.cos(jnp.arange(A * D))) + 3.0 * kl
    return f


@pytest.mark.parametrize("tier", ["xla", "kernels"])
@pytest.mark.parametrize("topk", [40, 200], ids=["top40", "all_visible"])
def test_against_the_reference(tier, topk, request):
    """Output, loss and every gradient; ``topk`` 200 over rows of 128 is
    the case in which every visible key is kept."""
    if tier == "kernels":
        request.getfixturevalue("kernels_on")
    x = _inputs(0)
    got, want = _program_arrays(topk, **x), _reference(topk, **x)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-5)
    assert float(got[1]) == pytest.approx(float(want[1]), rel=1e-5)
    g = jax.grad(_scalar(_program_arrays, topk))(x)
    r = jax.grad(_scalar(_reference, topk))(x)
    for n in r:
        np.testing.assert_allclose(
            g[n], r[n], rtol=2e-4, atol=2e-5 * float(jnp.abs(r[n]).max()),
            err_msg=n)


@pytest.mark.parametrize("tier", ["xla", "kernels"])
def test_rows_no_longer_than_topk_are_causal_gqa(tier, request):
    if tier == "kernels":
        request.getfixturevalue("kernels_on")
    x = _inputs(1, seq=64)
    out, _ = _program_arrays(64, **x)
    rep = A // KV
    want = F.scaled_dot_product_attention(
        paddle.to_tensor(np.asarray(x["q"])),
        paddle.to_tensor(np.repeat(np.asarray(x["k"]), rep, 2)),
        paddle.to_tensor(np.repeat(np.asarray(x["v"]), rep, 2)),
        is_causal=True)
    np.testing.assert_allclose(
        out, np.asarray(want.data).reshape(out.shape), rtol=1e-4, atol=1e-5)


def test_equal_scores_keep_the_lower_positions(kernels_on):
    """All head weights zero: every score is 0, so query t keeps its
    first ``topk`` positions, in the kernels (a tied threshold takes the
    exact rule) as in the XLA path and in the reference."""
    x = _inputs(2, weight=0.0)
    topk = 24
    for fn in (sa.dsa_select, sa.dsa_select_xla):
        mask, lse = fn(x["qI"], x["w"], x["kI"], topk)
        keep = np.asarray(mask[0])                        # [keys, queries]
        for t in (0, 5, 23, 24, 90, T - 1):
            n = min(t + 1, topk)
            assert keep[:n, t].all() and not keep[n:, t].any(), (fn, t)
        np.testing.assert_allclose(
            lse[0], np.log(np.minimum(np.arange(T) + 1, topk)), rtol=1e-6)
    got, want = _program_arrays(topk, **x), _reference(topk, **x)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-5)


def test_kernels_match_the_xla_path_in_bfloat16(kernels_on):
    """Several blocks a row, bfloat16 operands: the mask bit for bit, the
    rest to bfloat16's rounding."""
    x = {n: a.astype(jnp.bfloat16) if n != "w" else a
         for n, a in _inputs(3, batch=1, seq=256).items()}
    m_k, l_k = sa.dsa_select(x["qI"], x["w"], x["kI"], 48, block=64)
    m_x, l_x = sa.dsa_select_xla(x["qI"], x["w"], x["kI"], 48)
    assert bool(jnp.all(m_k == m_x))
    assert int(m_k[0, :, 200].sum()) == 48
    np.testing.assert_allclose(l_k, l_x, rtol=1e-5)
    o_k, lse_k = sa.sparse_attention(x["q"], x["k"], x["v"], m_x, block=64)
    o_x, lse_x = sa.sparse_attention_xla(x["q"], x["k"], x["v"], m_x)
    np.testing.assert_allclose(o_k.astype(jnp.float32),
                               o_x.astype(jnp.float32), atol=3e-2)
    np.testing.assert_allclose(lse_k, lse_x, atol=2e-2)
    kl_k = sa.dsa_kl(x["qI"], x["w"], x["kI"], m_x, l_x, x["q"], x["k"],
                     lse_x, block=64)
    kl_x = sa.dsa_kl_xla(x["qI"], x["w"], x["kI"], m_x, l_x, x["q"], x["k"],
                         lse_x)
    assert float(kl_k) == pytest.approx(float(kl_x), rel=2e-2)


@pytest.mark.parametrize("topk", [24, 96], ids=["top24", "causal"])
@pytest.mark.parametrize("key_blocks", [1, 3])
@pytest.mark.parametrize("group", [1, 4])
def test_the_backward_walk_makes_dq_dk_and_dv(kernels_on, pallas_eqns, group,
                                              key_blocks, topk):
    """One kernel in the backward (``sparse_bwd_dkv``) and its dq, dk and
    dv against the blocked XLA path in float32.  One block a row zeroes
    and emits in the same grid step; three carry a head's dQ^T across its
    key blocks and dk / dv across the heads of a group; ``topk`` 96 over
    rows of 96 keeps every visible key (the causal case)."""
    block, seq = 96 // key_blocks, 96
    ks = jax.random.split(jax.random.key(7), 7)
    q = jax.random.normal(ks[0], (2, seq, 4, D))
    k, v = (jax.random.normal(a, (2, seq, 4 // group, D)) for a in ks[1:3])
    mask, _ = sa.dsa_select_xla(
        jax.random.normal(ks[3], (2, seq, J, DI)),
        0.3 * jax.random.normal(ks[4], (2, seq, J)),
        jax.random.normal(ks[5], (2, seq, DI)), topk)
    ct = jax.random.normal(ks[6], q.shape)

    def through(fn, **kw):
        return jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v, mask, **kw)[0] * ct),
            (0, 1, 2))

    kernels = through(sa.sparse_attention, block=block)
    before = monitor.get_stat("pallas.sparse.bwd_fused")
    names = [e.params["name"] for e in pallas_eqns(
        jax.make_jaxpr(kernels)(q, k, v).jaxpr)]
    assert names == ["sparse_fwd", "sparse_bwd_dkv"]
    assert monitor.get_stat("pallas.sparse.bwd_fused") == before + 1
    for name, g, r in zip("qkv", kernels(q, k, v),
                          through(sa.sparse_attention_xla)(q, k, v)):
        np.testing.assert_allclose(
            g, r, rtol=2e-4, atol=2e-5 * float(jnp.abs(r).max()),
            err_msg="d" + name)


def _kl_operands(dtype, seq=256, topk=48):
    """Inputs of the loss over rows of four blocks of 64, the selection
    and the attention's lse from the XLA path."""
    x = {n: a.astype(dtype) if n != "w" else a
         for n, a in _inputs(6, batch=2, seq=seq).items()}
    mask, idx_lse = sa.dsa_select_xla(x["qI"], x["w"], x["kI"], topk)
    _, lse = sa.sparse_attention_xla(x["q"], x["k"], x["v"], mask)
    return x, mask, idx_lse, lse


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
def test_the_loss_kernel_makes_its_gradients_with_its_value(kernels_on,
                                                            dtype, tol):
    """One ``dsa_kl`` call in the differentiated program, none in its
    backward; value and the three gradients against ``jax.grad`` of the
    XLA path under an upstream cotangent of 2.5; nothing reaches q or k."""
    x, mask, idx_lse, lse = _kl_operands(dtype)

    def through(fn, **kw):
        def loss(qI, w, kI, q, k):
            return 2.5 * fn(qI, w, kI, mask, idx_lse, q, k, lse, **kw)
        return jax.value_and_grad(loss, (0, 1, 2, 3, 4))

    args = [x[n] for n in ("qI", "w", "kI", "q", "k")]
    kernels = through(sa.dsa_kl, block=64)
    text = str(jax.make_jaxpr(kernels)(*args))
    assert len(re.findall(r"\bname=dsa_kl\b", text)) == 1
    assert "dsa_kl_bwd" not in text
    (got, got_g), (want, want_g) = kernels(*args), through(sa.dsa_kl_xla)(
        *args)
    assert float(got) == pytest.approx(float(want), rel=tol)
    for name, g, r in zip(("qI", "w", "kI"), got_g, want_g):
        assert g.dtype == r.dtype == x[name].dtype
        g, r = g.astype(jnp.float32), r.astype(jnp.float32)
        np.testing.assert_allclose(
            g, r, rtol=10 * tol, atol=tol * float(jnp.abs(r).max()),
            err_msg=name)
    assert not any(bool(jnp.any(g != 0)) for g in got_g[3:])


def test_the_undifferentiated_loss_runs_the_value_only_kernel(kernels_on,
                                                              pallas_eqns):
    """``_kl`` itself: the same body without the gradient outputs, and the
    same number bit for bit as the differentiated call's."""
    x, mask, idx_lse, lse = _kl_operands(jnp.float32)

    def loss(qI):
        return sa.dsa_kl(qI, x["w"], x["kI"], mask, idx_lse, x["q"], x["k"],
                         lse, block=64)

    def outputs(fn):
        return [len(e.outvars)
                for e in pallas_eqns(jax.make_jaxpr(fn)(x["qI"]).jaxpr)]

    assert outputs(loss) == [1]
    assert outputs(jax.grad(loss)) == [4]
    assert np.asarray(loss(x["qI"])).tobytes() == np.asarray(
        jax.value_and_grad(loss)(x["qI"])[0]).tobytes()


def test_the_functionals_take_the_kernels_only_where_the_tier_is_on(
        kernels_on):
    from paddle_tpu.core.flags import set_flags
    x = _inputs(4, batch=1, seq=64)

    def counts():
        s = monitor.all_stats()
        return tuple(s.get(n, 0) for n in (
            "pallas.selected.sparse_attention", "sparse_attention.xla_path",
            "pallas.selected.dsa_indexer", "dsa_indexer.xla_path"))

    before = counts()
    _program(x, 16)
    assert counts() == (before[0] + 1, before[1], before[2] + 1, before[3])
    set_flags({"pallas_interpret": False})
    before = counts()
    _program(x, 16)
    assert counts() == (before[0], before[1] + 1, before[2], before[3] + 1)


# ------------------------------------------------ the model, end to end --
@pytest.fixture(scope="module")
def harness():
    import run
    return run


CELL = "keye_vl2_30b_a3b.train_bf16_b4_s8192"


def _cell(harness, dtype):
    cell, cfg, mix, model_mod, reference, runner = harness.load_parts(
        CELL, rehearse=True)
    cell = copy.deepcopy(cell)
    cell["dtype"] = dtype
    ring, theta0 = harness.seeded_inputs(cell, cfg, mix, reference, seed=5)
    return cell, cfg, mix, model_mod, reference, runner, ring, theta0


@pytest.mark.parametrize("tier", ["xla", "kernels"])
def test_keye_vl2_through_trainstep_against_the_reference(harness, tier,
                                                          request):
    """Float32 at the rehearsal's widths (two layers, 4 of 16 experts
    held, top-16 of rows of 64): the loss to 1e-6 and the first gradient
    leaf by leaf (from Adam's first moment, before any clip)."""
    if tier == "kernels":
        request.getfixturevalue("kernels_on")
    import check
    cell, cfg, mix, model_mod, reference, runner, ring, theta0 = _cell(
        harness, "float32")
    cell["optimizer"]["clip_global_norm"] = 1e9
    ids, labels = ring[0]
    want_loss, want_grad = jax.value_and_grad(reference.loss)(
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
            got, want, rtol=3e-4, atol=3e-5 * float(np.abs(want).max()),
            err_msg=leaf)


def test_keye_vl2_bf16_o2_follows_the_reference(harness):
    """The cell's own path at the rehearsal's widths, bfloat16 O2 through
    ``TrainStep``, by the comparison that decides ``correct``: inside the
    rehearsal's limits."""
    import check
    cell, cfg, mix, model_mod, reference, runner, ring, theta0 = _cell(
        harness, "bfloat16")
    want = harness.follow_reference(check, reference, cell, cfg, mix, ring,
                                    theta0)
    state = runner.build(cell, cfg, model_mod, theta0(), mix)
    try:
        got = harness.follow_program(check, runner, state, cell, ring, theta0)
    finally:
        runner.close(state)
    numbers = check.compare(got, want)
    assert check.verdict(numbers, cell["check"]["limits"]), numbers
