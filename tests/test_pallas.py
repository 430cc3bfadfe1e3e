"""Pallas kernel tests (interpret mode on the CPU mesh; the same kernels
compile on TPU — parity there was measured during bring-up).

Modelled on the reference's fused-op tests (test_fused_attention_op.py
pattern: fused output vs composed-op oracle, fwd + grad)."""
import functools
import glob
import importlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import flags as flags_mod
from paddle_tpu.core.flags import get_flag, set_flags
from paddle_tpu.ops.pallas import (flash_attention,
                                   flash_attention_supported, mha_reference)

# the package re-exports a function under the module's name
fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


@pytest.fixture
def kernel_from_16(monkeypatch, kernels_on):
    """The kernel at the tests' small shapes, which the measured
    crossover leaves to XLA."""
    monkeypatch.setattr(fa, "_KERNEL_FROM", 16)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_flash_forward_parity(causal, dtype, tol):
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(2, 128, 2, 32), dtype)
    k = jnp.asarray(r.randn(2, 128, 2, 32), dtype)
    v = jnp.asarray(r.randn(2, 128, 2, 32), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grad_parity(causal):
    r = np.random.RandomState(1)
    q = jnp.asarray(r.randn(1, 64, 2, 16), jnp.float32)
    k = jnp.asarray(r.randn(1, 64, 2, 16), jnp.float32)
    v = jnp.asarray(r.randn(1, 64, 2, 16), jnp.float32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
        return jnp.sum(o * o)

    def loss_ref(q, k, v):
        o = mha_reference(q, k, v, causal=causal)
        return jnp.sum(o * o)

    gf = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("L,block_q,block_k", [
    (256, 64, 64),      # full loop, diagonal loop and their boundary
    (256, 128, 32),     # a q block's diagonal spans several k blocks
    (256, 32, 128),     # several q blocks share one diagonal k block
    (128, 128, 128),    # one block: the diagonal loop alone
])
def test_flash_causal_block_schedule_parity(L, block_q, block_k, dtype,
                                            tol):
    """Forward and gradients of the aligned causal schedule (unmasked
    blocks below the diagonal, masked blocks on it) against the oracle,
    with block_q != block_k too."""
    r = np.random.RandomState(7)
    q, k, v = (jnp.asarray(r.randn(2, L, 2, 32), dtype) for _ in range(3))

    def sq(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) ** 2)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block_q,
                               block_k=block_k)

    def oracle(q, k, v):
        return mha_reference(q, k, v, causal=True)

    np.testing.assert_allclose(np.asarray(flash(q, k, v), np.float32),
                               np.asarray(oracle(q, k, v), np.float32),
                               atol=tol)
    got = jax.grad(sq(flash), (0, 1, 2))(q, k, v)
    ref = jax.grad(sq(oracle), (0, 1, 2))(q, k, v)
    for a, b in zip(got, ref):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max())


@pytest.mark.parametrize("L,D", [(512, 64), (1024, 96), (2048, 96)],
                         ids=["bert", "gpt1024", "gpt2048"])
def test_flash_chosen_blocks_tile_the_sequence(L, D):
    from paddle_tpu.ops.pallas.flash_attention import _resolve_blocks
    block_q, block_k = _resolve_blocks(None, None, L, L)
    assert L % block_q == 0 and L % block_k == 0
    assert block_q % 128 == 0 and block_k % 128 == 0
    shape = (4, L, 12, D)
    assert flash_attention_supported(shape, shape, jnp.bfloat16)
    # what is chosen is what an explicit caller would have to pass
    assert flash_attention_supported(shape, shape, jnp.bfloat16,
                                     block_q=block_q, block_k=block_k)


def _counted(names, fn, *args):
    """By how much ``fn(*args)`` moved each of the program's counters."""
    from paddle_tpu.utils import monitor
    before = monitor.all_stats()
    fn(*args)
    after = monitor.all_stats()
    return tuple(after.get(n, 0) - before.get(n, 0) for n in names)


def test_flash_block_counters():
    """pallas.flash.blocks_full / blocks_masked: block iterations per
    (batch, head) of each kernel traced, by whether they run a mask."""
    def counts(fn, *args):
        return _counted(("pallas.flash.blocks_full",
                         "pallas.flash.blocks_masked"), fn, *args)

    r = np.random.RandomState(8)
    q, k, v = (jnp.asarray(r.randn(1, 256, 1, 16), jnp.float32)
               for _ in range(3))

    def fwd(causal):
        return lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=64, block_k=64)

    # 4 x 4 blocks: 6 below the diagonal, 4 on it, 6 skipped
    assert counts(fwd(True), q, k, v) == (6, 4)
    assert counts(fwd(False), q, k, v) == (16, 0)
    # differentiated: the forward kernel and the one backward walk, the
    # same pairs each
    grad = jax.grad(lambda q, k, v: jnp.sum(fwd(True)(q, k, v)), (0, 1, 2))
    assert counts(grad, q, k, v) == (12, 8)
    # the cell's shape under the chosen blocks (PERF.md): 2048 / 512
    from paddle_tpu.ops.pallas.flash_attention import _count_blocks
    assert counts(_count_blocks, 2048, 2048, 512, 512, True, True) == (6, 4)
    # the ring path masks every block
    assert counts(_count_blocks, 256, 256, 64, 64, True, False) == (0, 16)


def _traces_one_backward_kernel(grad, *args):
    """The dK/dV walk, under the name the benchmark reads, and no other."""
    text = str(jax.make_jaxpr(grad)(*args))
    return (len(re.findall(r"\bname=flash_bwd_dkv\b", text)) == 1
            and "flash_bwd_dq" not in text)


def _ring_block(q, k, v, q_off, k_off):
    """``flash_attention_block`` on [B, L, H, D]: (out, lse [B, L, H])."""
    off = [jnp.full((1, 1), o, jnp.float32) for o in (q_off, k_off)]
    out, lse = fa.flash_attention_block(
        *(jnp.swapaxes(a, 1, 2) for a in (q, k, v)), *off,
        q.shape[-1] ** -0.5, 32, 32)
    return jnp.swapaxes(out, 1, 2), jnp.swapaxes(lse, 1, 2)


def _ring_block_reference(q, k, v, q_off, k_off):
    s = jnp.einsum("blhd,bshd->bhls", q, k) * q.shape[-1] ** -0.5
    seen = (q_off + jnp.arange(q.shape[1])[:, None]
            >= k_off + jnp.arange(k.shape[1])[None])
    s = jnp.where(seen, s, -1e30)
    lse = jax.nn.logsumexp(s, -1)
    p = jnp.where(seen, jnp.exp(s - lse[..., None]), 0.0)
    return jnp.einsum("bhls,bshd->blhd", p, v), jnp.swapaxes(lse, 1, 2)


# (Lq, Lk, D, Dv, causal, block_q, block_k)
_BACKWARD_WALKS = {
    "causal_4_k_blocks": (256, 256, 32, 32, True, 64, 64),
    "bert_one_block": (128, 128, 64, 64, False, 128, 128),
    "full_4x2_blocks": (256, 128, 32, 32, False, 64, 64),
    "cross_short_q": (64, 256, 16, 16, False, 32, 64),
    "cross_long_q": (256, 64, 16, 16, False, 64, 32),
    "causal_keys_past_the_queries": (128, 256, 16, 16, True, 64, 64),
    "causal_queries_past_the_keys": (256, 128, 16, 16, True, 64, 64),
    "values_narrower": (256, 256, 48, 32, True, 64, 128),
    "values_wider": (128, 128, 16, 40, False, 32, 64),
}


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("walk", sorted(_BACKWARD_WALKS))
def test_flash_backward_is_one_kernel(walk, dtype, tol):
    """The dK/dV walk makes dQ too: a differentiated call traces one
    backward kernel, under the name the benchmark reads, and its three
    gradients are the oracle's (float32 tight, bfloat16 at the forward's
    tolerance)."""
    Lq, Lk, D, Dv, causal, block_q, block_k = _BACKWARD_WALKS[walk]
    r = np.random.RandomState(21)
    q = jnp.asarray(r.randn(2, Lq, 2, D), dtype)
    k = jnp.asarray(r.randn(2, Lk, 2, D), dtype)
    v = jnp.asarray(r.randn(2, Lk, 2, Dv), dtype)
    w = jnp.asarray(r.randn(2, Lq, 2, Dv), jnp.float32)

    def grads(fn):
        return jax.grad(lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=causal).astype(jnp.float32) * w), (0, 1, 2))

    flash = grads(functools.partial(flash_attention, block_q=block_q,
                                    block_k=block_k))
    assert _traces_one_backward_kernel(flash, q, k, v)
    assert _counted(("pallas.flash.bwd_fused",), flash, q, k, v) == (1,)
    for got, want, name in zip(flash(q, k, v),
                               grads(mha_reference)(q, k, v), "qkv"):
        assert got.shape == want.shape and got.dtype == dtype
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert (np.abs(got - want).max()
                <= tol * max(1.0, np.abs(want).max())), name


@pytest.mark.parametrize("q_off,k_off", [(0, 0), (64, 0), (0, 64), (32, 64)],
                         ids=["diagonal", "earlier_shard", "later_shard",
                              "half_hidden"])
def test_flash_backward_of_the_ring_block_takes_the_lse_cotangent(q_off,
                                                                  k_off):
    """The ring path: position mask on every block, ``dlse`` folded into
    delta, queries that see no key (a later shard hides them all)."""
    r = np.random.RandomState(22)
    q, k, v = (jnp.asarray(r.randn(1, 64, 2, 16), jnp.float32)
               for _ in range(3))
    w = jnp.asarray(r.randn(1, 64, 2, 16), jnp.float32)
    u = jnp.asarray(r.randn(1, 64, 2), jnp.float32)

    def grads(fn):
        def loss(q, k, v):
            out, lse = fn(q, k, v, q_off, k_off)
            # a hidden query's lse is about -1e30: no cotangent there,
            # as the ring's merge gives it none
            return jnp.sum(out * w) + jnp.sum(
                jnp.where(lse > -1e29, lse, 0.0) * u)
        return jax.grad(loss, (0, 1, 2))

    flash = grads(_ring_block)
    assert _traces_one_backward_kernel(flash, q, k, v)
    for got, want, name in zip(flash(q, k, v),
                               grads(_ring_block_reference)(q, k, v), "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


def test_flash_cross_attention_shapes():
    r = np.random.RandomState(2)
    q = jnp.asarray(r.randn(2, 64, 2, 16), jnp.float32)
    k = jnp.asarray(r.randn(2, 128, 2, 16), jnp.float32)
    v = jnp.asarray(r.randn(2, 128, 2, 16), jnp.float32)
    out = flash_attention(q, k, v, causal=False, block_q=32, block_k=64)
    ref = mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_supported_capability_checks(kernel_from_16):
    shape = (2, 128, 2, 32)
    assert flash_attention_supported(shape, shape, jnp.float32)
    assert not flash_attention_supported(shape, shape, jnp.float16)
    assert not flash_attention_supported(shape, shape, jnp.float32,
                                         attn_mask=object())
    assert not flash_attention_supported(shape, shape, jnp.float32,
                                         dropout_p=0.1)
    assert not flash_attention_supported((2, 128, 2, 30), shape, jnp.float32)


# ---- which attention sdpa takes: the measured crossover (PERF.md, PR 27)

def _check_rule(monkeypatch, shape, taken):
    assert flash_attention_supported(shape, shape, jnp.bfloat16) == taken


def _check_incapable(monkeypatch, q_shape, dtype, kwargs):
    # long enough for the kernel to pay: only the capability says no
    assert q_shape[1] >= fa._KERNEL_FROM
    assert not flash_attention_supported(q_shape, q_shape, dtype, **kwargs)


def _check_sdpa_counts(monkeypatch, length, tier_on, want):
    import paddle_tpu.nn.functional as F
    if not tier_on:
        monkeypatch.setitem(flags_mod._values, "use_pallas_kernels", False)
    q = paddle.to_tensor(np.ones((2, length, 4, 64), np.float32))
    assert _counted(("pallas.selected.flash_attention",
                     "attention.xla_path"),
                    F.scaled_dot_product_attention, q, q, q) == want


def _check_sdpa_paths_agree(monkeypatch, differentiate):
    """The kernel (interpret mode) against XLA's path, through sdpa."""
    import paddle_tpu.nn.functional as F
    r = np.random.RandomState(11)
    qkv = [jnp.asarray(r.randn(1, 512, 2, 64), jnp.float32)
           for _ in range(3)]

    def run():
        def f(q, k, v):
            out = F.scaled_dot_product_attention(q, k, v).data
            return jnp.sum(out * out) if differentiate else out
        return jax.grad(f, (0, 1, 2))(*qkv) if differentiate else (f(*qkv),)

    kernel = run()
    monkeypatch.setitem(flags_mod._values, "use_pallas_kernels", False)
    for a, b in zip(kernel, run()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def _check_flag_is_gone(monkeypatch, which):
    # the two thresholds a user could set before the crossover was measured
    name = "pallas_attention_" + which
    with pytest.raises(KeyError, match="Unknown flag"):
        get_flag(name)
    with pytest.raises(KeyError, match="Unknown flag"):
        set_flags({name: 16})


@pytest.mark.parametrize("check,args", [
    # the rule at the three cells' attention shapes and on XLA's side
    pytest.param(_check_rule, ((64, 512, 12, 64), True), id="rule-bert_cell"),
    pytest.param(_check_rule, ((8, 2048, 16, 96), True), id="rule-gpt_cell"),
    pytest.param(_check_rule, ((1, 8192, 32, 128), True),
                 id="rule-evabyte_cell"),
    pytest.param(_check_rule, ((85, 384, 12, 64), False), id="rule-384"),
    pytest.param(_check_rule, ((128, 256, 6, 128), False), id="rule-256"),
    # the capability checks, unchanged
    pytest.param(_check_incapable,
                 ((2, 512, 4, 64), jnp.bfloat16, {"attn_mask": object()}),
                 id="incapable-attn_mask"),
    pytest.param(_check_incapable, ((2, 512, 4, 60), jnp.bfloat16, {}),
                 id="incapable-head_dim_60"),
    pytest.param(_check_incapable, ((1, 32768, 1, 64), jnp.float32, {}),
                 id="incapable-over_2MiB"),
    pytest.param(_check_incapable,
                 ((2, 512, 4, 64), jnp.bfloat16, {"dropout_p": 0.1}),
                 id="incapable-dropout_interpreted"),
    pytest.param(_check_incapable, ((2, 520, 4, 64), jnp.bfloat16, {}),
                 id="incapable-blocks_do_not_tile"),
    # (kernel, XLA) as sdpa counts its choice at [2, L, 4, 64]
    pytest.param(_check_sdpa_counts, (512, True, (1, 0)),
                 id="sdpa-512-kernel"),
    pytest.param(_check_sdpa_counts, (256, True, (0, 1)), id="sdpa-256-xla"),
    pytest.param(_check_sdpa_counts, (512, False, (0, 1)),
                 id="sdpa-512-tier_off-xla"),
    pytest.param(_check_sdpa_paths_agree, (False,), id="paths-forward"),
    pytest.param(_check_sdpa_paths_agree, (True,), id="paths-gradient"),
    pytest.param(_check_flag_is_gone, ("min_seqlen",),
                 id="flag_gone-min_seqlen"),
    pytest.param(_check_flag_is_gone, ("dropout_min_seqlen",),
                 id="flag_gone-dropout_min_seqlen"),
])
def test_attention_choice(monkeypatch, kernels_on, check, args):
    check(monkeypatch, *args)


# ---- one gate: every call site asks support.tier_enabled --------------

def _sdpa_takes_kernel():
    import paddle_tpu.nn.functional as F
    q = paddle.to_tensor(np.ones((1, 512, 1, 64), np.float32))
    took = _counted(("pallas.selected.flash_attention",
                     "attention.xla_path"),
                    F.scaled_dot_product_attention, q, q, q)
    assert took in ((1, 0), (0, 1))
    return took == (1, 0)


def _ring_takes_kernel():
    from paddle_tpu.parallel.ring_attention import _flash_eligible
    return _flash_eligible(jnp.ones((1, 512, 1, 64), jnp.float32))


def _paged_takes_kernel():
    from paddle_tpu.ops import attention as attn

    def kernel(*a, **k):
        raise AssertionError("only the gate is asked")
    kernel.interpret_ok = True
    attn.register_paged_attention_kernel(kernel)
    try:
        return attn.paged_attention_supported(
            (2, 4, 128), (8, 8, 1, 128), jnp.float32, 8)
    finally:
        attn.register_paged_attention_kernel(None)


def _eva_takes_kernel():
    import paddle_tpu.nn.functional as F
    r = np.random.RandomState(0)
    qkv = [paddle.to_tensor(r.randn(1, 64, 2, 16).astype(np.float32))
           for _ in range(3)]
    vec = [paddle.to_tensor(r.randn(2, 16).astype(np.float32))
           for _ in range(2)]
    # the kernels count their blocks where they are traced; XLA's path
    # (eva_attention_xla) counts nothing
    return _counted(("pallas.eva.blocks_local",), F.eva_attention,
                    *qkv, *vec, 16, 4) != (0,)


@pytest.mark.parametrize("takes_kernel", [
    _sdpa_takes_kernel, _ring_takes_kernel, _paged_takes_kernel,
    _eva_takes_kernel], ids=["sdpa", "ring", "paged", "eva"])
def test_call_sites_share_the_tier_gate(takes_kernel):
    """``use_pallas_kernels`` on, CPU backend: XLA's path, unless
    ``pallas_interpret`` opts the process into interpret mode."""
    assert get_flag("use_pallas_kernels")
    assert not get_flag("pallas_interpret")
    assert not takes_kernel()
    set_flags({"pallas_interpret": True})
    try:
        assert takes_kernel()
    finally:
        set_flags({"pallas_interpret": False})


def test_tier_flags_are_read_in_one_module():
    root = os.path.dirname(os.path.abspath(paddle.__file__))
    read = re.compile(r"get_flags?\(\s*\[?\s*[\"'](use_pallas_kernels|"
                      r"pallas_interpret)[\"']")
    readers = set()
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        with open(path) as f:
            if read.search(f.read()):
                readers.add(os.path.relpath(path, root))
    assert readers == {os.path.join("ops", "pallas", "support.py")}


def test_sdpa_dispatches_to_flash(kernel_from_16):
    import paddle_tpu.nn.functional as F
    r = np.random.RandomState(3)
    q = paddle.to_tensor(r.randn(1, 64, 2, 16).astype(np.float32),
                         stop_gradient=False)
    k = paddle.to_tensor(r.randn(1, 64, 2, 16).astype(np.float32),
                         stop_gradient=False)
    v = paddle.to_tensor(r.randn(1, 64, 2, 16).astype(np.float32),
                         stop_gradient=False)
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    ref = mha_reference(q.data, k.data, v.data, causal=True)
    np.testing.assert_allclose(np.asarray(out.data), np.asarray(ref),
                               atol=1e-5)
    # autograd flows through the custom vjp
    out.sum().backward()
    assert q.grad is not None and np.isfinite(q.grad.numpy()).all()


def test_ring_attention_flash_path(kernel_from_16):
    from paddle_tpu.distributed.mesh import init_mesh
    from paddle_tpu.parallel.ring_attention import (reference_attention,
                                                    ring_attention)
    mesh = init_mesh({"sp": 4})
    r = np.random.RandomState(4)
    # 32 positions per device >= the lowered threshold -> flash block math
    q = paddle.to_tensor(r.randn(1, 128, 2, 16).astype(np.float32))
    k = paddle.to_tensor(r.randn(1, 128, 2, 16).astype(np.float32))
    v = paddle.to_tensor(r.randn(1, 128, 2, 16).astype(np.float32))
    for causal in (False, True):
        out = ring_attention(q, k, v, is_causal=causal, mesh=mesh)
        ref = reference_attention(q, k, v, is_causal=causal)
        np.testing.assert_allclose(np.asarray(out.data),
                                   np.asarray(ref.data),
                                   rtol=1e-4, atol=1e-5)


def test_ring_attention_flash_grads(kernel_from_16):
    from paddle_tpu.distributed.mesh import init_mesh
    from paddle_tpu.parallel.ring_attention import (
        reference_attention, ring_attention_per_device_flash)
    from jax.sharding import PartitionSpec
    from jax import shard_map
    mesh = init_mesh({"sp": 4})
    r = np.random.RandomState(5)
    qkv = [jnp.asarray(r.randn(1, 128, 2, 16), jnp.float32)
           for _ in range(3)]
    spec = PartitionSpec(None, "sp", None, None)

    def ring_loss(q, k, v):
        fn = shard_map(
            lambda a, b, c: ring_attention_per_device_flash(
                a, b, c, "sp", True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        return jnp.sum(fn(q, k, v) ** 2)

    def ref_loss(q, k, v):
        o = reference_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                                paddle.to_tensor(v), is_causal=True)
        return jnp.sum(o.data ** 2)

    g_ring = jax.grad(ring_loss, (0, 1, 2))(*qkv)
    g_ref = jax.grad(ref_loss, (0, 1, 2))(*qkv)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-5)


def test_ring_attention_non_block_multiple_falls_back(kernel_from_16):
    # local shard 520 is not a multiple of the 512 block: eligibility must
    # reject it and the jnp ring path must produce exact results
    from paddle_tpu.distributed.mesh import init_mesh
    from paddle_tpu.parallel.ring_attention import (reference_attention,
                                                    ring_attention)
    mesh = init_mesh({"sp": 2})
    r = np.random.RandomState(6)
    q = paddle.to_tensor(r.randn(1, 1040, 1, 8).astype(np.float32))
    k = paddle.to_tensor(r.randn(1, 1040, 1, 8).astype(np.float32))
    v = paddle.to_tensor(r.randn(1, 1040, 1, 8).astype(np.float32))
    out = ring_attention(q, k, v, is_causal=True, mesh=mesh)
    ref = reference_attention(q, k, v, is_causal=True)
    assert np.isfinite(np.asarray(out.data)).all()
    np.testing.assert_allclose(np.asarray(out.data), np.asarray(ref.data),
                               rtol=1e-4, atol=1e-5)


def test_supported_vmem_cap():
    # 32k x 64 f32 K/V cannot be staged whole in VMEM -> not supported
    big = (1, 32768, 1, 64)
    assert not flash_attention_supported(big, big, jnp.float32)


def test_flash_dropout_raises_off_tpu():
    import jax
    import jax.numpy as jnp
    import pytest
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    if jax.default_backend() == "tpu":
        pytest.skip("TPU runs dropout in-kernel")
    q = jnp.ones((1, 8, 1, 8), jnp.float32)
    with pytest.raises(NotImplementedError, match="TPU"):
        flash_attention(q, q, q, dropout_p=0.1)


# ---------------------------------------------------------------------------
# fused matmul-epilogue kernels (ISSUE 11 tentpole)
# ---------------------------------------------------------------------------

def _epilogue_case(stages, r, M=(2, 16), K=16, N=128):
    q = {
        "x": jnp.asarray(r.randn(*M, K), jnp.float32),
        "w": jnp.asarray(r.randn(K, N) * 0.3, jnp.float32),
        "b": jnp.asarray(r.randn(N) * 0.1, jnp.float32),
    }
    ops = []
    for st in stages:
        if st[0] == "add":
            ops.append(jnp.asarray(r.randn(*M, N), jnp.float32))
        elif st[0] == "layer_norm":
            if st[2]:
                ops.append(jnp.asarray(1.0 + 0.1 * r.randn(N),
                                       jnp.float32))
            if st[3]:
                ops.append(jnp.asarray(0.1 * r.randn(N), jnp.float32))
    return q, tuple(ops)


@pytest.mark.parametrize("stages", [
    (),
    (("gelu", False),),
    (("gelu", True),),
    (("relu",),),
    (("add",),),
    (("add",), ("layer_norm", 1e-5, True, True)),
    (("layer_norm", 1e-5, True, True),),
], ids=lambda s: "+".join(x[0] for x in s) or "bias_only")
def test_fused_epilogue_fwd_bwd_oracle(stages):
    from paddle_tpu.ops.pallas.fused_epilogue import (
        fused_linear_epilogue, reference_epilogue)
    r = np.random.RandomState(0)
    q, ops = _epilogue_case(stages, r)

    out = fused_linear_epilogue(q["x"], q["w"], q["b"], stages, ops,
                                interpret=True)
    ref = reference_epilogue(q["x"], q["w"], q["b"], stages, ops)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)

    def loss_fused(x, w, b, *ops):
        o = fused_linear_epilogue(x, w, b, stages, ops, interpret=True)
        return jnp.sum(o * o)

    def loss_ref(x, w, b, *ops):
        o = reference_epilogue(x, w, b, stages, ops)
        return jnp.sum(o * o)

    argn = tuple(range(3 + len(ops)))
    gf = jax.grad(loss_fused, argn)(q["x"], q["w"], q["b"], *ops)
    gr = jax.grad(loss_ref, argn)(q["x"], q["w"], q["b"], *ops)
    for a, b in zip(gf, gr):
        scale = max(float(jnp.max(jnp.abs(b))), 1e-6)
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale,
                                   atol=1e-5, rtol=1e-4)


def test_fused_epilogue_bf16():
    from paddle_tpu.ops.pallas.fused_epilogue import (
        fused_linear_epilogue, reference_epilogue)
    r = np.random.RandomState(1)
    stages = (("gelu", True),)
    x = jnp.asarray(r.randn(16, 16), jnp.bfloat16)
    w = jnp.asarray(r.randn(16, 128) * 0.3, jnp.bfloat16)
    b = jnp.asarray(r.randn(128) * 0.1, jnp.bfloat16)
    out = fused_linear_epilogue(x, w, b, stages, interpret=True)
    ref = reference_epilogue(x, w, b, stages)
    assert out.dtype == jnp.bfloat16
    # the kernel holds the f32 accumulator through the epilogue while
    # the composite rounds to bf16 after the matmul — bf16-step
    # tolerance, not parity
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=6e-2, rtol=6e-2)


def test_fused_epilogue_gate():
    from paddle_tpu.ops.pallas.fused_epilogue import \
        fused_epilogue_supported
    ok = fused_epilogue_supported((32, 16), (16, 128), jnp.float32)
    assert ok
    # misaligned N / rows, wrong dtype, K mismatch
    assert not fused_epilogue_supported((32, 16), (16, 100), jnp.float32)
    assert not fused_epilogue_supported((33, 16), (16, 128), jnp.float32)
    assert not fused_epilogue_supported((32, 16), (16, 128), jnp.int32)
    assert not fused_epilogue_supported((32, 8), (16, 128), jnp.float32)
    # operand shape must match its stage
    assert fused_epilogue_supported(
        (32, 16), (16, 128), jnp.float32, (("add",),), ((32, 128),))
    assert not fused_epilogue_supported(
        (32, 16), (16, 128), jnp.float32, (("add",),), ((16, 128),))


# ---------------------------------------------------------------------------
# fused Adam
# ---------------------------------------------------------------------------

def test_fused_adam_trajectory_vs_unfused():
    from paddle_tpu.optimizer.optimizer import Adam
    from paddle_tpu.ops.pallas.fused_adam import fused_adam_update
    r = np.random.RandomState(0)
    opt = Adam(learning_rate=1e-3)
    for shape in [(7,), (130, 33)]:  # pad-exercising ragged shapes
        p = jnp.asarray(r.randn(*shape), jnp.float32)
        s = opt.init_slots(p)
        pf, mf, vf = p, s["m"], s["v"]
        pr, sr = p, dict(s)
        for step in range(1, 7):
            g = jnp.asarray(r.randn(*shape), jnp.float32)
            pf, mf, vf = fused_adam_update(pf, g, mf, vf, 1e-3,
                                           float(step), interpret=True)
            pr, sr = opt.update_param(
                pr, g, sr, jnp.asarray(1e-3, jnp.float32),
                jnp.asarray(step, jnp.float32))
        assert float(jnp.max(jnp.abs(pf - pr))) < 1e-6
        assert float(jnp.max(jnp.abs(mf - sr["m"]))) < 1e-6
        assert float(jnp.max(jnp.abs(vf - sr["v"]))) < 1e-6


def test_fused_adam_eligibility():
    from paddle_tpu import optimizer
    from paddle_tpu.ops.pallas.fused_adam import fused_update_for
    p = jnp.zeros((8, 8), jnp.float32)
    assert fused_update_for(optimizer.Adam(1e-3), [None], [p]) is not None
    # AdamW (decoupled decay), clip, multi-precision, bf16: composite
    assert fused_update_for(
        optimizer.AdamW(1e-3, weight_decay=0.01), [None], [p]) is None
    from paddle_tpu.optimizer.clip import ClipGradByGlobalNorm
    assert fused_update_for(
        optimizer.Adam(1e-3, grad_clip=ClipGradByGlobalNorm(1.0)),
        [None], [p]) is None
    assert fused_update_for(
        optimizer.Adam(1e-3), [None],
        [jnp.zeros((8, 8), jnp.bfloat16)]) is None


# ---------------------------------------------------------------------------
# paged-attention decode kernel
# ---------------------------------------------------------------------------

def _paged_case(r, S=3, H=4, Hkv=2, D=128, page=8, P=4, N=12, layers=0):
    pool_shape = ((layers, N, page, Hkv, D) if layers
                  else (N, page, Hkv, D))
    return (jnp.asarray(r.randn(S, H, D), jnp.float32),
            jnp.asarray(r.randn(*pool_shape), jnp.float32),
            jnp.asarray(r.randn(*pool_shape), jnp.float32),
            jnp.asarray(r.randint(0, N, (S, P)), jnp.int32),
            jnp.asarray([1, 13, 32], jnp.int32)[:S])


def test_paged_decode_kernel_vs_reference_gqa_ragged():
    from paddle_tpu.ops.attention import paged_attention_reference
    from paddle_tpu.ops.pallas.paged_attention import \
        paged_attention_decode
    r = np.random.RandomState(0)
    q, kp, vp, table, lens = _paged_case(r)
    got = paged_attention_decode(q, kp, vp, table, lens, interpret=True)
    ref = paged_attention_reference(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


def test_paged_decode_kernel_stacked_layers():
    from paddle_tpu.ops.attention import paged_attention_reference
    from paddle_tpu.ops.pallas.paged_attention import \
        paged_attention_decode
    r = np.random.RandomState(1)
    q, kp, vp, table, lens = _paged_case(r, layers=3)
    for layer in range(3):
        got = paged_attention_decode(q, kp, vp, table, lens,
                                     layer=layer, interpret=True)
        ref = paged_attention_reference(q, kp, vp, table, lens,
                                        layer=layer)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-6, rtol=2e-6)


def test_paged_decode_gate():
    from paddle_tpu.ops.pallas.paged_attention import \
        paged_decode_supported
    assert paged_decode_supported((4, 4, 128), (9, 8, 2, 128),
                                  jnp.float32, 8)
    assert paged_decode_supported((4, 4, 128), (3, 9, 8, 2, 128),
                                  jnp.float32, 8)          # stacked
    assert not paged_decode_supported((4, 4, 64), (9, 8, 2, 64),
                                      jnp.float32, 8)      # lane align
    assert not paged_decode_supported((4, 4, 128), (9, 6, 2, 128),
                                      jnp.float32, 6)      # page align
    assert not paged_decode_supported((4, 3, 128), (9, 8, 2, 128),
                                      jnp.float32, 8)      # ragged GQA
    assert not paged_decode_supported((4, 4, 128), (9, 8, 2, 128),
                                      jnp.int32, 8)


# ---------------------------------------------------------------------------
# collective-matmul chunk kernel (ISSUE 17)
# ---------------------------------------------------------------------------

def test_chunk_matmul_kernel_vs_matmul():
    from paddle_tpu.ops.pallas.collective_matmul import chunk_matmul
    r = np.random.RandomState(4)
    for m, k, nc in [(16, 128, 128), (256, 256, 128)]:
        x = jnp.asarray(r.standard_normal((m, k)), jnp.float32)
        w = jnp.asarray(r.standard_normal((k, nc)), jnp.float32)
        got = chunk_matmul(x, w, interpret=True)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                                   rtol=1e-6)
    # bf16 operands accumulate in f32, cast back on the way out
    xb = jnp.asarray(r.standard_normal((16, 128)), jnp.bfloat16)
    wb = jnp.asarray(r.standard_normal((128, 128)), jnp.bfloat16)
    assert chunk_matmul(xb, wb, interpret=True).dtype == jnp.bfloat16


def test_chunk_matmul_gate():
    from paddle_tpu.ops.pallas.collective_matmul import \
        chunk_matmul_supported
    f32 = jnp.float32
    assert chunk_matmul_supported((16, 128), (128, 128), f32, f32)
    assert not chunk_matmul_supported((15, 128), (128, 128), f32, f32)
    assert not chunk_matmul_supported((16, 100), (100, 128), f32, f32)
    assert not chunk_matmul_supported((16, 128), (128, 100), f32, f32)
    assert not chunk_matmul_supported((16, 128), (128, 128),
                                      jnp.int32, f32)
    assert not chunk_matmul_supported((2, 16, 128), (128, 128), f32, f32)
    assert not chunk_matmul_supported((16, 128), (64, 128), f32, f32)


def test_collective_matmul_tier_selection_contract():
    """Tier off -> the composite jnp.matmul path, ZERO Pallas
    selections; tier on (interpret opt-in) with qualifying chunk shapes
    -> the chunk kernel is selected and counted, results matching the
    composite."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu import distributed as dist
    from jax import shard_map
    from paddle_tpu.ops.collective_matmul import (all_gather_matmul,
                                                  lowering_label)
    from paddle_tpu.ops.pallas.support import kernel_selections
    dist.init_mesh({"dp": 8})
    mesh = dist.get_mesh()
    r = np.random.RandomState(6)
    x = jnp.asarray(r.standard_normal((16, 128)), jnp.float32)
    w = jnp.asarray(r.standard_normal((128, 1024)), jnp.float32)

    def run():
        def col(wv):
            return all_gather_matmul(x, wv, "dp", 8, ring=True)
        return np.asarray(shard_map(col, mesh=mesh,
                                    in_specs=(P(None, "dp"),),
                                    out_specs=P(), check_vma=False)(w))

    set_flags({"use_pallas_kernels": False})
    try:
        before = dict(kernel_selections)
        off = run()
        assert dict(kernel_selections) == before
        assert lowering_label() == "composite"
        set_flags({"use_pallas_kernels": True, "pallas_interpret": True})
        assert lowering_label() == "pallas"
        on = run()
        assert kernel_selections.get("collective_matmul", 0) \
            > before.get("collective_matmul", 0)
    finally:
        set_flags({"pallas_interpret": False,
                   "use_pallas_kernels": True})
    np.testing.assert_allclose(on, off, rtol=1e-6)


# ---------------------------------------------------------------------------
# executor fusion pass: selection, fallback, OFF contract
# ---------------------------------------------------------------------------

@pytest.fixture
def static_guard():
    paddle.enable_static()
    set_flags({"pallas_interpret": True})
    yield
    set_flags({"pallas_interpret": False, "use_pallas_kernels": True})
    paddle.disable_static()
    paddle.static.reset_default_programs()


def _mini_program(width=128):
    import paddle_tpu.nn.functional as F
    from paddle_tpu import optimizer
    paddle.seed(3)
    main = paddle.static.Program()
    with paddle.static.program_guard(main):
        x = paddle.static.data("x", [None, width], "float32")
        y = paddle.static.data("y", [None, 1], "float32")
        h = paddle.static.nn.fc(x, width, activation="relu")
        loss = F.mse_loss(paddle.static.nn.fc(h, 1), y)
        optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main, loss


def _feed(width, batch=16):
    r = np.random.RandomState(0)
    return {"x": jnp.asarray(r.standard_normal(
                (batch, width)).astype(np.float32)),
            "y": jnp.asarray(r.standard_normal(
                (batch, 1)).astype(np.float32))}


def test_executor_realizes_and_records_selection(static_guard):
    from paddle_tpu.observability import explain_compiles
    main, loss = _mini_program()
    exe = paddle.static.Executor()
    for _ in range(3):
        out = exe.run(main, feed=_feed(128), fetch_list=[loss])
    assert np.isfinite(out[0]).all()
    assert exe.compile_count == 1  # 0 recompiles after warmup
    recs = [r for r in explain_compiles("executor")["records"]
            if r["identity"] == main._serial]
    kernels = recs[-1].get("kernels", [])
    assert any(k.startswith("fused_epilogue[matmul+bias+relu]")
               for k in kernels)
    assert "fused_adam" in kernels
    # analyze marks the same candidate realized (shared matcher); the
    # batch_size hint re-derives the dynamic batch dim — the recorded
    # placeholder of 1 fails the row-tile gate, as it should
    rep = main.analyze(fetch_list=[loss], batch_size=16)
    assert any(c.get("realized") for c in rep.fusion_candidates)
    assert "realized" in rep.render()
    exe.close()


def test_flag_off_is_bitwise_and_selects_nothing(static_guard):
    from paddle_tpu.observability import explain_compiles
    from paddle_tpu.ops.pallas.support import kernel_selections

    def losses(flag):
        set_flags({"use_pallas_kernels": flag})
        main, loss = _mini_program()
        exe = paddle.static.Executor()
        out = [float(exe.run(main, feed=_feed(128),
                             fetch_list=[loss])[0])
               for _ in range(4)]
        serial = main._serial
        exe.close()
        return out, serial

    before = dict(kernel_selections)
    off, off_serial = losses(False)
    assert dict(kernel_selections) == before  # zero Pallas selections
    recs = [r for r in explain_compiles("executor")["records"]
            if r["identity"] == off_serial]
    assert not recs[-1].get("kernels")
    on, _ = losses(True)
    # the tier changes float association; the OFF path must be the
    # exact pre-tier composite, so two OFF runs are bitwise
    off2, _ = losses(False)
    assert off == off2
    assert max(abs(a - b) for a, b in zip(on, off)) < 1e-4


def test_gated_out_shapes_fall_back_to_composite(static_guard):
    from paddle_tpu.observability import explain_compiles
    # width 100 fails the N%128 gate -> no epilogue; fused_adam still
    # eligible and selected
    main, loss = _mini_program(width=100)
    exe = paddle.static.Executor()
    out = exe.run(main, feed=_feed(100), fetch_list=[loss])
    assert np.isfinite(out[0]).all()
    recs = [r for r in explain_compiles("executor")["records"]
            if r["identity"] == main._serial]
    kernels = recs[-1].get("kernels", [])
    assert not any(k.startswith("fused_epilogue") for k in kernels)
    exe.close()


def test_kernel_smoke_in_process():
    import sys
    TOOLS = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    sys.path.insert(0, TOOLS)
    try:
        import kernel_smoke
    finally:
        sys.path.remove(TOOLS)
    failures = kernel_smoke.run_checks()
    assert not failures, failures
