"""``parallel.recompute`` keeps an attention kernel's ``out`` and ``lse``,
the three gradients the indexer's loss makes with its value and what the
sparse-attention kernels take (q, k, v and the selection's mask) across its
replay (``observability.scopes.RESIDUALS``): the gradient of a recomputed
block runs its forward attention kernel, ``dsa_kl`` and the selection once
a layer, not twice, and is bit for bit the bare checkpoint's.  CPU,
interpret mode, tiny shapes."""
import importlib
import inspect
import re

import jax
import jax.numpy as jnp
from jax._src.ad_checkpoint import saved_residuals
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import nn
from paddle_tpu.core import autograd
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit.bind import bind, param_arrays
from paddle_tpu.observability import scopes
from paddle_tpu.parallel import recompute
from paddle_tpu.utils import monitor

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
recompute_mod = importlib.import_module("paddle_tpu.parallel.recompute")

HID, HEADS, SEQ, LAYERS = 32, 2, 32, 2
KEPT = tuple(f"recompute.kept.{name}" for name in scopes.RESIDUALS)
# what a segment counts: nothing, an attention kernel's two, and with the
# indexer's three and the sparse kernels' four operands every name
NOTHING, ALL = (0,) * len(KEPT), (LAYERS,) * len(KEPT)
ATTENTION = (LAYERS, LAYERS) + NOTHING[2:]
IDX_HEADS, IDX_DIM, TOPK = 2, 8, 12


class _AttentionBlock(nn.Layer):
    """Pre-norm attention block; ``attend`` is the subclass's kernel."""

    def __init__(self):
        super().__init__()
        self.ln = nn.LayerNorm(HID)
        self.qkv = nn.Linear(HID, 3 * HID)
        self.proj = nn.Linear(HID, HID)

    def forward(self, x):
        B, S = x.shape[0], x.shape[1]
        qkv = self.qkv(self.ln(x)).reshape([B, S, 3, HEADS, HID // HEADS])
        a = self.attend(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2])
        return x + self.proj(a.reshape([B, S, HID]))


class FlashBlock(_AttentionBlock):
    """Over ``sdpa`` (the flash kernel)."""
    FORWARD = scopes.FLASH_FWD
    BACKWARD = (scopes.FLASH_BWD_DKV,)

    def attend(self, q, k, v):
        return F.scaled_dot_product_attention(q, k, v, is_causal=True)


class EvaBlock(_AttentionBlock):
    """Over ``F.eva_attention``: two windows of 16, chunks of 8."""
    FORWARD = scopes.EVA_FWD
    BACKWARD = (scopes.EVA_BWD_DQ, scopes.FLASH_BWD_DKV)

    def __init__(self):
        super().__init__()
        init = nn.initializer.Normal(0.0, 0.5)
        self.mu = self.create_parameter([HEADS, HID // HEADS],
                                        default_initializer=init)
        self.phi = self.create_parameter([HEADS, HID // HEADS],
                                         default_initializer=init)

    def attend(self, q, k, v):
        return F.eva_attention(q, k, v, self.mu, self.phi, window_size=16,
                               chunk_size=8)


class SparseBlock(_AttentionBlock):
    """Over ``F.dsa_indexer`` / ``F.sparse_attention``, the indexer's loss
    (``F.dsa_indexer_loss``) added to the stream: top 12 of rows of 32."""
    FORWARD = scopes.SPARSE_FWD
    BACKWARD = (scopes.SPARSE_BWD_DKV,)

    def __init__(self):
        super().__init__()
        self.idx_q = nn.Linear(HID, IDX_HEADS * IDX_DIM)
        self.idx_k = nn.Linear(HID, IDX_DIM)
        self.idx_w = nn.Linear(HID, IDX_HEADS)

    def forward(self, x):
        B, S = x.shape[0], x.shape[1]
        h = self.ln(x)
        qkv = self.qkv(h).reshape([B, S, 3, HEADS, HID // HEADS])
        q, k, v = self.normed(qkv[:, :, 0], qkv[:, :, 1]) + (qkv[:, :, 2],)
        qI = self.idx_q(h).reshape([B, S, IDX_HEADS, IDX_DIM])
        kI, w = self.idx_k(h), self.idx_w(h)
        mask, idx_lse = F.dsa_indexer(qI, kI, w, TOPK)
        a, lse = F.sparse_attention(q, k, v, mask, return_lse=True)
        kl = F.dsa_indexer_loss(qI, kI, w, mask, idx_lse, q, k, lse)
        return x + self.proj(a.reshape([B, S, HID])) + kl

    def normed(self, q, k):
        return q, k


class NormedSparseBlock(SparseBlock):
    """The same with q and k normed a head before the kernels, as Keye's
    blocks norm theirs: a norm's backward reads the norm's input."""

    def normed(self, q, k):
        return F.rms_norm(q), F.rms_norm(k)


class PlainBlock(nn.Layer):
    """No kernel, so no named value."""

    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(HID, HID)

    def forward(self, x):
        return x + F.gelu(self.fc(x))


def _bare_checkpoint(blk, x):
    """What ``recompute`` was before it had a policy, and what
    ``TrainStep(recompute=True)`` still wraps a whole loss in."""
    return Tensor(jax.checkpoint(lambda a: blk(Tensor(a)).data)(x.data))


def _no_checkpoint(blk, x):
    return blk(x)


class Stack(nn.Layer):
    def __init__(self, block, wrap):
        super().__init__()
        self.blocks = nn.LayerList([block() for _ in range(LAYERS)])
        self.wrap = wrap

    def forward(self, x):
        for blk in self.blocks:
            x = self.wrap(blk, x)
        return x


@pytest.fixture
def kernels(monkeypatch, kernels_on):
    """The flash kernel at the tests' lengths."""
    monkeypatch.setattr(fa, "_KERNEL_FROM", 16)


def _loss_of(block, wrap):
    """-> (loss over the parameter arrays, the arrays): one seed, so every
    ``wrap`` of a ``block`` starts from the same weights."""
    paddle.seed(7)
    net = Stack(block, wrap)
    x = jnp.asarray(np.random.RandomState(0).randn(2, SEQ, HID), jnp.float32)

    def loss(arrays):
        with bind(net, arrays), autograd.no_grad():
            return (net(Tensor(x)).data ** 2).mean()

    return loss, param_arrays(net)


def _grad_jaxpr(block, wrap):
    loss, arrays = _loss_of(block, wrap)
    return str(jax.make_jaxpr(jax.grad(loss))(arrays))


def _kernel_calls(jaxpr_text, kernel):
    return len(re.findall(rf"\bname={kernel}\b", jaxpr_text))


def _counted(names, fn, *args):
    before = [monitor.get_stat(n) for n in names]
    fn(*args)
    return tuple(monitor.get_stat(n) - b for n, b in zip(names, before))


# (1), (2), (5): forward kernels a layer in the gradient's program
@pytest.mark.parametrize("block,wrap,forwards", [
    pytest.param(FlashBlock, recompute, 1, id="flash-recompute"),
    pytest.param(EvaBlock, recompute, 1, id="eva-recompute"),
    pytest.param(FlashBlock, _bare_checkpoint, 2, id="flash-bare_checkpoint"),
    pytest.param(EvaBlock, _bare_checkpoint, 2, id="eva-bare_checkpoint"),
    pytest.param(FlashBlock, _no_checkpoint, 1, id="flash-no_checkpoint"),
    pytest.param(SparseBlock, recompute, 1, id="sparse-recompute"),
    pytest.param(SparseBlock, _bare_checkpoint, 2,
                 id="sparse-bare_checkpoint"),
    pytest.param(SparseBlock, _no_checkpoint, 1, id="sparse-no_checkpoint"),
])
def test_forward_kernels_a_layer(kernels, block, wrap, forwards):
    text = _grad_jaxpr(block, wrap)
    assert _kernel_calls(text, block.FORWARD) == forwards * LAYERS
    for kernel in block.BACKWARD:
        assert _kernel_calls(text, kernel) == LAYERS, kernel
    if block is SparseBlock:
        # the loss's kernel makes its gradient with its value: no second
        # call for the backward, and none in the replay where it is kept
        assert _kernel_calls(text, scopes.DSA_KL) == forwards * LAYERS


@pytest.mark.parametrize("wrap,replayed", [
    pytest.param(recompute, set(), id="recompute"),
    pytest.param(_bare_checkpoint, {
        scopes.DSA_SCORES, scopes.DSA_THRESHOLD, scopes.SPARSE_FWD,
        scopes.DSA_KL}, id="bare_checkpoint"),
])
def test_the_replayed_segment_holds_no_loss_kernel(kernels, pallas_eqns, wrap,
                                                   replayed):
    """What a sparse block's replay (the gradient program's ``remat2``
    equations) runs besides the backward kernel: nothing, where the mask,
    ``out``, ``lse`` and the loss's gradients are kept; with nothing kept,
    the selection, the attention's forward and ``dsa_kl``."""
    loss, arrays = _loss_of(SparseBlock, wrap)
    ran = [eqn.params["name"] for eqn in pallas_eqns(
        jax.make_jaxpr(jax.grad(loss))(arrays).jaxpr, within="remat2")]
    assert set(ran) - set(SparseBlock.BACKWARD) == replayed
    assert len(ran) == LAYERS * (len(replayed) + len(SparseBlock.BACKWARD))


@pytest.mark.parametrize("block,wrap,projections", [
    pytest.param(SparseBlock, recompute, 0, id="sparse-recompute"),
    pytest.param(NormedSparseBlock, recompute, 1, id="normed-recompute"),
    pytest.param(SparseBlock, _bare_checkpoint, 4,
                 id="sparse-bare_checkpoint"),
])
def test_the_replayed_segment_holds_no_projection(kernels, pallas_eqns, block,
                                                  wrap, projections):
    """The q / k / v projection and the indexer's three in a sparse
    block's replay (the [batch, row, width] results of those widths: a
    kernel's products and a weight's gradient are 2-D): q, k and v as the
    kernels take them are kept and the mask is, so nothing reads any of the
    four a second time; the bare checkpoint replays them all.  Where q and
    k are normed on their way to the kernels the projection stays, for the
    norms' backward, and nothing after the norms does."""
    widths = {3 * HID, IDX_HEADS * IDX_DIM, IDX_DIM, IDX_HEADS}
    loss, arrays = _loss_of(block, wrap)
    jaxpr = jax.make_jaxpr(jax.grad(loss))(arrays).jaxpr
    shapes = [eqn.outvars[0].aval.shape for eqn in pallas_eqns(
        jaxpr, within="remat2", primitive="dot_general")]
    assert len([s for s in shapes if len(s) == 3 and s[-1] in widths]) \
        == projections * LAYERS
    if wrap is recompute:
        assert [eqn.params["name"] for eqn in pallas_eqns(
            jaxpr, within="remat2")] == list(block.BACKWARD) * LAYERS


# (3): the kept values are the ones the replay would have recomputed
@pytest.mark.parametrize("block", [FlashBlock, EvaBlock, SparseBlock,
                                   NormedSparseBlock],
                         ids=["flash", "eva", "sparse", "sparse_normed"])
def test_gradients_are_the_bare_checkpoints_bit_for_bit(
        kernels, monkeypatch, block):
    """Op by op, where no compiler fuses the two programs differently
    (under one ``jax.jit`` XLA:CPU rounds a layer norm's backward
    differently beside a kept value: 7e-9)."""
    def grads(wrap):
        loss, arrays = _loss_of(block, wrap)
        value, grad = jax.value_and_grad(loss)(arrays)
        return [np.asarray(value)] + [np.asarray(g) for g in grad]

    kept, plain = grads(recompute), grads(_no_checkpoint)
    # the same segments under ``jax.checkpoint(policy=None)``
    monkeypatch.setattr(recompute_mod, "_keep_attention_residuals", None)
    bare = grads(recompute)
    for a, b in zip(kept, bare):
        assert a.tobytes() == b.tobytes()
    for a, b in zip(kept, plain):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# (4): a block with no named value is the bare checkpoint
def test_a_block_without_a_named_value_keeps_its_arguments_only():
    paddle.seed(0)
    blk = PlainBlock()
    x = jnp.ones((2, SEQ, HID), jnp.float32)

    def through(wrap):
        def fn(arrays, x):
            with bind(blk, arrays), autograd.no_grad():
                return wrap(blk, Tensor(x)).data.sum()
        return saved_residuals(fn, param_arrays(blk), x)

    kept, bare = through(recompute), through(_bare_checkpoint)
    assert [(aval.shape, aval.dtype) for aval, _ in kept] \
        == [(aval.shape, aval.dtype) for aval, _ in bare]
    assert all("argument" in why for _, why in kept), kept


# (4) again, with a kernel inside: what is pinned beside the arguments
def test_a_flash_block_keeps_out_and_lse_beside_its_arguments(kernels):
    paddle.seed(0)
    blk = FlashBlock()
    x = jnp.ones((2, SEQ, HID), jnp.float32)

    def fn(arrays, x):
        with bind(blk, arrays), autograd.no_grad():
            return recompute(blk, Tensor(x)).data.sum()

    res = saved_residuals(fn, param_arrays(blk), x)
    # ``out`` is also the segment's primal, which jax lists by the
    # ``reduce_precision`` it pins such a value with, not by its name
    kept = sorted(aval.shape for aval, why in res if "argument" not in why)
    assert kept == [(2, HEADS, SEQ), (2, HEADS, SEQ, HID // HEADS)], res
    assert any(f"named '{scopes.ATTN_LSE}'" in why for _, why in res), res


# (4) once more: the loss's three gradients and the kernels' four operands
def test_a_sparse_block_keeps_the_loss_gradients_too(kernels):
    paddle.seed(0)
    blk = SparseBlock()
    x = jnp.ones((2, SEQ, HID), jnp.float32)

    def fn(arrays, x):
        with bind(blk, arrays), autograd.no_grad():
            return recompute(blk, Tensor(x)).data.sum()

    res = saved_residuals(fn, param_arrays(blk), x)
    kept = sorted(aval.shape for aval, why in res if "argument" not in why)
    assert kept == sorted([
        (2, HEADS, SEQ), (2, HEADS, SEQ, HID // HEADS),     # lse, out
        (2, IDX_HEADS, IDX_DIM, SEQ), (2, IDX_HEADS, SEQ),  # dqI^T, dw
        (2, SEQ, IDX_DIM)]                                  # dkI
        + [(2, HEADS, SEQ, HID // HEADS)] * 3               # q, k, v
        + [(2, SEQ, SEQ)]), res                             # the mask
    assert [aval.dtype for aval, why in res if "argument" not in why
            and aval.shape == (2, SEQ, SEQ)] == [jnp.int8]
    # q, k and v, like ``out``, are listed by the ``reduce_precision`` jax
    # pins a float with that the forward pass reads too; an int8 gets none
    for name in scopes.DSA_KL_GRADS + (scopes.SPARSE_MASK,):
        assert any(f"named '{name}'" in why for _, why in res), (name, res)
    assert sum("reduce_precision" in why for _, why in res) == 5, res


# (6): the counter that says the mechanism engaged
@pytest.mark.parametrize("block,wrap,expected", [
    pytest.param(FlashBlock, recompute, ATTENTION, id="flash-recompute"),
    pytest.param(EvaBlock, recompute, ATTENTION, id="eva-recompute"),
    pytest.param(SparseBlock, recompute, ALL, id="sparse-recompute"),
    pytest.param(NormedSparseBlock, recompute, ALL,
                 id="sparse_normed-recompute"),
    pytest.param(SparseBlock, _no_checkpoint, NOTHING,
                 id="sparse-no_checkpoint"),
    pytest.param(PlainBlock, recompute, NOTHING, id="plain-recompute"),
    pytest.param(FlashBlock, _bare_checkpoint, NOTHING,
                 id="flash-bare_checkpoint"),
    pytest.param(SparseBlock, _bare_checkpoint, NOTHING,
                 id="sparse-bare_checkpoint"),
    pytest.param(FlashBlock, _no_checkpoint, NOTHING,
                 id="flash-no_checkpoint"),
])
def test_kept_counters(kernels, block, wrap, expected):
    assert _counted(KEPT, _grad_jaxpr, block, wrap) == expected


@pytest.mark.parametrize("steps", [1, 3], ids=["once", "three_passes"])
def test_a_block_replayed_T_times_keeps_T_of_each_residual(kernels,
                                                           pallas_eqns,
                                                           steps):
    """``nn.LoopedStack`` binds and replays each block ``steps`` times in
    one trace: the policy keeps (and counts) an ``out`` and an ``lse`` a
    block APPLICATION, the gradient's program holds a forward kernel and
    a backward walk an application and no second forward, and a shared
    weight's gradient is the bare checkpoint's bit for bit."""
    def looped(wrap):
        paddle.seed(7)
        net = nn.LoopedStack([FlashBlock() for _ in range(LAYERS)], steps,
                             norm=nn.LayerNorm(HID), recompute=wrap)
        x = jnp.asarray(np.random.RandomState(0).randn(2, SEQ, HID),
                        jnp.float32)

        def loss(arrays):
            with bind(net, arrays), autograd.no_grad():
                return (net(Tensor(x)).data ** 2).mean()

        return loss, param_arrays(net)

    loss, arrays = looped(True)
    calls = steps * LAYERS
    kept = _counted(KEPT, lambda: jax.make_jaxpr(jax.grad(loss))(arrays))
    assert kept == (calls, calls) + NOTHING[2:]
    text = str(jax.make_jaxpr(jax.grad(loss))(arrays))
    assert _kernel_calls(text, scopes.FLASH_FWD) == calls
    assert _kernel_calls(text, scopes.FLASH_BWD_DKV) == calls
    replayed = [eqn.params["name"] for eqn in pallas_eqns(
        jax.make_jaxpr(jax.grad(loss))(arrays).jaxpr, within="remat2")]
    assert scopes.FLASH_FWD not in replayed
    got = jax.grad(loss)(arrays)
    plain, plain_arrays = looped(False)
    want = jax.grad(plain)(plain_arrays)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("block", [FlashBlock, SparseBlock,
                                   NormedSparseBlock],
                         ids=["flash", "sparse", "sparse_normed"])
def test_the_forward_alone_keeps_nothing(kernels, block):
    """Nothing is differentiated, so nothing is kept or counted; the
    loss's kernel is the value-only one (one output)."""
    loss, arrays = _loss_of(block, recompute)
    assert _counted(KEPT, jax.make_jaxpr(loss), arrays) == NOTHING
    if issubclass(block, SparseBlock):
        text = str(jax.make_jaxpr(loss)(arrays))
        assert _kernel_calls(text, scopes.DSA_KL) == LAYERS
        assert not any(name in text for name in scopes.DSA_KL_GRADS)


def test_the_ring_block_names_its_residuals(kernels_on):
    """``flash_attention_block`` (the ring path) under the same policy."""
    r = np.random.RandomState(0)
    q, k, v = (jnp.asarray(r.randn(1, 2, 32, 16), jnp.float32)
               for _ in range(3))
    off = jnp.zeros((1, 1), jnp.float32)

    def f(q, k, v):
        out, lse = fa.flash_attention_block(q, k, v, off, off, 0.25, 16, 16)
        return out.sum() + lse.sum()

    policy = jax.checkpoint_policies.save_only_these_names(*scopes.RESIDUALS)
    grads = {}
    for name, g in (("kept", jax.checkpoint(f, policy=policy)),
                    ("bare", jax.checkpoint(f))):
        grad = jax.grad(g, argnums=(0, 1, 2))
        text = str(jax.make_jaxpr(grad)(q, k, v))
        grads[name] = (_kernel_calls(text, scopes.FLASH_FWD),
                       [np.asarray(a).tobytes() for a in grad(q, k, v)])
    assert grads["kept"][0] == 1 and grads["bare"][0] == 2
    assert grads["kept"][1] == grads["bare"][1]


@pytest.mark.parametrize("outputs,barriers", [(1, 0), (2, 1)],
                         ids=["one_output", "two_outputs"])
def test_several_outputs_leave_a_segment_together(outputs, barriers):
    """A block's stream and its loss term go through one
    ``optimization_barrier`` (the term's kernel may not be put off past the
    next block); a segment with one output has none; gradients are the
    unwrapped function's bit for bit."""
    def fn(x):
        return (F.gelu(x), (x ** 2).mean())[:outputs]

    def loss(wrap):
        def f(a):
            out = wrap(Tensor(a))
            return sum(o.data.sum() for o in out)
        return f

    a = jnp.asarray(np.random.RandomState(1).randn(4, 8), jnp.float32)
    wrapped = loss(lambda x: recompute(fn, x))
    assert str(jax.make_jaxpr(wrapped)(a)).count(
        "optimization_barrier") == barriers
    assert np.asarray(jax.grad(wrapped)(a)).tobytes() \
        == np.asarray(jax.grad(loss(fn))(a)).tobytes()


def test_recompute_takes_no_switch():
    """One behaviour: ``recompute(function, *args)`` and the reference's
    ``preserve_rng_state``; the policy is a constant of the module."""
    assert list(inspect.signature(recompute).parameters) \
        == ["function", "args", "kwargs"]
