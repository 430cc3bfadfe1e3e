"""How the flash kernels find a head's rows (ops/pallas/flash_attention.py,
``_as_it_lies``): as the projections leave them, a head a lane block of
[B, L, H*D], or in a [B, H, L, D] copy.  One body and one arithmetic, so
the two addressings agree to the rounding of a group's sum; which one a
call takes follows from its static shapes and is counted."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn.functional as F
from paddle_tpu.ops.pallas import flash_attention, mha_reference
from paddle_tpu.utils import monitor

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

LIES, TRANSPOSED = "flash_attention.as_it_lies", "flash_attention.transposed"


def _counted(fn, *args):
    """(result, by how much the two addressing counters moved)."""
    before = monitor.all_stats()
    out = fn(*args)
    after = monitor.all_stats()
    return out, tuple(after.get(n, 0) - before.get(n, 0)
                      for n in (LIES, TRANSPOSED))


def _operand_sized_transposes(pallas_eqns, jaxpr, elements):
    """How many transposes of a value with ``elements`` elements a jaxpr
    holds, its sub-jaxprs included."""
    return sum(eqn.invars[0].aval.size == elements
               for eqn in pallas_eqns(jaxpr, primitive="transpose"))


# (B, L, H, Hk, D, Dv, causal)
_LAID = {
    "heads_of_128": (2, 1024, 4, 4, 128, 128, False),
    "heads_of_128_causal": (2, 1024, 4, 4, 128, 128, True),
    "values_of_256": (1, 1024, 4, 4, 128, 256, True),
    # two heads of 64 to a lane block, one grid step a pair
    "pairs_of_64": (2, 512, 4, 4, 64, 64, False),
    "pairs_of_64_causal": (1, 1024, 6, 6, 64, 64, True),
}


@pytest.mark.parametrize("qk_lie", [False, True],
                         ids=["qk_transposed", "qk_lie"])
@pytest.mark.parametrize("case", _LAID)
def test_as_it_lies_is_the_transposed_path(case, qk_lie, monkeypatch):
    """Value and all three gradients of a call that takes its operands as
    they lie equal the transposed path's to the bit (the same kernels on
    the same tiles) and agree with the oracle: with q and k of 128-wide heads in their
    [B, H, L, D] copies, as shipped, and lying too (pairs of 64 always
    lie whole)."""
    B, L, H, Hk, D, Dv, causal = _LAID[case]
    monkeypatch.setattr(fa, "_QK_LIE_AT_128", qk_lie)
    r = np.random.RandomState(11)
    q = jnp.asarray(r.randn(B, L, H, D), jnp.float32)
    k = jnp.asarray(r.randn(B, L, Hk, D), jnp.float32)
    v = jnp.asarray(r.randn(B, L, Hk, Dv), jnp.float32)
    w = jnp.asarray(r.randn(B, L, H, Dv), jnp.float32)

    def value_and_grads(attend):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(attend(q, k, v, causal=causal) * w),
            (0, 1, 2))(q, k, v)

    laid, moved = _counted(value_and_grads, flash_attention)
    assert moved == (1, 0)
    monkeypatch.setattr(fa, "_as_it_lies", lambda *shapes: None)
    copied, moved = _counted(value_and_grads, flash_attention)
    assert moved == (0, 1)
    oracle = value_and_grads(mha_reference)
    for got, same, ref in zip(jax.tree.leaves(laid), jax.tree.leaves(copied),
                              jax.tree.leaves(oracle)):
        got, same, ref = (np.asarray(a) for a in (got, same, ref))
        np.testing.assert_array_equal(got, same)
        assert np.abs(got - ref).max() <= 2e-5 * max(1.0, np.abs(ref).max())


def _causal(q, k):
    return flash_attention(q, k, k, causal=True)


def _ring_block(q, k):
    off = jnp.zeros((1, 1), jnp.float32)
    return fa.flash_attention_block(q, k, k, off, off, 0.1)


@pytest.mark.parametrize("call,shape,kv_heads,copies", [
    (_causal, (1, 1024, 2, 96), 2, 4),      # q, k, v in and out back
    (_causal, (1, 1024, 8, 64), 2, 2),      # in groups: q and out that size
    (_causal, (1, 1024, 8, 128), 2, 2),     # at any width
    (_causal, (1, 1024, 3, 64), 3, 4),      # no pairs of three heads
    (_ring_block, (1, 2, 1024, 128), 2, 0),     # [B, H, L, D] by contract
], ids=["heads_of_96", "grouped_heads_of_64", "grouped_heads_of_128",
        "odd_heads_of_64", "ring_block"])
def test_the_transposed_path_is_the_one_it_was(call, shape, kv_heads, copies,
                                               pallas_eqns):
    """A head that is no whole number of lane tiles (nor half of one beside
    a neighbour), heads that share a key/value head, and the ring's block
    whatever its width, keep the [B, H, L, D] addressing: counted,
    the copies where the parent made them, and every block of the kernel
    a (1, 1, rows, width) of a four-dimensional operand."""
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    heads = 1 if call is _ring_block else 2
    k = jax.ShapeDtypeStruct(
        shape[:heads] + (kv_heads,) + shape[heads + 1:], jnp.bfloat16)
    closed, moved = _counted(jax.make_jaxpr(call), q, k)
    assert moved == (0, 1)
    assert _operand_sized_transposes(
        pallas_eqns, closed.jaxpr, int(np.prod(shape))) == copies
    kernel, = pallas_eqns(closed.jaxpr)
    assert all(v.aval.ndim in (2, 4) for v in kernel.invars)    # 2: scalars
    assert all(v.aval.ndim == 4 for v in kernel.outvars)


@pytest.mark.parametrize("shape,copies,counts", [
    ((2, 4096, 16, 128), 4, (1, 0)),    # the Ouro cell's: q, k, dq, dk
    ((64, 512, 12, 64), 0, (1, 0)),     # the BERT cell's, in pairs
    ((8, 2048, 16, 96), 8, (0, 1)),     # the GPT cell's: q, k, v, out and
], ids=["ouro_cell", "bert_cell", "gpt_cell"])      # dO, dq, dk, dv
def test_no_copy_is_left_around_a_call_taken_as_it_lies(
        kernels_on, pallas_eqns, shape, copies, counts):
    """The traced program of an attention call and its gradients, through
    the functional: v, out, dO and dv of 128-wide heads are transposed
    nowhere (by construction, not by XLA's grace; q and k keep their
    copies, which XLA folds into the rotation that writes them), nothing
    of 64-wide heads in pairs is, and at 96-wide heads the eight of the
    parent are."""
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def loss(q, k, v):
        out = F.scaled_dot_product_attention(q, k, v,
                                             is_causal=shape[-1] != 64)
        return jnp.sum(out.data.astype(jnp.float32))

    closed, moved = _counted(
        jax.make_jaxpr(jax.value_and_grad(loss, (0, 1, 2))), x, x, x)
    assert moved == counts
    assert _operand_sized_transposes(
        pallas_eqns, closed.jaxpr, int(np.prod(shape))) == copies
