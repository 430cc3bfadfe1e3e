"""In-kernel flash-attention dropout tests (reference analog: the fused
attention dropout path, fused_attention_op.cu).  The Pallas TPU PRNG has
no CPU lowering, so these run on real TPU only (the driver's bench
exercises them every round); CPU CI covers the p=0 path via
test_pallas.py."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.flash_attention import (flash_attention,
                                                   flash_attention_supported)

TPU = jax.default_backend() == "tpu"
pytestmark = pytest.mark.skipif(not TPU, reason="Pallas TPU PRNG is "
                                "TPU-only (no interpret lowering)")


def _qkv(L=256):
    ks = jax.random.split(jax.random.key(0), 3)
    return [jax.random.normal(k, (2, L, 2, 64), jnp.float32) for k in ks]


def test_p0_with_seed_matches_no_dropout_exactly():
    q, k, v = _qkv()
    base = np.asarray(flash_attention(q, k, v, causal=True))
    z = np.asarray(flash_attention(q, k, v, causal=True, dropout_p=0.0,
                                   seed=jnp.ones((1, 1), jnp.int32)))
    np.testing.assert_array_equal(z, base)


def test_deterministic_per_seed_and_varies_across_seeds():
    q, k, v = _qkv()
    f = lambda s: np.asarray(flash_attention(
        q, k, v, causal=True, dropout_p=0.2,
        seed=jnp.full((1, 1), s, jnp.int32)))
    a, b, c = f(7), f(7), f(8)
    np.testing.assert_array_equal(a, b)
    assert np.abs(a - c).max() > 1e-4


def test_expectation_unbiased():
    q, k, v = _qkv(128)
    base = np.asarray(flash_attention(q, k, v, causal=True))
    g = jax.jit(lambda s: flash_attention(q, k, v, causal=True,
                                          dropout_p=0.3, seed=s))
    acc = np.zeros_like(base)
    S = 96
    for i in range(S):
        acc += np.asarray(g(jnp.full((1, 1), 100 + i, jnp.int32)))
    rel = np.abs(acc / S - base).mean() / np.abs(base).mean()
    assert rel < 0.12, rel  # ~1/sqrt(S) sampling noise


@pytest.mark.parametrize("wrt", [0, 1, 2])
def test_custom_vjp_matches_finite_difference(wrt):
    qkv = _qkv(128)
    seed = jnp.full((1, 1), 42, jnp.int32)

    def f(x):
        args = list(qkv)
        args[wrt] = x
        return jnp.sum(flash_attention(*args, causal=True, dropout_p=0.25,
                                       seed=seed) ** 2)

    x0 = qkv[wrt]
    g = jax.grad(f)(x0)
    d = jax.random.normal(jax.random.key(9), x0.shape, jnp.float32)
    eps = 1e-3
    num = (float(f(x0 + eps * d)) - float(f(x0 - eps * d))) / (2 * eps)
    ana = float(jnp.vdot(g, d))
    assert abs(num - ana) / max(abs(num), 1e-6) < 2e-2, (num, ana)


def test_supported_crossover_is_the_same_with_dropout():
    # the kernel from 512 up with dropout on or off (PERF.md, PR 27:
    # with dropout it wins by 1.6x at 512 and loses by 6 % at 256)
    for L, taken in ((256, False), (512, True)):
        shp = (2, L, 4, 64)
        for p in (0.0, 0.1):
            assert flash_attention_supported(shp, shp, jnp.bfloat16, None,
                                             p) == taken


def test_dropout_p1_drops_everything():
    q, k, v = _qkv(128)
    out = np.asarray(flash_attention(q, k, v, causal=True, dropout_p=1.0,
                                     seed=jnp.ones((1, 1), jnp.int32)))
    assert np.abs(out).max() == 0.0
