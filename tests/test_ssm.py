"""The state-space mixer (``nn.Mamba2Mixer``; ``F.causal_conv1d``,
``F.ssd_scan``, ``F.gated_group_rms_norm``; ops/ssm.py) against the
position-at-a-time recurrence and the mixer of
benchmark/reference/nemotron_h.py: values and every gradient, in
float32."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import nn, observability
from paddle_tpu.observability import device_counters, scopes
from paddle_tpu.ops import ssm

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from reference import nemotron_h as ref  # noqa: E402


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _scan_inputs(B, T, H, P, G, N, seed=0):
    ks = jax.random.split(jax.random.key(seed), 7)
    return (jax.random.normal(ks[0], (B, T, H, P)),
            jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 1.0),
            -jnp.exp(0.5 * jax.random.normal(ks[2], (H,))),
            jax.random.normal(ks[3], (B, T, G, N)),
            jax.random.normal(ks[4], (B, T, G, N)),
            jax.random.normal(ks[5], (H,))), \
        jax.random.normal(ks[6], (B, T, H, P))


def _recurrence(x, dt, A, Bm, Cm, D):
    """The reference's scan, a position at a time, row by row."""
    return jax.vmap(lambda x, dt, Bm, Cm: ref._scan(x, dt, A, Bm, Cm, D)[0])(
        x, dt, Bm, Cm)


# Float32 at matmul precision "highest" on both sides.  The two sum the
# same terms in another order: a position's output is a sum over up to T
# earlier positions of products of decays, and the chunked form takes
# exp(cs_t - cs_s) where the recurrence multiplies exp(dt A) step by step.
# With values of order 1 and outputs and gradients of order 10 to 100 the
# difference read 1e-6 to 6e-5 absolute (PR 39); 2e-4 of the largest
# element leaves three times that and is a thousand times under what a
# dropped term (one position's contribution, order 1) would give.
def _close(got, want, what):
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4 * scale,
                               err_msg=what)


@pytest.mark.parametrize("B,T,H,P,G,N,chunk", [
    (2, 32, 4, 8, 2, 16, 8),     # T a multiple of the chunk, G < H
    (1, 37, 4, 8, 2, 16, 8),     # not a multiple: the last chunk is padded
    (2, 20, 6, 4, 1, 8, 8),      # one group for all heads, over two chunks
    (1, 5, 2, 4, 2, 8, 8),       # a row shorter than one chunk, G = H
    (1, 50, 4, 8, 4, 16, 16),    # the state crosses three boundaries
], ids=["whole_chunks", "padded", "one_group", "short_row", "long_row"])
def test_chunked_scan_is_the_recurrence(B, T, H, P, G, N, chunk):
    args, ct = _scan_inputs(B, T, H, P, G, N)
    got = ssm.ssd_scan(*args, chunk)
    want = _recurrence(*args)
    _close(got, want, "y")
    got_g = jax.grad(lambda *a: jnp.sum(ssm.ssd_scan(*a, chunk) * ct),
                     range(6))(*args)
    want_g = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * ct),
                      range(6))(*args)
    for name, g, w in zip(("x", "dt", "A", "B", "C", "D"), got_g, want_g):
        assert g.shape == w.shape and g.dtype == w.dtype
        _close(g, w, "d" + name)


def test_the_state_crosses_a_chunk_boundary():
    """What position 0 put into the state reaches a position two chunks
    on: with every other input's x zeroed, the output there is not zero,
    and is the recurrence's."""
    (x, dt, A, Bm, Cm, D), _ = _scan_inputs(1, 40, 2, 4, 1, 8, seed=3)
    x = x.at[:, 1:].set(0.0)
    dt = 0.1 * dt                      # a state that lasts forty positions
    got = ssm.ssd_scan(x, dt, A, Bm, Cm, D, 16)
    want = _recurrence(x, dt, A, Bm, Cm, D)
    assert float(jnp.max(jnp.abs(want[0, 39]))) > 1e-3
    np.testing.assert_allclose(got[0, 39], want[0, 39], rtol=1e-4)


def test_bfloat16_operands_keep_float32_decays():
    """bfloat16 x, B and C (the program's under O2) with float32 dt and A:
    the result is bfloat16 and within bfloat16's rounding of the float32
    scan of the same values (8 bits: 4e-3 relative a product, summed)."""
    (x, dt, A, Bm, Cm, D), _ = _scan_inputs(2, 48, 4, 8, 2, 16, seed=5)
    low = [a.astype(jnp.bfloat16) for a in (x, Bm, Cm)]
    got = ssm.ssd_scan(low[0], dt, A, low[1], low[2], D, 16)
    want = ssm.ssd_scan(low[0].astype(jnp.float32), dt, A,
                        low[1].astype(jnp.float32),
                        low[2].astype(jnp.float32), D, 16)
    assert got.dtype == jnp.bfloat16
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert err < 0.02 * float(jnp.max(jnp.abs(want))), err


def test_causal_conv_is_a_depthwise_convolution():
    ks = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(ks[0], (2, 19, 6))
    w = jax.random.normal(ks[1], (4, 6))
    b = jax.random.normal(ks[2], (6,))
    got = F.causal_conv1d(paddle.to_tensor(x), paddle.to_tensor(w),
                          paddle.to_tensor(b), "silu").data
    want = jax.lax.conv_general_dilated(
        x, w[:, None, :], (1,), [(3, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=6)
    np.testing.assert_allclose(got, jax.nn.silu(want + b), rtol=1e-5,
                               atol=1e-6)
    # causal: a later input moves no earlier output
    moved = ssm.causal_conv1d(x.at[:, 10:].add(1.0), w, b)
    np.testing.assert_array_equal(moved[:, :10],
                                  ssm.causal_conv1d(x, w, b)[:, :10])
    with pytest.raises(ValueError, match="activation"):
        ssm.causal_conv1d(x, w, b, "gelu")


def test_gated_group_norm_gates_before_it_norms():
    ks = jax.random.split(jax.random.key(2), 3)
    y, z = (jax.random.normal(k, (3, 5, 24)) for k in ks[:2])
    g = 1.0 + 0.1 * jax.random.normal(ks[2], (24,))
    got = F.gated_group_rms_norm(paddle.to_tensor(y), paddle.to_tensor(z),
                                 paddle.to_tensor(g), 4, 1e-5).data
    v = (y * jax.nn.silu(z)).reshape(3, 5, 4, 6)
    want = (v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True) + 1e-5)
            ).reshape(3, 5, 24) * g
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------- the mixer --
CFG = {"hidden_size": 32, "mamba_num_heads": 4, "mamba_head_dim": 8,
       "n_groups": 2, "ssm_state_size": 16, "conv_kernel": 4,
       "chunk_size": 8, "layer_norm_epsilon": 1e-5,
       # sizes() reads these too
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
       "moe_intermediate_size": 16, "moe_shared_expert_intermediate_size": 32,
       "published": {"n_routed_experts": 16}, "n_routed_experts": 4,
       "num_experts_per_tok": 2, "vocab_size": 64,
       "hybrid_override_pattern": "M"}
_LEAVES = {"in_proj.weight": "in.w", "conv_weight": "conv.w",
           "conv_bias": "conv.b", "dt_bias": "dt_bias", "A_log": "A_log",
           "D": "D", "norm_weight": "gate_norm.g", "out_proj.weight": "out.w"}


def _mixer_and_weights(seed):
    z = ref.sizes(CFG)
    shapes = {n: s for n, s in ref._kind_shapes(z)["m"].items()
              if n != "norm.g"}
    ks = jax.random.split(jax.random.key(seed), len(shapes) + 1)
    w = {n: base + 0.3 * jax.random.normal(k, shape)
         for k, (n, (shape, base)) in zip(ks, sorted(shapes.items()))}
    layer = nn.Mamba2Mixer(32, 4, 8, 2, 16, 4, 8, 1e-5)
    for pname, p in layer.named_parameters():
        assert tuple(p.shape) == w[_LEAVES[pname]].shape, pname
        p.data = w[_LEAVES[pname]]
    return layer, w, jax.random.normal(ks[-1], (2, 21, 32))


def test_the_mixer_is_the_references():
    layer, w, a = _mixer_and_weights(7)
    names = [n for n, _ in layer.named_parameters()]

    def program(a, *leaves):
        for (_, p), leaf in zip(layer.named_parameters(), leaves):
            p.data = leaf
        return layer(paddle.to_tensor(a)).data

    def reference(a, *leaves):
        p = {_LEAVES[n]: leaf for n, leaf in zip(names, leaves)}
        return jnp.stack([ref.mamba_mixer(row, p, CFG, lambda t: t)[0]
                          for row in a])

    leaves = [w[_LEAVES[n]] for n in names]
    ct = jnp.cos(jnp.arange(32.0))
    got, got_g = jax.value_and_grad(
        lambda *args: jnp.sum(program(*args) * ct),
        range(len(leaves) + 1))(a, *leaves)
    want, want_g = jax.value_and_grad(
        lambda *args: jnp.sum(reference(*args) * ct),
        range(len(leaves) + 1))(a, *leaves)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, g, wnt in zip(["a"] + names, got_g, want_g):
        _close(g, wnt, name)


def test_the_mixer_counts_only_where_somebody_collects():
    layer, _, a = _mixer_and_weights(9)
    assert not device_counters.collecting()
    layer(paddle.to_tensor(a))          # nothing emitted, nothing raised
    with device_counters.collect() as counted:
        layer(paddle.to_tensor(a))
    got = counted.stacked()
    assert set(got) == {scopes.SSM_STATE_SHARE, scopes.SSM_MEAN_DECAY}
    share, decay = (float(got[n][0]) for n in (scopes.SSM_STATE_SHARE,
                                               scopes.SSM_MEAN_DECAY))
    _, readings = ref.mamba_mixer(
        a[0], {_LEAVES[n]: p.data for n, p in layer.named_parameters()},
        CFG, lambda t: t)
    assert 0.0 < share < 1.0 and 0.0 < decay < 1.0
    # one row's readings against the two rows' counter: the same order
    np.testing.assert_allclose(decay, float(readings[2]), rtol=0.1)
    for name in (scopes.SSM_STATE_SHARE, scopes.SSM_MEAN_DECAY):
        assert name in scopes.DEVICE_COUNTERS
    assert observability.device_counter is device_counters.device_counter


def test_the_mixer_refuses_groups_that_do_not_divide_the_heads():
    with pytest.raises(ValueError, match="groups"):
        nn.Mamba2Mixer(32, 6, 8, 4, 16)
