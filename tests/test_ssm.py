"""The state-space mixer (``nn.Mamba2Mixer``; ``F.causal_conv1d``,
``F.ssd_scan``, ``F.gated_group_rms_norm``; ops/ssm.py) against the
position-at-a-time recurrence and the mixer of
benchmark/reference/nemotron_h.py: values and every gradient, in
float32."""
import functools
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
from paddle_tpu.ops.pallas import causal_conv as conv_kernels
from paddle_tpu.ops.pallas import ssd_scan as kernels
from paddle_tpu.utils import monitor

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


# ---------------------------------------- the kernels (interpret mode) --
# (B, T, H, P, G, N) the gate of ops/pallas/ssd_scan.py takes, at chunk 128
_KERNEL_SHAPES = {
    "whole_chunks": (2, 256, 16, 64, 2, 128),   # G < H, two heads a slab
    "padded": (1, 300, 16, 64, 2, 128),         # T no multiple of the chunk
    "one_group": (1, 200, 8, 64, 1, 128),
    "wide_heads": (1, 130, 8, 128, 1, 256),     # a head a slab, a state of
                                                # two lane tiles
    # a group of more heads than a step takes is walked in head blocks:
    # one step's at 16 heads, blocks of 16 at 32, at 64 (granite-4.0-h's
    # one group of 64) and at two groups of 48, of 8 at one group of 24,
    # dB and dC summed over a group's blocks
    "one_group_of_16": (1, 200, 16, 64, 1, 128),
    "one_group_of_24": (1, 130, 24, 64, 1, 128),
    "one_group_of_32": (1, 200, 32, 64, 1, 128),
    "one_group_of_64": (2, 200, 64, 64, 1, 128),
    "two_groups_of_48": (1, 130, 96, 64, 2, 128),
    "eight_groups_of_8": (1, 200, 64, 64, 8, 128),  # Nemotron's: one step
}


def _low(args, ct, dtype):
    x, dt, A, Bm, Cm, D = args
    return (x.astype(dtype), dt, A, 0.3 * Bm.astype(dtype),
            0.3 * Cm.astype(dtype), D), ct.astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", sorted(_KERNEL_SHAPES))
def test_the_kernels_are_the_xla_form(kernels_on, shape, dtype):
    """``ssd_fwd`` / ``ssd_bwd`` against the chunked form in ``jax.numpy``
    on the same operands: the value and all six gradients.  float32: the
    two differ in the order of their sums (``_close``'s 2e-4 of the
    largest element).  bfloat16: each rounds ``M``, ``dCB`` and its
    results to 8 bits once, on sums in another order: 0.02 of the largest
    element, as ``test_bfloat16_operands_keep_float32_decays`` allows."""
    size = _KERNEL_SHAPES[shape]
    assert kernels.ssd_scan_supported(size[:4], (size[0], size[1]) + size[4:],
                                      dtype, 128)
    args, ct = _low(*_scan_inputs(*size, seed=11), dtype)

    def both(fn):
        def loss(*a):
            y = fn(*a)
            return jnp.sum((y * ct).astype(jnp.float32)), y
        (_, y), grads = jax.jit(jax.value_and_grad(
            loss, range(6), has_aux=True))(*args)
        return (y,) + grads

    got = both(kernels.ssd_scan)
    want = both(lambda *a: ssm.ssd_scan(*a, 128))
    for name, g, w in zip(("y", "dx", "ddt", "dA", "dB", "dC", "dD"),
                          got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        if dtype == jnp.float32:
            _close(g, w, name)
        else:
            err = float(jnp.max(jnp.abs(g - w)))
            assert err < 0.02 * float(jnp.max(jnp.abs(w))), (name, err)


@pytest.mark.parametrize("heads,groups,a_step,takes", [
    (64, 8, 8, True),       # Nemotron's: a group a step
    (16, 1, 16, True),      # the largest group one step takes
    (40, 1, 8, True),       # 16 does not divide it: blocks of 8
    (64, 1, 16, True),      # Mamba-2's default, granite-4.0-h's
    (96, 2, 16, True),
    (44, 1, 8, False),      # no whole sublane tiles of heads
    (4, 1, 8, False),
], ids=lambda v: str(v))
def test_a_group_is_one_step_or_blocks_of_sixteen_heads(heads, groups, a_step,
                                                       takes):
    """The gate and the wrapper agree on the heads a grid step takes: 16
    where that divides a group, else 8; a group of more is walked in
    blocks."""
    assert kernels._heads_a_step(heads // groups) == a_step
    assert kernels.ssd_scan_supported(
        (1, 256, heads, 64), (1, 256, groups, 128), jnp.bfloat16,
        128) is takes


def test_the_kernels_carry_the_state_across_chunks(kernels_on):
    """What position 0 put into the state reaches a position two chunks
    on through ``ssd_fwd``'s scratch, and its gradient comes back through
    ``ssd_bwd``'s: with every other input's x zeroed, the output there and
    the gradient to x at position 0 are the recurrence's, and not zero."""
    (x, dt, A, Bm, Cm, D), _ = _scan_inputs(1, 300, 8, 64, 1, 128, seed=3)
    x = x.at[:, 1:].set(0.0)
    dt = 0.02 * dt                     # a state that lasts 300 positions

    def at_the_end(fn):
        return jax.value_and_grad(
            lambda x: jnp.sum(fn(x, dt, A, Bm, Cm, D)[0, 299]))(x)

    got, got_g = at_the_end(kernels.ssd_scan)
    want, want_g = at_the_end(_recurrence)
    assert abs(float(want)) > 1e-3
    np.testing.assert_allclose(got, want, rtol=1e-4)
    assert float(jnp.max(jnp.abs(want_g[0, 0]))) > 1e-3
    _close(got_g[0, 0], want_g[0, 0], "dx at position 0")


def _through_the_functional(size, chunk=128, dtype=jnp.float32):
    """``F.ssd_scan`` on a draw of ``size`` -> (its result, the moves of
    ``pallas.selected.ssd_scan`` and of ``ssd_scan.xla_path``)."""
    args, _ = _scan_inputs(*size, seed=13)
    args = (args[0].astype(dtype),) + args[1:3] + tuple(
        a.astype(dtype) for a in args[3:5]) + args[5:]

    def counts():
        s = monitor.all_stats()
        return (s.get("pallas.selected.ssd_scan", 0),
                s.get("ssd_scan.xla_path", 0))

    before = counts()
    got = F.ssd_scan(*(paddle.to_tensor(a) for a in args), chunk).data
    return got, args, tuple(a - b for a, b in zip(counts(), before))


@pytest.mark.parametrize("size,chunk,dtype", [
    ((1, 130, 8, 64, 1, 128), 64, jnp.float32),    # a chunk of half a tile
    ((1, 130, 8, 8, 1, 128), 128, jnp.float32),    # heads of 8 lanes
    ((1, 130, 8, 64, 1, 16), 128, jnp.float32),    # a state of 16 lanes
    ((1, 130, 4, 64, 2, 128), 128, jnp.float32),   # two heads a group
    ((1, 130, 8, 64, 1, 128), 128, jnp.float16),   # no kernel dtype
], ids=["chunk", "head", "state", "heads_a_group", "dtype"])
def test_a_shape_outside_the_gate_runs_the_xla_form(kernels_on, size, chunk,
                                                    dtype):
    assert not kernels.ssd_scan_supported(
        size[:4], (size[0], size[1]) + size[4:], dtype, chunk)
    got, args, moved = _through_the_functional(size, chunk, dtype)
    assert moved == (0, 1)
    np.testing.assert_array_equal(got, ssm.ssd_scan(*args, chunk))


def test_the_functional_takes_the_kernels_where_the_tier_is_on(kernels_on):
    size = (1, 130, 8, 64, 1, 128)
    got, args, moved = _through_the_functional(size)
    assert moved == (1, 0)
    _close(got, ssm.ssd_scan(*args, 128), "y")


@pytest.mark.parametrize("flags", [
    {"use_pallas_kernels": False, "pallas_interpret": True},
    {"use_pallas_kernels": True, "pallas_interpret": False},
], ids=["tier_off", "no_interpret_opt_in"])
def test_no_kernel_is_selected_where_the_tier_is_off(flags):
    """The OFF contract: with ``FLAGS_use_pallas_kernels`` off, or off a
    TPU without the interpret opt-in, a shape the gate takes runs the XLA
    form to the bit and no ``pallas.selected.ssd_scan`` moves."""
    from paddle_tpu.core.flags import get_flag, set_flags
    old = {name: get_flag(name) for name in flags}
    set_flags(flags)
    try:
        got, args, moved = _through_the_functional((1, 130, 8, 64, 1, 128))
    finally:
        set_flags(old)
    assert moved == (0, 1)
    np.testing.assert_array_equal(got, ssm.ssd_scan(*args, 128))


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


# ------------------------------------------ the convolution's two kernels --
def _conv_inputs(B, T, W, parts, K, dtype, seed=5):
    ks = jax.random.split(jax.random.key(seed), 3 + len(parts))
    C = sum(parts)
    return ((jax.random.normal(ks[0], (B, T, W)).astype(dtype),
             (0.5 * jax.random.normal(ks[1], (K, C))).astype(dtype),
             (0.5 * jax.random.normal(ks[2], (C,))).astype(dtype)),
            [jax.random.normal(k, (B, T, n)) for k, n in zip(ks[3:], parts)])


def _conv_and_slices(x, w, b, activation, first, parts):
    """``ops.ssm.causal_conv1d`` over XLA's slice of the channels, its
    result sliced into the parts: what the kernels stand in for."""
    out = ssm.causal_conv1d(x[:, :, first:first + w.shape[1]], w, b,
                            activation)
    starts = [sum(parts[:i]) for i in range(len(parts))]
    return tuple(out[:, :, s:s + n] for s, n in zip(starts, parts))


@pytest.mark.parametrize("B,T,W,first,parts,K,dtype,activation", [
    # the two cells' widths at a short T: one T block of one chunk
    (1, 32, 10304, 4096, (4096, 1024, 1024), 4, jnp.bfloat16, "silu"),
    (1, 32, 8512, 4096, (4096, 128, 128), 4, jnp.bfloat16, "silu"),
    # a first channel of 0; one T block of four chunks
    (2, 256, 640, 0, (256, 128, 128), 4, jnp.float32, "silu"),
    # a later lane tile; three T blocks of two chunks: the halo across a
    # block's edge and across a chunk's, zeros at both rows' starts
    (2, 384, 1024, 256, (256, 128, 128), 4, jnp.float32, "silu"),
    # two T blocks of eight chunks
    (2, 1024, 384, 128, (128,), 4, jnp.float32, "silu"),
    # three T blocks of one chunk, bfloat16: a block is one tile of rows
    (2, 48, 512, 128, (128, 128), 4, jnp.bfloat16, "silu"),
    (2, 192, 512, 128, (128, 128), 3, jnp.float32, None),
    (1, 64, 256, 0, (256,), 8, jnp.float32, "silu"),
    (1, 32, 256, 128, (128,), 1, jnp.float32, "silu"),
], ids=["nemotron", "granite", "first_0", "later_tile", "two_blocks",
        "tile_blocks_bf16", "no_activation", "eight_taps", "one_tap"])
def test_the_conv_kernels_are_the_convolution_and_its_slices(
        kernels_on, B, T, W, first, parts, K, dtype, activation):
    """``conv_fwd`` / ``conv_bwd`` in interpret mode against
    ``ops.ssm.causal_conv1d`` plus slices in float32: every part, and the
    gradients to the operand (zeros outside the convolved channels), the
    taps and the bias."""
    assert conv_kernels.causal_conv1d_supported(
        (B, T, W), (K, sum(parts)), dtype, first, parts, activation)
    args, cts = _conv_inputs(B, T, W, parts, K, dtype)

    def loss(fn, *a):
        outs = fn(*a, activation, first, parts)
        return sum(jnp.sum(o.astype(jnp.float32) * c)
                   for o, c in zip(outs, cts)), outs

    (_, got), got_g = jax.value_and_grad(
        functools.partial(loss, conv_kernels.causal_conv1d), (0, 1, 2),
        has_aux=True)(*args)
    (_, want), want_g = jax.value_and_grad(
        functools.partial(loss, _conv_and_slices), (0, 1, 2),
        has_aux=True)(*(a.astype(jnp.float32) for a in args))
    # float32: the same products summed in another order; bfloat16: one
    # rounding of the result (2 ** -9 of it) on the kernels' side
    tol = 1e-5 if dtype == jnp.float32 else 6e-3
    for name, g, w in zip(
            [f"part {i}" for i in range(len(parts))] + ["dx", "dw", "db"],
            got + got_g, want + want_g):
        assert g.shape == w.shape and g.dtype == dtype, name
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(g.astype(jnp.float32), w, rtol=0,
                                   atol=tol * scale, err_msg=name)
    outside = jnp.concatenate([got_g[0][:, :, :first],
                               got_g[0][:, :, first + sum(parts):]], 2)
    assert not outside.size or float(jnp.max(jnp.abs(outside))) == 0.0


def _conv_through_the_functional(B, T, W, first, parts, K, dtype,
                                 activation="silu"):
    """``F.causal_conv1d`` by keyword on a draw -> (its results, the
    arguments, the moves of ``pallas.selected.causal_conv1d`` and of
    ``causal_conv1d.xla_path``)."""
    args, _ = _conv_inputs(B, T, W, parts, K, dtype, seed=11)

    def counts():
        s = monitor.all_stats()
        return (s.get("pallas.selected.causal_conv1d", 0),
                s.get("causal_conv1d.xla_path", 0))

    before = counts()
    got = F.causal_conv1d(*(paddle.to_tensor(a) for a in args), activation,
                          first_channel=first, parts=parts)
    return (tuple(t.data for t in got), args,
            tuple(a - b for a, b in zip(counts(), before)))


@pytest.mark.parametrize("B,T,W,first,parts,K,dtype", [
    (1, 64, 512, 128, (128, 64, 64), 4, jnp.float32),   # parts of 64 lanes
    (1, 100, 512, 128, (128, 128), 4, jnp.float32),     # no whole T blocks
    (1, 64, 512, 128, (128, 128), 9, jnp.float32),      # nine taps
    (1, 64, 512, 64, (128, 128), 4, jnp.float32),       # a first of 64
    (1, 64, 512, 128, (256, 128), 4, jnp.float32),      # a part that does
                                                        # not start on a
                                                        # multiple of itself
    (1, 64, 512, 128, (128, 128), 4, jnp.float16),      # no kernel dtype
], ids=["part_of_64", "ragged_T", "nine_taps", "first_of_64",
        "part_off_its_width", "dtype"])
def test_a_conv_outside_the_gate_runs_the_xla_form(kernels_on, B, T, W,
                                                   first, parts, K, dtype):
    assert not conv_kernels.causal_conv1d_supported(
        (B, T, W), (K, sum(parts)), dtype, first, parts, "silu")
    got, args, moved = _conv_through_the_functional(B, T, W, first, parts, K,
                                                    dtype)
    assert moved == (0, 1)
    for g, w in zip(got, _conv_and_slices(*args, "silu", first, parts),
                    strict=True):
        np.testing.assert_array_equal(g, w)


def test_another_activation_still_raises(kernels_on):
    assert not conv_kernels.causal_conv1d_supported(
        (1, 64, 512), (4, 256), jnp.float32, 128, (128, 128), "gelu")
    before = dict(monitor.all_stats())
    with pytest.raises(ValueError, match="activation"):
        _conv_through_the_functional(1, 64, 512, 128, (128, 128), 4,
                                     jnp.float32, "gelu")
    after = monitor.all_stats()
    assert after.get("causal_conv1d.xla_path", 0) \
        == before.get("causal_conv1d.xla_path", 0) + 1
    assert after.get("pallas.selected.causal_conv1d", 0) \
        == before.get("pallas.selected.causal_conv1d", 0)


def test_the_conv_functional_takes_the_kernels_where_the_tier_is_on(
        kernels_on):
    size = (2, 128, 512, 128, (128, 128), 4, jnp.float32)
    got, args, moved = _conv_through_the_functional(*size)
    assert moved == (1, 0)
    for g, w in zip(got, _conv_and_slices(*args, "silu", 128, (128, 128)),
                    strict=True):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
    # the plain call: every channel of x, one array back
    x, w, b = args[0][:, :, 128:384], args[1], args[2]
    before = monitor.all_stats().get("pallas.selected.causal_conv1d", 0)
    one = F.causal_conv1d(paddle.to_tensor(x), paddle.to_tensor(w),
                          paddle.to_tensor(b), "silu").data
    assert monitor.all_stats()["pallas.selected.causal_conv1d"] == before + 1
    np.testing.assert_allclose(one, ssm.causal_conv1d(x, w, b, "silu"),
                               rtol=1e-5, atol=1e-6)


def test_no_conv_kernel_is_selected_off_a_tpu_without_the_opt_in():
    """On the CPU, the tier's opt-in unset, a shape the gate takes runs
    XLA's slices and form to the bit and the other counter moves."""
    size = (2, 128, 512, 128, (128, 128), 4, jnp.float32)
    assert conv_kernels.causal_conv1d_supported(
        size[:3], (4, 256), jnp.float32, 128, (128, 128), "silu")
    got, args, moved = _conv_through_the_functional(*size)
    assert moved == (0, 1)
    for g, w in zip(got, _conv_and_slices(*args, "silu", 128, (128, 128)),
                    strict=True):
        np.testing.assert_array_equal(g, w)


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


@pytest.mark.parametrize("T,conv", [(200, (0, 1)), (256, (1, 0))],
                         ids=["padded_scan_xla_conv", "both_on_kernels"])
def test_the_mixer_through_the_kernels_is_the_mixer(kernels_on, T, conv):
    """``nn.Mamba2Mixer`` at sizes the gates take (8 heads of 64 in one
    group on a state of 128, chunks of 128): the branch and its gradients
    to the input and to every parameter with the scan on the kernels,
    against the same layer on the XLA form.  Over 200 positions the scan
    pads its last chunk and the convolution, whose kernels want whole T
    blocks, takes XLA's form; over 256 the convolution's kernels hand
    the scan's their x, B and C."""
    from paddle_tpu.core.flags import set_flags
    layer = nn.Mamba2Mixer(32, 8, 64, 1, 128, 4, 128, 1e-5)
    ks = jax.random.split(jax.random.key(17), 9)
    leaves = [0.3 * jax.random.normal(k, tuple(p.shape))
              for k, (_, p) in zip(ks, layer.named_parameters())]
    a = jax.random.normal(ks[-1], (1, T, 32))

    def program(a, *leaves):
        for (_, p), leaf in zip(layer.named_parameters(), leaves):
            p.data = leaf
        with paddle.no_grad():          # jax differentiates, as TrainStep
            return jnp.sum(layer(paddle.to_tensor(a)).data
                           * jnp.cos(jnp.arange(32.0)))

    grad = jax.value_and_grad(program, range(len(leaves) + 1))
    monitor.stat_reset()
    got, got_g = grad(a, *leaves)
    stats = monitor.all_stats()
    assert stats["pallas.selected.ssd_scan"] == 1
    assert (stats.get("pallas.selected.causal_conv1d", 0),
            stats.get("causal_conv1d.xla_path", 0)) == conv
    set_flags({"pallas_interpret": False})
    want, want_g = grad(a, *leaves)
    assert monitor.all_stats()["ssd_scan.xla_path"] == 1
    np.testing.assert_allclose(got, want, rtol=1e-5)
    names = ["a"] + [n for n, _ in layer.named_parameters()]
    for name, g, w in zip(names, got_g, want_g):
        _close(g, w, name)
