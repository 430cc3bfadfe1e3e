"""The attention family's shared tile mathematics
(``ops/pallas/attention_tiles.py``) against plain jnp, and the module
boundary it draws: no kernel file reads a sibling's private names."""
import ast
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import attention_tiles as tiles
from paddle_tpu.ops.pallas.support import NEG_INF

PALLAS = pathlib.Path(tiles.__file__).parent


def _tiles(seed=0, keys=16, block=8, width=4):
    """Two [keys, block] score tiles, their values, and dO."""
    ks = jax.random.split(jax.random.key(seed), 3)
    s = 2.0 * jax.random.normal(ks[0], (2, keys, block))
    v = jax.random.normal(ks[1], (2, keys, width))
    do = jax.random.normal(ks[2], (block, width))
    return s, v, do


def _start(block, width):
    return (jnp.full((1, block), NEG_INF, jnp.float32),
            jnp.zeros((1, block), jnp.float32),
            jnp.zeros((width, block), jnp.float32))


def test_two_online_steps_are_the_softmax_over_both_tiles():
    s, v, _ = _tiles()
    carry = _start(8, 4)
    for t in range(2):
        carry = tiles.online_step(carry, s[t], v[t])
    m, l, acc = carry
    both, values = jnp.concatenate(s), jnp.concatenate(v)
    want = jax.nn.softmax(both, axis=0).T @ values            # [block, Dv]
    np.testing.assert_allclose((acc / l).T, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose((m + jnp.log(l))[0],
                               jax.nn.logsumexp(both, axis=0), rtol=1e-6)


@pytest.mark.parametrize("guard", [False, True], ids=["unguarded", "guarded"])
def test_a_query_that_no_tile_shows_a_key_needs_the_guard(guard):
    """Query 3 sees no key of either tile (a ring round that lies wholly
    after it).  Guarded, its carry stays empty, for the kernel to write
    out 0 and lse -inf; unguarded, ``s - m_new`` is 0 down its column and
    it attends uniformly to keys it may not see.  Either way the others
    are the softmax over both tiles."""
    s, v, _ = _tiles(1)
    s = s.at[:, :, 3].set(NEG_INF)
    carry = _start(8, 4)
    for t in range(2):
        carry = tiles.online_step(carry, s[t], v[t], may_hide_query=guard)
    m, l, acc = carry
    if guard:
        assert float(l[0, 3]) == 0.0 and not np.any(np.asarray(acc[:, 3]))
    else:
        assert float(l[0, 3]) == 2 * s.shape[1]
    both, values = jnp.concatenate(s), jnp.concatenate(v)
    np.testing.assert_allclose(
        jnp.delete((acc / l).T, 3, axis=0),
        jnp.delete(jax.nn.softmax(both, axis=0).T @ values, 3, axis=0),
        rtol=1e-5, atol=1e-6)


def test_the_dropout_hook_meets_the_values_and_not_the_denominator():
    s, v, _ = _tiles(2)
    plain = tiles.online_step(_start(8, 4), s[0], v[0])
    dropped = tiles.online_step(_start(8, 4), s[0], v[0],
                                drop=lambda p: 2.0 * p)
    np.testing.assert_array_equal(dropped[1], plain[1])
    np.testing.assert_allclose(dropped[2], 2.0 * plain[2], rtol=1e-6)


@pytest.mark.parametrize("guard", [False, True], ids=["unguarded", "guarded"])
def test_p_ds_is_the_softmax_backward_of_a_tile(guard):
    """dS of a tile is what jax's own derivative of ``sum(dO * out)``
    gives for the scores; a query the tile hides gets p = dS = 0 under
    the guard, and exp(NEG_INF - lse) underflows to the same without."""
    s, v, do = _tiles(3)
    s, v = s[0].at[:, 5].set(NEG_INF), v[0]
    lse = jax.nn.logsumexp(s.at[:, 5].set(0.0), axis=0)[None]
    p = jnp.exp(s - lse)
    delta = jnp.sum((p.T @ v) * do, axis=-1)[None]            # [1, block]
    got_p, got_ds = tiles.p_ds(s, lse, do, v, delta, may_hide_query=guard)
    np.testing.assert_allclose(got_p, p, rtol=1e-6)
    assert not np.any(np.asarray(got_p[:, 5]))
    want = jax.grad(lambda s: jnp.sum(
        (jax.nn.softmax(s, axis=0).T @ v) * do))(s.at[:, 5].set(0.0))
    np.testing.assert_allclose(jnp.delete(got_ds, 5, axis=1),
                               jnp.delete(want, 5, axis=1),
                               rtol=1e-4, atol=1e-6)
    assert not np.any(np.asarray(got_ds[:, 5]))


def test_p_ds_with_dropout_keeps_the_undropped_denominator():
    s, v, do = _tiles(4)
    lse = jax.nn.logsumexp(s[0], axis=0)[None]
    delta = jnp.full((1, 8), 0.25)
    p, _ = tiles.p_ds(s[0], lse, do, v[0], delta)
    u, ds = tiles.p_ds(s[0], lse, do, v[0], delta, drop=lambda p: 2.0 * p)
    np.testing.assert_allclose(u, 2.0 * p, rtol=1e-6)
    np.testing.assert_allclose(ds, u * (v[0] @ do.T) - p * delta, rtol=1e-5)


@pytest.mark.parametrize("outputs", [1, 2], ids=["dq", "dq_and_shared"])
def test_the_dq_accumulators_over_two_key_blocks_are_k_transposed_ds(outputs):
    """The walk's protocol in a kernel of its own (interpret mode): zero
    at the first key block, a pair's ``k^T dS`` added a query block, the
    scale, the transpose and the write at the last."""
    L, block, bk, widths, scale = 16, 8, 4, (8, 4)[:outputs], 0.5
    ks = jax.random.split(jax.random.key(5), 1 + outputs)
    ds = jax.random.normal(ks[0], (2 * bk, L))
    keys = [jax.random.normal(k, (2 * bk, w)) for k, w in zip(ks[1:], widths)]

    def kernel(ds_ref, *rest):
        key_refs, dq_refs = rest[:outputs], rest[outputs:2 * outputs]
        accs = rest[2 * outputs:]
        j = pl.program_id(0)

        @pl.when(j == 0)
        def _():
            tiles.dq_zero(accs)

        for i in range(L // block):
            tiles.dq_add(accs, [r[...] for r in key_refs], i,
                         tiles.rows(ds_ref, i, block).T)

        @pl.when(j == pl.num_programs(0) - 1)
        def _():
            tiles.dq_emit(dq_refs, accs, L // block, block, scale)

    got = pl.pallas_call(
        kernel, grid=(2,),
        in_specs=[pl.BlockSpec((L, bk), lambda j: (0, j))] + [
            pl.BlockSpec((bk, w), lambda j: (j, 0)) for w in widths],
        out_specs=[pl.BlockSpec((1, 1, L, w), lambda j: (0, 0, 0, 0))
                   for w in widths],
        out_shape=[jax.ShapeDtypeStruct((1, 1, L, w), jnp.float32)
                   for w in widths],
        scratch_shapes=[pltpu.VMEM((L // block, w, block), jnp.float32)
                        for w in widths],
        interpret=True,
    )(ds.T, *keys)
    for dq, key in zip(got, keys):
        np.testing.assert_allclose(dq[0, 0], scale * (ds.T @ key),
                                   rtol=1e-5, atol=1e-6)


def test_rows_reads_a_block_under_any_leading_unit_dimensions():
    x = jnp.arange(2 * 16 * 4.0).reshape(2, 16, 4)

    def kernel(a_ref, b_ref, o_ref):
        o_ref[...] = tiles.rows(a_ref, 1, 8) + tiles.rows(b_ref, 1, 8)

    got = pl.pallas_call(
        kernel, grid=(1,),
        in_specs=[pl.BlockSpec((1, 16, 4), lambda i: (0, 0, 0)),
                  pl.BlockSpec((1, 1, 16, 4), lambda i: (0, 1, 0, 0))],
        out_specs=pl.BlockSpec((8, 4), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 4), jnp.float32),
        interpret=True)(x, x[None])
    np.testing.assert_array_equal(got, x[0, 8:] + x[1, 8:])


def test_the_boundary_of_a_per_query_row():
    do = jnp.arange(24.0).reshape(2, 3, 4).astype(jnp.bfloat16)
    out = jnp.ones((2, 3, 4), jnp.bfloat16)
    d = tiles.delta(do, out)
    assert d.dtype == jnp.float32 and d.shape == (2, 3)
    np.testing.assert_array_equal(d, jnp.sum(do.astype(jnp.float32), -1))
    r = tiles.rows8(d)
    assert r.shape == (2, 8, 3)
    np.testing.assert_array_equal(r[:, 5], d)

    def kernel(x_ref, o_ref):
        tiles.write_row8(o_ref, x_ref[0][0:1, :] * 2.0)

    got = pl.pallas_call(
        kernel, grid=(2,),
        in_specs=[pl.BlockSpec((1, 8, 3), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, 8, 3), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((2, 8, 3), jnp.float32),
        interpret=True)(r)
    np.testing.assert_array_equal(got, 2.0 * r)


def _kind(tile):
    return None if tile.all() else "diagonal" if tile.any() else "skip"


@pytest.mark.parametrize("qi", range(4))
def test_the_causal_spans_cover_what_the_diagonal_mask_shows(qi):
    """A query block's spans, and a key block's, against the causal rule
    over the whole [keys, queries] square: an unmasked span holds blocks
    with nothing hidden, a "diagonal" one blocks the rule cuts (where
    `mask_diagonal` is the rule), no span the blocks with nothing seen."""
    bq, bk, n = 8, 4, 4
    visible = np.arange(2 * n * bk)[:, None] <= np.arange(n * bq)[None, :]

    def tile(j, i):
        return visible[j * bk:(j + 1) * bk, i * bq:(i + 1) * bq]

    got = ["skip"] * (2 * n)
    for lo, hi, mask in tiles.kv_spans(qi, bq, bk, 2 * n, minimum=min):
        got[lo:hi] = [mask] * (hi - lo)
    assert got == [_kind(tile(j, qi)) for j in range(2 * n)]
    for kj in (2 * qi, 2 * qi + 1):         # the key blocks on the diagonal
        got = ["skip"] * n
        for lo, hi, mask in tiles.q_spans(kj, bq, bk, n):
            got[int(lo):int(hi)] = [mask] * (int(hi) - int(lo))
        assert got == [_kind(tile(kj, i)) for i in range(n)]
        np.testing.assert_array_equal(
            tiles.mask_diagonal(jnp.zeros((bk, bq)), qi, kj, bq, bk) == 0,
            tile(kj, qi))


@pytest.mark.parametrize("bq,bk,window", [
    (8, 8, 8), (8, 8, 16), (8, 8, 5), (8, 8, 19), (8, 4, 6), (4, 8, 13),
    (8, 8, 1), (8, 8, 64), (16, 8, 24),
], ids=["one_block", "two_blocks", "under_a_block", "not_a_multiple",
        "narrow_keys", "narrow_queries", "itself_alone", "the_whole_row",
        "wide_queries"])
def test_the_windowed_spans_cover_what_the_band_shows(bq, bk, window):
    """Every (key block, query block) of a row of 64 under a sliding
    window, against the band ``q - window < k <= q`` over the whole
    square: a block in no span shows nothing; an unmasked span's blocks
    hide nothing; a masked span's blocks are cut, and the span's mask
    (`mask_diagonal`, or `mask_window` with or without the diagonal) is
    the band on them.  Seen from the queries (`kv_spans`, traced and as
    Python ints for the counters) and from the keys (`q_spans`)."""
    L = 64
    nq, nk = L // bq, L // bk
    k, q = np.arange(L)[:, None], np.arange(L)[None, :]
    band = (k <= q) & (k > q - window)

    def tile(j, i):
        return band[j * bk:(j + 1) * bk, i * bq:(i + 1) * bq]

    def masked(mask, i, j):
        zeros = jnp.zeros((bk, bq))
        if mask == "diagonal":
            return tiles.mask_diagonal(zeros, i, j, bq, bk) == 0
        return tiles.mask_window(zeros, i, j, bq, bk, window,
                                 diagonal=mask == "both") == 0

    def check(spans, n, tile_of, at, from_queries):
        kinds = ["skip"] * n
        for lo, hi, mask in spans:
            assert 0 <= int(lo) <= max(int(lo), int(hi)) <= n
            kinds[int(lo):int(hi)] = [mask] * max(int(hi) - int(lo), 0)
        for other, kind in enumerate(kinds):
            want = tile_of(other)
            if kind == "skip":
                assert not want.any(), (at, other)
            elif kind is None:
                assert want.all(), (at, other)
            else:
                assert want.any(), (at, other, kind)
                i, j = (at, other) if from_queries else (other, at)
                np.testing.assert_array_equal(masked(kind, i, j), want)

    for qi in range(nq):
        def keys_of(j, qi=qi):
            return tile(j, qi)
        check(tiles.kv_spans(qi, bq, bk, nk, minimum=min, window=window),
              nk, keys_of, qi, True)
        check(tiles.kv_spans(jnp.int32(qi), bq, bk, nk, window=window), nk,
              keys_of, qi, True)
    for kj in range(nk):
        check(tiles.q_spans(jnp.int32(kj), bq, bk, nq, window), nq,
              lambda i, kj=kj: tile(kj, i), kj, False)


def test_no_kernel_module_imports_a_private_name_of_a_sibling():
    """The boundary: what two kernel files share has a public name (in
    ``attention_tiles``, ``support`` or the file that launches it)."""
    found = []
    for path in sorted(PALLAS.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.ImportFrom) and node.level == 1:
                found += [f"{path.name}: from .{node.module or ''} import "
                          f"{a.name}" for a in node.names
                          if a.name.startswith("_")]
    assert not found, found
