"""Trinity-Mini as the program builds it (benchmark/models/afmoe.py over
``nn.GroupedQueryAttention`` told each layer's kind, ``nn.GatedFFN``,
``nn.MoELayer``) against benchmark/reference/afmoe.py on seeded weights at
the cell's rehearsal widths: the loss with every gradient leaf and one
AdamW step in float32, the whole step through ``TrainStep`` in bfloat16
O2 (the harness's rehearsal); the sliding window in the flash kernels
(interpret mode) against the masked XLA form, forward and all three
gradients; ``window=None`` traced to the program it was before the
argument existed; and the chip's share of an expert layer tied to the
uncut model."""
import argparse
import hashlib
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
from paddle_tpu import nn
from paddle_tpu.observability import scopes
from paddle_tpu.utils import monitor

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCH)
import run as harness  # noqa: E402

CELL = "trinity_mini.train_bf16_b1_s16384"
fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def parts():
    """The rehearsal's cell (a dense window layer, an expert window layer
    and an expert full layer at tiny widths, window 24 in rows of 64) in
    float32: its files, its seeded weights as the reference's leaves, and
    a batch."""
    cell, cfg, mix, model_mod, ref, runner = harness.load_parts(
        CELL, rehearse=True)
    cell = {**cell, "dtype": "float32"}
    ring, theta0 = harness.seeded_inputs(cell, cfg, mix, ref, seed=5)
    return cell, cfg, mix, model_mod, ref, runner, ring, theta0


def _laid_in(model_mod, cfg, theta):
    """The program's model with the reference's leaves laid into it."""
    import check
    paddle.seed(0)
    model, loss_fn = model_mod.build(cfg, {})
    names = model_mod.param_map(cfg, {})
    for pname, p in model.named_parameters():
        p.data = check.take(theta, check.key_of(*names[pname]))
    return model, loss_fn, names


def _close(got, want, what, tol=2e-4):
    scale = max(float(jnp.max(jnp.abs(want))), 1e-12)
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * scale,
                               err_msg=what)


# ------------------------------------------- the model against the reference
def _loss_and_gradients(parts, program_cfg=None):
    """-> (the program's loss and gradients keyed as the reference's
    leaves, the reference's) on the first batch, float32."""
    import check
    _, cfg, _, model_mod, ref, _, ring, theta0 = parts
    theta = theta0()
    model, loss_fn, names = _laid_in(model_mod, program_cfg or cfg, theta)
    ids, labels = (jnp.asarray(a) for a in ring[0])
    params = list(model.named_parameters())

    def program(values):
        for (_, p), v in zip(params, values):
            p.data = v
        return loss_fn(model(paddle.to_tensor(ids)),
                       paddle.to_tensor(labels)).data

    got, grads = jax.value_and_grad(program)([p.data for _, p in params])
    got_g = {check.key_of(*names[n]): g for (n, _), g in zip(params, grads)}
    want, want_g = jax.value_and_grad(ref.loss)(theta, ids, labels, cfg, {})
    return (got, got_g), (want, {k: check.take(want_g, k) for k in got_g})


def _worst(got_g, want_g):
    """The largest difference of a leaf's gradients over that leaf's
    largest element, over the leaves with a gradient."""
    return max(float(jnp.max(jnp.abs(got_g[k] - w)))
               / float(jnp.max(jnp.abs(w)))
               for k, w in want_g.items() if float(jnp.max(jnp.abs(w))) > 0)


# Float32 at precision "highest" on both sides; the same sums in another
# order (XLA's attention over all queries against 256 at a time, the
# grouped products against an expert at a time, a fused in-projection
# against its halves): 2e-4 of a leaf's largest element, granite's limit,
# far under what a missing term gives (`test_each_part_matters`).
def test_the_loss_and_every_gradient_are_the_references(parts):
    """The whole model and the chunked head in float32: the loss to 1e-5
    and every leaf's gradient to 2e-4 of its largest element.  The routers
    are held still, so ``router.w`` and the selection bias have none, on
    both sides."""
    (got, got_g), (want, want_g) = _loss_and_gradients(parts)
    # 13 leaves of the dense layer, 19 of each expert layer, 3 outside
    assert sorted(got_g) == sorted(want_g) and len(got_g) == 13 + 2 * 19 + 3
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for k, w in want_g.items():
        still = k.startswith("layers.router.")
        assert (float(jnp.max(jnp.abs(w))) > 0) != still, k
        _close(got_g[k], w, k)


@pytest.mark.parametrize("change", [
    {"sliding_window": 64}, {"mup_enabled": False}, {"route_scale": 1.0},
    {"layer_types": ["sliding_attention"] * 3},
    {"layer_types": ["full_attention"] * 3},
], ids=["no_window", "no_mup", "no_route_scale", "rope_on_the_full_layer",
        "no_rope_on_the_window_layers"])
def test_each_part_matters(parts, change):
    """A program built without the window (64 covers the row), without
    muP's factor, without the routers' scale, or with the layers' kinds
    moved (positions on the full layer; none, and no window, on the window
    layers) fails the comparison the true one passes at 2e-4 by a hundred
    times."""
    cfg = parts[1]
    (got, got_g), (want, want_g) = _loss_and_gradients(
        parts, {**cfg, **change})
    off = max(_worst(got_g, want_g), abs(float(got - want)) / float(want))
    assert off > 2e-2, (change, off)


def test_one_adamw_step_in_float32(parts):
    """The harness's comparison (benchmark/check.py) with the cell in
    float32: the first two losses, the first gradient as the optimizer got
    it and the parameters' change over two AdamW steps with the clip;
    granite's limits (float32 against float32 at "highest": a hundred
    times under the bfloat16 rehearsal's)."""
    import check
    cell, cfg, mix, model_mod, ref, runner, ring, theta0 = parts
    want = harness.follow_reference(check, ref, cell, cfg, mix, ring, theta0)
    state = runner.build(cell, cfg, model_mod, theta0(), mix)
    got = harness.follow_program(check, runner, state, cell, ring, theta0)
    numbers = {k: v[0] for k, v in check.compare(got, want).items()}
    runner.close(state)
    assert numbers["loss_gap"] < 1e-6, numbers
    assert numbers["grad_norm_gap"] < 2e-5, numbers
    assert numbers["update_norm_gap"] < 1e-3, numbers
    assert numbers["grad_diff"] < 2e-5, numbers


def test_the_whole_step_follows_the_reference_through_trainstep():
    """``run.py --rehearse``: the model through ``TrainStep``, ``amp`` O2
    (bfloat16), AdamW and per-block recompute over two steps against the
    float32 reference, under the rehearsal's limits (the cell's file says
    what each was set from)."""
    args = argparse.Namespace(workload=CELL, seed=7, seconds=0.5, trace=0,
                              keep_trace=None)
    assert harness.run_cell(args, rehearse=True)["correct"] is True


# ------------------------------------------------------------- the share --
def test_the_eight_members_parts_add_up_to_the_uncut_layer(parts):
    """The share tied to the model (the model-configs guide, section 4):
    one expert layer's feed-forward part over 16 experts, 4 members of 4
    at the rehearsal's widths.  Each member's routed part, by the program's
    ``nn.MoELayer`` told which experts it holds, is the reference's for
    that share; the four parts and the shared expert COUNTED ONCE add up
    to what the uncut reference gives for the whole layer."""
    _, cfg, _, _, ref, _, _, theta0 = parts
    E, H, Fw = 16, cfg["hidden_size"], cfg["moe_intermediate_size"]
    assert cfg["published"]["num_experts"] == E and cfg["num_experts"] == 4
    keys = jax.random.split(jax.random.key(3), 8)
    whole = {"router.w": 0.5 * jax.random.normal(keys[0], (H, E)),
             "router.bias": 0.02 * jax.random.normal(keys[1], (E,)),
             "experts.gate": 0.2 * jax.random.normal(keys[2], (E, H, Fw)),
             "experts.up": 0.2 * jax.random.normal(keys[3], (E, H, Fw)),
             "experts.down": 0.2 * jax.random.normal(keys[4], (E, Fw, H)),
             "shared.gate.w": 0.2 * jax.random.normal(keys[5], (H, Fw)),
             "shared.up.w": 0.2 * jax.random.normal(keys[6], (H, Fw)),
             "shared.down.w": 0.2 * jax.random.normal(keys[7], (Fw, H))}
    b = jax.random.normal(jax.random.key(4), (96, H))
    uncut = {**cfg, "num_experts": E}
    want, chosen = ref.feed_forward(b, whole, uncut, tuple(range(E)))
    assert chosen.shape == (96, cfg["num_experts_per_tok"])
    total = 0.0
    for first in range(0, E, 4):
        held = range(first, first + 4)
        share = {k: (v[first:first + 4] if k.startswith("experts.") else v)
                 for k, v in whole.items()}
        part, _ = ref.feed_forward(b, share, cfg, tuple(held),
                                   with_shared=False)
        layer = nn.MoELayer(
            H, Fw, E, cfg["num_experts_per_tok"], held=held,
            norm_topk_prob=cfg["route_norm"], scoring=cfg["score_func"],
            selection_bias=True, routed_scaling_factor=cfg["route_scale"],
            train_router=False)
        for name, leaf in (("router_weight", "router.w"),
                           ("router_bias", "router.bias"),
                           ("w_gate", "experts.gate"), ("w_up", "experts.up"),
                           ("w_down", "experts.down")):
            getattr(layer, name).data = share[leaf]
        _close(layer(paddle.to_tensor(b)).data, part, f"members {held}")
        total = total + part
    only_shared = ref.swiglu(b, whole["shared.gate.w"], whole["shared.up.w"],
                             whole["shared.down.w"], lambda a: a)
    assert float(jnp.max(jnp.abs(only_shared))) > 0.1
    _close(total + only_shared, want, "the members' parts")


# ------------------------------------------------- the window in the kernels
def _qkv(L, heads, kv, D, seed=6):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (1, L, heads, D)),
            jax.random.normal(ks[1], (1, L, kv, D)),
            jax.random.normal(ks[2], (1, L, kv, D)),
            jax.random.normal(ks[3], (1, L, heads, D)))


def _sdpa(window):
    def call(q, k, v):
        return F.scaled_dot_product_attention(
            *(paddle.to_tensor(a) for a in (q, k, v)), is_causal=True,
            window=window).data
    return call


def _value_and_grads(fn, q, k, v, ct):
    return jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * ct),
                              (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("L,window,heads,kv,blocks", [
    (1024, 512, 4, 2, None),        # a multiple of the block (one block)
    (1024, 200, 4, 1, None),        # shorter than a block
    (1024, 700, 2, 2, None),        # straddles a block boundary
    (1024, 2048, 4, 2, None),       # longer than the row: plain causal
    (512, 96, 8, 2, (64, 64)),      # several blocks a window, 4 heads a group
    (512, 100, 4, 2, (128, 64)),    # unequal blocks, not a multiple
    (512, 1, 2, 1, (64, 64)),       # a query sees itself alone
], ids=["one_block", "under_a_block", "not_a_multiple", "over_the_row",
        "whole_blocks", "unequal_blocks", "window_of_one"])
def test_the_window_kernels_are_the_masked_xla_form(kernels_on, L, window,
                                                    heads, kv, blocks):
    """``window=`` through the flash kernels (interpret mode) against
    ``F.scaled_dot_product_attention``'s own XLA form, which masks the
    band: the value and all three gradients, grouped heads, float32 on
    both sides (blocks against all keys at once: 1e-4 of the largest
    element, granite's limit)."""
    q, k, v, ct = _qkv(L, heads, kv, 32)
    if blocks is None:
        before = monitor.all_stats().get("pallas.selected.flash_attention", 0)
        kernel = _sdpa(window)
    else:           # the functional leaves the blocks to the kernels
        def kernel(q, k, v):
            return fa.flash_attention(q, k, v, causal=True, window=window,
                                      block_q=blocks[0], block_k=blocks[1])
    got, got_g = _value_and_grads(kernel, q, k, v, ct)
    if blocks is None:
        assert monitor.all_stats()["pallas.selected.flash_attention"] > before
    from paddle_tpu.core.flags import set_flags
    set_flags({"pallas_interpret": False})          # XLA's side
    before = monitor.all_stats().get("attention.xla_path", 0)
    want, want_g = _value_and_grads(_sdpa(window), q, k, v, ct)
    assert monitor.all_stats()["attention.xla_path"] > before
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for name, g, w in zip("qkv", got_g, want_g):
        assert g.shape == w.shape
        # a window of one leaves dq nothing but rounding: the floor
        np.testing.assert_allclose(
            g, w, rtol=0, err_msg="d" + name,
            atol=1e-4 * max(float(jnp.max(jnp.abs(w))), 0.1))
    # and the band is what the formula says: a query's last `window` keys
    seen = (jnp.arange(L)[:, None] >= jnp.arange(L)[None]) & (
        jnp.arange(L)[:, None] - jnp.arange(L)[None] < window)
    s = jnp.einsum("blhd,bshd->bhls", q, jnp.repeat(k, heads // kv, 2))
    w_ = jax.nn.softmax(jnp.where(seen, s * 32 ** -0.5, -jnp.inf), -1)
    _close(_sdpa(window)(q, k, v),
           jnp.einsum("bhls,bshd->blhd", w_, jnp.repeat(v, heads // kv, 2)),
           "the band", 1e-5)


def test_a_window_skips_the_blocks_below_it(kernels_on):
    """The schedule's counters: at 2048 positions in blocks of 512 with a
    window of 512, a q block runs 2 k blocks (1 at the row's start) of the
    triangle's 1..4: 7 of 10 a kernel traced, 3 skipped; the window's
    lower edge and the diagonal each mask one of a q block's two."""
    q, k, v, _ = _qkv(2048, 2, 1, 32)
    monitor.stat_reset()
    _sdpa(512)(q, k, v)
    stats = monitor.all_stats()
    assert (stats["pallas.flash.window_blocks_full"],
            stats["pallas.flash.window_blocks_masked"],
            stats["pallas.flash.window_blocks_skipped"]) == (0, 7, 3)
    assert (stats["pallas.flash.blocks_full"],
            stats["pallas.flash.blocks_masked"]) == (0, 7)
    monitor.stat_reset()
    _sdpa(None)(q, k, v)
    stats = monitor.all_stats()
    assert "pallas.flash.window_blocks_full" not in stats
    assert (stats["pallas.flash.blocks_full"],
            stats["pallas.flash.blocks_masked"]) == (6, 4)


def test_a_window_wants_a_causal_aligned_call():
    q, k, v, _ = _qkv(64, 2, 2, 16)
    with pytest.raises(ValueError, match="window"):
        F.scaled_dot_product_attention(*(paddle.to_tensor(a)
                                         for a in (q, k, v)), window=8)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, k, v, causal=False, window=8)
    # the ring's blocks carry traced offsets: no window there
    with pytest.raises(NotImplementedError, match="window"):
        fa._fwd(*(jnp.swapaxes(a, 1, 2) for a in (q, k, v)), fa.zero_off(),
                fa.zero_off(), fa.zero_seed(), 1.0, True, (64, 64), False,
                window=8)


# Digests of ``str(jax.make_jaxpr(...))`` (addresses dropped) made at this
# PR's parent, fe5a678, before `window` existed, under the jax that
# pyproject.toml pins; the same script on this tree gives the same five.
# A PR that changes the kernels on purpose reads the failure's digest and
# moves them.
_PARENTS = {
    "causal_grouped": "06cda13a598befd0", "causal_96": "509770ff4b2c04c4",
    "bidirectional_64": "c85cf3207123f841", "lying_128": "52fbb2bd29f647c8",
    "shared_key": "95a8cc3e7516d88c"}


@pytest.mark.parametrize("case", sorted(_PARENTS))
def test_without_a_window_the_kernels_trace_to_the_parents_jaxpr(case):
    def x(*shape):
        return jnp.zeros(shape, jnp.bfloat16)

    def loss(fn, wrt=(0, 1, 2)):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2), wrt)

    def causal(q, k, v):
        return fa.flash_attention(q, k, v, causal=True)
    fn, args = {
        "causal_grouped": (loss(causal), (x(1, 1024, 8, 128),
                                          x(1, 1024, 2, 128),
                                          x(1, 1024, 2, 128))),
        "causal_96": (loss(causal), (x(2, 1024, 4, 96),) * 3),
        "bidirectional_64": (loss(fa.flash_attention),
                             (x(2, 512, 4, 64),) * 3),
        "lying_128": (loss(causal), (x(1, 1024, 4, 128),) * 3),
        "shared_key": (loss(fa.flash_attention_shared_key, (0, 1, 2, 3, 4)),
                       (x(1, 1024, 4, 128), x(1, 1024, 4, 64),
                        x(1, 1024, 4, 128), x(1, 1024, 64),
                        x(1, 1024, 4, 128))),
    }[case]
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(fn)(*args)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == _PARENTS[case]
    # and naming no window is leaving the argument out
    if case != "shared_key" and case != "bidirectional_64":
        with_none = loss(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=None))
        assert re.sub(r" at 0x[0-9a-f]+", "", str(
            jax.make_jaxpr(with_none)(*args))) == text


# -------------------------------------------------- the layer and the gate --
def test_the_output_gate_is_a_sigmoid_under_its_scope():
    a = jax.random.normal(jax.random.key(1), (2, 5, 16))
    g = jax.random.normal(jax.random.key(2), (2, 5, 16))
    got = F.attention_output_gate(paddle.to_tensor(a), paddle.to_tensor(g))
    np.testing.assert_allclose(got.data, a * jax.nn.sigmoid(g), rtol=1e-6)
    half = F.attention_output_gate(paddle.to_tensor(a.astype(jnp.bfloat16)),
                                   paddle.to_tensor(g.astype(jnp.bfloat16)))
    assert half.data.dtype == jnp.bfloat16
    text = jax.jit(lambda a, g: F.attention_output_gate(
        paddle.to_tensor(a), paddle.to_tensor(g)).data).lower(
            a, g).as_text(debug_info=True)
    assert f"{scopes.ATTN_GATE}/" in text
    assert {scopes.ATTN_GATE, scopes.WINDOW_ATTENTION} <= set(
        scopes.FUNCTIONALS)


@pytest.mark.parametrize("window,theta", [(24, 1e4), (None, None)],
                         ids=["window_with_rope", "full_without"])
def test_an_attention_layer_is_told_its_kind(parts, window, theta):
    """``nn.GroupedQueryAttention`` as the model file builds its two kinds,
    against the reference's ``attention`` for that kind; the window call
    sits under ``window_attention`` inside the attention scope, the full
    call outside it."""
    _, cfg, _, _, ref, _, _, _ = parts
    H, A, KV, D = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], cfg["head_dim"])
    layer = nn.GroupedQueryAttention(
        H, A, KV, D, window=window, rope_theta=theta, qk_norm=True,
        output_gate=True, epsilon=cfg["rms_norm_eps"])
    assert [n for n, _ in layer.named_parameters()] == [
        "q.weight", "k.weight", "v.weight", "gate.weight", "q_norm.weight",
        "k_norm.weight", "o.weight"]
    for i, (_, p) in enumerate(layer.named_parameters()):
        p.data = (p.data * 0 + 1.0 if p.data.ndim == 1 else 0.0) + \
            0.3 * jax.random.normal(jax.random.key(i), p.data.shape)
    p = {n.replace(".weight", ".g" if "norm" in n else ".w"): q.data
         for n, q in layer.named_parameters()}
    u = jax.random.normal(jax.random.key(9), (64, H))
    kind = "sliding_attention" if window else "full_attention"
    want = ref.attention(u, p, {**cfg, "sliding_window": 24}, kind,
                         lambda a: a)
    got = layer(paddle.to_tensor(u[None])).data[0]
    _close(got, want, kind, 1e-5)
    text = jax.jit(lambda u: layer(paddle.to_tensor(u)).data).lower(
        u[None]).as_text(debug_info=True)
    inside = f"{scopes.ATTENTION}/{scopes.WINDOW_ATTENTION}/" in text
    assert inside == bool(window)
    assert (f"{scopes.ROPE}/" in text) == bool(window)
    assert f"{scopes.QK_NORM}/" in text and f"{scopes.ATTN_GATE}/" in text
    assert ("window 24" if window else "full") in repr(layer)
