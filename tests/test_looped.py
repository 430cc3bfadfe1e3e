"""A looped decoder (one stack of blocks run T times on shared weights,
an exit gate, the expected loss over T exits) against the plain reference
of benchmark/reference/ouro.py: the whole model's loss and every gradient
leaf, T = 1 against the plain stack, the exit distribution and its
entropy's gradient at saturated gates, a shared weight's gradient as the
sum over four untied copies (and what adding the four in bfloat16 costs),
and the weighted chunked head against an unchunked one."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import nn
from paddle_tpu.core import autograd
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit.bind import bind
from paddle_tpu.utils import monitor

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCH)
import check  # noqa: E402
import run as harness  # noqa: E402
import weights  # noqa: E402
from reference import ouro as ref  # noqa: E402

CELL = "ouro_2_6b.train_bf16_b2_s4096"


def ident(a):
    return a


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


# ------------------------------------------------------ the whole model --
def _cell(**overrides):
    """The cell's files at their rehearsal widths (T = 4, L = 3)."""
    cell, cfg, mix, model_mod, _, _ = harness.load_parts(CELL, rehearse=True)
    return cell, {**cfg, "vocab_size": 96, **overrides}, model_mod


def _inputs(cfg, seed, batch=2, seq=24):
    rng = np.random.default_rng(seed)
    ids, labels = (jnp.asarray(rng.integers(0, cfg["vocab_size"],
                                            (batch, seq), dtype=np.int32))
                   for _ in range(2))
    # gains and a gate away from their symmetric points, so that no
    # gradient is small by accident
    theta = weights.maker(seed, ref.param_shapes(cfg, {}))()
    theta["gate.w"] = theta["gate.w"] * 10.0
    theta["gate.b"] = theta["gate.b"] + 0.3
    return ids, labels, theta


def _program(cfg, model_mod, theta, ids, labels):
    """-> (loss, {reference leaf: gradient}) of the program's model in
    float32, differentiated by jax as under ``TrainStep``."""
    paddle.seed(0)
    model, loss_fn = model_mod.build(cfg, {})
    names = model_mod.param_map(cfg, {})
    keys = [check.key_of(*names[n]) for n, _ in model.named_parameters()]
    assert sorted(keys) == sorted(check.expanded_keys(theta))

    def loss(arrays):
        with bind(model, arrays), autograd.no_grad():
            return loss_fn(model(Tensor(ids)), Tensor(labels)).data

    value, grads = jax.value_and_grad(loss)(
        [check.take(theta, k) for k in keys])
    return value, dict(zip(keys, grads))


def test_loss_and_every_gradient_leaf_against_the_reference():
    _, cfg, model_mod = _cell()
    assert (cfg["total_ut_steps"], cfg["num_hidden_layers"]) == (4, 3)
    ids, labels, theta = _inputs(cfg, 11)
    got, got_g = _program(cfg, model_mod, theta, ids, labels)
    want, want_g = jax.value_and_grad(ref.loss)(theta, ids, labels, cfg, {})
    np.testing.assert_allclose(got, want, rtol=2e-6)
    for key, g in got_g.items():
        w = check.take(want_g, key)
        assert float(jnp.linalg.norm(w)) > 0, key
        np.testing.assert_allclose(
            g, w, rtol=2e-3, atol=2e-4 * float(jnp.max(jnp.abs(w))),
            err_msg=key)


def test_one_pass_is_the_plain_stack_with_plain_cross_entropy():
    """T = 1: p_1 = 1 whatever the gate says, the entropy is 0, and what
    is left is a decoder with a final norm and mean cross-entropy."""
    _, cfg, model_mod = _cell(total_ut_steps=1)
    ids, labels, theta = _inputs(cfg, 12)
    got, got_g = _program(cfg, model_mod, theta, ids, labels)

    def plain(params):
        blocks = {n[len("layers."):]: a for n, a in params.items()
                  if n.startswith("layers.")}
        h = params["tok"][ids]
        for i in range(cfg["num_hidden_layers"]):
            h = ref._block(h, {n: a[i] for n, a in blocks.items()}, cfg,
                           ident)
        z = ref.rms_norm(h, params["norm_f.g"], cfg["rms_norm_eps"])
        logp = jax.nn.log_softmax(z @ params["head.w"], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))

    want, want_g = jax.value_and_grad(plain)(theta)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    for key, g in got_g.items():
        w = check.take(want_g, key)
        np.testing.assert_allclose(
            g, w, rtol=2e-3, atol=2e-4 * float(jnp.max(jnp.abs(w))) + 1e-12,
            err_msg=key)
    # the gate is in nothing: its gradient is zero by the mathematics
    assert float(jnp.max(jnp.abs(got_g["gate.w"]))) == 0.0


def test_a_shared_weights_gradient_is_the_sum_over_four_untied_copies():
    """The reference with a copy of one layer's q weight a pass: the
    program's gradient of the shared leaf is the sum of the four; and
    adding the four parts in bfloat16, as the O2 step does, is as far from
    the float32 sum as rounding that sum once."""
    _, cfg, model_mod = _cell()
    ids, labels, theta = _inputs(cfg, 13)
    _, got_g = _program(cfg, model_mod, theta, ids, labels)
    T = cfg["total_ut_steps"]
    blocks = {n[len("layers."):]: a for n, a in theta.items()
              if n.startswith("layers.")}

    def untied(copies):
        h, states = theta["tok"][ids], []
        for t in range(T):
            for i in range(cfg["num_hidden_layers"]):
                p = {n: a[i] for n, a in blocks.items()}
                if i == 1:
                    p["q.w"] = copies[t]
                h = ref._block(h, p, cfg, ident)
            h = ref.rms_norm(h, theta["norm_f.g"], cfg["rms_norm_eps"])
            states.append(h)
        return ref.objective(theta, states, labels, cfg, ident)

    parts = jax.grad(untied)([theta["layers.q.w"][1]] * T)
    assert all(float(jnp.linalg.norm(g)) > 0 for g in parts)
    whole = sum(parts)
    np.testing.assert_allclose(got_g["layers.q.w[1]"], whole, rtol=2e-3,
                               atol=2e-4 * float(jnp.max(jnp.abs(whole))))

    def gap(g):
        return float(jnp.linalg.norm(g.astype(jnp.float32) - whole)
                     / jnp.linalg.norm(whole))

    # the backward pass delivers the last pass's part first
    in_bf16 = jnp.zeros_like(whole, jnp.bfloat16)
    for g in reversed(parts):
        in_bf16 = in_bf16 + g.astype(jnp.bfloat16)
    in_f32 = sum(g.astype(jnp.bfloat16).astype(jnp.float32)
                 for g in parts).astype(jnp.bfloat16)
    # a bfloat16 rounding is 2^-9 relative at most, 0.1 % in the norm:
    # both sums sit there, a twentieth of the cell's grad_diff (0.02)
    assert gap(in_f32) < 2.5e-3 and gap(in_bf16) < 4e-3
    assert gap(in_bf16) < 2.5 * gap(in_f32)


# ------------------------------------------------- the exit distribution --
def _written_entropy(z):
    """H(p) as the issue writes it, from lambda = sigmoid(z); the last
    pass's gate is not read."""
    p = ref.exit_distribution(jax.nn.sigmoid(z[:-1]))
    return -jnp.sum(p * jnp.log(p))


@pytest.mark.parametrize("scale", [1.0, 6.0], ids=["mild", "steep"])
def test_the_exit_distribution_sums_to_one_and_matches_the_written_form(
        scale):
    z = scale * jax.random.normal(jax.random.key(0), (4, 5, 7))
    p, log_p = F.loop_exit_distribution(paddle.to_tensor(z))
    np.testing.assert_allclose(jnp.sum(p.data, 0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(
        p.data, ref.exit_distribution(jax.nn.sigmoid(z[:-1])), rtol=1e-5,
        atol=1e-6)      # 1 - lambda loses its last bits near lambda = 1
    np.testing.assert_allclose(jnp.exp(log_p.data), p.data, rtol=1e-6)
    # the last pass's gate is not read
    again, _ = F.loop_exit_distribution(paddle.to_tensor(z.at[-1].set(9.0)))
    np.testing.assert_array_equal(again.data, p.data)


def test_the_entropys_gradient_is_the_written_formulas():
    z = 2.0 * jax.random.normal(jax.random.key(1), (4, 33))

    def mine(z):
        with paddle.no_grad():
            p, log_p = F.loop_exit_distribution(Tensor(z))
            return -jnp.sum(p.data * log_p.data)

    got, want = jax.grad(mine)(z), jax.grad(_written_entropy)(z)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-6)
    assert float(jnp.max(jnp.abs(got[-1]))) == 0.0


@pytest.mark.parametrize("logit", [-40.0, -200.0, 40.0, 200.0, 1e4])
def test_a_saturated_gate_gives_a_finite_entropy_and_gradient(logit):
    """lambda at 0 or 1 to the last bit: the written formula's 0 log 0 is
    NaN there; the sum of log-sigmoids is not, and its gradient is the
    limit's: zero."""
    z = jnp.full((4, 3), logit, jnp.float32).at[1].set(0.3)

    def mine(z):
        with paddle.no_grad():
            p, log_p = F.loop_exit_distribution(Tensor(z))
            return -jnp.sum(p.data * log_p.data), p.data

    (h, p), g = jax.value_and_grad(mine, has_aux=True)(z)
    assert np.isfinite(h) and np.all(np.isfinite(g)) and h >= 0
    np.testing.assert_allclose(jnp.sum(p, 0), 1.0, rtol=1e-6)
    if abs(logit) >= 200:
        assert not np.isfinite(_written_entropy(z))
        np.testing.assert_allclose(g[0], 0.0, atol=1e-30)


# --------------------------------------------- the weighted chunked head --
def _head_case(T, seed=2, H=16, V=50):
    ks = jax.random.split(jax.random.key(seed), 5)
    lab = jax.random.randint(ks[3], (T,), 0, V)
    lab = lab.at[jnp.arange(0, T, 5)].set(-100)
    return (jax.random.normal(ks[0], (T, H)),
            0.3 * jax.random.normal(ks[1], (H, V)),
            0.1 * jax.random.normal(ks[2], (V,)), lab,
            jax.random.uniform(ks[4], (T,)))


def _unchunked(h, w, b, lab, tw):
    logp = jax.nn.log_softmax((h @ w + b).astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.maximum(lab, 0)[:, None], -1)[:, 0]
    return jnp.sum(jnp.where(lab != -100, nll * tw, 0.0))


@pytest.mark.parametrize("T,chunk", [(64, 16), (61, 16), (40, 1024)],
                         ids=["whole_chunks", "padded_tail", "one_chunk"])
def test_the_weighted_chunked_head_against_an_unchunked_one(T, chunk):
    h, w, b, lab, tw = _head_case(T)

    def chunked(h, w, tw):
        with paddle.no_grad():
            return F.linear_cross_entropy(
                Tensor(h), Tensor(w), Tensor(b), Tensor(lab), chunk=chunk,
                token_weight=Tensor(tw)).data

    got = jax.value_and_grad(chunked, (0, 1, 2))(h, w, tw)
    want = jax.value_and_grad(
        lambda h, w, tw: _unchunked(h, w, b, lab, tw), (0, 1, 2))(h, w, tw)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, r, name in zip(got[1], want[1], ("hidden", "head", "weights")):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-6, err_msg=name)
    # the weight's gradient is the token's loss; an ignored token's is 0
    assert float(jnp.max(jnp.abs(got[1][2][lab == -100]))) == 0.0
    assert float(jnp.min(got[1][2][lab != -100])) > 0.0


def test_the_weighted_head_works_on_the_eager_tape():
    h, w, b, lab, tw = _head_case(48)
    th, tw_ = paddle.to_tensor(h), paddle.to_tensor(tw)
    th.stop_gradient = tw_.stop_gradient = False
    loss = F.linear_cross_entropy(th, paddle.to_tensor(w),
                                  paddle.to_tensor(b), paddle.to_tensor(lab),
                                  chunk=16, token_weight=tw_)
    loss.backward()
    want = jax.grad(lambda h, tw: _unchunked(h, w, b, lab, tw), (0, 1))(h, tw)
    np.testing.assert_allclose(th.grad.data, want[0], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(tw_.grad.data, want[1], rtol=1e-4, atol=1e-6)


def _mean_path_as_it_was(h, w, b, lab, *, chunk, ignore_index):
    """``_linear_ce_fn`` of the tree before ``token_weight``, verbatim."""
    T = h.shape[0]
    n = max(1, -(-T // chunk))
    per = -(-T // n)
    if n * per != T:
        pad = n * per - T
        h = jnp.concatenate(
            [h, jnp.zeros((pad, h.shape[-1]), h.dtype)], axis=0)
        lab = jnp.concatenate(
            [lab, jnp.full((pad,), ignore_index, lab.dtype)], axis=0)
    hs = h.reshape(n, per, h.shape[-1])
    ls = lab.reshape(n, per)

    @jax.checkpoint
    def chunk_nll(hc, lc):
        logits = (jnp.matmul(hc, w) + b).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        safe = jnp.where(lc == ignore_index, 0, lc)
        tgt = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
        nll = lse - tgt
        keep = (lc != ignore_index)
        return jnp.sum(nll * keep), jnp.sum(keep)

    def body(carry, xs):
        s, c = carry
        hc, lc = xs
        ds, dc = chunk_nll(hc, lc)
        return (s + ds, c + dc), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.int32(0)), (hs, ls))
    return total / jnp.maximum(count, 1).astype(jnp.float32)


@pytest.mark.parametrize("T,dtype", [(64, jnp.float32), (61, jnp.float32),
                                     (64, jnp.bfloat16)],
                         ids=["whole_chunks", "padded_tail", "bfloat16"])
def test_the_mean_path_is_bit_for_bit_what_it_was(T, dtype):
    h, w, b, lab, _ = _head_case(T, seed=4)
    h, w, b = h.astype(dtype), w.astype(dtype), b.astype(dtype)

    # the labels are an argument, as a step's are: over constant labels
    # XLA folds the count of kept tokens, which is now taken before the
    # scan, and divides by it as a product with its reciprocal
    def now(h, w, lab):
        with paddle.no_grad():
            return F.linear_cross_entropy(Tensor(h), Tensor(w), Tensor(b),
                                          Tensor(lab), chunk=16).data

    def was(h, w, lab):
        return _mean_path_as_it_was(h, w, b, lab, chunk=16,
                                    ignore_index=-100)

    got = jax.jit(jax.value_and_grad(now, (0, 1)))(h, w, lab)
    want = jax.jit(jax.value_and_grad(was, (0, 1)))(h, w, lab)
    np.testing.assert_array_equal(got[0], want[0])
    for g, r in zip(got[1], want[1]):
        np.testing.assert_array_equal(g, r)


# ---------------------------------------------------- the objective itself --
def test_loop_exit_loss_is_the_reference_objective_with_ignored_tokens():
    """``F.loop_exit_loss`` on given states against the reference's
    ``objective`` over the kept tokens only; counters at trace time."""
    T, N, H, V = 4, 37, 16, 50
    ks = jax.random.split(jax.random.key(5), 4)
    states = jax.random.normal(ks[0], (T, N, H))
    z = 2.0 * jax.random.normal(ks[1], (T, N))
    head = 0.3 * jax.random.normal(ks[2], (H, V))
    lab = jax.random.randint(ks[3], (N,), 0, V).at[::4].set(-100)
    keep = np.asarray(lab != -100)
    monitor.stat_reset()

    def mine(states, z, head):
        with paddle.no_grad():
            return F.loop_exit_loss(
                Tensor(states), Tensor(z), Tensor(head),
                Tensor(jnp.zeros((V,))), Tensor(lab), beta=0.1, chunk=16).data

    def theirs(states, z, head):
        # the reference's gate reads the states: hand it the logits through
        # a gate that picks column 0 of states widened by z
        wide = jnp.concatenate([z[..., None], states], -1)[:, keep]
        params = {"gate.w": jnp.zeros((H + 1, 1)).at[0, 0].set(1.0),
                  "gate.b": jnp.zeros((1,)),
                  "head.w": jnp.concatenate([jnp.zeros((1, V)), head])}
        return ref.objective(params, [s[None] for s in wide],
                             lab[keep][None], {"exit_entropy_beta": 0.1},
                             ident)

    got = jax.value_and_grad(mine, (0, 1, 2))(states, z, head)
    want = jax.value_and_grad(theirs, (0, 1, 2))(states, z, head)
    assert monitor.get_stat("linear_cross_entropy.calls") == 1
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, r, name in zip(got[1], want[1], ("states", "gate", "head")):
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=1e-6, err_msg=name)


@pytest.mark.parametrize("dtype,chunk,rows", [
    ("float32", None, 1024), ("bfloat16", None, 1024), ("float32", 16, 16)],
    ids=["f32_chooses", "bf16_chooses", "given"])
def test_loop_exit_loss_hands_its_chunk_on_as_it_is(dtype, chunk, rows):
    """``F.loop_exit_loss`` has no chunk of its own: with none named the
    head sees ``None`` and chooses as for any caller (the counter
    ``linear_cross_entropy.rows.1024`` set by a call through it that names
    none: a default handed on by keyword hid Ouro's call from PR 45's rule),
    a named one arrives as given; and the objective and its gradients over
    4 x 1100 rows in chunks of 2048 are those at the chosen rows."""
    T, N, H, V = 4, 1100, 16, 50
    ks = jax.random.split(jax.random.key(8), 4)
    states = jax.random.normal(ks[0], (T, N, H)).astype(dtype)
    z = jax.random.normal(ks[1], (T, N))
    head = (0.3 * jax.random.normal(ks[2], (H, V))).astype(dtype)
    lab = jax.random.randint(ks[3], (N,), 0, V).at[::4].set(-100)

    def pulled(**chunk):
        def loss(states, z, head):
            with paddle.no_grad():
                return F.loop_exit_loss(
                    Tensor(states), Tensor(z), Tensor(head),
                    Tensor(jnp.zeros((V,), dtype)), Tensor(lab),
                    **chunk).data
        monitor.stat_reset()
        out = jax.jit(jax.value_and_grad(loss, (0, 1, 2)))(states, z, head)
        return out, {k: v for k, v in monitor.all_stats().items()
                     if k.startswith("linear_cross_entropy.rows.")}

    got, counted = pulled(**({} if chunk is None else {"chunk": chunk}))
    assert counted == {f"linear_cross_entropy.rows.{rows}": 1}
    want, counted = pulled(chunk=2048)
    assert counted == {"linear_cross_entropy.rows.2048": 1}
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(got[0], want[0], rtol=tol)
    for g, r, name in zip(got[1], want[1], ("states", "gate", "head")):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        assert np.abs(r).max() > 0, name
        np.testing.assert_allclose(g, r, rtol=tol,
                                   atol=tol * np.abs(r).max(), err_msg=name)


def _count(jaxpr, primitive):
    """How many ``primitive`` equations a jaxpr holds, its sub-jaxprs
    (a scan's body, a custom rule's primal) counted once each."""
    total = 0
    for eqn in jaxpr.eqns:
        total += eqn.primitive.name == primitive
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    total += _count(sub, primitive)
    return total


@pytest.mark.parametrize("differentiated,products", [(False, 1), (True, 3)],
                         ids=["value_only", "with_gradients"])
def test_the_objectives_head_runs_no_matmul_twice(differentiated, products):
    """``F.loop_exit_loss`` by the products its trace holds: the value
    alone is one matmul a chunk; with gradients the one scan holds the
    logits, ``dh`` and ``dw`` and nothing is replayed.  The gate still
    gets its gradient through ``token_weight`` (the test above holds it
    to the reference's)."""
    T, N, H, V = 4, 37, 16, 50
    ks = jax.random.split(jax.random.key(6), 3)
    states = jax.random.normal(ks[0], (T, N, H))
    z = jax.random.normal(ks[1], (T, N))
    head = 0.3 * jax.random.normal(ks[2], (H, V))
    lab = jnp.arange(N) % V

    def loss(states, z, head):
        with paddle.no_grad():
            return F.loop_exit_loss(
                Tensor(states), Tensor(z), Tensor(head),
                Tensor(jnp.zeros((V,))), Tensor(lab), chunk=16).data

    fn = jax.grad(loss, (0, 1, 2)) if differentiated else loss
    jaxpr = jax.make_jaxpr(fn)(states, z, head).jaxpr
    assert _count(jaxpr, "dot_general") == products
    assert _count(jaxpr, "remat2") == 0     # jax.checkpoint's primitive
    if differentiated:
        assert all(bool(jnp.any(g != 0)) for g in fn(states, z, head))


def test_looped_stack_runs_its_blocks_steps_times_on_one_set_of_weights():
    paddle.seed(3)
    stack = nn.LoopedStack([nn.Linear(8, 8) for _ in range(2)], 3,
                           norm=nn.RMSNorm(8))
    assert len(list(stack.named_parameters())) == 5
    x = paddle.to_tensor(np.random.RandomState(0).randn(2, 5, 8)
                         .astype("float32"))
    monitor.stat_reset()
    out = stack(x)
    assert tuple(out.shape) == (3, 2, 5, 8)
    assert monitor.get_stat("loop.steps") == 3
    assert monitor.get_stat("loop.block_calls") == 6
    h = x
    for t in range(3):
        for blk in stack.blocks:
            h = blk(h)
        h = stack.norm(h)
        np.testing.assert_allclose(out.data[t], h.data, rtol=1e-6)
    with pytest.raises(ValueError):
        nn.LoopedStack([nn.Linear(8, 8)], 0)
    gate = nn.LoopExitGate(8)
    logits = gate(out)
    assert tuple(logits.shape) == (3, 2, 5) and logits.dtype == jnp.float32
    np.testing.assert_allclose(
        logits.data, (out.data @ gate.weight.data)[..., 0] + gate.bias.data,
        rtol=1e-5, atol=1e-6)
