"""nn.Layer system + layer correctness tests (modelled on the reference's
test_layers.py / per-op OpTest suites)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
import paddle_tpu.nn.functional as F


def test_linear_matches_numpy():
    l = nn.Linear(4, 3)
    x = paddle.randn([5, 4])
    y = l(x)
    expect = x.numpy() @ l.weight.numpy() + l.bias.numpy()
    np.testing.assert_allclose(y.numpy(), expect, rtol=1e-5)


def test_layer_registry_and_state_dict():
    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(4, 8)
            self.fc2 = nn.Linear(8, 2)
            self.act = nn.ReLU()

        def forward(self, x):
            return self.fc2(self.act(self.fc1(x)))

    net = Net()
    names = [n for n, _ in net.named_parameters()]
    assert names == ["fc1.weight", "fc1.bias", "fc2.weight", "fc2.bias"]
    sd = net.state_dict()
    assert set(sd) == set(names)

    net2 = Net()
    net2.set_state_dict(sd)
    for (n1, p1), (n2, p2) in zip(net.named_parameters(),
                                  net2.named_parameters()):
        np.testing.assert_allclose(p1.numpy(), p2.numpy())


def test_state_dict_save_load_roundtrip(tmp_path):
    net = nn.Sequential(nn.Linear(3, 4), nn.ReLU(), nn.Linear(4, 2))
    path = str(tmp_path / "model.pdparams")
    paddle.save(net.state_dict(), path)
    net2 = nn.Sequential(nn.Linear(3, 4), nn.ReLU(), nn.Linear(4, 2))
    net2.set_state_dict(paddle.load(path))
    x = paddle.randn([2, 3])
    np.testing.assert_allclose(net(x).numpy(), net2(x).numpy(), rtol=1e-6)


def test_conv2d_shape_and_grad():
    conv = nn.Conv2D(3, 8, 3, stride=2, padding=1)
    x = paddle.randn([2, 3, 16, 16])
    x.stop_gradient = False
    y = conv(x)
    assert y.shape == [2, 8, 8, 8]
    y.sum().backward()
    assert x.grad is not None and conv.weight.grad is not None
    assert conv.weight.grad.shape == [8, 3, 3, 3]


def test_conv2d_matches_manual():
    # 1x1 conv == matmul over channels
    conv = nn.Conv2D(4, 2, 1, bias_attr=False)
    x = paddle.randn([1, 4, 5, 5])
    y = conv(x)
    w = conv.weight.numpy().reshape(2, 4)
    expect = np.einsum("oc,nchw->nohw", w, x.numpy())
    np.testing.assert_allclose(y.numpy(), expect, rtol=1e-4, atol=1e-5)


def test_depthwise_and_grouped_conv():
    conv = nn.Conv2D(4, 4, 3, groups=4, padding=1)
    y = conv(paddle.randn([1, 4, 8, 8]))
    assert y.shape == [1, 4, 8, 8]
    assert conv.weight.shape == [4, 1, 3, 3]


def test_conv2d_transpose():
    convt = nn.Conv2DTranspose(3, 6, 4, stride=2, padding=1)
    y = convt(paddle.randn([1, 3, 8, 8]))
    assert y.shape == [1, 6, 16, 16]


def test_batchnorm_train_eval():
    bn = nn.BatchNorm2D(3, momentum=0.5)
    x = paddle.randn([8, 3, 4, 4]) * 2 + 1
    bn.train()
    y = bn(x)
    # normalized output: near zero mean / unit var per channel
    yn = y.numpy()
    assert abs(yn.mean()) < 1e-5
    np.testing.assert_allclose(yn.var(axis=(0, 2, 3)), np.ones(3), rtol=1e-3)
    # running stats moved toward batch stats
    assert float(bn._mean.abs().sum()) > 0
    bn.eval()
    y2 = bn(x)
    assert not np.allclose(y2.numpy(), yn)


def test_layernorm():
    ln = nn.LayerNorm(8)
    x = paddle.randn([4, 8]) * 3 + 2
    y = ln(x).numpy()
    np.testing.assert_allclose(y.mean(axis=-1), np.zeros(4), atol=1e-5)
    np.testing.assert_allclose(y.var(axis=-1), np.ones(4), rtol=1e-3)


def test_groupnorm():
    gn = nn.GroupNorm(2, 4)
    y = gn(paddle.randn([2, 4, 4, 4]))
    assert y.shape == [2, 4, 4, 4]


def test_embedding_padding_idx():
    emb = nn.Embedding(10, 4, padding_idx=0)
    ids = paddle.to_tensor(np.array([[0, 1], [2, 0]]))
    out = emb(ids)
    assert out.shape == [2, 2, 4]
    np.testing.assert_allclose(out.numpy()[0, 0], np.zeros(4))
    np.testing.assert_allclose(out.numpy()[1, 1], np.zeros(4))


def test_dropout_train_eval():
    d = nn.Dropout(0.5)
    x = paddle.ones([100, 100])
    d.train()
    y = d(x)
    frac = (y.numpy() == 0).mean()
    assert 0.4 < frac < 0.6
    # upscale keeps expectation
    assert abs(y.numpy().mean() - 1.0) < 0.05
    d.eval()
    np.testing.assert_allclose(d(x).numpy(), x.numpy())


def test_pools():
    x = paddle.to_tensor(np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4))
    mp = nn.MaxPool2D(2, 2)(x)
    np.testing.assert_allclose(mp.numpy()[0, 0], [[5, 7], [13, 15]])
    ap = nn.AvgPool2D(2, 2)(x)
    np.testing.assert_allclose(ap.numpy()[0, 0], [[2.5, 4.5], [10.5, 12.5]])
    aap = nn.AdaptiveAvgPool2D(1)(x)
    np.testing.assert_allclose(aap.numpy()[0, 0], [[7.5]])
    amp = nn.AdaptiveMaxPool2D(2)(x)
    np.testing.assert_allclose(amp.numpy()[0, 0], [[5, 7], [13, 15]])


def test_activations_values():
    x = paddle.to_tensor([-2.0, -0.5, 0.0, 0.5, 2.0])
    np.testing.assert_allclose(nn.ReLU()(x).numpy(), [0, 0, 0, 0.5, 2])
    np.testing.assert_allclose(
        nn.LeakyReLU(0.1)(x).numpy(), [-0.2, -0.05, 0, 0.5, 2], rtol=1e-6)
    np.testing.assert_allclose(
        nn.Sigmoid()(x).numpy(), 1 / (1 + np.exp(-x.numpy())), rtol=1e-5)
    s = nn.Softmax()(x).numpy()
    np.testing.assert_allclose(s.sum(), 1.0, rtol=1e-6)
    g = F.gelu(x).numpy()
    assert g[2] == 0 and g[4] > 1.9


def test_sequential_and_layerlist():
    seq = nn.Sequential(nn.Linear(2, 3), nn.ReLU(), nn.Linear(3, 1))
    assert len(seq) == 3
    assert isinstance(seq[0], nn.Linear)
    ll = nn.LayerList([nn.Linear(2, 2) for _ in range(3)])
    ll.append(nn.Linear(2, 2))
    assert len(ll) == 4
    assert len(list(ll.parameters())) == 8


def test_cross_entropy_matches_manual():
    logits = paddle.randn([4, 5])
    labels = paddle.to_tensor(np.array([0, 2, 4, 1]))
    loss = F.cross_entropy(logits, labels)
    lp = logits.numpy() - logits.numpy().max(axis=1, keepdims=True)
    lp = lp - np.log(np.exp(lp).sum(axis=1, keepdims=True))
    expect = -lp[np.arange(4), labels.numpy()].mean()
    np.testing.assert_allclose(float(loss), expect, rtol=1e-5)


def test_cross_entropy_ignore_index():
    logits = paddle.randn([4, 5])
    labels = paddle.to_tensor(np.array([0, -100, 4, -100]))
    loss = F.cross_entropy(logits, labels, ignore_index=-100)
    lp = logits.numpy() - logits.numpy().max(axis=1, keepdims=True)
    lp = lp - np.log(np.exp(lp).sum(axis=1, keepdims=True))
    expect = -(lp[0, 0] + lp[2, 4]) / 2
    np.testing.assert_allclose(float(loss), expect, rtol=1e-5)


def test_losses():
    a = paddle.to_tensor([1.0, 2.0, 3.0])
    b = paddle.to_tensor([1.5, 2.0, 2.0])
    np.testing.assert_allclose(float(nn.MSELoss()(a, b)),
                               np.mean([0.25, 0, 1]), rtol=1e-6)
    np.testing.assert_allclose(float(nn.L1Loss()(a, b)),
                               np.mean([0.5, 0, 1]), rtol=1e-6)
    p = paddle.to_tensor([0.9, 0.1])
    t = paddle.to_tensor([1.0, 0.0])
    np.testing.assert_allclose(float(nn.BCELoss()(p, t)),
                               -np.mean([np.log(0.9), np.log(0.9)]),
                               rtol=1e-4)
    z = paddle.to_tensor([2.0, -1.0])
    bwl = float(nn.BCEWithLogitsLoss()(z, t))
    expect = np.mean([np.log1p(np.exp(-2.0)), np.log1p(np.exp(-1.0))])
    np.testing.assert_allclose(bwl, expect, rtol=1e-5)


def test_multihead_attention_shapes_and_grad():
    mha = nn.MultiHeadAttention(32, 4)
    q = paddle.randn([2, 6, 32])
    out = mha(q, q, q)
    assert out.shape == [2, 6, 32]
    out.sum().backward()
    assert mha.q_proj.weight.grad is not None


def test_mha_causal_mask():
    mha = nn.MultiHeadAttention(16, 2)
    mha.eval()
    x = paddle.randn([1, 4, 16])
    L = 4
    mask = paddle.to_tensor(np.tril(np.ones((1, 1, L, L), bool)))
    y_masked = mha(x, x, x, attn_mask=mask)
    # position 0 attends only to itself; change in later tokens must not
    # affect position 0 output
    x2 = x.clone()
    x2[0, 3] = paddle.randn([16])
    y2 = mha(x2, x2, x2, attn_mask=mask)
    np.testing.assert_allclose(y_masked.numpy()[0, 0], y2.numpy()[0, 0],
                               rtol=2e-3, atol=2e-5)


def test_transformer_encoder_decoder():
    model = nn.Transformer(d_model=32, nhead=4, num_encoder_layers=2,
                           num_decoder_layers=2, dim_feedforward=64)
    src = paddle.randn([2, 5, 32])
    tgt = paddle.randn([2, 3, 32])
    out = model(src, tgt)
    assert out.shape == [2, 3, 32]


def test_lstm_and_gru():
    lstm = nn.LSTM(4, 8)
    x = paddle.randn([2, 6, 4])
    y, (h, c) = lstm(x)
    assert y.shape == [2, 6, 8]
    assert h.shape == [1, 2, 8] and c.shape == [1, 2, 8]
    # final hidden equals last output step for unidirectional lstm
    np.testing.assert_allclose(y.numpy()[:, -1], h.numpy()[0], rtol=1e-5)

    gru = nn.GRU(4, 8, direction="bidirect")
    y2, h2 = gru(x)
    assert y2.shape == [2, 6, 16]
    assert h2.shape == [2, 2, 8]
    y2.sum().backward()
    assert gru.weight_ih_l0.grad is not None


def test_lstm_cell_vs_layer():
    cell = nn.LSTMCell(4, 8)
    rnn = nn.RNN(cell)
    x = paddle.randn([2, 5, 4])
    y, state = rnn(x)
    assert y.shape == [2, 5, 8]


def test_train_eval_propagates():
    net = nn.Sequential(nn.Linear(2, 2), nn.Dropout(0.5))
    net.eval()
    assert not net[1].training
    net.train()
    assert net[1].training


def test_interpolate():
    x = paddle.to_tensor(np.arange(4, dtype=np.float32).reshape(1, 1, 2, 2))
    y = F.interpolate(x, scale_factor=2, mode="nearest")
    assert y.shape == [1, 1, 4, 4]
    np.testing.assert_allclose(y.numpy()[0, 0, :2, :2], 0)
    b = F.interpolate(x, size=[4, 4], mode="bilinear")
    assert b.shape == [1, 1, 4, 4]


def test_forward_hooks():
    l = nn.Linear(2, 2)
    calls = []
    h = l.register_forward_post_hook(lambda layer, inp, out: calls.append(1))
    l(paddle.randn([1, 2]))
    assert calls == [1]
    h.remove()
    l(paddle.randn([1, 2]))
    assert calls == [1]


def test_cross_entropy_weighted_mean_semantics():
    # ADVICE r1: weighted mean divides by the sum of selected class weights.
    logits = paddle.to_tensor(np.array(
        [[2.0, 1.0, 0.1], [0.5, 2.5, 0.3]], np.float32))
    label = paddle.to_tensor(np.array([0, 1], np.int64))
    weight = paddle.to_tensor(np.array([0.5, 2.0, 1.0], np.float32))
    out = F.cross_entropy(logits, label, weight=weight, reduction="mean")
    logp = np.log(np.exp(np.asarray(logits.data))
                  / np.exp(np.asarray(logits.data)).sum(-1, keepdims=True))
    per = -logp[np.arange(2), [0, 1]] * np.array([0.5, 2.0])
    expect = per.sum() / (0.5 + 2.0)
    np.testing.assert_allclose(float(out), expect, rtol=1e-5)


def test_sublayer_non_persistable_buffer_excluded():
    # ADVICE r1: sublayer non-persistable buffers must not hit state_dict.
    class Sub(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.register_buffer("scratch", paddle.to_tensor(
                np.zeros(2, np.float32)), persistable=False)
            self.register_buffer("kept", paddle.to_tensor(
                np.ones(2, np.float32)), persistable=True)

    class Top(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.sub = Sub()
            self.register_buffer("kept", paddle.to_tensor(
                np.full(2, 2.0, np.float32)), persistable=True)

    sd = Top().state_dict()
    assert "sub.scratch" not in sd
    assert "sub.kept" in sd and "kept" in sd


def test_linear_cross_entropy_matches_unfused():
    import numpy as np
    paddle.seed(33)
    T, H, V = 32, 16, 50
    h = paddle.randn([T, H]); h.stop_gradient = False
    w = paddle.randn([H, V]); w.stop_gradient = False
    b = paddle.zeros([V]); b.stop_gradient = False
    lab = paddle.to_tensor(np.random.RandomState(0).randint(0, V, (T,)))

    fused = F.linear_cross_entropy(h, w, b, lab, chunk=8)
    ref = F.cross_entropy(h.matmul(w) + b, lab)
    np.testing.assert_allclose(float(fused), float(ref), rtol=1e-5)

    fused.backward()
    gh, gw = h.grad.numpy().copy(), w.grad.numpy().copy()
    h2 = h.detach(); h2.stop_gradient = False
    w2 = w.detach(); w2.stop_gradient = False
    b2 = b.detach(); b2.stop_gradient = False
    F.cross_entropy(h2.matmul(w2) + b2, lab).backward()
    np.testing.assert_allclose(gh, h2.grad.numpy(), rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(gw, w2.grad.numpy(), rtol=1e-4, atol=1e-6)


def _linear_ce_fn_as_it_was(h, w, b, lab, *tw, chunk, ignore_index):
    """``_linear_ce_fn`` of the tree before the head made its gradients
    in its forward rule (PR 37's), verbatim: a checkpointed chunk body in
    a scan, differentiated by jax."""
    import jax
    import jax.numpy as jnp
    T = h.shape[0]
    n = max(1, -(-T // chunk))          # ceil: pad the tail chunk
    per = -(-T // n)
    if n * per != T:
        pad = n * per - T
        h = jnp.concatenate(
            [h, jnp.zeros((pad, h.shape[-1]), h.dtype)], axis=0)
        lab = jnp.concatenate(
            [lab, jnp.full((pad,), ignore_index, lab.dtype)], axis=0)
        tw = tuple(jnp.concatenate([t, jnp.zeros((pad,), t.dtype)])
                   for t in tw)
    hs = h.reshape(n, per, h.shape[-1])
    ls = lab.reshape(n, per)

    @jax.checkpoint
    def chunk_nll(hc, lc, *wc):
        logits = (jnp.matmul(hc, w) + b).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        safe = jnp.where(lc == ignore_index, 0, lc)
        tgt = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
        nll = lse - tgt
        keep = (lc != ignore_index)
        if wc:
            # where, not a product: an ignored token's weight may be
            # anything, and its loss gives the weight no gradient
            return jnp.sum(jnp.where(keep, nll * wc[0], 0.0)), jnp.sum(keep)
        return jnp.sum(nll * keep), jnp.sum(keep)

    def body(carry, xs):
        s, c = carry
        ds, dc = chunk_nll(*xs)
        return (s + ds, c + dc), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.int32(0)),
        (hs, ls) + tuple(t.reshape(n, per) for t in tw))
    if tw:
        return total
    return total / jnp.maximum(count, 1).astype(jnp.float32)


def _head_now_and_as_it_was(dtype, T, H, V, ignored, weighted, cotangent):
    """One seeded case of the chunked head (chunks of 16) pulled at
    ``cotangent`` through ``F.linear_cross_entropy`` and through
    ``_linear_ce_fn_as_it_was``, each under one jit -> ((loss, gradients
    of hidden, weight, bias and the token weights) now, the same as it
    was, the loss of the value-only call).  The labels are an argument,
    as a step's are: over constant labels XLA folds the count of kept
    tokens and divides by its reciprocal."""
    import functools
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.tensor import Tensor
    ks = jax.random.split(jax.random.key(7), 5)
    lab = jax.random.randint(ks[3], (T,), 0, V)
    lab = (jnp.full((T,), -100) if ignored == "all"
           else lab.at[jnp.arange(0, T, 5)].set(-100))
    h = jax.random.normal(ks[0], (T, H)).astype(dtype)
    w = (0.3 * jax.random.normal(ks[1], (H, V))).astype(dtype)
    b = (0.1 * jax.random.normal(ks[2], (V,))).astype(dtype)
    tw = (jax.random.uniform(ks[4], (T,)),) if weighted else ()

    def now(lab, h, w, b, *tw):
        with paddle.no_grad():
            return F.linear_cross_entropy(
                Tensor(h), Tensor(w), Tensor(b), Tensor(lab), chunk=16,
                token_weight=Tensor(tw[0]) if tw else None).data

    def was(lab, h, w, b, *tw):
        return _linear_ce_fn_as_it_was(h, w, b, lab, *tw, chunk=16,
                                       ignore_index=-100)

    def pulled(fn):
        def both(lab, *args):
            loss, pull = jax.vjp(functools.partial(fn, lab), *args)
            return loss, pull(jnp.float32(cotangent))
        return jax.jit(both)(lab, h, w, b, *tw)

    return pulled(now), pulled(was), jax.jit(now)(lab, h, w, b, *tw)


@pytest.mark.parametrize("cotangent", [1.0, 0.3])
@pytest.mark.parametrize("weighted", [False, True],
                         ids=["mean", "token_weight"])
@pytest.mark.parametrize("ignored", ["some", "all"])
@pytest.mark.parametrize("T", [64, 61], ids=["whole_chunks", "ragged_tail"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_linear_cross_entropy_makes_the_gradients_it_made(
        dtype, T, ignored, weighted, cotangent):
    """The head's forward rule makes the loss and, in the same scan,
    ``dh``, ``dw``, ``db`` and the token weights' gradient; against the
    scan jax differentiated: the loss bit for bit, every gradient bit for
    bit at a cotangent of 1 and to one rounding of its own type where the
    cotangent multiplies after the products instead of before them."""
    import jax.numpy as jnp
    (loss, got), (loss_was, want), value = _head_now_and_as_it_was(
        dtype, T, 16, 50, ignored, weighted, cotangent)
    np.testing.assert_array_equal(loss, value)
    np.testing.assert_array_equal(loss, loss_was)
    names = ("hidden", "weight", "bias", "token_weight")
    for g, r, name in zip(got, want, names):
        assert (g.shape, g.dtype) == (r.shape, r.dtype), name
        eps = float(jnp.finfo(r.dtype).eps)
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        if cotangent == 1.0:
            np.testing.assert_array_equal(g, r, err_msg=name)
        else:
            np.testing.assert_allclose(g, r, rtol=2 * eps,
                                       atol=2 * eps * np.abs(r).max(),
                                       err_msg=name)
    if ignored == "all":
        assert float(loss) == 0.0
        assert all(not np.asarray(g, np.float32).any() for g in got)


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["mean", "token_weight"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_head_narrower_than_its_hidden_width_keeps_the_replay(dtype,
                                                                weighted):
    """What the forward rule would keep for the backward pass, ``dh``
    [T, H], is larger than the logits [T, vocab] of a head narrower than
    its hidden width: that head stays the checkpointed scan that jax
    differentiates, the program it was to the bit at any cotangent, and
    counts no gradients in its forward pass."""
    import jax
    from paddle_tpu.utils import monitor
    monitor.stat_reset()
    got, want, _ = _head_now_and_as_it_was(dtype, 61, 64, 50, "some",
                                           weighted, 0.3)
    assert monitor.get_stat("linear_cross_entropy.calls") == 2
    assert "linear_cross_entropy.grads_in_forward" not in monitor.all_stats()
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(r, np.float32))


def _eqns(jaxpr):
    """Every equation a jaxpr holds, its sub-jaxprs' (a scan's body, a
    custom rule's primal) once each."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _scan_lengths(jaxpr):
    return [e.params["length"] for e in _eqns(jaxpr)
            if e.primitive.name == "scan"]


def _count_eqns(jaxpr, primitive):
    return sum(e.primitive.name == primitive for e in _eqns(jaxpr))


def _head_counters():
    from paddle_tpu.utils import monitor
    return {k[len("linear_cross_entropy."):]: v
            for k, v in monitor.all_stats().items()
            if k.startswith(("linear_cross_entropy.rows.",
                             "linear_cross_entropy.dh_rows."))}


@pytest.mark.parametrize("dtype,H,V,chunk,rows,dh_rows", [
    ("bfloat16", 16, 64, None, 1024, 1024),
    ("float32", 16, 64, None, 1024, 1024),
    ("float32", 8, 32768, None, 1024, 512),    # a long vocabulary
    ("bfloat16", 8, 32768, None, 1024, 1024),
    ("float32", 64, 16, None, 1024, None),     # narrower than its hidden
    ("float32", 16, 64, 512, 512, 512),        # a caller's own is honoured
    ("float32", 8, 32768, 2048, 2048, 512),
    ("float32", 8, 32768, 768, 768, 683),      # 6 chunks of 683: no blocks
], ids=["bf16", "f32", "f32_long", "bf16_long", "f32_narrow", "f32_given",
        "f32_long_given", "f32_long_ragged"])
def test_the_head_chooses_its_rows_where_none_are_named(dtype, H, V, chunk,
                                                        rows, dh_rows):
    """``chunk=None``: 1024 rows a chunk, whatever the weight's itemsize
    (the measured best: PR 50 tried 2048 for float32 and lost in every
    cell); an int is taken as given.  Read where it acts, the trip count
    of the traced scan over 4096 rows, and in the trace-time counter
    ``linear_cross_entropy.rows.<rows>``.  ``dh`` walks 512 rows a product
    for a float32 weight of at least 32,768 columns where 512 divides the
    chunk's rows, else the chunk whole (``.dh_rows.<rows>``; the narrow
    head's checkpointed body has no such product)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.utils import monitor
    T = 4096
    monitor.stat_reset()

    def loss(h, w, b, lab):
        with paddle.no_grad():
            return F.linear_cross_entropy(
                Tensor(h), Tensor(w), Tensor(b), Tensor(lab), **(
                    {} if chunk is None else {"chunk": chunk})).data

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(
        jnp.zeros((T, H), dtype), jnp.zeros((H, V), dtype),
        jnp.zeros((V,), dtype), jnp.zeros((T,), jnp.int32)).jaxpr
    n = -(-T // rows)
    assert n in _scan_lengths(jaxpr)
    want = {f"rows.{rows}": 1}
    if dh_rows is not None:
        want[f"dh_rows.{dh_rows}"] = 1
        # the logits, dw, and dh's: nothing is made twice
        assert _count_eqns(jaxpr, "dot_general") == 2 + -(-T // n) // dh_rows
    assert _head_counters() == want


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["mean", "token_weight"])
@pytest.mark.parametrize("T", [4096, 4099], ids=["whole_chunks",
                                                 "ragged_tail"])
def test_chunks_of_2048_rows_give_what_1024_give(T, weighted):
    """The rows change how many partial sums are added into the loss, ``dw``
    and ``db`` (2 or 3 chunks for 4 or 5 here) and nothing else: loss,
    ``dh``, ``dw``, ``db`` and the token weight's gradient of a float32
    head at ``chunk=2048`` against the same head at the rows it chooses,
    to float32 tolerance, the padded tail of 4099 rows included."""
    import functools
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.tensor import Tensor
    H, V = 16, 64
    ks = jax.random.split(jax.random.key(11), 5)
    lab = jax.random.randint(ks[3], (T,), 0, V)
    lab = lab.at[jnp.arange(0, T, 7)].set(-100)
    h = jax.random.normal(ks[0], (T, H))
    w = 0.3 * jax.random.normal(ks[1], (H, V))
    b = 0.1 * jax.random.normal(ks[2], (V,))
    tw = (jax.random.uniform(ks[4], (T,)) / T,) if weighted else ()

    def head(chunk, lab, h, w, b, *tw):
        with paddle.no_grad():
            return F.linear_cross_entropy(
                Tensor(h), Tensor(w), Tensor(b), Tensor(lab), chunk=chunk,
                token_weight=Tensor(tw[0]) if tw else None).data

    def pulled(chunk):
        fn = jax.value_and_grad(functools.partial(head, chunk, lab),
                                tuple(range(3 + len(tw))))
        return jax.jit(fn)(h, w, b, *tw)

    (loss, got), (loss_was, want) = pulled(2048), pulled(None)
    np.testing.assert_allclose(loss, loss_was, rtol=1e-6)
    for g, r, name in zip(got, want,
                          ("hidden", "weight", "bias", "token_weight")):
        assert (g.shape, g.dtype) == (r.shape, r.dtype), name
        assert np.abs(np.asarray(r)).max() > 0, name
        np.testing.assert_allclose(g, r, rtol=1e-5,
                                   atol=1e-6 * np.abs(np.asarray(r)).max(),
                                   err_msg=name)


@pytest.mark.parametrize("dtype,products", [("float32", 4), ("bfloat16", 3)])
@pytest.mark.parametrize("weighted", [False, True],
                         ids=["mean", "token_weight"])
def test_a_long_float32_heads_dh_walks_512_rows_a_product(dtype, products,
                                                          weighted):
    """A chunk of 1024 rows of a float32 head over 32,768 columns makes
    ``dh`` in two products of 512 rows (logits, ``dw`` and the two: four
    ``dot_general`` in the scan, none of them a forward pass made again)
    and a bfloat16 head in one.  A row's ``dh`` is its own sum over the
    vocabulary, so it is the row's of a head in chunks of 512 bit for bit;
    the loss, ``dw``, ``db`` and the token weight's gradient to the adding
    of 1 partial sum for 2."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.tensor import Tensor
    T, H, V = 1024, 8, 32768
    ks = jax.random.split(jax.random.key(13), 5)
    lab = jax.random.randint(ks[3], (T,), 0, V).at[::9].set(-100)
    h = jax.random.normal(ks[0], (T, H)).astype(dtype)
    w = (0.3 * jax.random.normal(ks[1], (H, V))).astype(dtype)
    b = (0.1 * jax.random.normal(ks[2], (V,))).astype(dtype)
    tw = (jax.random.uniform(ks[4], (T,)) / T,) if weighted else ()

    def pulled(chunk):
        def head(h, w, b, *tw):
            with paddle.no_grad():
                return F.linear_cross_entropy(
                    Tensor(h), Tensor(w), Tensor(b), Tensor(lab),
                    chunk=chunk,
                    token_weight=Tensor(tw[0]) if tw else None).data
        fn = jax.value_and_grad(head, tuple(range(3 + len(tw))))
        return (jax.jit(fn)(h, w, b, *tw),
                _count_eqns(jax.make_jaxpr(fn)(h, w, b, *tw).jaxpr,
                            "dot_general"))

    (loss, got), count = pulled(1024)
    (loss_512, want), count_512 = pulled(512)
    assert (count, count_512) == (products, 3)
    np.testing.assert_array_equal(np.asarray(got[0], np.float32),
                                  np.asarray(want[0], np.float32))
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(loss, loss_512, rtol=tol)
    for g, r in zip(got[1:], want[1:]):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        np.testing.assert_allclose(g, r, rtol=tol, atol=tol * np.abs(r).max())


def test_linear_cross_entropy_ignore_index():
    import numpy as np
    paddle.seed(34)
    h = paddle.randn([8, 4])
    w = paddle.randn([4, 10])
    b = paddle.zeros([10])
    lab = np.random.RandomState(1).randint(0, 10, (8,))
    lab[::2] = -100
    fused = F.linear_cross_entropy(h, w, b, paddle.to_tensor(lab), chunk=4)
    ref = F.cross_entropy(h.matmul(w) + b, paddle.to_tensor(lab),
                          ignore_index=-100)
    np.testing.assert_allclose(float(fused), float(ref), rtol=1e-5)
