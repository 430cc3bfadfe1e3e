"""Round-4 API-parity additions, audited against the reference's public
alias lists (python/paddle/__init__.py, nn/__init__.py,
nn/functional/__init__.py)."""
import os
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
import paddle_tpu.nn.functional as F


# the reference's source tree, where the machine has it
REFERENCE = "/root/reference/python/paddle"
needs_reference = pytest.mark.skipif(
    not os.path.isdir(REFERENCE), reason="reference tree not mounted")


@needs_reference
def test_top_level_alias_audit():
    """Every alias the reference re-exports at top level must exist
    (whitelist: monkey-patch internals)."""
    src = open(f"{REFERENCE}/__init__.py").read()
    names = set(re.findall(r"^from \.\S+ import (\w+)", src, re.M))
    names -= {"monkey_patch_variable", "monkey_patch_math_varbase",
              "VarBase"}
    missing = sorted(n for n in names if not hasattr(paddle, n))
    assert not missing, missing


@needs_reference
def test_nn_alias_audit():
    src = open(f"{REFERENCE}/nn/__init__.py").read()
    names = set(re.findall(r"^from \.[\w.]* import (\w+)", src, re.M))
    names = {n for n in names if not n.startswith("_")}
    missing = sorted(n for n in names if not hasattr(nn, n))
    assert not missing, missing


@needs_reference
def test_functional_alias_audit():
    src = open(f"{REFERENCE}/nn/functional/__init__.py").read()
    names = set(re.findall(r"^from \.[\w.]* import (\w+)", src, re.M))
    names = {n for n in names if not n.startswith("_")}
    missing = sorted(n for n in names if not hasattr(F, n))
    assert not missing, missing


def test_places_and_modes():
    p = paddle.CUDAPlace(0)
    assert p == paddle.CUDAPlace(0) and p != paddle.CPUPlace(0)
    paddle.disable_dygraph()
    assert not paddle.in_dygraph_mode()
    paddle.enable_dygraph()
    assert paddle.in_dygraph_mode()
    st = paddle.get_cuda_rng_state()
    paddle.set_cuda_rng_state(st)
    assert paddle.get_cudnn_version() is None


def test_slice_family_oracle():
    x = paddle.to_tensor(np.arange(24).reshape(4, 6).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(paddle.slice(x, [0, 1], [1, 2], [3, 5]).data),
        np.arange(24).reshape(4, 6)[1:3, 2:5])
    np.testing.assert_allclose(
        np.asarray(paddle.strided_slice(x, [1], [0], [6], [2]).data),
        np.arange(24).reshape(4, 6)[:, ::2])
    np.testing.assert_allclose(
        np.asarray(paddle.crop_tensor(x, shape=[2, 3],
                                      offsets=[1, 2]).data),
        np.arange(24).reshape(4, 6)[1:3, 2:5])


def test_shard_index_semantics():
    ids = paddle.to_tensor(np.array([0, 4, 5, 9, 15], np.int64))
    out = np.asarray(paddle.shard_index(ids, 16, 4, 1).data)
    # shard 1 owns [4, 8): local ids 0..3
    np.testing.assert_array_equal(out, [-1, 0, 1, -1, -1])


def test_add_n_mv_inplace_ops():
    a = paddle.to_tensor(np.ones((2, 2), np.float32))
    b = paddle.to_tensor(np.full((2, 2), 2.0, np.float32))
    np.testing.assert_allclose(np.asarray(paddle.add_n([a, b]).data), 3.0)
    m = paddle.to_tensor(np.eye(3, dtype=np.float32) * 2)
    v = paddle.to_tensor(np.arange(3, dtype=np.float32))
    np.testing.assert_allclose(np.asarray(paddle.mv(m, v).data),
                               [0, 2, 4])
    t = paddle.to_tensor(np.zeros(3, np.float32))
    paddle.tanh_(t)
    np.testing.assert_allclose(np.asarray(t.data), 0.0)
    u = paddle.to_tensor(np.ones((3,), np.float32))
    paddle.unsqueeze_(u, 0)
    assert u.shape_tuple == (1, 3)
    paddle.squeeze_(u, 0)
    assert u.shape_tuple == (3,)
    assert paddle.broadcast_shape([2, 1, 3], [4, 3]) == [2, 4, 3]
    assert int(paddle.rank(m)) == 2
    np.testing.assert_array_equal(np.asarray(paddle.shape(m).data), [3, 3])


def test_flops_matches_reference_convention():
    net = nn.Sequential(nn.Conv2D(1, 4, 3, padding=1), nn.ReLU(),
                        nn.Flatten(), nn.Linear(4 * 8 * 8, 10))
    got = paddle.flops(net, [1, 1, 8, 8])
    conv = 4 * 8 * 8 * (1 * 9 + 1)     # out_elems * (kernel + bias)
    fc = 1 * (4 * 8 * 8 * 10)
    assert got == conv + fc, (got, conv + fc)


def test_grid_sample_warp_oracle():
    """Shift-by-one warp against a numpy oracle."""
    img = paddle.to_tensor(
        np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4))
    th = paddle.to_tensor(
        np.array([[[1, 0, 2.0 / 3.0], [0, 1, 0]]], np.float32))
    g = F.affine_grid(th, [1, 1, 4, 4])
    out = np.asarray(F.grid_sample(img, g).data)
    base = np.arange(16, dtype=np.float32).reshape(4, 4)
    # x' = x + 1 pixel (2/3 normalized with align_corners over width 4)
    np.testing.assert_allclose(out[0, 0, :, :3], base[:, 1:], atol=1e-5)
    np.testing.assert_allclose(out[0, 0, :, 3], 0.0, atol=1e-5)  # zeros pad


def test_conv_transpose_1d_3d_grad():
    paddle.seed(111)
    c1 = nn.Conv1DTranspose(3, 5, 3, stride=2)
    x = paddle.to_tensor(np.random.randn(2, 3, 8).astype(np.float32))
    out = c1(x)
    assert out.shape_tuple[:2] == (2, 5)
    out.sum().backward()
    assert float(abs(c1.weight.grad.data).sum()) > 0

    c3 = nn.Conv3DTranspose(2, 3, 3, stride=2)
    x3 = paddle.to_tensor(np.random.randn(1, 2, 4, 4, 4).astype(np.float32))
    o3 = c3(x3)
    assert o3.shape_tuple == (1, 3, 9, 9, 9)


def test_hsigmoid_loss_trains():
    paddle.seed(112)
    layer = nn.HSigmoidLoss(8, num_classes=6)
    x = paddle.to_tensor(np.random.randn(16, 8).astype(np.float32))
    y = paddle.to_tensor(np.random.randint(0, 6, (16,)).astype(np.int64))
    from paddle_tpu import optimizer
    opt = optimizer.Adam(learning_rate=0.05,
                         parameters=layer.parameters())
    first = last = None
    for _ in range(12):
        loss = layer(x, y).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        first = first if first is not None else float(loss)
        last = float(loss)
    assert last < first * 0.8, (first, last)


def test_misc_new_losses_and_activations():
    p = paddle.to_tensor(np.array([0.9, 0.1], np.float32))
    y = paddle.to_tensor(np.array([1.0, 0.0], np.float32))
    ll = np.asarray(F.log_loss(p, y).data)
    np.testing.assert_allclose(ll, -np.log([0.9 + 1e-4, 0.9 + 1e-4]),
                               rtol=1e-3)
    np.testing.assert_allclose(
        np.asarray(F.square_error_cost(p, y).data),
        [0.01, 0.01], rtol=1e-4)
    x = paddle.to_tensor(np.array([-1.0, 0.5, 2.0], np.float32))
    np.testing.assert_allclose(np.asarray(F.thresholded_relu(x).data),
                               [0, 0, 2.0])
    ls = np.asarray(F.log_sigmoid(x).data)
    np.testing.assert_allclose(ls, np.log(1 / (1 + np.exp(-np.asarray(
        [-1.0, 0.5, 2.0])))), rtol=1e-5)
    # inplace variants mutate
    t = paddle.to_tensor(np.array([-1.0, 1.0], np.float32))
    F.relu_(t)
    np.testing.assert_allclose(np.asarray(t.data), [0, 1.0])


def test_upsampling_pairwise_logsigmoid_layers():
    up = nn.UpsamplingNearest2D(scale_factor=2)
    x = paddle.to_tensor(np.random.randn(1, 2, 3, 3).astype(np.float32))
    assert up(x).shape_tuple == (1, 2, 6, 6)
    ub = nn.UpsamplingBilinear2D(size=[5, 5])
    assert ub(x).shape_tuple == (1, 2, 5, 5)
    pd = nn.PairwiseDistance()
    a = paddle.to_tensor(np.array([[0.0, 0.0]], np.float32))
    b = paddle.to_tensor(np.array([[3.0, 4.0]], np.float32))
    np.testing.assert_allclose(float(pd(a, b)), 5.0, rtol=1e-4)
    assert nn.LogSigmoid()(a).shape_tuple == (1, 2)
    d3 = nn.Dropout3D(p=0.5)
    d3.eval()
    x5 = paddle.to_tensor(np.ones((1, 2, 2, 2, 2), np.float32))
    np.testing.assert_allclose(np.asarray(d3(x5).data), 1.0)


# -- r4 review regressions ------------------------------------------------

def test_inplace_ops_keep_gradient_chain():
    """r4 review: x.data assignment broke the tape; _rebind keeps it."""
    t = paddle.to_tensor(np.array([0.5, 1.0], np.float32),
                         stop_gradient=False)
    h = F.tanh_(t)
    (h * h).sum().backward()
    th = np.tanh([0.5, 1.0])
    expect = 2 * th * (1 - th ** 2)
    np.testing.assert_allclose(np.asarray(t.grad.data), expect, rtol=1e-5)

    x = paddle.to_tensor(np.zeros(4, np.float32), stop_gradient=False)
    upd = paddle.to_tensor(np.array([1.0, 2.0], np.float32),
                           stop_gradient=False)
    idx = paddle.to_tensor(np.array([1, 3]))
    paddle.scatter_(x, idx, upd)
    x.sum().backward()
    assert upd.grad is not None
    np.testing.assert_allclose(np.asarray(upd.grad.data), [1.0, 1.0])


def test_grid_sample_boundary_partial_contribution():
    """r4 review: zeros padding must mask per tap, not per sample."""
    img = paddle.to_tensor(
        np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4))
    # sample at fx=3.5, fy=0 (half past the last column)
    gx = (3.5 * 2 / 3 - 1)   # inverse of align_corners mapping (W=4)
    grid = paddle.to_tensor(
        np.array([[[[gx, -1.0]]]], np.float32))
    out = float(np.asarray(F.grid_sample(img, grid).data))
    np.testing.assert_allclose(out, 0.5 * 3.0, rtol=1e-5)


def test_conv1d_transpose_nlc_and_output_size():
    paddle.seed(113)
    x = paddle.to_tensor(np.random.randn(2, 8, 3).astype(np.float32))
    w = paddle.to_tensor(np.random.randn(3, 5, 3).astype(np.float32))
    out = F.conv1d_transpose(x, w, stride=2, data_format="NLC")
    assert out.shape_tuple == (2, 17, 5)
    # output_size picks the longer valid length
    xc = paddle.to_tensor(np.random.randn(2, 3, 8).astype(np.float32))
    o18 = F.conv1d_transpose(xc, w, stride=2, output_size=[18])
    assert o18.shape_tuple == (2, 5, 18)
    with pytest.raises(ValueError, match="not reachable"):
        F.conv1d_transpose(xc, w, stride=2, output_size=[25])


def test_adaptive_pool3d_ndhwc_and_mask_raises():
    x = paddle.to_tensor(np.random.randn(1, 4, 4, 4, 2).astype(np.float32))
    out = F.adaptive_avg_pool3d(x, 2, data_format="NDHWC")
    assert out.shape_tuple == (1, 2, 2, 2, 2)
    xc = paddle.to_tensor(np.random.randn(1, 2, 4, 4, 4).astype(np.float32))
    with pytest.raises(NotImplementedError, match="return_mask"):
        F.adaptive_max_pool3d(xc, 2, return_mask=True)


def test_hsigmoid_custom_table_requires_code():
    x = paddle.to_tensor(np.random.randn(2, 4).astype(np.float32))
    y = paddle.to_tensor(np.array([0, 1], np.int64))
    w = paddle.to_tensor(np.random.randn(3, 4).astype(np.float32))
    tbl = paddle.to_tensor(np.zeros((2, 2), np.int64))
    with pytest.raises(ValueError, match="path_code"):
        F.hsigmoid_loss(x, y, 4, w, path_table=tbl)


def test_flops_accumulates_shared_layers():
    class Siamese(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(4, 4)

        def forward(self, x):
            return self.fc(self.fc(x))   # same layer twice

    got = paddle.flops(Siamese(), [1, 4])
    assert got == 2 * (4 * 4), got
