"""utils (custom ops, monitor, auto-checkpoint) + optimizer extras tests
(reference analogs: test_custom_op.py, test_monitor.py,
test_auto_checkpoint.py, test_ema.py, test_lookahead.py)."""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import nn, optimizer
from paddle_tpu.utils import monitor, register_custom_op, train_epoch_range


# -- custom ops --------------------------------------------------------------

def test_custom_op_forward_and_autodiff():
    import jax.numpy as jnp
    relu3 = register_custom_op("relu_cubed", lambda a: jnp.maximum(a, 0) ** 3)
    x = paddle.to_tensor(np.array([-1.0, 2.0], np.float32),
                         stop_gradient=False)
    y = relu3(x)
    np.testing.assert_allclose(y.numpy(), [0.0, 8.0])
    y.sum().backward()
    np.testing.assert_allclose(x.grad.numpy(), [0.0, 12.0])  # 3x^2


def test_custom_op_custom_vjp():
    import jax.numpy as jnp
    # straight-through sign: forward sign(x), backward passes grad through
    st_sign = register_custom_op(
        "st_sign", lambda a: jnp.sign(a),
        backward=lambda res, ct: (ct,))
    x = paddle.to_tensor(np.array([-2.0, 3.0], np.float32),
                         stop_gradient=False)
    y = st_sign(x)
    np.testing.assert_allclose(y.numpy(), [-1.0, 1.0])
    y.sum().backward()
    np.testing.assert_allclose(x.grad.numpy(), [1.0, 1.0])


# -- monitor -----------------------------------------------------------------

def test_monitor_gauges():
    monitor.stat_reset()
    monitor.stat_add("STAT_total_feasign_num_in_mem", 5)
    monitor.stat_add("STAT_total_feasign_num_in_mem", 2)
    monitor.stat_set("STAT_epoch", 3)
    assert monitor.get_stat("STAT_total_feasign_num_in_mem") == 7
    assert monitor.all_stats()["STAT_epoch"] == 3
    monitor.stat_reset("STAT_epoch")
    assert monitor.get_stat("STAT_epoch") == 0


# -- auto checkpoint ---------------------------------------------------------

def test_train_epoch_range_resume(tmp_path):
    paddle.seed(0)
    d = str(tmp_path / "acp")

    def make():
        paddle.seed(0)
        m = nn.Linear(4, 2)
        o = optimizer.SGD(learning_rate=0.1, parameters=m.parameters())
        return m, o

    x = paddle.randn([8, 4])
    y = paddle.randn([8, 2])

    m1, o1 = make()
    ran = []
    for epoch in train_epoch_range(5, d, model=m1, opt=o1):
        ran.append(epoch)
        F.mse_loss(m1(x), y).backward()
        o1.step()
        o1.clear_grad()
        if epoch == 2:
            break  # simulated preemption AFTER epoch-2 body but pre-save
    assert ran == [0, 1, 2]

    # restart: epochs 0-1 were snapshotted; epoch 2 (interrupted before
    # its save) re-runs
    m2, o2 = make()
    ran2 = [e for e in train_epoch_range(5, d, model=m2, opt=o2)
            if True]
    assert ran2 == [2, 3, 4]


# -- optimizer extras --------------------------------------------------------

def test_ema_apply_restore():
    paddle.seed(1)
    m = nn.Linear(4, 2)
    ema = optimizer.ExponentialMovingAverage(
        0.9, parameters=list(m.parameters()))
    w0 = m.weight.numpy().copy()
    m.weight.data = m.weight.data + 1.0
    ema.update()
    live = m.weight.numpy().copy()
    with ema.apply():
        applied = m.weight.numpy().copy()
    np.testing.assert_allclose(m.weight.numpy(), live)  # restored
    # shadow is between w0 and live
    assert np.all(applied > w0 - 1e-6) and np.all(applied < live + 1e-6)
    assert not np.allclose(applied, live)


def test_model_average():
    paddle.seed(2)
    m = nn.Linear(2, 2)
    ma = optimizer.ModelAverage(parameters=list(m.parameters()))
    vals = []
    for i in range(4):
        m.weight.data = m.weight.data * 0 + float(i)
        ma.step()
        vals.append(float(i))
    with ma.apply():
        np.testing.assert_allclose(m.weight.numpy(),
                                   np.full((2, 2), np.mean(vals)),
                                   rtol=1e-6)
    np.testing.assert_allclose(m.weight.numpy(), np.full((2, 2), 3.0))


def test_lookahead_converges_and_syncs():
    paddle.seed(3)
    m = nn.Linear(8, 1)
    inner = optimizer.SGD(learning_rate=0.05, parameters=m.parameters())
    opt = optimizer.Lookahead(inner, alpha=0.5, k=5)
    x = paddle.randn([64, 8])
    w = paddle.randn([8, 1])
    y = x.matmul(w)
    losses = []
    for _ in range(60):
        loss = F.mse_loss(m(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.1, (losses[0], losses[-1])


def test_need_weights_returns_real_weights():
    paddle.seed(4)
    mha = nn.MultiHeadAttention(16, 4, need_weights=True)
    x = paddle.randn([2, 5, 16])
    out, w = mha(x, x, x)
    assert w is not None
    assert w.shape == [2, 4, 5, 5]
    np.testing.assert_allclose(w.numpy().sum(-1),
                               np.ones((2, 4, 5)), rtol=1e-5)
    # parity with the fused (no-weights) path
    mha.need_weights = False
    out2 = mha(x, x, x)
    np.testing.assert_allclose(out.numpy(), out2.numpy(), rtol=2e-3,
                               atol=1e-5)


def test_launch_watches_and_terminates(tmp_path):
    """launch() parity with launch_utils child-watching: a failing worker
    takes the pod down with a non-zero exit code."""
    from paddle_tpu.distributed.launch import launch
    ok = tmp_path / "ok.py"
    ok.write_text("import os\n"
                  "assert os.environ['PADDLE_TRAINERS_NUM'] == '2'\n"
                  "assert os.environ['PADDLE_TRAINER_ID'] in '01'\n"
                  "assert 'COORDINATOR_ADDRESS' in os.environ\n")
    assert launch(str(ok), nproc_per_node=2) == 0
    bad = tmp_path / "bad.py"
    bad.write_text("import sys, os, time\n"
                   "if os.environ['PADDLE_TRAINER_ID'] == '1':\n"
                   "    sys.exit(3)\n"
                   "time.sleep(60)\n")
    assert launch(str(bad), nproc_per_node=2) == 3


def test_need_weights_respects_bool_mask():
    """Bool attn_mask (True=keep) must mask weights to zero on the
    need_weights path exactly like the fused path."""
    paddle.seed(5)
    mha = nn.MultiHeadAttention(8, 2, need_weights=True)
    x = paddle.randn([1, 4, 8])
    mask = np.ones((1, 1, 4, 4), bool)
    mask[..., -1] = False  # nobody may attend to the last position
    out, w = mha(x, x, x, attn_mask=paddle.to_tensor(mask))
    assert np.allclose(w.numpy()[..., -1], 0.0)
    mha.need_weights = False
    out2 = mha(x, x, x, attn_mask=paddle.to_tensor(mask))
    np.testing.assert_allclose(out.numpy(), out2.numpy(), rtol=2e-3,
                               atol=1e-5)


def test_spawn_runs_module_level_fn(tmp_path):
    from paddle_tpu.distributed.launch import spawn
    marker = str(tmp_path)
    spawn(_spawn_probe, args=(marker,), nprocs=2)
    got = sorted(os.listdir(marker))
    assert got == ["rank0", "rank1"], got


def _spawn_probe(marker):
    rank = os.environ["PADDLE_TRAINER_ID"]
    assert os.environ["PADDLE_TRAINERS_NUM"] == "2"
    open(os.path.join(marker, f"rank{rank}"), "w").close()


# -- no fallback that hides the device ------------------------------------
def test_set_device_never_substitutes_the_cpu():
    import jax
    try:
        with pytest.raises(RuntimeError):
            paddle.set_device("tpu")        # no TPU in the test process
        with pytest.raises(RuntimeError):
            paddle.set_device("gpu:0")      # the alias means TPU too
        with pytest.raises(ValueError, match="unknown device"):
            paddle.set_device("npu")
        assert paddle.set_device("cpu:1").id == 1
        assert paddle.get_device() == "cpu:1"
    finally:
        jax.config.update("jax_default_device", None)
        paddle.device._current_device = None


def test_place_compile_cache(monkeypatch):
    """The environment variable wins and no directory is set in code;
    without it the cache goes to the fixed <checkout>/.jax_cache.  Either
    way the key takes the program's metadata in (its scope names)."""
    import jax
    from paddle_tpu.core.xla_env import place_compile_cache
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    in_key = "jax_compilation_cache_include_metadata_in_key"
    assert not getattr(jax.config, in_key)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert place_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before
        assert getattr(jax.config, in_key)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert place_compile_cache() == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update(in_key, False)


def test_import_creates_no_backend():
    """One process owns a chip: a parent that only imports the package
    (launcher, supervisor) must not have created a jax backend, and the
    probe ``core/xla_env.py`` uses for that must read right."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import paddle_tpu, jax\n"
        "import jax._src.xla_bridge as xb\n"
        "from paddle_tpu.core.xla_env import _backend_initialized\n"
        "assert not xb._backends and not _backend_initialized()\n"
        "jax.devices()\n"
        "assert _backend_initialized()\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=repo,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
