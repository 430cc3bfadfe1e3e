"""Kernel or XLA form: the one decision behind every kernel-backed
functional (``ops.pallas.support.choose_kernel``) and its record.  A
traced call moves exactly one of ``pallas.selected.<kernel>`` (counted
by the kernel's own entry) and ``<functional>.xla_path`` (counted by the
chooser); with the tier off no selection moves at all."""
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.core.flags import get_flag, set_flags
from paddle_tpu.ops.pallas.support import choose_kernel, kernel_selections
from paddle_tpu.utils import monitor


def _sds(*shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _sdpa(dtype):
    x = _sds(1, 512, 1, 8, dtype=dtype)
    return (lambda q, k, v: F.scaled_dot_product_attention(
        q, k, v, is_causal=True)), (x, x, x)


def _eva(dtype):
    x, vec = _sds(1, 64, 2, 8, dtype=dtype), _sds(2, 8, dtype=dtype)
    return (lambda q, k, v, mu, phi: F.eva_attention(
        q, k, v, mu, phi, 16, 4)), (x, x, x, vec, vec)


def _keye(dtype):
    f32 = jnp.float32
    return dict(
        q=_sds(1, 64, 2, 8, dtype=dtype), k=_sds(1, 64, 1, 8, dtype=dtype),
        qI=_sds(1, 64, 2, 8, dtype=dtype), kI=_sds(1, 64, 8, dtype=dtype),
        w=_sds(1, 64, 2, dtype=dtype), mask=_sds(1, 64, 64, dtype=jnp.int8),
        lseI=_sds(1, 64, dtype=f32), lse=_sds(1, 2, 64, dtype=f32))


def _dsa_indexer(dtype):
    x = _keye(dtype)
    return (lambda qI, kI, w: F.dsa_indexer(qI, kI, w, 16)[0]), (
        x["qI"], x["kI"], x["w"])


def _sparse_attention(dtype):
    x = _keye(dtype)
    return F.sparse_attention, (x["q"], x["k"], x["k"], x["mask"])


def _dsa_indexer_loss(dtype):
    x = _keye(dtype)
    return F.dsa_indexer_loss, (x["qI"], x["kI"], x["w"], x["mask"],
                                x["lseI"], x["q"], x["k"], x["lse"])


def _mla(dtype):
    heads, shared = _sds(1, 512, 1, 8, dtype=dtype), _sds(1, 512, 8,
                                                         dtype=dtype)
    return F.mla_attention, (heads, heads, heads, shared, heads)


def _ssd_scan(dtype):
    f32 = jnp.float32
    return (lambda *a: F.ssd_scan(*a, 128)), (
        _sds(1, 130, 8, 64, dtype=dtype), _sds(1, 130, 8, dtype=f32),
        _sds(8, dtype=f32), _sds(1, 130, 1, 128, dtype=dtype),
        _sds(1, 130, 1, 128, dtype=dtype), _sds(8, dtype=f32))


def _gated_short_conv(dtype):
    return F.gated_short_conv, (_sds(1, 64, 384, dtype=dtype),
                                _sds(3, 128, dtype=dtype))


# functional -> (its call at a small shape its gate takes in interpret
# mode, the kernel selections of one traced call, its XLA side's counter)
FUNCTIONALS = {
    "sdpa": (_sdpa, ("flash_attention",), "attention"),
    "eva_attention": (_eva, ("eva_attention",), "eva_attention"),
    "dsa_indexer": (_dsa_indexer, ("dsa_indexer",), "dsa_indexer"),
    "sparse_attention": (_sparse_attention, ("sparse_attention",),
                         "sparse_attention"),
    "dsa_indexer_loss": (_dsa_indexer_loss, ("dsa_kl",), "dsa_indexer_loss"),
    # latent attention counts itself and the flash kernels it runs on
    "mla_attention": (_mla, ("mla_attention", "flash_attention"),
                      "mla_attention"),
    "ssd_scan": (_ssd_scan, ("ssd_scan",), "ssd_scan"),
    "gated_short_conv": (_gated_short_conv, ("gated_short_conv",),
                         "gated_short_conv"),
}

# flags, the operands' dtype (float16: no gate takes it) -> kernels?
CONDITIONS = {
    "kernels": (dict(use_pallas_kernels=True, pallas_interpret=True),
                jnp.float32, True),
    "tier_off": (dict(use_pallas_kernels=False, pallas_interpret=True),
                 jnp.float32, False),
    "no_interpret_opt_in": (dict(use_pallas_kernels=True,
                                 pallas_interpret=False), jnp.float32, False),
    "unsupported": (dict(use_pallas_kernels=True, pallas_interpret=True),
                    jnp.float16, False),
}


def _choices():
    """Every ``pallas.selected.*`` and ``*.xla_path`` counter."""
    return {k: v for k, v in monitor.all_stats().items()
            if k.startswith("pallas.selected.") or k.endswith(".xla_path")}


@pytest.fixture
def flags(request):
    old = {name: get_flag(name) for name in request.param}
    set_flags(request.param)
    yield
    set_flags(old)


@pytest.mark.parametrize("flags,dtype,kernels", CONDITIONS.values(),
                         ids=CONDITIONS, indirect=["flags"])
@pytest.mark.parametrize("functional", FUNCTIONALS)
def test_a_traced_call_counts_the_one_side_it_took(functional, flags, dtype,
                                                   kernels):
    build, selected, xla_side = FUNCTIONALS[functional]
    fn, shapes = build(dtype)

    def call(*arrays):
        with paddle.no_grad():
            return fn(*map(paddle.Tensor, arrays)).data

    before, selections = _choices(), dict(kernel_selections)
    jax.eval_shape(call, *shapes)       # traced, not run: trace-time counters
    moved = {k: v - before.get(k, 0) for k, v in _choices().items()
             if v != before.get(k, 0)}
    if kernels:
        assert moved == {f"pallas.selected.{k}": 1 for k in selected}
    else:
        assert moved == {f"{xla_side}.xla_path": 1}
        # "flag off => zero selections", and so for a shape no gate takes
        assert kernel_selections == selections


def test_the_chooser_counts_the_xla_side_alone(kernels_on):
    before = _choices()
    assert choose_kernel("some_functional", True) is True
    assert _choices() == before         # the kernel's entry counts itself
    assert choose_kernel("some_functional", False) is False
    assert _choices() == {**before, "some_functional.xla_path": 1}
