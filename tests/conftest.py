"""Test harness config.

Tests run on a virtual 8-device CPU mesh (SURVEY §4 implication: XLA gives
true single-process multi-device, unlike the reference's subprocess-based
TestDistBase) — set env BEFORE jax initialises.
"""
import os

# Tier-1 runs on the CPU backend whatever the shell presets: the unit
# tests want 8 devices, and a chip belongs to one process at a time.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

# a plugin may have imported jax before this file ran, in which case the
# variable above was read too late — the config API still wins
jax.config.update("jax_platforms", "cpu")

# exact-ish matmuls for numeric checks (bench sets its own precision)
jax.config.update("jax_default_matmul_precision", "highest")
# no persistent compile cache for tier-1: every run compiles what it
# tests, and the described-chip compiles of test_chip_compile.py could
# not be read back from one anyway
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu
    paddle_tpu.seed(2024)
    np.random.seed(2024)
    yield


@pytest.fixture
def kernels_on():
    """The Pallas tier in interpret mode: off a TPU
    ``ops.pallas.support.tier_enabled`` wants the opt-in."""
    from paddle_tpu.core.flags import get_flag, set_flags
    old = get_flag("pallas_interpret")
    set_flags({"pallas_interpret": True})
    yield
    set_flags({"pallas_interpret": old})


def _pallas_eqns(jaxpr, within=None, primitive="pallas_call"):
    """Every ``pallas_call`` equation (or every one of ``primitive``) of a
    jaxpr and of the jaxprs in its equations' parameters; with ``within``,
    only those under an equation of that primitive (``"remat2"``: what
    ``jax.checkpoint`` replays)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive and within is None:
            found.append(eqn)
        inner_within = None if eqn.primitive.name == within else within
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _pallas_eqns(inner, inner_within, primitive)
    return found


@pytest.fixture
def pallas_eqns():
    """``pallas_eqns(jaxpr, within=None, primitive="pallas_call")``: see
    ``_pallas_eqns``."""
    return _pallas_eqns


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`: mark tests that duplicate a tools/
    # smoke gate (chaos_smoke, serve_smoke) so they stay runnable
    # without charging the tier-1 time budget twice.
    config.addinivalue_line(
        "markers", "slow: excluded from tier-1; covered by a tools/ gate")
