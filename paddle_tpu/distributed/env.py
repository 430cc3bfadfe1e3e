"""Process-level distributed environment.

Reference analog: ``paddle.distributed.init_parallel_env`` (parallel.py:57),
RoleMaker env parsing (role_maker.py:528), launch/fleetrun.  On TPU the
process model is jax's: one controller process per host, all devices visible;
``jax.distributed.initialize`` is the TCP-bootstrap equivalent
(gen_comm_id_helper.cc analog) for multi-host.
"""
from __future__ import annotations

import os

import jax

from .mesh import ensure_mesh, init_mesh

_initialized = False


def early_init():
    """Run the jax.distributed TCP rendezvous NOW, before anything
    initialises the XLA backend.  Importing paddle_tpu itself touches
    jax.random, so multi-process entrypoints that import the framework at
    module top must call this first (the launcher's env provides the
    coordinator parameters).  Safe no-op when not under a launcher or
    already initialised."""
    coord = os.environ.get("COORDINATOR_ADDRESS") or os.environ.get(
        "PADDLE_MASTER")
    n_proc = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    # NB: probe with is_initialized(), NOT jax.process_count() — the
    # latter initialises the backend, which would itself make the
    # rendezvous impossible
    if coord and n_proc > 1 and not jax.distributed.is_initialized():
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=n_proc,
            process_id=int(os.environ.get("PADDLE_TRAINER_ID", "0")))


def init_parallel_env(mesh_shape=None):
    """paddle.distributed.init_parallel_env parity.

    Single-host: builds the global mesh over local devices.  Multi-host (env
    ``PADDLE_TRAINERS_NUM``>1 or jax coordinator envs set): calls
    ``jax.distributed.initialize`` first so jax.devices() spans all hosts.
    """
    global _initialized
    from .mesh import get_mesh
    cur = get_mesh()
    if _initialized:
        if mesh_shape is None or (
                cur is not None and dict(cur.shape) == dict(mesh_shape)):
            return ensure_mesh()
        # an explicit, different shape re-derives the mesh (the guard in
        # init_mesh rejects it while compiled programs hold shardings)
        return init_mesh(mesh_shape)
    early_init()
    if cur is not None and (mesh_shape is None
                            or dict(cur.shape) == dict(mesh_shape)):
        # a pre-pinned live mesh (possibly over a custom device subset)
        # that already has the requested shape stays installed AS-IS —
        # init_mesh would rebuild it over the default device prefix and
        # silently move the pin
        mesh = cur
    else:
        mesh = init_mesh(mesh_shape)
    _initialized = True
    return mesh


def get_rank(group=None) -> int:
    """Process rank (reference: paddle.distributed.get_rank)."""
    return jax.process_index()


def get_world_size(group=None) -> int:
    """Number of *processes* (reference: get_world_size).  Note: on TPU a
    process controls many devices; device-level parallelism lives in the
    mesh axes, not in process count."""
    return jax.process_count()


def device_world_size() -> int:
    return len(jax.devices())


def is_initialized() -> bool:
    return _initialized


class ParallelEnv:
    """reference: fluid/dygraph/parallel.py ParallelEnv."""

    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def device_id(self):
        return 0

    @property
    def current_endpoint(self):
        return os.environ.get("PADDLE_CURRENT_ENDPOINT", "127.0.0.1:0")

    @property
    def trainer_endpoints(self):
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        return eps.split(",") if eps else []
