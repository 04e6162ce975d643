"""Parallel environment bootstrap.

Reference analog: paddle.distributed.init_parallel_env + ParallelEnv
(/root/reference/python/paddle/distributed/parallel.py:875 env-var contract
PADDLE_TRAINER_ID / PADDLE_TRAINER_ENDPOINTS / PADDLE_TRAINERS_NUM) and the TCPStore
bootstrap (phi/core/distributed/store/tcp_store.cc).

TPU-native: one OS process per HOST (not per chip — jax owns all local chips);
multi-host rendezvous goes through `jax.distributed.initialize` (its coordination
service is the TCPStore analog). The "world" is the device count, not the process
count: rank maps onto mesh coordinates, and collective placement is compiled into
programs rather than negotiated per-call.
"""
from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

_env = {"initialized": False, "mesh": None, "hcg": None}

# env-var contract (reference: launch/context/args_envs.py + parallel.py)
ENV_RANK = "PADDLE_TRAINER_ID"
ENV_WORLD_SIZE = "PADDLE_TRAINERS_NUM"
ENV_MASTER = "PADDLE_MASTER"
ENV_ENDPOINTS = "PADDLE_TRAINER_ENDPOINTS"


class ParallelEnv:
    """Snapshot view of the distributed environment (reference ParallelEnv)."""

    @property
    def rank(self) -> int:
        return jax.process_index()

    @property
    def world_size(self) -> int:
        # device-level world size: TPU idiom (1 process : N chips)
        return jax.device_count()

    @property
    def local_rank(self) -> int:
        return jax.process_index()

    @property
    def nranks(self) -> int:
        return self.world_size

    @property
    def device_id(self) -> int:
        return 0

    @property
    def dev_id(self) -> int:
        return 0


def _maybe_init_multihost():
    """Initialize jax.distributed from the PADDLE_* env contract when present.

    The launcher (paddle_tpu.distributed.launch) exports PADDLE_MASTER (jax
    coordinator address), PADDLE_TRAINER_ID (process rank) and
    PADDLE_TRAINERS_NUM (process world size); jax's coordination service is the
    TCPStore analog, so bootstrap is just agreeing on that address."""
    master = os.environ.get(ENV_MASTER)
    nproc = int(os.environ.get(ENV_WORLD_SIZE, "1"))
    # NB: must not call jax.process_count() here — it would initialize the XLA
    # backend, after which jax.distributed.initialize refuses to run
    if master and nproc > 1 and not jax.distributed.is_initialized():
        rank = int(os.environ.get(ENV_RANK, "0"))
        jax.distributed.initialize(coordinator_address=master,
                                   num_processes=nproc, process_id=rank)


def init_parallel_env(mesh_shape: Optional[Sequence[int]] = None,
                      axis_names: Optional[Sequence[str]] = None):
    """Create the global device mesh.

    Default: 1-D mesh over every device with axis "data" (pure DP — matches the
    reference default where init_parallel_env creates the global NCCL group).
    fleet.init replaces this with the 4-D hybrid mesh.
    """
    if _env["initialized"] and _env["mesh"] is not None:
        return ParallelEnv()
    _maybe_init_multihost()
    devices = np.asarray(jax.devices())
    if mesh_shape is None:
        mesh_shape = (len(devices),)
    if axis_names is None:
        axis_names = (("data",) if len(mesh_shape) == 1 else
                      tuple(f"axis_{i}" for i in range(len(mesh_shape))))
    if len(axis_names) != len(mesh_shape):
        raise ValueError(f"axis_names {axis_names} does not match mesh_shape "
                         f"{tuple(mesh_shape)}")
    mesh = Mesh(devices.reshape(tuple(mesh_shape)), tuple(axis_names))
    _env["mesh"] = mesh
    _env["initialized"] = True
    return ParallelEnv()


def is_initialized() -> bool:
    return _env["initialized"]


def get_rank(group=None) -> int:
    if group is not None:
        return group.rank
    return jax.process_index()


def get_world_size(group=None) -> int:
    if group is not None:
        return group.nranks
    return jax.device_count()


def get_mesh() -> Optional[Mesh]:
    return _env["mesh"]


def set_mesh(mesh: Mesh):
    _env["mesh"] = mesh
    _env["initialized"] = True


def set_hcg(hcg):
    _env["hcg"] = hcg


def get_hcg():
    return _env["hcg"]


def device_mesh_shape() -> Tuple[int, ...]:
    mesh = get_mesh()
    return tuple(mesh.devices.shape) if mesh is not None else (1,)
