"""Device mesh construction.

The reference's "cluster topology" is N worker containers in a star around one
gRPC server (terraform/main.tf:327-435). Here a *worker* is a logical index
along the ``data`` axis of a `jax.sharding.Mesh`; registration/membership
(server.py:190-211) is replaced by the mesh — worker_id == axis index, always
contiguous, never duplicated (the reference's restart-induced duplicate-id
pollution, README.md:368-371, cannot occur by construction).
"""

from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh

DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_mesh(num_workers: int | None = None,
              axis_names: tuple[str, ...] = (DATA_AXIS,),
              devices=None) -> Mesh:
    """Build a mesh whose leading axis is the logical worker (data) axis.

    With a single axis name, shape is ``(num_workers,)``. With two
    (``('data','model')``), the trailing ``model`` axis takes all remaining
    devices: ``(num_workers, len(devices)//num_workers)``.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if num_workers is None:
        num_workers = n
    if len(axis_names) == 1:
        if num_workers > n:
            raise ValueError(
                f"{num_workers} workers > {n} devices; shrink the worker "
                f"count or use a CPU mesh with "
                f"--xla_force_host_platform_device_count")
        shape = (num_workers,)
        devs = np.array(devices[:num_workers]).reshape(shape)
    else:
        if n % num_workers:
            raise ValueError(f"{n} devices not divisible by {num_workers}")
        shape = (num_workers, n // num_workers)
        devs = np.array(devices).reshape(shape)
    return Mesh(devs, axis_names)


def worker_axis_size(mesh: Mesh, axis: str = DATA_AXIS) -> int:
    return mesh.shape[axis]
