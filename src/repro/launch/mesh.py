"""Device meshes of the simulator: the replica, neuron-shard and 2-D sweep
layouts, over the axes declared in sharding/rules.AXIS_CONTRACTS.

Functions, not module-level constants: importing this module never touches
jax device state (a dry-run sets XLA_FLAGS before first jax init; smoke
tests must keep seeing 1 device).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_ensemble_mesh(num_devices: int | None = None, axis: str = "ensemble") -> Mesh:
    """1-D mesh for the replica axis of core/ensemble.py (its size must
    divide the replica count K).

    Replicas never communicate, so any device set works — no pod topology
    constraints; defaults to every visible device."""
    devs = jax.devices()
    if num_devices is not None:
        devs = devs[:num_devices]
    return Mesh(np.array(devs), (axis,))


def make_data_mesh(data: int | None = None, axis: str = "data") -> Mesh:
    """1-D neuron-decomposition mesh for `DistributedPlasticityEngine`.

    `data` devices along the paper's MPI-rank axis (defaults to every
    visible device); the engine's per-step psum/all_gather and the
    owner-span pyramid exchange all name this axis.  The engine requires
    the shard count to divide the neuron count (n % data == 0).
    """
    devs = jax.devices()
    if data is not None:
        if len(devs) < data:
            raise ValueError(f"data mesh needs {data} devices, " f"have {len(devs)}")
        devs = devs[:data]
    return Mesh(np.array(devs), (axis,))


def make_sweep_mesh(
    ensemble: int, data: int, ensemble_axis: str = "ensemble", data_axis: str = "data"
) -> Mesh:
    """2-D (ensemble x data) mesh for distributed parameter sweeps
    (core/distributed.DistributedEnsembleEngine): K replicas sharded over
    `ensemble` device rows, each replica's neurons/edges decomposed over
    `data` devices per row.

    The data axis is innermost: the per-step psum/all_gather run only along
    it, between devices the default device order places closest; the replica
    axis exchanges nothing, so it can span hosts/pods freely."""
    need = ensemble * data
    devs = jax.devices()
    if len(devs) < need:
        raise ValueError(
            f"sweep mesh needs {need} devices " f"({ensemble} x {data}), have {len(devs)}"
        )
    return Mesh(np.array(devs[:need]).reshape(ensemble, data), (ensemble_axis, data_axis))
