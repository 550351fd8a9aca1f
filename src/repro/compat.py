"""The mesh and ``shard_map`` choices the sharded solvers share.

``fl.simulator.run_fleet``, ``core.disba.disba_sharded``,
``launch.mesh.make_fleet_mesh`` and the MoE dispatch build their meshes and
``shard_map`` calls here, so each choice (replication checking off, one-axis
Auto meshes) keeps one home.
"""
from __future__ import annotations

import functools

import jax
from jax.sharding import AxisType

# ``shard_map`` with replication checking off: the fleet and sharded-solver
# scans carry values whose replication differs between input and output.
shard_map_unchecked = functools.partial(jax.shard_map, check_vma=False)


def flat_mesh(n_devices: int | None = None, axis_name: str = "data",
              devices=None):
    """One-axis device mesh over the first ``n_devices`` devices.

    The single mesh-construction path for every batch/seed-sharded solver
    (``fl.simulator.run_fleet``, ``core.disba.disba_sharded``,
    ``launch.mesh.make_fleet_mesh``).  ``n_devices=None`` takes every
    visible device.
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    if not 1 <= n_devices <= len(devices):
        raise ValueError(
            f"n_devices={n_devices} outside [1, {len(devices)}] visible "
            f"devices")
    return jax.make_mesh((n_devices,), (axis_name,),
                         axis_types=(AxisType.Auto,),
                         devices=devices[:n_devices])


__all__ = ["shard_map_unchecked", "flat_mesh"]
