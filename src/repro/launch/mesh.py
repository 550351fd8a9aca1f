"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state -- the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count BEFORE any jax init, and
smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.compat import flat_mesh

FLEET_AXIS = "seeds"


def make_fleet_mesh(n_devices: int | None = None):
    """One-axis mesh over the seed dimension for Monte-Carlo episode sweeps.

    ``fl.simulator.run_fleet`` shards its fleet of episodes over this axis;
    defaults to every visible device (8 under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``, a full pod slice
    in production).  Routed through ``compat.flat_mesh`` so fleet sweeps and
    ``disba_sharded`` share one mesh-construction path.
    """
    return flat_mesh(n_devices, axis_name=FLEET_AXIS)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model); the pod axis crosses
    the data-center interconnect, so steady-state traffic on it is limited to
    gradient all-reduce (DESIGN.md §4)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_elastic_mesh(n_devices: int, model_parallel: int = 16):
    """Elastic variant: factor whatever device count survives a failure into
    (data, model), shrinking model-parallel if needed (repro.distributed.elastic)."""
    while model_parallel > 1 and n_devices % model_parallel != 0:
        model_parallel //= 2
    data = n_devices // model_parallel
    return jax.make_mesh((data, model_parallel), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))
