"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not module-level state) so importing
this module never touches jax device initialization. Single pod = 256 chips
as (data=16, model=16); multi-pod = 512 chips as (pod=2, data=16, model=16).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(*, data: int = 1, model: int = 1):
    """(data x model) mesh over the host's real local devices; the 1x1
    default serves tests/examples. Requesting more devices than the host
    exposes fails HERE with the fix in the message — previously this
    surfaced as an opaque XLA device-assignment error at first trace."""
    need = data * model
    have = jax.local_device_count()
    if need > have:
        raise ValueError(
            f"local mesh (data={data} x model={model}) needs {need} "
            f"devices but this host exposes {have}; on CPU hosts set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} in "
            f"the environment before jax initializes, or shrink the "
            f"requested topology")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_serving_mesh(topology):
    """Mesh for one sharded ``ServingEngine`` replica
    (``repro.serving.config.DeviceTopology``)."""
    return make_local_mesh(data=topology.dp, model=topology.tp)
