"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state.  The dry-run launcher
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else (tests, benchmarks) sees the real single device.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

DEVICES_PER_POD = 256  # 16 x 16


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh with every axis ``Auto`` (tests use small ones)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_variant_mesh(num_devices: Optional[int] = None):
    """1-D ``("variants",)`` mesh over every local device.

    The mega-sweep data layout: the machine-variant axis is embarrassingly
    parallel (profiles replicated, variants split), so ``shard_sweep``
    wants all devices on one axis regardless of the production 2-D/3-D
    topology.  ``Backend.sharded_stats`` consumes this mesh for both the
    NamedSharding (jax) and shard_map (pallas) distribution strategies.
    """
    ndev = int(num_devices or max(1, len(jax.devices())))
    return make_mesh((ndev,), ("variants",))


def mesh_name(mesh) -> str:
    return "x".join(str(mesh.shape[a]) for a in mesh.axis_names)
