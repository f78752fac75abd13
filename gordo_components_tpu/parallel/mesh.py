"""Mesh helpers for many-model sharding.

The fleet's canonical mesh is 1-D over all addressable devices with a
``models`` axis: stacked member arrays/params are sharded along their
leading (model) axis, so every device holds and trains ``M/n_devices``
models independently — the ICI carries no training traffic at all, which is
what makes many-model parallelism embarrassingly efficient on a TPU slice.
Multi-host pods work unchanged: ``jax.devices()`` spans the pod under
``jax.distributed``, and XLA keeps each model's computation local to its
shard.
"""

from typing import Any, Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MODEL_AXIS = "models"


def fleet_mesh(n_devices: Optional[int] = None, devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over (up to) all devices with the ``models`` axis."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (MODEL_AXIS,))


def shard_model_axis(mesh: Mesh) -> NamedSharding:
    """Sharding placing a stacked array's leading axis over ``models``."""
    return NamedSharding(mesh, P(MODEL_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_count_to_mesh(count: int, mesh: Mesh) -> int:
    """Smallest multiple of the mesh's model-axis size >= count."""
    size = mesh.shape[MODEL_AXIS]
    return -(-count // size) * size


def device_block(arrays: Any) -> Optional[Dict[str, Any]]:
    """``{"platform", "kind", "count"}`` of the devices that actually hold
    ``arrays`` (a pytree of jax Arrays), read from the arrays themselves —
    what ran where, not what ``jax.devices()`` offers. A parent process
    that stays off JAX reports its child's device from this (the
    fleet-build manifest, ``GET /models``). None for an empty tree."""
    devices = set()
    for leaf in jax.tree.leaves(arrays):
        devices |= leaf.devices()
    if not devices:
        return None
    first = min(devices, key=lambda d: d.id)
    return {
        "platform": first.platform,
        "kind": first.device_kind,
        "count": len(devices),
    }
