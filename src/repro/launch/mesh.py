"""Production mesh construction.

A function (NOT a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then calls `make_production_mesh()`.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) data×model single-pod or (2,16,16) pod×data×model multi-pod."""
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    import jax

    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def flush_mesh(max_devices: Optional[int] = None):
    """1-axis ``("rows",)`` mesh over local devices for sharding giant
    prediction flushes (whole NAS generations / RPC micro-batches), or
    None on a single-device host so callers keep the unsharded path.

    The bank is replicated across the axis and flush rows sharded along
    it; reassembly is deterministic because rows are padded to a device
    multiple and gathered back in row order (see
    `repro.kernels.tree_gather.DeviceBank`).
    """
    import jax

    n = len(jax.devices())
    if max_devices is not None:
        n = min(n, max_devices)
    if n <= 1:
        return None
    return make_mesh((n,), ("rows",))


def elastic_mesh_shape(n_devices: int, *, model_parallel: int = 16,
                       pods: int = 1) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    """Choose a mesh for whatever device count survived (elastic restart).

    Keeps the model axis fixed (sharding of weights must still fit) and
    gives the remainder to data; drops to a 1-axis mesh for tiny counts.
    """
    model_parallel = min(model_parallel, n_devices)
    while n_devices % model_parallel != 0:
        model_parallel //= 2
    data = n_devices // model_parallel // pods
    if pods > 1 and data >= 1:
        return (pods, data, model_parallel), ("pod", "data", "model")
    data = n_devices // model_parallel
    return (data, model_parallel), ("data", "model")


def data_axes(mesh) -> Tuple[str, ...]:
    """Axes that shard the batch (pod+data when present)."""
    names = tuple(mesh.axis_names)
    return tuple(a for a in ("pod", "data") if a in names)
