"""Device-mesh construction from topology.

The single most important architectural inversion versus the reference: where
``/root/reference/src/accelerate/state.py:734-799`` selects one of ten
process-group backends, a TPU program has exactly one runtime (PJRT) and one
distribution mechanism — a :class:`jax.sharding.Mesh` whose axes carry every
parallelism strategy simultaneously (dp / fsdp / tp / sp / ep / pp).
Collectives ride ICI within a slice and DCN across slices; XLA chooses them
from sharding specs, we only lay out the mesh so that the heavily-communicating
axes (tp, sp) map to physically adjacent devices.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from ..utils.constants import ALL_MESH_AXES


def shard_map_compat(f, mesh, in_specs, out_specs, check: bool = False):
    """The package's one ``jax.shard_map`` call (pipeline schedules, ring
    attention, the flash kernel on a mesh, the collective-matmul ring,
    tests): keyword-only API, ``check_vma`` off unless asked."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check
    )


def make_mesh(
    axis_sizes: dict[str, int],
    devices: Optional[Sequence[jax.Device]] = None,
    axis_order: Sequence[str] = ALL_MESH_AXES,
) -> Mesh:
    """Build a Mesh with the given axis sizes.

    Axis order is chosen so that the *fastest-varying* (innermost) axes are the
    most communication-hungry: ``tp`` and ``sp`` land on adjacent chips
    (ICI-neighbouring), ``dp`` is outermost (cheapest collectives: one psum per
    step, latency-tolerant).  ``mesh_utils.create_device_mesh`` then maps the
    logical mesh onto the physical torus so nearest-neighbour ICI links are
    used for the inner axes.
    """
    if devices is None:
        devices = jax.devices()
    sizes = [axis_sizes.get(name, 1) for name in axis_order]
    total = int(np.prod(sizes))
    if total != len(devices):
        raise ValueError(
            f"mesh axis sizes {dict(zip(axis_order, sizes))} require {total} "
            f"devices, have {len(devices)}"
        )
    if devices[0].platform == "cpu":
        # virtual host devices have no topology to map onto
        device_array = np.asarray(list(devices)).reshape(tuple(sizes))
    else:
        # a real topology this cannot lay out is an error to see, not a
        # reshape that quietly puts tp/sp neighbours on distant chips
        from jax.experimental import mesh_utils

        device_array = mesh_utils.create_device_mesh(
            tuple(sizes), devices=list(devices)
        )
    return Mesh(device_array, axis_names=tuple(axis_order))


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis] if axis in mesh.axis_names else 1


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes over which the global batch is sharded.

    dp and fsdp both consume batch (ZeRO shards params but still feeds each
    device distinct data); sp shards the sequence dimension, not batch.
    """
    return tuple(a for a in ("dp", "fsdp") if mesh_axis_size(mesh, a) > 1) or ("dp",)


def batch_sharding_size(mesh: Mesh) -> int:
    """Number of distinct per-device batch shards."""
    return int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
