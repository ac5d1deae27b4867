"""Device-mesh construction for 3D grid decomposition.

The TPU-native replacement for the reference's rank/GPU assignment and
topology discovery (reference: src/stencil.cu:9-137, mpi_topology.hpp,
gpu_topology.cpp): instead of probing NVML link distances and enabling CUDA
peer access, we lay the partition grid onto a ``jax.sharding.Mesh`` whose
axis ordering determines which grid neighbors are ICI-adjacent.
``mesh_utils.create_device_mesh`` performs the physical-topology-aware
assignment that the reference's ``NodeAware`` QAP placement computes
numerically (placement refinements live in ``placement.py``).

Mesh axis names are ``('z', 'y', 'x')`` in that order, matching the stacked
block array layout ``(bz, by, bx, pz, py, px)``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..geometry import Dim3

AXIS_X = "x"
AXIS_Y = "y"
AXIS_Z = "z"
# Mesh/array-major order: z slowest, x fastest.
MESH_AXES = (AXIS_Z, AXIS_Y, AXIS_X)

# The one PartitionSpec of the stacked-block layout (bz, by, bx, pz, py, px):
# block-grid dims sharded over the mesh, data dims replicated. It lives here
# (not in exchange.py) because it is a fact of the mesh-axis naming, shared
# by the manual shard_map exchanges AND the AUTO_SPMD jit programs whose
# collectives the SPMD partitioner synthesizes from this sharding.
BLOCK_PSPEC = P(AXIS_Z, AXIS_Y, AXIS_X, None, None, None)


def block_sharding(mesh: Mesh) -> NamedSharding:
    """NamedSharding of the stacked-block layout over a grid mesh — the
    in/out sharding every exchange strategy and jitted step pins."""
    return NamedSharding(mesh, BLOCK_PSPEC)


def sharded_full(shape, fill_value, dtype, sharding: NamedSharding):
    """``() -> array``: a jitted constant fill whose result is BORN with
    ``sharding`` — every device writes only its own shard, so no device
    ever stages the whole global array (``device_put(jnp.full(...))``
    materializes it on the first device before resharding)."""
    return jax.jit(lambda: jnp.full(shape, fill_value, dtype),
                   out_shardings=sharding)


def grid_mesh(dim, devices: Optional[Sequence] = None, ordered: bool = False) -> Mesh:
    """Build a ``(dz, dy, dx)`` mesh for a partition grid ``dim`` (x, y, z).

    ``devices=None`` uses all local devices; on a real multi-chip TPU slice
    the layout goes through ``mesh_utils.create_device_mesh`` (ICI-aware —
    the built-in NodeAware analogue). ``ordered=True`` keeps the caller's
    exact device order (used when a Placement strategy has already arranged
    them; the Trivial-placement analogue, partition.hpp:291).
    """
    d = Dim3.of(dim)
    shape = (d.z, d.y, d.x)
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    n = int(np.prod(shape))
    if len(devices) != n:
        raise ValueError(f"partition {d} needs {n} devices, have {len(devices)}")
    if (
        not ordered
        and n > 1
        and len({dev.platform for dev in devices}) == 1
        and devices[0].platform == "tpu"
    ):
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_device_mesh(shape, devices=devices)
    else:
        arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, MESH_AXES)


def mesh_dim(mesh: Mesh) -> Dim3:
    """Partition grid extent (x, y, z) of a grid mesh."""
    return Dim3(
        mesh.shape[AXIS_X],
        mesh.shape[AXIS_Y],
        mesh.shape[AXIS_Z],
    )
