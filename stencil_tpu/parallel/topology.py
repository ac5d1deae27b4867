"""Logical neighbor topology over the subdomain grid + link-cost discovery.

TPU-native analogue of the reference ``Topology``
(reference: include/stencil/topology.hpp:9-30, src/topology.cpp) — periodic
boundaries only, like the reference (non-periodic is fatal there).

:func:`link_cost_matrix` is the physical half the placement leg consumes
(plan/cost.py's topology-aware PlanChoice dimension): the per-device-pair
distance matrix the QAP prices wire volume against — ICI torus hop
distance where device coords exist (TPU slices), the process-boundary
penalty ladder elsewhere (the reference's NVML ancestor-ladder distances,
src/gpu_topology.cpp:22-95, re-read from the JAX device objects)."""

from __future__ import annotations

import enum
from dataclasses import dataclass
from typing import Sequence

from ..geometry import Dim3
from .device_topo import distance_matrix


class Boundary(enum.Enum):
    NONE = 0
    PERIODIC = 1


@dataclass(frozen=True)
class Neighbor:
    index: Dim3
    exists: bool


class Topology:
    def __init__(self, extent, boundary: Boundary = Boundary.PERIODIC):
        if boundary != Boundary.PERIODIC:
            raise ValueError("only periodic boundaries are supported (as in the reference)")
        self.extent = Dim3.of(extent)
        self.boundary = boundary

    def get_neighbor(self, index, direction) -> Neighbor:
        idx = Dim3.of(index)
        d = Dim3.of(direction)
        if not (abs(d.x) <= 1 and abs(d.y) <= 1 and abs(d.z) <= 1):
            raise ValueError(f"direction components must be in "
                             f"{{-1, 0, 1}}; got {d}")
        return Neighbor(index=(idx + d).wrap(self.extent), exists=True)


def link_cost_matrix(devices: Sequence):
    """Per-device-pair link cost (lower = faster) for the placement QAP.

    Delegates to :func:`~.device_topo.distance_matrix`: ICI torus hop
    count between chips that expose ``coords`` (every extra hop costs
    proportionally more wire time — the manhattan model, exact for
    non-wrapped observable meshes), and the locality ladder for devices
    without coords — same process 1.0, cross-process 7.0 (the reference's
    remote-rank penalty). A single-process CPU mesh is therefore UNIFORM
    off-diagonal, which the plan search recognizes
    (``plan.cost.uniform_link_costs``) and prices every placement
    identically — identity wins, by design: placement only pays off where
    the fabric is actually non-uniform."""
    return distance_matrix(devices)
