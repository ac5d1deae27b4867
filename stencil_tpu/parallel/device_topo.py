"""Device-interconnect distance discovery.

TPU-native analogue of the reference's NVML-based GPU topology probing
(reference: include/stencil/gpu_topology.hpp, src/gpu_topology.cpp:22-95 —
NVLink/PCIe ancestor-ladder distances 0.1–7.0, bandwidth = 1/distance).

On TPU the interconnect facts come from the device objects themselves:
``device.coords`` gives the chip's position in the physical ICI torus, so
the distance between two chips of one process is their torus hop count;
chips in different processes (hosts) take the remote cost, and devices
without coords (CPU/virtual) fall back to process locality. As in
the reference, bandwidth is modeled as 1/distance.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

# distance constants, same spirit as the reference's ladder
# (src/gpu_topology.cpp:22-27): self < linked < same-host < remote
DIST_SELF = 0.1
DIST_SAME_PROCESS = 1.0
DIST_REMOTE = 7.0

def device_distance(a, b) -> float:
    """Hop distance between two JAX devices."""
    if a == b:
        return DIST_SELF
    if getattr(a, "process_index", 0) != getattr(b, "process_index", 0):
        return DIST_REMOTE
    ca = getattr(a, "coords", None)
    cb = getattr(b, "coords", None)
    if ca is not None and cb is not None and len(ca) == len(cb):
        # ICI torus hops; axis sizes unknown here so use plain manhattan
        # distance (exact for the non-wrapped meshes we can observe)
        hops = sum(abs(int(x) - int(y)) for x, y in zip(ca, cb))
        if hops > 0:
            return float(hops)
    return DIST_SAME_PROCESS


def distance_matrix(devices: Sequence) -> np.ndarray:
    n = len(devices)
    m = np.zeros((n, n), dtype=np.float64)
    for i, a in enumerate(devices):
        for j, b in enumerate(devices):
            m[i, j] = device_distance(a, b)
    return m


def bandwidth_matrix(devices: Sequence) -> np.ndarray:
    """bandwidth = 1/distance (reference: src/gpu_topology.cpp:95)."""
    return 1.0 / distance_matrix(devices)
