"""Placement strategies: which device hosts which subdomain.

TPU-native re-design of the reference's Placement classes
(reference: include/stencil/partition.hpp:264-289 abstract, :291-445
Trivial, :525-831 NodeAware QAP placement;
src/placement_intranoderandom.cpp IntraNodeRandom ablation baseline).

A placement's job here is to ORDER the device list before the 3D grid mesh
is built: grid position (ix, iy, iz) takes the device at row-major (z, y, x)
index ``iz*dy*dx + iy*dx + ix`` of the arranged list. On real TPU slices
``mesh_utils.create_device_mesh`` already produces an ICI-aware layout;
NodeAware reproduces the reference's *numeric* approach (QAP over a
comm-volume matrix and a 1/bandwidth distance matrix) and is useful when
the automatic layout is unavailable (explicit device lists, CPU meshes) and
as the placement-ablation axis of the benchmarks (--naive / --random flags,
bin/exchange_weak.cu:74,149-153).
"""

from __future__ import annotations

import random
from typing import List, Sequence

import numpy as np

from ..geometry import Dim3, halo_extent
from ..utils import logging as log
from . import qap
from .device_topo import distance_matrix


class Placement:
    """Orders devices for mesh construction (lowest index = block (0,0,0))."""

    def arrange(self, devices: Sequence, spec) -> List:
        raise NotImplementedError


class Trivial(Placement):
    """Devices in given order — the reference's rank-order round-robin
    (partition.hpp:291-445)."""

    def arrange(self, devices: Sequence, spec) -> List:
        return list(devices)


class IntraNodeRandom(Placement):
    """Deterministic random shuffle within each host's devices — the
    placement-ablation baseline (reference:
    src/placement_intranoderandom.cpp, seeded mt19937(0) shuffle)."""

    def __init__(self, seed: int = 0):
        self.seed = seed

    def arrange(self, devices: Sequence, spec) -> List:
        rng = random.Random(self.seed)
        by_host: dict = {}
        order: List = []
        for d in devices:
            by_host.setdefault(d.process_index, []).append(d)
        for host in sorted(by_host):
            group = by_host[host]
            rng.shuffle(group)
            order.extend(group)
        return order


def comm_matrix(spec) -> np.ndarray:
    """Pairwise halo-volume matrix between grid positions, periodic wrap
    (reference: partition.hpp:722-752; cost = halo_extent(dir).flatten(),
    :535-540)."""
    dim = spec.dim
    n = dim.flatten()
    m = np.zeros((n, n), dtype=np.float64)

    def lin(idx: Dim3) -> int:
        return idx.x + idx.y * dim.x + idx.z * dim.x * dim.y

    for iz in range(dim.z):
        for iy in range(dim.y):
            for ix in range(dim.x):
                src = Dim3(ix, iy, iz)
                sz = spec.block_size(src)
                for dz in (-1, 0, 1):
                    for dy in (-1, 0, 1):
                        for dx in (-1, 0, 1):
                            d = Dim3(dx, dy, dz)
                            if d == Dim3(0, 0, 0):
                                continue
                            if spec.radius.dir(d) == 0:
                                continue
                            dst = (src + d).wrap(dim)
                            if dst == src:
                                continue  # self-wrap: no inter-device traffic
                            m[lin(src), lin(dst)] += halo_extent(
                                d, sz, spec.radius
                            ).flatten()
    return m


class FixedAssignment(Placement):
    """An explicit, pre-solved block→device assignment — the strategy
    form of a ``PlanChoice.placement`` tuple: grid position i (row-major
    z, y, x) is hosted by ``devices[assignment[i]]``. What the plan
    probes and the placed bench legs arrange with (the tuned assignment
    must realize EXACTLY, not be re-solved)."""

    def __init__(self, assignment):
        self.assignment = tuple(int(v) for v in assignment)
        if sorted(self.assignment) != list(range(len(self.assignment))):
            raise ValueError(
                f"assignment {self.assignment} is not a permutation of "
                f"range({len(self.assignment)})")

    def arrange(self, devices: Sequence, spec) -> List:
        if len(devices) != len(self.assignment):
            raise ValueError(
                f"assignment covers {len(self.assignment)} devices; "
                f"got {len(devices)}")
        return [devices[self.assignment[i]]
                for i in range(len(self.assignment))]


class NodeAware(Placement):
    """QAP-matched placement: assign subdomains to devices so that heavy
    halo traffic rides the fastest links (reference: partition.hpp:525-831,
    rank 0 solves and broadcasts; here every process computes the same
    deterministic answer)."""

    def __init__(self, timeout_s: float = 10.0, exact_limit: int = 8):
        self.timeout_s = timeout_s
        self.exact_limit = exact_limit

    def arrange(self, devices: Sequence, spec) -> List:
        n = len(devices)
        w = comm_matrix(spec)
        dist = distance_matrix(devices)
        if n <= self.exact_limit:
            f, cost = qap.solve(w, dist, timeout_s=self.timeout_s)
        else:
            f, cost = qap.solve_catch(w, dist)
        log.debug(f"NodeAware placement cost {cost}: {f}")
        # f[i] = device slot for grid position i (row-major z,y,x)
        return [devices[f[i]] for i in range(n)]
