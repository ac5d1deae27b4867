"""Domain partitioners: split a global 3D extent into subdomains.

TPU-native re-implementation of the reference's partition math
(reference: include/stencil/partition.hpp:20-256). Two strategies:

- :class:`RankPartition` splits repeatedly along the *longest* axis by the
  prime factors of N (largest factor first).
- :class:`NodePartition` is a two-level split (hosts, then chips per host)
  that each step cuts the axis with the smallest radius-weighted interface
  area — the communication-minimizing split.

On TPU these decide the shape of the 3D device mesh
(``jax.sharding.Mesh``) and the per-shard logical sizes; the remainder
handling below reproduces the reference's uneven-split semantics exactly
(pinned by tests ported from test/test_cpu_partition.cpp).
"""

from __future__ import annotations

from .dim3 import Dim3
from .numeric import div_ceil, prime_factors
from .radius import Radius


def decompose_zy(p: int) -> Dim3:
    """TPU-first device decomposition: split over z and y ONLY, keeping
    the lane (x) axis whole.

    Three wins over the reference's 3-axis decomposition
    (astaroth.cu:263-276) on TPU hardware: (1) every chip keeps the
    tight-x layout — no x halo columns, periodic x via lane rolls
    (1.36-1.62x measured per chip, BASELINE.md round 3); (2) the exchange
    never slices the minor dim, so no slab pays (8,128) lane-tile
    amplification; (3) splitting two axes moves fewer halo bytes for the
    same shard volume (4 split faces instead of 6) and the 2D z x y mesh
    maps directly onto the v5e ICI torus. z grows first (matches the
    slowest-varying layout dim)."""
    y = z = 1
    for pf in prime_factors(max(p, 1)):
        if z <= y:
            z *= pf
        else:
            y *= pf
    return Dim3(1, y, z)


def stack_residents(dim: Dim3, c: int) -> Dim3:
    """Mesh dims for stacking ``c`` resident blocks per device onto
    partition ``dim``: the z-heaviest (cz, cy, cx) factorization of ``c``
    whose components divide the partition axes (exhaustive — divisor
    triples of c are few). Reference envelope: dd.set_gpus accepts any
    block multiset per device (stencil.hpp:154). Shared by
    ``api.realize`` and the plan cost model, which must predict the same
    mesh a realize() of the candidate would build."""
    best = None
    for cz in range(c, 0, -1):
        if c % cz or dim.z % cz:
            continue
        cyx = c // cz
        for cy in range(cyx, 0, -1):
            if cyx % cy or dim.y % cy:
                continue
            cx = cyx // cy
            if dim.x % cx:
                continue
            best = Dim3(dim.x // cx, dim.y // cy, dim.z // cz)
            break
        if best is not None:
            break
    if best is None:
        raise ValueError(
            f"cannot stack {c} resident blocks per device onto partition "
            f"{dim}: no divisor triple of {c} divides the axes"
        )
    return best


class RankPartition:
    """Split ``size`` into ``n`` subdomains along the longest axes.

    Reference: partition.hpp:28-115. Each prime factor of ``n`` (largest
    first) divides the currently-longest axis (ties: x wins over y wins
    over z). Remainders shrink trailing subdomains by one.
    """

    def __init__(self, size, n: int):
        size = Dim3.of(size)
        self._input = size
        dim = Dim3(1, 1, 1)
        sz = size
        for amt in prime_factors(max(n, 1)):
            if amt < 2:
                continue
            if sz.x >= sz.y and sz.x >= sz.z:
                sz = Dim3(div_ceil(sz.x, amt), sz.y, sz.z)
                dim = Dim3(dim.x * amt, dim.y, dim.z)
            elif sz.y >= sz.z:
                sz = Dim3(sz.x, div_ceil(sz.y, amt), sz.z)
                dim = Dim3(dim.x, dim.y * amt, dim.z)
            else:
                sz = Dim3(sz.x, sz.y, div_ceil(sz.z, amt))
                dim = Dim3(dim.x, dim.y, dim.z * amt)
        self._dim = dim
        self._size = sz
        self._rem = size % dim

    def dim(self) -> Dim3:
        return self._dim

    def base_size(self) -> Dim3:
        """The largest subdomain size (shards with idx < rem per axis)."""
        return self._size

    def subdomain_size(self, idx) -> Dim3:
        """Reference: partition.hpp:55-70 — trailing subdomains lose one."""
        idx = Dim3.of(idx)
        r = self._rem
        s = self._size
        return Dim3(
            s.x - (1 if (r.x != 0 and idx.x >= r.x) else 0),
            s.y - (1 if (r.y != 0 and idx.y >= r.y) else 0),
            s.z - (1 if (r.z != 0 and idx.z >= r.z) else 0),
        )

    def subdomain_origin(self, idx) -> Dim3:
        """Reference: partition.hpp:72-86."""
        idx = Dim3.of(idx)
        r = self._rem
        ret = self._size * idx
        return Dim3(
            ret.x - ((idx.x - r.x) if (r.x != 0 and idx.x >= r.x) else 0),
            ret.y - ((idx.y - r.y) if (r.y != 0 and idx.y >= r.y) else 0),
            ret.z - ((idx.z - r.z) if (r.z != 0 and idx.z >= r.z) else 0),
        )

    def is_uniform(self) -> bool:
        return self._rem == Dim3(0, 0, 0)

    def linearize(self, idx) -> int:
        """x-fastest linear index (reference: partition.hpp:89-101)."""
        idx = Dim3.of(idx)
        d = self._dim
        if not (0 <= idx.x < d.x and 0 <= idx.y < d.y and 0 <= idx.z < d.z):
            raise IndexError(f"block index {idx} outside partition {d}")
        return idx.x + idx.y * d.x + idx.z * d.y * d.x

    def dimensionize(self, i: int) -> Dim3:
        """Reference: partition.hpp:104-115."""
        d = self._dim
        if not 0 <= i < d.flatten():
            raise IndexError(f"linear index {i} outside partition {d}")
        x = i % d.x
        i //= d.x
        y = i % d.y
        i //= d.y
        return Dim3(x, y, i)


def _min_interface_split(sz: Dim3, dim: Dim3, radius: Radius, amt: int) -> tuple[Dim3, Dim3]:
    """One communication-minimizing cut (reference: partition.hpp:167-208).

    Chooses the axis whose interface area (orthogonal extent x sum of +/-
    face radii) is smallest; ties prefer x, then y.
    """
    x_iface = sz.y * sz.z * (radius.dir(1, 0, 0) + radius.dir(-1, 0, 0))
    y_iface = sz.x * sz.z * (radius.dir(0, 1, 0) + radius.dir(0, -1, 0))
    z_iface = sz.x * sz.y * (radius.dir(0, 0, 1) + radius.dir(0, 0, -1))
    if x_iface <= y_iface and x_iface <= z_iface:
        return Dim3(div_ceil(sz.x, amt), sz.y, sz.z), Dim3(dim.x * amt, dim.y, dim.z)
    elif y_iface <= z_iface:
        return Dim3(sz.x, div_ceil(sz.y, amt), sz.z), Dim3(dim.x, dim.y * amt, dim.z)
    else:
        return Dim3(sz.x, sz.y, div_ceil(sz.z, amt)), Dim3(dim.x, dim.y, dim.z * amt)


class NodePartition:
    """Two-level communication-minimizing partition.

    Reference: partition.hpp:120-256. First splits among ``nodes`` (hosts /
    TPU slices), then among ``gpus`` (chips per host), each cut taken on the
    axis with the smallest radius-weighted interface. On TPU the outer level
    maps to the network between slices and the inner level to ICI within a
    slice.
    """

    def __init__(self, size, radius: Radius, nodes: int, gpus: int):
        size = Dim3.of(size)
        sys_dim = Dim3(1, 1, 1)
        node_dim = Dim3(1, 1, 1)
        sz = size
        for amt in prime_factors(max(nodes, 1)):
            if amt < 2:
                continue
            sz, sys_dim = _min_interface_split(sz, sys_dim, radius, amt)
        for amt in prime_factors(max(gpus, 1)):
            if amt < 2:
                continue
            sz, node_dim = _min_interface_split(sz, node_dim, radius, amt)
        self._sys_dim = sys_dim
        self._node_dim = node_dim
        self._size = sz
        self._rem = size % (sys_dim * node_dim)

    def sys_dim(self) -> Dim3:
        return self._sys_dim

    def node_dim(self) -> Dim3:
        return self._node_dim

    def dim(self) -> Dim3:
        return self._sys_dim * self._node_dim

    def base_size(self) -> Dim3:
        return self._size

    def subdomain_size(self, idx) -> Dim3:
        """Reference: partition.hpp:221-236 (same remainder rule as
        RankPartition)."""
        idx = Dim3.of(idx)
        r = self._rem
        s = self._size
        return Dim3(
            s.x - (1 if (r.x != 0 and idx.x >= r.x) else 0),
            s.y - (1 if (r.y != 0 and idx.y >= r.y) else 0),
            s.z - (1 if (r.z != 0 and idx.z >= r.z) else 0),
        )

    def subdomain_origin(self, idx) -> Dim3:
        """Reference: partition.hpp:238-252."""
        idx = Dim3.of(idx)
        r = self._rem
        ret = self._size * idx
        return Dim3(
            ret.x - ((idx.x - r.x) if (r.x != 0 and idx.x >= r.x) else 0),
            ret.y - ((idx.y - r.y) if (r.y != 0 and idx.y >= r.y) else 0),
            ret.z - ((idx.z - r.z) if (r.z != 0 and idx.z >= r.z) else 0),
        )

    def is_uniform(self) -> bool:
        return self._rem == Dim3(0, 0, 0)

    @staticmethod
    def _dimensionize(i: int, dim: Dim3) -> Dim3:
        assert 0 <= i < dim.flatten()
        x = i % dim.x
        i //= dim.x
        y = i % dim.y
        i //= dim.y
        return Dim3(x, y, i)

    def sys_idx(self, i: int) -> Dim3:
        return self._dimensionize(i, self._sys_dim)

    def node_idx(self, i: int) -> Dim3:
        return self._dimensionize(i, self._node_dim)
