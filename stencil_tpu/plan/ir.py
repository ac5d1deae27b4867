"""ExchangePlan IR — the declarative form of a halo exchange.

Historically ``parallel/exchange.py`` branched three ways on ``Method``
and recomputed its geometry (axis tables, permute pairs, slab extents)
inline in each lowering body. This module lifts that geometry into a
small declarative plan — phases, directions, pack-group policy, carrier
dtypes, permute pairs — that AXIS_COMPOSED, DIRECT26 *and* AUTO_SPMD all
lower from (the reference analogue: the 26-direction transport plan
``realize`` builds before any sender exists, src/stencil.cu:327-464).

Why an IR at all: the autotuner (plan/cost.py, plan/autotune.py)
searches (partition shape x method x quantity batching x temporal k).
With the plan as data, a candidate is *described and
costed without compiling it* — collective counts and on-wire bytes fall
out of the phase list — and the lowering stays a single code path per
phase kind.

The IR is pure geometry: building a plan touches no jax and no devices,
so the cost model can enumerate hundreds of candidates cheaply. The
lowering in ``HaloExchange`` is required to compile bit-identically to
the historical method branches — pinned by the census pins and parity
fixtures in tests/test_plan_ir.py and tests/test_exchange*.py.
"""

from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

from ..geometry import DIRECTIONS_26, Dim3, Radius

# Method value strings (mirrors parallel.exchange.Method — the IR must not
# import the lowering module, which imports this one).
AXIS_COMPOSED = "axis-composed"
DIRECT26 = "direct26"
AUTO_SPMD = "auto-spmd"
METHODS = (AXIS_COMPOSED, DIRECT26, AUTO_SPMD)

# Wire-compression itemsizes the IR can model without importing jax/numpy
# (bfloat16 / float8_* are not numpy dtype names; everything else resolves
# lazily). The fp8 tier (float8_e4m3fn) quarters fp32 on-wire bytes the
# way bfloat16 halves them — same narrowing policy, one more row.
_WIRE_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4, "float64": 8,
                  "float8_e4m3fn": 1, "float8_e5m2": 1}


def wire_itemsize(wire_dtype: Optional[str]) -> Optional[int]:
    """Bytes per cell a wire-compressed carrier pays (None = native)."""
    if wire_dtype is None:
        return None
    if wire_dtype in _WIRE_ITEMSIZE:
        return _WIRE_ITEMSIZE[wire_dtype]
    import numpy as np

    return np.dtype(wire_dtype).itemsize

# (axis name, stacked-array data dim, block dim) in exchange-phase order —
# the one authority for phase order; exchange.py consumes it via the plan.
AXIS_ORDER = (("x", 5, 2), ("y", 4, 1), ("z", 3, 0))


@dataclass(frozen=True)
class SideIR:
    """One direction of an axis phase of a plan with a radius a quantity
    (``build_plan(quantity_radius=...)``): the halo on ONE side of the axis
    and the quantities whose radius wants it filled."""

    # the quantities (state keys) carried, in the order they were declared
    keys: Tuple
    # ``(data dim, start, width)`` of each EARLIER axis whose halo the slab
    # leaves out (as ``AxisPhaseIR.trim``): no carried quantity has an edge
    # or corner direction on this side that reaches into it. The lane axis
    # rides whole, so only a z slab's rows are ever cut.
    trim: Tuple[Tuple[int, int, int], ...]
    cells: int              # cells of ONE quantity's slabs, every block


@dataclass(frozen=True)
class AxisPhaseIR:
    """One composed axis phase (or one AUTO_SPMD roll phase).

    ``sizes`` is the full per-axis block-size table (length ``ring *
    resident``); ``ring`` is the number of permute participants along the
    mesh axis; ``resident`` the oversubscription factor (blocks stacked
    per device). ``fwd``/``bwd`` are the literal ``lax.ppermute`` pair
    lists toward +axis/-axis (empty when the phase is local-only or the
    schedule is partitioner-synthesized).
    """

    axis: str               # 'x' | 'y' | 'z' (mesh axis name)
    adim: int               # stacked-array data dim
    bdim: int               # stacked-array block dim
    ring: int               # permute participants along this axis
    resident: int           # blocks resident per device along this axis
    rm: int                 # low-side radius (data received from -axis)
    rp: int                 # high-side radius
    offset: int             # allocation-local compute origin on this axis
    sizes: Tuple[int, ...]  # per-block logical sizes (full table)
    fwd: Tuple[Tuple[int, int], ...]
    bwd: Tuple[Tuple[int, int], ...]
    wire_cells: int         # cells permuted per exchange per quantity (all devices)
    local_cells: int        # cells moved locally (self-wrap / resident shifts)
    # False on a FIXED axis (the domain's boundary halo is a ghost the
    # exchange never writes): ``fwd``/``bwd`` hold no wrap pair, so the
    # first block receives nothing from below and the last nothing from
    # above. A fixed axis of one block has no phase at all.
    periodic: bool = True
    # a star stencil's faces-only extent: ``(data dim, start, width)`` for
    # each orthogonal leading axis whose slab is cut to the compute region
    # (z for a y slab, y for a z slab; the lane axis always rides whole).
    # Empty: the slab spans the full padded extent and composes edges and
    # corners across phases.
    trim: Tuple[Tuple[int, int, int], ...] = ()
    # ``(low, high)`` under a radius a quantity: what each direction
    # carries. ``None``: both carry every exchanged quantity, and
    # ``wire_cells`` / ``local_cells`` count one quantity's two slabs.
    sides: Optional[Tuple[SideIR, SideIR]] = None

    @property
    def blocks(self) -> int:
        return self.ring * self.resident

    @property
    def uniform(self) -> bool:
        return len(set(self.sizes)) == 1

    @property
    def active(self) -> bool:
        return self.rm > 0 or self.rp > 0

    def carried(self):
        """``[(SideIR, width)]`` of the directions that move anything under
        a radius a quantity, low first."""
        return [(side, r) for side, r in zip(self.sides, (self.rm, self.rp))
                if r > 0 and side.keys]

    @property
    def merged(self) -> bool:
        """``fwd`` and ``bwd`` together are one permutation of the whole
        ring and both slabs are one shape: every block has ONE neighbour on
        this axis (a fixed axis of two blocks), one slab to send and one to
        receive, so the lowering sends one carrier in one ``ppermute`` over
        ``fwd + bwd``. On a periodic ring of two a block would be a source
        twice; on a longer fixed axis the inner blocks would."""
        pairs = self.fwd + self.bwd
        ring = list(range(self.ring))
        return (self.rm == self.rp > 0
                and sorted(s for s, _ in pairs) == ring
                and sorted(d for _, d in pairs) == ring)

    def collectives(self) -> int:
        """ppermutes one lowering of this phase emits (per carrier)."""
        if self.ring <= 1 or not self.active:
            return 0
        if self.sides is not None:
            return len(self.carried())
        if self.merged:
            return 1
        return (1 if self.rm > 0 else 0) + (1 if self.rp > 0 else 0)


@dataclass(frozen=True)
class DirectPhaseIR:
    """One DIRECT26 direction message.

    ``src``/``dst`` are static allocation-local (z, y, x) starts on a
    uniform partition; on uneven partitions they are traced per-block
    size-table lookups at lowering time, and ``shape`` is the base-padded
    static carrier extent every permute participant shares. ``pairs`` is
    the flattened 26-neighbor permutation when the mesh matches the
    partition (no oversubscription); with residents the lowering composes
    per-axis rolls instead (see HaloExchange._roll_blocks).
    """

    direction: Tuple[int, int, int]       # (dx, dy, dz)
    shape: Tuple[int, int, int]           # carrier extent (z, y, x)
    src: Optional[Tuple[int, int, int]]   # uniform-only static starts (z, y, x)
    dst: Optional[Tuple[int, int, int]]
    pairs: Tuple[Tuple[int, int], ...]    # flattened permute pairs (may be ())
    collective_count: int                 # permutes per carrier for this message
    wire_cells: int
    local_cells: int

    def collectives(self) -> int:
        return self.collective_count


@dataclass(frozen=True)
class ExchangePlan:
    """The full declarative exchange program for one (spec, mesh, method).

    ``pack_groups`` is the carrier policy: ``"dtype"`` packs every
    same-dtype quantity's slab into one carrier per collective (PR 5's
    batched bodies — the collective count is Q-independent),
    ``"quantity"`` is the historical one-collective-per-quantity program.
    AUTO_SPMD plans are ``synthesized``: the phase list describes the
    slab program handed to the SPMD partitioner, which owns the actual
    collective schedule (and emits per-quantity permutes today — the
    round-7 census).
    """

    method: str
    pack_groups: str                      # 'dtype' | 'quantity'
    partition: Tuple[int, int, int]       # blocks (x, y, z)
    mesh_dim: Tuple[int, int, int]        # devices (x, y, z)
    resident: Tuple[int, int, int]
    axis_phases: Tuple[AxisPhaseIR, ...]  # always built (composed geometry)
    direct_phases: Tuple[DirectPhaseIR, ...] = ()
    synthesized: bool = False
    # bf16-on-the-wire halo compression: wire-crossing carriers narrow to
    # this dtype before the send and widen on unpack (None = native).
    # Applies to the packed-carrier methods (composed/direct26);
    # local copies and self-wrap fills always stay native/lossless.
    wire_dtype: Optional[str] = None
    # per axis (x, y, z): False = a fixed boundary the exchange leaves
    # alone; and whether slabs carry faces only (a star stencil)
    periodic: Tuple[bool, bool, bool] = (True, True, True)
    faces_only: bool = False
    # a radius a quantity: ``((key, radius_dirs(radius)), ...)`` of the
    # quantities that gave one; the axis phases then say what each
    # direction carries (``AxisPhaseIR.sides``). ``None``: the spec's
    # radius for every quantity, the plan of every domain that passes none.
    quantity_radius: Optional[Tuple] = None

    @property
    def batch_quantities(self) -> bool:
        return self.pack_groups == "dtype"

    def _carried_bytes(self, itemsizes, wire: bool) -> int:
        """Bytes of a plan with a radius a quantity: a direction's cells
        times the itemsizes of what it carries. ``itemsizes`` maps the
        state's keys to theirs."""
        if not hasattr(itemsizes, "keys"):
            raise ValueError(
                "a plan with a radius a quantity counts bytes by key: pass a "
                "mapping of each state key to its itemsize")
        return sum(side.cells * sum(itemsizes[k] for k in side.keys)
                   for ph in self.axis_phases if (ph.ring > 1) == wire
                   for side, _r in ph.carried())

    @property
    def phases(self) -> Tuple:
        if self.method == DIRECT26:
            return self.direct_phases
        return self.axis_phases

    def collectives_per_exchange(self, quantities: int = 1,
                                 dtype_groups: int = 1) -> int:
        """Predicted collective-permute count of one compiled exchange —
        the number the census pins (6 composed / <=26 direct26 on a
        one-block-per-device mesh; Q-independent when pack_groups='dtype').
        AUTO_SPMD is predicted from the round-7 finding: the partitioner
        reinvents the composed schedule, per quantity."""
        carriers = dtype_groups if self.batch_quantities else quantities
        if self.synthesized:
            carriers = quantities  # the partitioner packs nothing today
        return sum(p.collectives() for p in self.phases) * carriers

    def wire_bytes(self, itemsizes: Sequence[int],
                   floating: Optional[Sequence[bool]] = None) -> int:
        """Estimated bytes on the interconnect per exchange (all
        quantities). Exact on one-block-per-device meshes; under
        oversubscription DIRECT26 carriers are counted whole although
        resident-internal shifts stay local (a deliberate overestimate —
        the census remains the compile-time truth). With ``wire_dtype``
        set, wire-crossing cells pay the narrowed itemsize (the bf16
        compression halves fp32 on-wire bytes; local bytes stay native).
        ``floating`` flags which quantities can narrow at all — the
        lowering (halo_fill.wire_narrow_dtype) never compresses integer
        carriers, so their wire bytes must stay native; omitted, every
        quantity is assumed floating (this framework's default)."""
        if self.quantity_radius is not None:
            return self._carried_bytes(itemsizes, wire=True)
        w = wire_itemsize(self.wire_dtype) if not self.synthesized else None
        if w is None:
            per_cell = sum(itemsizes)
        else:
            fl = ([True] * len(itemsizes) if floating is None
                  else list(floating))
            per_cell = sum(min(i, w) if f else i
                           for i, f in zip(itemsizes, fl))
        return sum(p.wire_cells for p in self.phases) * per_cell

    def local_bytes(self, itemsizes: Sequence[int]) -> int:
        """Estimated bytes moved without touching the interconnect
        (self-wrap fills, resident-neighbor shifts)."""
        if self.quantity_radius is not None:
            return self._carried_bytes(itemsizes, wire=False)
        per_cell = sum(itemsizes)
        return sum(p.local_cells for p in self.phases) * per_cell

    def describe(self) -> str:
        """Human-readable plan dump (plan_tool explain)."""
        lines = [
            f"method={self.method} pack_groups={self.pack_groups} "
            f"partition={self.partition} mesh={self.mesh_dim} "
            f"resident={self.resident}"
            + (" (schedule synthesized by the SPMD partitioner)"
               if self.synthesized else "")
            + (f" wire_dtype={self.wire_dtype}" if self.wire_dtype else "")
            + (f" periodic={self.periodic}" if not all(self.periodic) else "")
            + (" faces-only" if self.faces_only else ""),
        ]
        for p in self.phases:
            if isinstance(p, AxisPhaseIR):
                lines.append(
                    f"  axis {p.axis}: ring={p.ring} resident={p.resident} "
                    f"rm={p.rm} rp={p.rp} permutes={p.collectives()} "
                    f"wire_cells={p.wire_cells} local_cells={p.local_cells}"
                )
                for sign, side in zip("-+", (p.active and p.sides) or ()):
                    lines.append(
                        f"    {sign}{p.axis} halo: {len(side.keys)} "
                        f"quantities {list(side.keys)} cells={side.cells}"
                        + (" rows cut to the compute region"
                           if side.trim else ""))
            else:
                lines.append(
                    f"  dir {p.direction}: shape(zyx)={p.shape} "
                    f"permutes={p.collectives()} wire_cells={p.wire_cells}"
                )
        lines.append(
            f"  total permutes/exchange (1 group): "
            f"{self.collectives_per_exchange()}"
        )
        if self.wire_dtype and not self.synthesized:
            import dataclasses

            native = dataclasses.replace(self, wire_dtype=None)
            lines.append(
                f"  wire bytes (1 fp32 quantity): {self.wire_bytes([4])} "
                f"({self.wire_dtype} on the wire; {native.wire_bytes([4])} "
                "native)"
            )
        return "\n".join(lines)


# -- plan construction --------------------------------------------------------


def spec_axis(spec, name: str):
    """(per-index sizes, low radius, high radius, compute offset) along
    one axis — THE axis-geometry accessor: the plan builder below and the
    lowering in parallel/exchange.py both import this one function, so
    predicted and lowered geometry cannot desynchronize. The offset can
    exceed the low radius in aligned layouts (the y compute origin is
    rounded to the 8-row tile); the halo always sits immediately adjacent
    to the compute region, at [offset - rm, offset)."""
    off = spec.compute_offset()
    if name == "x":
        return spec.sizes_x, spec.radius.x(-1), spec.radius.x(1), off.x
    if name == "y":
        return spec.sizes_y, spec.radius.y(-1), spec.radius.y(1), off.y
    return spec.sizes_z, spec.radius.z(-1), spec.radius.z(1), off.z


def _ring_pairs(n: int, periodic: bool = True
                ) -> Tuple[Tuple[Tuple[int, int], ...],
                           Tuple[Tuple[int, int], ...]]:
    """Permute pairs toward +axis / -axis; a fixed axis leaves the wrap
    pair out (``lax.ppermute`` then delivers nothing across the boundary)."""
    fwd = tuple((i, (i + 1) % n) for i in range(n))
    bwd = tuple((i, (i - 1) % n) for i in range(n))
    if not periodic:
        fwd, bwd = fwd[:-1], bwd[1:]
    return fwd, bwd


def _perm26(dim: Dim3, d: Dim3) -> Tuple[Tuple[int, int], ...]:
    """Flattened (z, y, x)-major permutation sending toward ``d`` (one
    block per device — mesh dims == partition dims)."""
    pairs = []
    for iz in range(dim.z):
        for iy in range(dim.y):
            for ix in range(dim.x):
                src = (iz * dim.y + iy) * dim.x + ix
                jz = (iz + d.z) % dim.z
                jy = (iy + d.y) % dim.y
                jx = (ix + d.x) % dim.x
                pairs.append((src, (jz * dim.y + jy) * dim.x + jx))
    return tuple(pairs)


def _phase_sides(spec, name: str, radii, orth_cells: int
                 ) -> Tuple[SideIR, SideIR]:
    """(low, high) of one axis phase under a radius a quantity (``radii``:
    ``{key: Radius}`` of EVERY exchanged quantity). A quantity is carried
    toward the side where any of its directions points (a face, or an edge
    or corner that the phases compose through this one); a slab keeps an
    earlier axis's halo rows where a carried quantity's edge or corner
    gate on this side reaches into them, and is cut to the compute region
    there otherwise (the lane axis rides whole, as in a star's slabs)."""
    comp = {"x": 0, "y": 1, "z": 2}
    a = comp[name]
    off, base, p = spec.compute_offset(), spec.base, spec.padded()
    # the earlier, non-lane axes of this phase: (component, data dim,
    # compute start, compute width, padded width)
    earlier = [(1, 4, off.y, base.y, p.y)] if name == "z" else []
    _sizes, rm, rp, _off = spec_axis(spec, name)
    sides = []
    for sign, width in ((-1, rm), (1, rp)):
        def points(r, also=None):
            return any(v for d, v in r._r.items() if d[a] == sign
                       and d != (0, 0, 0)
                       and (also is None or d[also] != 0))

        keys = tuple(k for k, r in radii.items() if points(r))
        trim, cells = [], width * orth_cells
        for e, dim, lo, n, padded in earlier:
            if keys and not any(points(radii[k], e) for k in keys):
                trim.append((dim, lo, n))
                cells = cells // padded * n
        sides.append(SideIR(keys=keys, trim=tuple(trim), cells=cells))
    return tuple(sides)


def _axis_phases(spec, mesh_dim: Dim3, resident: Dim3,
                 synthesized: bool, periodic=(True, True, True),
                 faces_only: bool = False,
                 radii=None) -> Tuple[AxisPhaseIR, ...]:
    p = spec.padded()
    orth = {  # padded cells orthogonal to each axis, per block
        "x": p.y * p.z,
        "y": p.x * p.z,
        "z": p.x * p.y,
    }
    trims = {"x": (), "y": (), "z": ()}
    if faces_only:
        # a star reads no edge and no corner: a slab is cut to the compute
        # region on its orthogonal leading axes (the base block's, so the
        # extent is static on an uneven split too). The lane axis rides
        # whole: rows are the unit a slab copy moves, and cutting them
        # would cost a relayout to save the ring's columns.
        off, b = spec.compute_offset(), spec.base
        orth = {"x": b.y * b.z, "y": p.x * b.z, "z": p.x * b.y}
        trims = {"x": ((3, off.z, b.z), (4, off.y, b.y)),
                 "y": ((3, off.z, b.z),),
                 "z": ((4, off.y, b.y),)}
    wraps = {"x": bool(periodic[0]), "y": bool(periodic[1]),
             "z": bool(periodic[2])}
    res = {"x": resident.x, "y": resident.y, "z": resident.z}
    md = {"x": mesh_dim.x, "y": mesh_dim.y, "z": mesh_dim.z}
    nblocks = spec.num_blocks()
    phases = []
    for name, adim, bdim in AXIS_ORDER:
        sizes, rm, rp, off = spec_axis(spec, name)
        c = 1 if synthesized else res[name]
        ring = len(sizes) if synthesized else md[name]
        if not wraps[name] and ring * c == 1:
            continue                # a fixed axis of one block: no phase
        if ring > 1 and not synthesized:
            fwd, bwd = _ring_pairs(ring, wraps[name])
        else:
            fwd, bwd = (), ()
        slab_cells = (rm + rp) * orth[name] * nblocks  # every block's slabs
        if not wraps[name]:
            # the two boundary slabs of every line of blocks are not sent
            slab_cells = slab_cells * (ring - 1) // ring
        if ring > 1:
            if c > 1:
                # only the two boundary slabs of each device's resident
                # stack ride the permute; the rest shift locally
                wire = (rm + rp) * orth[name] * (nblocks // c)
            else:
                wire = slab_cells
        else:
            wire = 0
        phases.append(AxisPhaseIR(
            axis=name, adim=adim, bdim=bdim, ring=ring, resident=c,
            rm=rm, rp=rp, offset=off, sizes=tuple(sizes),
            fwd=fwd if not synthesized else (),
            bwd=bwd if not synthesized else (),
            wire_cells=wire, local_cells=slab_cells - wire,
            periodic=wraps[name], trim=trims[name],
            sides=None if radii is None else _phase_sides(
                spec, name, radii, orth[name] * nblocks),
        ))
    return tuple(phases)


def _direct_phases(spec, mesh_dim: Dim3,
                   resident: Dim3) -> Tuple[DirectPhaseIR, ...]:
    r = spec.radius
    off = spec.compute_offset()
    base = spec.base
    uniform = spec.is_uniform()
    oversub = resident != Dim3(1, 1, 1)
    nblocks = spec.num_blocks()
    dirs = [d for d in DIRECTIONS_26 if r.dir(-d) != 0]
    if not uniform:
        # face -> edge -> corner apply order (stable within each rank)
        dirs.sort(key=lambda d: abs(d.x) + abs(d.y) + abs(d.z))
    phases = []
    for d in dirs:
        shape, src, dst = [], [], []
        for dc, s, rmin, rplus, o in zip(
            (d.z, d.y, d.x),
            (base.z, base.y, base.x),
            (r.z(-1), r.y(-1), r.x(-1)),
            (r.z(1), r.y(1), r.x(1)),
            (off.z, off.y, off.x),
        ):
            if dc == 1:
                shape.append(rmin)
                src.append(o + s - rmin)
                dst.append(o - rmin)
            elif dc == -1:
                shape.append(rplus)
                src.append(o)
                dst.append(o + s)
            else:
                shape.append(s)
                src.append(o)
                dst.append(o)
        if any(e == 0 for e in shape):
            continue
        if oversub:
            # per-axis composition: one permute per nonzero component
            # whose mesh axis actually has >1 device
            md = {"z": mesh_dim.z, "y": mesh_dim.y, "x": mesh_dim.x}
            comp = {"z": d.z, "y": d.y, "x": d.x}
            count = sum(1 for a in ("z", "y", "x")
                        if comp[a] != 0 and md[a] > 1)
            pairs: Tuple[Tuple[int, int], ...] = ()
        else:
            count = 1
            pairs = _perm26(spec.dim, d)
        cells = shape[0] * shape[1] * shape[2] * nblocks
        phases.append(DirectPhaseIR(
            direction=(d.x, d.y, d.z), shape=tuple(shape),
            src=tuple(src) if uniform else None,
            dst=tuple(dst) if uniform else None,
            pairs=pairs, collective_count=count,
            wire_cells=cells if count else 0,
            local_cells=0 if count else cells,
        ))
    return tuple(phases)


def build_plan(spec, mesh_dim, method, batch_quantities: bool = True,
               resident: Optional[Dim3] = None,
               wire_dtype: Optional[str] = None,
               periodic=(True, True, True),
               faces_only: bool = False,
               quantity_radius=None) -> ExchangePlan:
    """Build the ExchangePlan of one (GridSpec, mesh shape, method).

    Pure geometry — no jax, no devices. ``method`` may be the enum from
    ``parallel.exchange`` or its value string. ``mesh_dim`` is the device
    grid (x, y, z); ``resident`` (blocks stacked per device) defaults to
    ``spec.dim / mesh_dim`` and must divide it exactly. ``wire_dtype``
    narrows wire-crossing carriers in the byte model (the bf16/fp8
    on-the-wire halo compression knob). ``periodic`` (x, y, z)
    marks fixed axes and ``faces_only`` a star stencil's slab extents
    (see :class:`AxisPhaseIR`); both are lowerings of AXIS_COMPOSED with
    one block a device, and anything else refuses them.
    ``quantity_radius`` (``{key: Radius}``, one entry for EVERY quantity
    the exchange will be handed, in their order) is a radius a quantity:
    which of the 26 directions' halos are filled for it, each either 0 or
    what ``spec.radius`` allocates. The axis phases then carry, a
    direction, the quantities that want that side
    (:class:`SideIR`). AXIS_COMPOSED, one block a device, periodic, no
    star and no wire dtype; ``None`` or empty is the plan there always
    was.
    """
    mval = getattr(method, "value", method)
    if mval not in METHODS:
        raise ValueError(f"unknown exchange method {method!r}")
    md = Dim3.of(mesh_dim)
    if spec.dim.x % md.x or spec.dim.y % md.y or spec.dim.z % md.z:
        raise ValueError(
            f"mesh {md} does not divide partition {spec.dim}"
        )
    if resident is None:
        resident = Dim3(spec.dim.x // md.x, spec.dim.y // md.y,
                        spec.dim.z // md.z)
    synthesized = mval == AUTO_SPMD
    periodic = tuple(bool(v) for v in periodic)
    if len(periodic) != 3:
        raise ValueError(f"periodic is (x, y, z), got {periodic!r}")
    if not all(periodic) or faces_only:
        what = ("a fixed (non-periodic) axis" if not all(periodic)
                else "a faces-only exchange")
        if mval != AXIS_COMPOSED:
            raise ValueError(
                f"{what} is lowered by {AXIS_COMPOSED!r} only; got method "
                f"{mval!r}")
        if resident != Dim3(1, 1, 1):
            raise ValueError(
                f"{what} needs one block a device (got resident "
                f"{resident})")
    if faces_only and any(
            v for (dx, dy, dz, v) in radius_dirs(spec.radius)
            if abs(dx) + abs(dy) + abs(dz) > 1):
        raise ValueError(
            "a faces-only exchange is a star stencil's: the radius has "
            "edge or corner directions set (Radius.face_edge_corner(r, 0, "
            "0) is a star)")
    radii = dict(quantity_radius) if quantity_radius else None
    if radii is not None:
        if (mval != AXIS_COMPOSED or resident != Dim3(1, 1, 1)
                or not all(periodic) or faces_only or wire_dtype is not None):
            raise ValueError(
                f"a radius a quantity is lowered by {AXIS_COMPOSED!r} with "
                "one block a device, periodic on every axis, without "
                f"faces_only or a wire dtype (got method {mval!r}, resident "
                f"{resident}, periodic {periodic}, faces_only {faces_only}, "
                f"wire_dtype {wire_dtype})")
        for key, r in radii.items():
            for d, v in r._r.items():
                have = spec.radius.dir(d)
                if d != (0, 0, 0) and v and v != have and (
                        not have or sum(map(abs, d)) == 1):
                    raise ValueError(
                        f"quantity {key!r} asks radius {v} toward {d}; the "
                        f"domain allocates {have} there: a quantity's radius "
                        "selects among the domain's halos (0, or the "
                        "domain's own)")
    axis_phases = _axis_phases(spec, md, resident, synthesized, periodic,
                               faces_only, radii)
    direct_phases = (
        _direct_phases(spec, md, resident) if mval == DIRECT26 else ()
    )
    return ExchangePlan(
        method=mval,
        pack_groups="dtype" if batch_quantities else "quantity",
        partition=(spec.dim.x, spec.dim.y, spec.dim.z),
        mesh_dim=(md.x, md.y, md.z),
        resident=(resident.x, resident.y, resident.z),
        axis_phases=axis_phases,
        direct_phases=direct_phases,
        synthesized=synthesized,
        wire_dtype=wire_dtype,
        periodic=periodic,
        faces_only=bool(faces_only),
        quantity_radius=None if radii is None else tuple(
            (k, radius_dirs(r)) for k, r in radii.items()),
    )


# -- planner vocabulary: config keys and plan choices -------------------------


def radius_dirs(radius: Radius) -> Tuple[Tuple[int, int, int, int], ...]:
    """Canonical nonzero-direction serialization of a Radius — the same
    [[dx,dy,dz,r], ...] convention the ckpt manifests record."""
    return tuple(
        (d[0], d[1], d[2], r) for d, r in sorted(radius._r.items())
        if r and d != (0, 0, 0)  # the center cell never exchanges
    )


def radius_from_dirs(dirs) -> Radius:
    r = Radius.constant(0)
    for dx, dy, dz, v in dirs:
        r.set_dir((dx, dy, dz), v)
    return r


@dataclass(frozen=True)
class PlanConfig:
    """Canonical problem key: what a tuned plan is valid FOR.

    ``quantities`` is a dtype *multiset* — ``(("float32", 4),)`` — sorted
    by dtype name, so permuting a domain's quantity declaration order
    never changes the key (or, by construction, the cost ranking:
    tests/test_plan_cost.py pins the invariance).
    """

    grid: Tuple[int, int, int]                       # (x, y, z)
    radius: Tuple[Tuple[int, int, int, int], ...]    # radius_dirs()
    quantities: Tuple[Tuple[str, int], ...]          # sorted (dtype, count)
    ndev: int
    platform: str = "cpu"

    @classmethod
    def make(cls, size, radius: Radius, dtypes: Sequence[str], ndev: int,
             platform: str = "cpu") -> "PlanConfig":
        size = Dim3.of(size)
        counts: Dict[str, int] = {}
        for dt in dtypes:
            counts[str(dt)] = counts.get(str(dt), 0) + 1
        return cls(
            grid=(size.x, size.y, size.z),
            radius=radius_dirs(radius),
            quantities=tuple(sorted(counts.items())),
            ndev=int(ndev),
            platform=str(platform),
        )

    @property
    def num_quantities(self) -> int:
        return sum(n for _dt, n in self.quantities)

    @property
    def dtype_group_count(self) -> int:
        return max(1, len(self.quantities))

    def itemsizes(self) -> Tuple[int, ...]:
        import numpy as np

        out = []
        for dt, n in self.quantities:
            out.extend([np.dtype(dt).itemsize] * n)
        return tuple(out)

    def floating_flags(self) -> Tuple[bool, ...]:
        """Per-quantity floatness, aligned with :meth:`itemsizes` — the
        wire-compression eligibility mask for ``ExchangePlan.wire_bytes``
        (integer carriers never narrow)."""
        import numpy as np

        out = []
        for dt, n in self.quantities:
            out.extend([np.issubdtype(np.dtype(dt), np.floating)] * n)
        return tuple(out)

    def radius_obj(self) -> Radius:
        return radius_from_dirs(self.radius)

    def key(self) -> str:
        """Stable string key for the plan DB."""
        return json.dumps({
            "grid": list(self.grid),
            "radius": [list(t) for t in self.radius],
            "quantities": [list(t) for t in self.quantities],
            "ndev": self.ndev,
            "platform": self.platform,
        }, sort_keys=True, separators=(",", ":"))

    def to_json(self) -> dict:
        return json.loads(self.key())

    @classmethod
    def from_json(cls, obj: dict) -> "PlanConfig":
        return cls(
            grid=tuple(obj["grid"]),
            radius=tuple(tuple(t) for t in obj["radius"]),
            quantities=tuple((str(d), int(n)) for d, n in obj["quantities"]),
            ndev=int(obj["ndev"]),
            platform=str(obj.get("platform", "cpu")),
        )


def validate_placement(placement, ndev: int) -> Optional[str]:
    """The one placement-shape authority: ``None`` (identity) or a
    permutation of ``range(ndev)`` mapping mesh position i (row-major
    z, y, x over the mesh grid) to the index of the device that hosts it
    in the original device list — the reference's ``qap::solve``
    assignment vector. Returns an error string, or None when valid."""
    if placement is None:
        return None
    try:
        f = [int(v) for v in placement]
    except (TypeError, ValueError):
        return f"placement must be a sequence of ints, got {placement!r}"
    if len(f) != ndev:
        return (f"placement has {len(f)} entries for {ndev} mesh "
                "positions")
    if sorted(f) != list(range(ndev)):
        return f"placement {f} is not a permutation of range({ndev})"
    return None


# What a stored choice may still name and this program no longer runs:
# the kernel-initiated transport (PRs 10 to 45) and its two kernel variants.
RETIRED_METHODS = ("remote-dma",)
RETIRED_VARIANTS = ("fused", "persistent")


def retired_choice_key(obj) -> Optional[str]:
    """The retired key a stored choice USES, or None. Plan DBs and
    checkpoint manifests written by PRs 17 to 29 carry ``hierarchy``
    (the outer split of a two-level ICI+DCN exchange) and
    ``host_placement`` (its blocks-to-hosts assignment). Absent, null or
    an identity ``host_placement`` is the one-level plan it always was.
    Ones written by PRs 10 to 45 may name the kernel-initiated transport
    (``method`` ``remote-dma``) or one of its kernel variants
    (``kernel_variant`` ``fused`` / ``persistent``). Each names a plan
    this program cannot run, and replaying it as the composed plan would
    be a different plan in silence."""
    if not isinstance(obj, dict):
        return None
    if obj.get("hierarchy") is not None:
        return "hierarchy"
    hp = obj.get("host_placement")
    if hp is not None and list(hp) != list(range(len(hp))):
        return "host_placement"
    if obj.get("method") in RETIRED_METHODS:
        return "method"
    if obj.get("kernel_variant") in RETIRED_VARIANTS:
        return "kernel_variant"
    return None


@dataclass(frozen=True)
class PlanChoice:
    """One point in the search space — what the autotuner picks and the
    DB persists: partition shape x exchange method x quantity batching x
    temporal depth k x kernel variant x block placement.

    ``placement`` is the topology-aware block→device assignment
    (reference: ``NodeAware``/``qap::solve``): ``placement[i]`` is the
    index (into the original device list) of the device hosting mesh
    position i, row-major (z, y, x) over the mesh grid. ``None`` is the
    identity assignment — the historical block order = device order —
    and is what every pre-placement DB entry deserializes to (the
    schema-migration default: an absent field IS identity)."""

    partition: Tuple[int, int, int]   # blocks (x, y, z)
    method: str                       # METHODS value string
    batch_quantities: bool = True
    multistep_k: int = 1
    kernel_variant: Optional[str] = None
    placement: Optional[Tuple[int, ...]] = None

    def to_json(self) -> dict:
        return {
            "partition": list(self.partition),
            "method": self.method,
            "batch_quantities": self.batch_quantities,
            "multistep_k": self.multistep_k,
            "kernel_variant": self.kernel_variant,
            "placement": (None if self.placement is None
                          else list(self.placement)),
        }

    @classmethod
    def from_json(cls, obj: dict) -> "PlanChoice":
        placement = obj.get("placement")
        retired = retired_choice_key(obj)
        if retired is not None:
            raise ValueError(
                f"plan choice carries the retired key {retired!r} "
                f"({obj[retired]!r}): the exchange has one level and no "
                "kernel-initiated transport")
        return cls(
            partition=tuple(obj["partition"]),
            method=str(obj["method"]),
            batch_quantities=bool(obj.get("batch_quantities", True)),
            multistep_k=int(obj.get("multistep_k", 1)),
            kernel_variant=obj.get("kernel_variant"),
            placement=(None if placement is None
                       else tuple(int(v) for v in placement)),
        )

    @property
    def is_placed(self) -> bool:
        """True when the choice carries a non-identity block placement."""
        return (self.placement is not None
                and list(self.placement) != list(range(len(self.placement))))

    def fingerprint(self) -> str:
        """Short stable content hash of the choice (12 hex chars of the
        sha256 of its canonical JSON). The observatory's join key: a
        telemetry/ledger/bench record stamped with it is attributable to
        exactly this plan, where ``label()`` elides identity placements
        and default fields for readability."""
        blob = json.dumps(self.to_json(), sort_keys=True,
                          separators=(",", ":"))
        return hashlib.sha256(blob.encode()).hexdigest()[:12]

    def label(self) -> str:
        px, py, pz = self.partition
        s = f"{px}x{py}x{pz}/{self.method}"
        s += "/batched" if self.batch_quantities else "/per-quantity"
        if self.multistep_k > 1:
            s += f"/k={self.multistep_k}"
        if self.kernel_variant:
            s += f"/{self.kernel_variant}"
        if self.is_placed:
            s += "/p=" + "-".join(str(v) for v in self.placement)
        return s
