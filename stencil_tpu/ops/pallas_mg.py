"""Pallas TPU kernels for NPB MG: the box, the transfers between two
tight-x levels, and (last section) the V-cycle's whole coarse half as one
call that never leaves VMEM.

The two box operators, ``resid`` and ``psinv``, have one builder: ``out =
p +- Box(q)`` with ``Box`` a 27-point stencil whose weight depends only on
the neighbour's class (centre, faces, edges, corners).

    resid   r = v - A u      (q = u, p = v or r, sign -)
    psinv   u = u + S r      (q = r, p = u,      sign +)

One call is one operator on one block of the tight-x layout (no x halo:
``px == nx``, a multiple of 128, x wraps by a lane roll, so the x axis is
one periodic block). The block is streamed in z, ONE plane of ``q`` a grid
step through the pipeline: step s brings plane s (plane 0 the low halo
plane), keeps it and its y sums in a ring of three, and writes result plane
s - 1 from planes s - 2, s - 1, s. So ``q`` is read from HBM once, ``p``
once and the result written once: 12 bytes a cell.

The arithmetic is the source's (``mg.f``): with ``C`` a plane and ``Y`` its
two y neighbours summed (once a plane, kept in the ring),

    u1 = Y(z) + C(z-1) + C(z+1)        the 4 faces in the y-z plane
    u2 = Y(z-1) + Y(z+1)               the 4 diagonals in the y-z plane
    Box = w0 C + w1 (C[x-1] + C[x+1] + u1) + w2 (u2 + u1[x-1] + u1[x+1])
          + w3 (u2[x-1] + u2[x+1])

about 20 operations a cell; a class whose weight is 0 is left out. The
kernel writes owned rows of owned planes; the halo rows of a plane it
writes take ``p``'s back (or 0 where the result is a third array), and the
exchange that follows every operator fills them.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..domain.grid import GridSpec
from ..obs import scopes

_VREG_CELLS = 4096          # cells of a row chunk: 4 vregs an array
LANE = 128


def _chunk_rows(ny: int, px: int) -> int:
    """Rows the body computes at a time: the most of 32, 16, 8 that divide
    the block's rows and keep a chunk to ``_VREG_CELLS`` cells."""
    for rows in (32, 16, 8):
        if ny % rows == 0 and rows * px <= _VREG_CELLS:
            return rows
    return 8


def box_supported(spec: GridSpec, dtype) -> bool:
    """Whether the kernel takes this block layout: aligned fp32 blocks of a
    uniform partition on the tight-x layout (x whole, no x halo, rows a
    multiple of the 128-lane tile), halos of 1 or more in y and z, owned
    rows a multiple of the 8-row tile starting on one."""
    if not spec.aligned or dtype != jnp.float32 or not spec.is_uniform():
        return False
    r, o, p, b = spec.radius, spec.compute_offset(), spec.padded(), spec.base
    if r.x(-1) or r.x(1) or spec.dim.x != 1 or b.x % LANE or p.x != b.x:
        return False
    if min(r.y(-1), r.y(1), r.z(-1), r.z(1)) < 1:
        return False
    return b.y % 8 == 0 and o.y % 8 == 0 and o.y >= 1 and o.y + b.y < p.y


def x_neighbours(px: int, periodic_x: bool = True):
    """``both(t) = t[x - 1] + t[x + 1]`` of whole tight-x rows by two lane
    rolls. A roll WRAPS: on a periodic x that is the neighbourhood; on a
    fixed x (``periodic_x`` false: a Dirichlet ring that the tight-x
    layout does not store) the wrapped lane of each roll is dropped, so
    that a cell at the domain's x edge reads nothing from beyond it (two
    lane selects a roll pair)."""

    def both(t):
        left, right = pltpu.roll(t, 1, 1), pltpu.roll(t, px - 1, 1)
        if not periodic_x:
            # lane 0 of the first lane tile and the last lane of the last
            lane = jax.lax.broadcasted_iota(jnp.int32, (t.shape[0], LANE), 1)
            first = jnp.where(lane == 0, 0.0, left[:, :LANE])
            last = jnp.where(lane == LANE - 1, 0.0, right[:, px - LANE:])
            if px == LANE:
                left, right = first, last
            else:
                left = jnp.concatenate([first, left[:, LANE:]], axis=1)
                right = jnp.concatenate([right[:, :px - LANE], last], axis=1)
        return left + right

    return both


def keep_plane(q_ref, c_ring, y_ring, slot, yo: int, ny: int, rows: int):
    """The fresh plane of the box kernel's ring: kept, with its y sums,
    over the owned rows."""
    for row in range(0, ny, rows):
        at = pl.ds(yo + row, rows)
        c_ring[slot, pl.ds(row, rows), :] = q_ref[at, :]
        y_ring[slot, pl.ds(row, rows), :] = (
            q_ref[pl.ds(yo + row - 1, rows), :]
            + q_ref[pl.ds(yo + row + 1, rows), :])


def box_of_rows(c_ring, y_ring, lo, mid, hi, own, weights, both):
    """``Box`` over the rows ``own`` of the ring's middle plane, by the
    source's partial sums (module docstring); a class whose weight is 0 is
    left out."""
    w0, w1, w2, w3 = weights
    c = c_ring[mid, own, :]
    u1 = (y_ring[mid, own, :] + c_ring[lo, own, :]
          + c_ring[hi, own, :])
    box = w0 * c
    if w1:
        box = box + w1 * (both(c) + u1)
    if w2 or w3:
        u2 = y_ring[lo, own, :] + y_ring[hi, own, :]
        if w2:
            box = box + w2 * (u2 + both(u1))
        if w3:
            box = box + w3 * both(u2)
    return box


def box_stream(spec: GridSpec, vma=None):
    """What every streamed box call shares: ``(plane, fresh, written,
    shape, scratch, grid)``: a plane's block shape, the index maps of the
    plane step s brings and of the one it writes, the result's shape, the
    two rings, and the grid (the owned planes and the two round them)."""
    p, off, b = spec.padded(), spec.compute_offset(), spec.base

    def fresh(s):
        return (s + off.z - 1, 0, 0)

    def written(s):
        # the plane step s writes, held at the first owned plane until the
        # ring is full (no write-back happens while the index stands still)
        return (jnp.clip(s - 2, 0, b.z - 1) + off.z, 0, 0)

    shape = jax.ShapeDtypeStruct(
        (p.z, p.y, p.x), jnp.float32,
        vma=frozenset(vma) if vma is not None else None)
    scratch = [pltpu.VMEM((3, b.y, p.x), jnp.float32),
               pltpu.VMEM((3, b.y, p.x), jnp.float32)]
    return (None, p.y, p.x), fresh, written, shape, scratch, (b.z + 2,)


def make_pallas_mg_box(
    spec: GridSpec,
    name: str,
    weights: Sequence[float],
    sign: float,
    separate_dst: bool = False,
    interpret: bool = False,
    vma=None,
    periodic_x: bool = True,
):
    """Build ``fn(q, p) -> out`` (``out`` aliased to ``p``) or, with
    ``separate_dst``, ``fn(q, p, dst) -> out`` (aliased to ``dst``, which
    is not read) over padded ``(pz, py, px)`` fp32 blocks: ``out = p + sign
    * Box(q)`` on the owned cells. ``name`` is the operator's kernel name
    (``mg_resid`` or ``mg_psinv``; ``hpcg_resid``: HPCG's residual, the
    same box at the weights ``(26, -1, -1, -1)``).

    What it assumes of the x axis: the block is the whole axis on the
    tight-x layout (no x halo), and ``x -+ 1`` is a lane roll of the row.
    ``periodic_x`` (the domain's own ``periodic[0]``) says what the roll's
    wrap means: the periodic neighbour (NPB MG), or nothing at all (a fixed
    x: the wrapped lanes are dropped, :func:`x_neighbours`). In y and z the
    kernel reads the block's halo rows and planes as they stand: the
    periodic fill's on a periodic axis, the application's ghost ring (zero
    for a homogeneous Dirichlet face) on a fixed one."""
    if not box_supported(spec, jnp.float32):
        raise ValueError("pallas mg box unsupported on this spec")
    if len(weights) != 4:
        raise ValueError("a box takes four class weights")
    weights = tuple(float(w) for w in weights)
    p, off, b = spec.padded(), spec.compute_offset(), spec.base
    py, px = p.y, p.x
    yo, ny = off.y, b.y
    rows = _chunk_rows(ny, px)
    minus = sign < 0
    both = x_neighbours(px, periodic_x)

    def kernel(*refs):
        if separate_dst:
            q_ref, p_ref, _dst, out_ref, c_ring, y_ring = refs
        else:
            q_ref, p_ref, out_ref, c_ring, y_ring = refs
        s = pl.program_id(0)
        hi = s % 3
        keep_plane(q_ref, c_ring, y_ring, hi, yo, ny, rows)

        @pl.when(s >= 2)
        def _():
            lo, mid = (s + 1) % 3, (s + 2) % 3          # s - 2, s - 1
            for row in range(0, ny, rows):
                box = box_of_rows(c_ring, y_ring, lo, mid, hi,
                                  pl.ds(row, rows), weights, both)
                at = pl.ds(yo + row, rows)
                out_ref[at, :] = (p_ref[at, :] - box if minus
                                  else p_ref[at, :] + box)
            for start, stop in ((0, yo), (yo + ny, py)):
                edge = pl.ds(start, stop - start)
                out_ref[edge, :] = (jnp.zeros((stop - start, px), jnp.float32)
                                    if separate_dst else p_ref[edge, :])

    plane, fresh, written, shape, scratch, grid = box_stream(spec, vma)
    in_specs = [pl.BlockSpec(plane, fresh), pl.BlockSpec(plane, written)]
    if separate_dst:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    params = dict(
        grid=grid,
        out_shape=shape,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(plane, written),
        scratch_shapes=scratch,
        input_output_aliases={2 if separate_dst else 1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )
    if name == "mg_resid":
        return scopes.kernel_call("mg_resid", kernel, **params)
    if name == "mg_psinv":
        return scopes.kernel_call("mg_psinv", kernel, **params)
    if name == "hpcg_resid":
        return scopes.kernel_call("hpcg_resid", kernel, **params)
    raise ValueError(f"{name!r} is not a box operator")


# ------------------------------------------------------------ the transfers
#
# ``rprj3`` and ``interp`` between two tight-x levels. Both are the product
# of a 3-point rule along each axis. Along z it is plane arithmetic, along
# y loads and stores at a row stride of 2, and along x, where a stride of 2
# would cut every 128-lane tile in half, a matrix product on the MXU: the
# rule's weights (1/4, 1/2, 1) are exact in bfloat16 and a float32 value is
# the sum of three bfloat16 pieces, so three bfloat16 products accumulated
# in float32 lose nothing but the rounding of the few sums an operator has
# anyway. The x wrap is in the matrix.


def transfer_supported(fine: GridSpec, coarse: GridSpec, dtype) -> bool:
    """Both levels on the layout the box kernel takes, the coarse block
    half the fine one on every axis."""
    if not (box_supported(fine, dtype) and box_supported(coarse, dtype)):
        return False
    f, c = fine.base, coarse.base
    return (f.x, f.y, f.z) == (2 * c.x, 2 * c.y, 2 * c.z)


def _restrict_matrix(nx: int):
    """(nx, nx / 2): column c takes 1/4 of fine 2c and 2c + 2 (wrapped)
    and 1/2 of fine 2c + 1: the x rule (1/2, 1, 1/2) with the operator's
    overall 1/2 folded in."""
    import numpy as np

    m = np.zeros((nx, nx // 2), np.float32)
    c = np.arange(nx // 2)
    m[2 * c, c] += 0.25
    m[2 * c + 1, c] += 0.5
    m[(2 * c + 2) % nx, c] += 0.25
    return jnp.asarray(m, jnp.bfloat16)


def _prolong_matrix(mx: int):
    """(mx, 2 mx): fine column 2c + 1 takes coarse c, fine 2c half of
    coarse c - 1 (wrapped) and half of c."""
    import numpy as np

    m = np.zeros((mx, 2 * mx), np.float32)
    c = np.arange(mx)
    m[c, 2 * c + 1] += 1.0
    m[c, 2 * c] += 0.5
    m[(c - 1) % mx, 2 * c] += 0.5
    return jnp.asarray(m, jnp.bfloat16)


def _dot3(x, m):
    """``x @ m`` for float32 ``x`` and a bfloat16 matrix whose entries are
    exact: ``x`` in three bfloat16 pieces, three products, float32 sums."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    dot = lambda a: jnp.dot(a, m, preferred_element_type=jnp.float32)  # noqa: E731
    return (dot(lo) + dot(mid)) + dot(hi)


def _varying(x, vma):
    return jax.lax.pcast(x, tuple(vma), to="varying") if vma else x


def make_pallas_mg_rprj3(fine: GridSpec, coarse: GridSpec,
                         interpret: bool = False, vma=None):
    """Build ``fn(r_fine, r_coarse) -> r_coarse`` over padded fp32 blocks:
    the full weighting of the fine level's owned cells into the coarse
    level's (coarse cell c on fine cell 2c + 1), written over
    ``r_coarse``, which is not read. One coarse plane a grid step from the
    three fine planes it reads."""
    if not transfer_supported(fine, coarse, jnp.float32):
        raise ValueError("pallas mg rprj3 unsupported on these specs")
    pf, of = fine.padded(), fine.compute_offset()
    pc, oc, bc = coarse.padded(), coarse.compute_offset(), coarse.base
    mz, my = bc.z, bc.y
    matrix = _restrict_matrix(fine.base.x)

    tiles = pf.x // LANE

    def kernel(a_ref, b_ref, c_ref, m_ref, _old, out_ref, t_ref):
        acc = None
        for k in range(tiles):
            cols = pl.ds(k * LANE, LANE)
            # z: planes 2c, 2c + 1, 2c + 2 of the fine block
            t_ref[k] = (0.5 * (a_ref[:, cols] + c_ref[:, cols])
                        + b_ref[:, cols])
            # y: rows 2j, 2j + 1, 2j + 2, at a row stride of 2 (which
            # Mosaic takes on a buffer one lane tile wide)
            even, odd, nxt = (t_ref[k, pl.ds(of.y + d, my, stride=2), :]
                              for d in range(3))
            part = _dot3(0.5 * (even + nxt) + odd, m_ref[cols, :])
            acc = part if acc is None else acc + part
        out_ref[pl.ds(oc.y, my), :] = acc
        for start, stop in ((0, oc.y), (oc.y + my, pc.y)):
            out_ref[pl.ds(start, stop - start), :] = jnp.zeros(
                (stop - start, pc.x), jnp.float32)

    fine_plane = (None, pf.y, pf.x)
    call = scopes.kernel_call(
        "mg_rprj3", kernel,
        grid=(mz,),
        out_shape=jax.ShapeDtypeStruct(
            (pc.z, pc.y, pc.x), jnp.float32,
            vma=frozenset(vma) if vma is not None else None),
        in_specs=[
            pl.BlockSpec(fine_plane, lambda c: (of.z + 2 * c, 0, 0)),
            pl.BlockSpec(fine_plane, lambda c: (of.z + 2 * c + 1, 0, 0)),
            pl.BlockSpec(fine_plane, lambda c: (of.z + 2 * c + 2, 0, 0)),
            pl.BlockSpec(matrix.shape, lambda c: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, pc.y, pc.x),
                               lambda c: (oc.z + c, 0, 0)),
        scratch_shapes=[pltpu.VMEM((tiles, pf.y, LANE), jnp.float32)],
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )

    def fn(rf, rc):
        return call(rf, rf, rf, _varying(matrix, vma), rc)

    return fn


def make_pallas_mg_interp(coarse: GridSpec, fine: GridSpec, add: bool,
                          interpret: bool = False, vma=None):
    """Build ``fn(u_coarse, u_fine) -> u_fine`` over padded fp32 blocks:
    the trilinear prolongation of the coarse level's cells (its low halo
    included) onto the fine level's owned cells, ADDED to ``u_fine`` or
    (``add`` false) written over it unread. One fine plane a grid step;
    a coarse plane is widened along x once, when it first appears, and
    kept for the three fine planes that read it."""
    if not transfer_supported(fine, coarse, jnp.float32):
        raise ValueError("pallas mg interp unsupported on these specs")
    pf, of, bf = fine.padded(), fine.compute_offset(), fine.base
    pc, oc, bc = coarse.padded(), coarse.compute_offset(), coarse.base
    my = bc.y
    matrix = _prolong_matrix(bc.x)

    tiles = pf.x // LANE

    def kernel(halo_ref, hi_ref, m_ref, old_ref, out_ref, wide, tall):
        f = pl.program_id(0)
        # coarse plane f // 2 sits in slot (f // 2) % 2, plane (f - 1) // 2
        # in the other slot when f is even and in the same when it is odd
        slot = (f // 2) % 2

        @pl.when(f == 0)
        def _():
            wide[1] = _dot3(halo_ref[...], m_ref[...])      # plane -1

        @pl.when(f % 2 == 0)
        def _():
            wide[slot] = _dot3(hi_ref[...], m_ref[...])

        # z: plane 2c + 1 takes coarse c, plane 2c the mean of c - 1 and c
        other = jnp.where(f % 2 == 0, 1 - slot, slot)
        wide[2] = 0.5 * (wide[slot] + wide[other])
        rows = pl.ds(of.y, 2 * my)
        for k in range(tiles):
            cols = pl.ds(k * LANE, LANE)
            # y: row 2j + 1 takes coarse row j, row 2j the mean of j - 1
            # and j, stored at a row stride of 2 (which Mosaic takes on a
            # buffer one lane tile wide)
            odd = wide[2, pl.ds(oc.y, my), cols]
            even = 0.5 * (wide[2, pl.ds(oc.y - 1, my), cols] + odd)
            tall[k, pl.ds(of.y, my, stride=2), :] = even
            tall[k, pl.ds(of.y + 1, my, stride=2), :] = odd
            out_ref[rows, cols] = (old_ref[rows, cols] + tall[k, rows, :]
                                   if add else tall[k, rows, :])
        for start, stop in ((0, of.y), (of.y + bf.y, pf.y)):
            edge = pl.ds(start, stop - start)
            out_ref[edge, :] = (old_ref[edge, :] if add else jnp.zeros(
                (stop - start, pf.x), jnp.float32))

    coarse_plane = (None, pc.y, pc.x)
    fine_plane = (None, pf.y, pf.x)

    def written(f):
        return (of.z + f, 0, 0)

    call = scopes.kernel_call(
        "mg_interp", kernel,
        grid=(bf.z,),
        out_shape=jax.ShapeDtypeStruct(
            (pf.z, pf.y, pf.x), jnp.float32,
            vma=frozenset(vma) if vma is not None else None),
        in_specs=[
            pl.BlockSpec(coarse_plane, lambda f: (oc.z - 1, 0, 0)),
            pl.BlockSpec(coarse_plane, lambda f: (oc.z + f // 2, 0, 0)),
            pl.BlockSpec(matrix.shape, lambda f: (0, 0)),
            (pl.BlockSpec(fine_plane, written) if add
             else pl.BlockSpec(memory_space=pl.ANY)),
        ],
        out_specs=pl.BlockSpec(fine_plane, written),
        scratch_shapes=[pltpu.VMEM((3, pc.y, pf.x), jnp.float32),
                        pltpu.VMEM((tiles, pf.y, LANE), jnp.float32)],
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )

    def fn(uc, uf):
        return call(uc, uc, _varying(matrix, vma), uf)

    return fn


# ------------------------------------------------------------ the coarse half
#
# Below the coarsest tight-x level a block is at most 64 cells wide: one
# lane tile holds a row with its two inline halo cells, and u and r of every
# such level together are a few megabytes. One call keeps them all in VMEM
# for the whole coarse half of the V-cycle: the restriction from the tight-x
# level above streams in, the 21 operators of the levels below run where the
# arrays lie, the prolongation onto the level above streams out, and every
# level is written to its HBM slot once. The periodic wrap after an operator
# is part of the operator: x by the halo lanes (a roll with the wrapped lane
# selected; in a transfer two more columns of the matrix), y by two rows, z
# by two planes, in that order, so edges and corners come along as in
# ``comm3``.

_COARSE_CHUNKS = 4          # DMAs of the level above's planes, either way
_COARSE_VMEM_LIMIT = 64 * 1024 * 1024
_COARSE_ROWS = 32           # rows an operator computes at a time


class _Geom(NamedTuple):
    """A block's layout: padded planes and rows, offsets, owned cells."""

    pz: int
    py: int
    zo: int
    yo: int
    xo: int
    nz: int
    ny: int
    nx: int


def _geom(spec: GridSpec) -> _Geom:
    p, o, b = spec.padded(), spec.compute_offset(), spec.base
    return _Geom(p.z, p.y, o.z, o.y, o.x, b.z, b.y, b.x)


def _inline_supported(spec: GridSpec) -> bool:
    """One block, one lane tile a row, radius 1 with room for it on every
    side: the layout of a level the coarse call keeps in VMEM."""
    if not spec.aligned or not spec.is_uniform():
        return False
    if (spec.dim.x, spec.dim.y, spec.dim.z) != (1, 1, 1):
        return False
    g, r = _geom(spec), spec.radius
    if spec.padded().x != LANE or any(
            face(side) != 1 for face in (r.x, r.y, r.z) for side in (-1, 1)):
        return False
    return (g.xo >= 1 and g.xo + g.nx < LANE and g.yo >= 1
            and g.yo + g.ny < g.py and g.zo >= 1 and g.zo + g.nz < g.pz)


def coarse_vmem_bytes(above: GridSpec, specs: Sequence[GridSpec]) -> int:
    """VMEM the coarse call holds: u and r of every level of ``specs``, one
    block of the level above (its r on the way in, its u on the way out),
    the transfers' matrices and three widened planes."""
    a = _geom(above)
    cells = a.pz * a.py * LANE + 3 * _geom(specs[0]).py * LANE
    cells += sum(2 * g.pz * g.py * LANE for g in map(_geom, specs))
    return 4 * cells + 2 * 2 * len(specs) * LANE * LANE


def coarse_supported(above: GridSpec, specs: Sequence[GridSpec],
                     dtype) -> bool:
    """Whether one call can hold the levels ``specs`` (finest first) under
    the tight-x level ``above``: fp32, ONE block a level (a coarse block of
    a split partition needs the wire), ``above`` on the box kernel's layout
    and one lane tile wide, every level below half the one before on the
    inline layout, all of it inside half the VMEM limit the call asks for."""
    if not specs or not box_supported(above, dtype):
        return False
    if (above.dim.y, above.dim.z) != (1, 1) or above.base.x != LANE:
        return False
    if not all(_inline_supported(s) for s in specs):
        return False
    sizes = [(s.base.x, s.base.y, s.base.z) for s in (above, *specs)]
    if any(f != tuple(2 * m for m in c) for f, c in zip(sizes, sizes[1:])):
        return False
    return 2 * coarse_vmem_bytes(above, specs) <= _COARSE_VMEM_LIMIT


def _restrict_lanes(fine: GridSpec, coarse: GridSpec):
    """(128, 128) over lanes: the coarse lane of cell c takes 1/4 of fine
    cells 2c and 2c + 2 and 1/2 of 2c + 1 (the x rule with the operator's
    overall 1/2 folded in), and the coarse level's two halo lanes take what
    the cells they mirror take. A tight fine row wraps in the matrix; an
    inline one is read with its high halo lane."""
    import numpy as np

    f, c = _geom(fine), _geom(coarse)
    tight = fine.radius.x(1) == 0
    m = np.zeros((LANE, LANE), np.float32)
    for cell in range(-1, c.nx + 1):
        for d, w in enumerate((0.25, 0.5, 0.25)):
            src = 2 * (cell % c.nx) + d
            m[f.xo + (src % f.nx if tight else src), c.xo + cell] += w
    return m


def _prolong_lanes(coarse: GridSpec, fine: GridSpec):
    """(128, 128) over lanes: fine cell 2c + 1 takes coarse c, fine 2c half
    of coarse c - 1 (the low halo lane for c = 0) and half of c; an inline
    fine level's two halo lanes take what the cells they mirror take."""
    import numpy as np

    c, f = _geom(coarse), _geom(fine)
    tight = fine.radius.x(1) == 0
    m = np.zeros((LANE, LANE), np.float32)
    for cell in range(0, f.nx) if tight else range(-1, f.nx + 1):
        src, odd = divmod(cell % f.nx, 2)
        if odd:
            m[c.xo + src, f.xo + cell] += 1.0
        else:
            m[c.xo + src - 1, f.xo + cell] += 0.5
            m[c.xo + src, f.xo + cell] += 0.5
    return m


def make_pallas_mg_coarse(above: GridSpec, specs: Sequence[GridSpec],
                          resid_weights: Sequence[float],
                          psinv_weights: Sequence[float], add: bool,
                          interpret: bool = False, vma=None):
    """Build ``fn(r_above, u_above, us, rs) -> (u_above, us, rs)`` over
    padded fp32 blocks: the V-cycle from the tight-x level ``above`` down
    through the levels ``specs`` (finest first; ``us`` / ``rs`` their u and
    r, lists in that order) and back, in the source's order: ``rprj3`` of
    ``r_above`` and on down, ``psinv`` at the bottom, then ``interp``,
    ``resid``, ``psinv`` a level up to the finest of ``specs``, then
    ``interp`` onto ``u_above`` (``add``: added to it, as on the finest
    level of a hierarchy; else written over it unread). Every level of
    ``specs`` comes back with its halos valid, having been read from HBM
    never and written once; ``u_above`` comes back with its owned planes'
    owned rows written and their other rows as ``make_pallas_mg_interp``
    leaves them, for the level's own fill. ``specs`` ends at NPB's level 1,
    which gives the level tags inside the kernel."""
    if not coarse_supported(above, specs, jnp.float32):
        raise ValueError("pallas mg coarse unsupported on these specs")
    import numpy as np

    A, G = _geom(above), [_geom(s) for s in specs]
    L = len(G)
    rw, sw = ([float(w) for w in ws] for ws in (resid_weights, psinv_weights))
    if len(rw) != 4 or len(sw) != 4:
        raise ValueError("a box takes four class weights")
    chain = [above, *specs]
    # 2i: the restriction INTO level i of ``specs``; 2i + 1: the
    # prolongation FROM it
    matrices = jnp.asarray(np.stack(
        [m for fine, coarse in zip(chain, chain[1:])
         for m in (_restrict_lanes(fine, coarse),
                   _prolong_lanes(coarse, fine))]), jnp.bfloat16)
    chunks = _COARSE_CHUNKS if G[0].nz % _COARSE_CHUNKS == 0 else 1
    per = G[0].nz // chunks             # coarse planes a chunk
    sem_in, sem_out, sem_add, sem_lv = 0, chunks + 1, 2 * chunks + 1, \
        3 * chunks + 1

    def rows_of(n):
        return min(n, _COARSE_ROWS)

    def kernel(*refs):
        r_hbm, u_hbm = refs[:2]
        m_ref = refs[2 + 2 * L]
        outs = refs[3 + 2 * L:4 + 4 * L]
        u_out, lv_out = outs[0], outs[1:]
        stage = refs[4 + 4 * L]
        u_s = refs[5 + 4 * L:5 + 5 * L]
        r_s = refs[5 + 5 * L:5 + 6 * L]
        wide, sems = refs[5 + 6 * L:]

        def wrap_x(res, g):
            """Owned lanes kept, the two halo lanes from the owned lanes
            they mirror, 0 elsewhere."""
            lane = jax.lax.broadcasted_iota(jnp.int32, res.shape, 1)
            low = pltpu.roll(res, LANE - g.nx, 1)
            high = pltpu.roll(res, g.nx, 1)
            own = (lane >= g.xo) & (lane < g.xo + g.nx)
            return jnp.where(own, res, jnp.where(
                lane == g.xo - 1, low,
                jnp.where(lane == g.xo + g.nx, high, 0.0)))

        def wrap_y(ref, z, g, row, rows, first, last):
            """The two halo rows of plane z from the chunk that holds the
            owned row each mirrors (``first`` / ``last``: a chunk's)."""
            if row == 0:
                ref[z, pl.ds(g.yo + g.ny, 1), :] = first
            if row + rows == g.ny:
                ref[z, pl.ds(g.yo - 1, 1), :] = last

        def wrap_z(ref, g):
            ref[g.zo - 1, :, :] = ref[g.zo + g.nz - 1, :, :]
            ref[g.zo + g.nz, :, :] = ref[g.zo, :, :]

        def both(t):
            return pltpu.roll(t, 1, 1) + pltpu.roll(t, LANE - 1, 1)

        def box(q, p, out, g, w, minus):
            """``out = p +- Box(q)`` (``p`` None: the box alone) on one
            level, ``p`` and ``out`` the same array or not, wrapped."""
            w0, w1, w2, w3 = w
            rows = rows_of(g.ny)

            def plane(z, carry):
                for row in range(0, g.ny, rows):
                    def at(dz, dy):
                        return q[z + dz, pl.ds(g.yo + row + dy, rows), :]

                    def ysum(dz):
                        return at(dz, -1) + at(dz, 1)

                    c = at(0, 0)
                    u1 = ysum(0) + at(-1, 0) + at(1, 0)
                    b = w0 * c
                    if w1:
                        b = b + w1 * (both(c) + u1)
                    if w2 or w3:
                        u2 = ysum(-1) + ysum(1)
                        if w2:
                            b = b + w2 * (u2 + both(u1))
                        if w3:
                            b = b + w3 * both(u2)
                    own = pl.ds(g.yo + row, rows)
                    if p is not None:
                        b = p[z, own, :] - b if minus else p[z, own, :] + b
                    res = wrap_x(b, g)
                    out[z, own, :] = res
                    wrap_y(out, z, g, row, rows, res[0:1, :],
                           res[rows - 1:rows, :])
                return carry

            jax.lax.fori_loop(g.zo, g.zo + g.nz, plane, 0)
            wrap_z(out, g)

        def restrict(src, sg, dst, dg, mi, first, count):
            """Coarse planes ``first .. first + count`` of ``dst`` from
            ``src``: z, then y at a row stride of 2, then x on the MXU."""
            rows = rows_of(dg.ny)

            def plane(i, carry):
                c = first + i
                f = sg.zo + 2 * c
                for row in range(0, dg.ny, rows):
                    def tz(d):
                        at = pl.ds(sg.yo + 2 * row + d, rows, stride=2)
                        return (0.5 * (src[f, at, :] + src[f + 2, at, :])
                                + src[f + 1, at, :])

                    res = _dot3(0.5 * (tz(0) + tz(2)) + tz(1), m_ref[mi])
                    dst[dg.zo + c, pl.ds(dg.yo + row, rows), :] = res
                    wrap_y(dst, dg.zo + c, dg, row, rows, res[0:1, :],
                           res[rows - 1:rows, :])
                return carry

            jax.lax.fori_loop(0, count, plane, 0)

        def widen(slot, src, sg, z, mi):
            wide[slot, pl.ds(0, sg.py), :] = _dot3(src[z, :, :], m_ref[mi])

        def prolong(src, sg, dst, dg, mi, first, count, onto_above):
            """Fine planes ``2 first .. 2 (first + count)`` of ``dst`` from
            ``src``: x on the MXU (a coarse plane once, kept in ``wide[1]``
            for the next), then z, then y at a row stride of 2."""
            rows = rows_of(sg.ny)
            held = pl.ds(0, sg.py)

            def emit(slot, z):
                for row in range(0, sg.ny, rows):
                    odd = wide[slot, pl.ds(sg.yo + row, rows), :]
                    even = 0.5 * (wide[slot, pl.ds(sg.yo + row - 1, rows), :]
                                  + odd)
                    to_even = pl.ds(dg.yo + 2 * row, rows, stride=2)
                    to_odd = pl.ds(dg.yo + 2 * row + 1, rows, stride=2)
                    if onto_above and add:
                        even = dst[z, to_even, :] + even
                        odd = dst[z, to_odd, :] + odd
                    dst[z, to_even, :] = even
                    dst[z, to_odd, :] = odd
                    if not onto_above:
                        wrap_y(dst, z, dg, 2 * row, 2 * rows, even[0:1, :],
                               odd[rows - 1:rows, :])
                if onto_above and not add:
                    for start, stop in ((0, dg.yo), (dg.yo + dg.ny, dg.py)):
                        dst[z, pl.ds(start, stop - start), :] = jnp.zeros(
                            (stop - start, LANE), jnp.float32)

            def plane(i, carry):
                c = first + i
                widen(0, src, sg, sg.zo + c, mi)
                wide[2, held, :] = 0.5 * (wide[0, held, :] + wide[1, held, :])
                emit(2, dg.zo + 2 * c)
                emit(0, dg.zo + 2 * c + 1)
                wide[1, held, :] = wide[0, held, :]
                return carry

            jax.lax.fori_loop(0, count, plane, 0)

        def level(i):
            return scopes.level_scope(L - i)

        def planes(ref, g, sem):            # a chunk of the level above
            at = pl.ds(A.zo + 2 * per * g, 2 * per)
            return ref.at[at], stage.at[at], sems.at[sem + g]

        def fetch(g):
            return pltpu.make_async_copy(*planes(r_hbm, g, sem_in))

        def old(g):
            return pltpu.make_async_copy(*planes(u_hbm, g, sem_add))

        def push(g):
            src, dst, sem = planes(u_out, g, sem_out)
            return pltpu.make_async_copy(dst, src, sem)

        def keep(i):                        # a level to its HBM slots
            return [pltpu.make_async_copy(held[i], lv_out[j * L + i],
                                          sems.at[sem_lv + j * L + i])
                    for j, held in enumerate((u_s, r_s))]

        top = pl.ds(A.zo + A.nz, 1)         # the high halo plane
        last = pltpu.make_async_copy(r_hbm.at[top], stage.at[top],
                                     sems.at[sem_in + chunks])
        for g in range(chunks):
            fetch(g).start()
        last.start()
        # rows and lanes that no operator writes hold 0, not what VMEM held
        for g, ref in zip(G + G, (*u_s, *r_s)):
            def clear(z, carry, ref=ref, g=g):
                ref[z, :, :] = jnp.zeros((g.py, LANE), jnp.float32)
                return carry

            jax.lax.fori_loop(0, g.pz, clear, 0)
        last.wait()
        fetch(0).wait()

        with level(0):
            def entrance(g, carry):
                @pl.when(g + 1 < chunks)
                def _():
                    fetch(g + 1).wait()

                restrict(stage, A, r_s[0], G[0], 0, g * per, per)
                return carry

            jax.lax.fori_loop(0, chunks, entrance, 0)
            wrap_z(r_s[0], G[0])
        if add:
            for g in range(chunks):
                old(g).start()
        for i in range(1, L):
            with level(i):
                restrict(r_s[i - 1], G[i - 1], r_s[i], G[i], 2 * i, 0,
                         G[i].nz)
                wrap_z(r_s[i], G[i])
        with level(L - 1):
            box(r_s[L - 1], None, u_s[L - 1], G[L - 1], sw, False)
        writes = keep(L - 1)
        for i in range(L - 2, -1, -1):
            with level(i):
                widen(1, u_s[i + 1], G[i + 1], G[i + 1].zo - 1, 2 * i + 3)
                prolong(u_s[i + 1], G[i + 1], u_s[i], G[i], 2 * i + 3, 0,
                        G[i + 1].nz, False)
                wrap_z(u_s[i], G[i])
                box(u_s[i], r_s[i], r_s[i], G[i], rw, True)
                box(r_s[i], u_s[i], u_s[i], G[i], sw, False)
            writes += keep(i)
        for cp in writes:
            cp.start()
        if add:
            for g in range(chunks):
                old(g).wait()
        widen(1, u_s[0], G[0], G[0].zo - 1, 1)

        def leave(g, carry):
            prolong(u_s[0], G[0], stage, A, 1, g * per, per, True)
            push(g).start()
            return carry

        jax.lax.fori_loop(0, chunks, leave, 0)
        for g in range(chunks):
            push(g).wait()
        for cp in writes:
            cp.wait()

    def shape(g):
        return jax.ShapeDtypeStruct(
            (g.pz, g.py, LANE), jnp.float32,
            vma=frozenset(vma) if vma is not None else None)

    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    call = scopes.kernel_call(
        "mg_coarse", kernel,
        out_shape=[shape(A)] + [shape(g) for g in G + G],
        in_specs=[anywhere] * (2 + 2 * L)
        + [pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=[anywhere] * (1 + 2 * L),
        scratch_shapes=[pltpu.VMEM((A.pz, A.py, LANE), jnp.float32)]
        + [pltpu.VMEM((g.pz, g.py, LANE), jnp.float32) for g in G + G]
        + [pltpu.VMEM((3, G[0].py, LANE), jnp.float32),
           pltpu.SemaphoreType.DMA((3 * chunks + 1 + 2 * L,))],
        # r_above is read only; every other array comes back in its slot
        input_output_aliases={1 + j: j for j in range(1 + 2 * L)},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_COARSE_VMEM_LIMIT),
        interpret=interpret,
    )

    def fn(r_above, u_above, us, rs):
        with scopes.scope(scopes.CARRY):
            lanes = _varying(matrices, vma)
        out = call(r_above, u_above, *us, *rs, lanes)
        return out[0], list(out[1:1 + L]), list(out[1 + L:])

    return fn
