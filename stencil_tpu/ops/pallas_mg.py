"""Pallas TPU kernel for NPB MG's two box operators, ``resid`` and
``psinv``: one builder, ``out = p +- Box(q)`` with ``Box`` a 27-point
stencil whose weight depends only on the neighbour's class (centre, faces,
edges, corners).

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

from typing import Sequence

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


def make_pallas_mg_box(
    spec: GridSpec,
    name: str,
    weights: Sequence[float],
    sign: float,
    separate_dst: bool = False,
    interpret: bool = False,
    vma=None,
):
    """Build ``fn(q, p) -> out`` (``out`` aliased to ``p``) or, with
    ``separate_dst``, ``fn(q, p, dst) -> out`` (aliased to ``dst``, which
    is not read) over padded ``(pz, py, px)`` fp32 blocks: ``out = p + sign
    * Box(q)`` on the owned cells. ``name`` is the operator's kernel name
    (``mg_resid`` or ``mg_psinv``)."""
    if not box_supported(spec, jnp.float32):
        raise ValueError("pallas mg box unsupported on this spec")
    if len(weights) != 4:
        raise ValueError("a box takes four class weights")
    w0, w1, w2, w3 = (float(w) for w in weights)
    p, off, b = spec.padded(), spec.compute_offset(), spec.base
    pz, py, px = p.z, p.y, p.x
    zo, yo = off.z, off.y
    nz, ny = b.z, b.y
    rows = _chunk_rows(ny, px)
    minus = sign < 0

    def kernel(*refs):
        if separate_dst:
            q_ref, p_ref, _dst, out_ref, c_ring, y_ring = refs
        else:
            q_ref, p_ref, out_ref, c_ring, y_ring = refs
        s = pl.program_id(0)
        hi = s % 3

        # the fresh plane: kept, with its y sums, over the owned rows
        for row in range(0, ny, rows):
            at = pl.ds(yo + row, rows)
            c_ring[hi, pl.ds(row, rows), :] = q_ref[at, :]
            y_ring[hi, pl.ds(row, rows), :] = (
                q_ref[pl.ds(yo + row - 1, rows), :]
                + q_ref[pl.ds(yo + row + 1, rows), :])

        @pl.when(s >= 2)
        def _():
            lo, mid = (s + 1) % 3, (s + 2) % 3          # s - 2, s - 1

            def both(t):
                return pltpu.roll(t, 1, 1) + pltpu.roll(t, px - 1, 1)

            for row in range(0, ny, rows):
                own = pl.ds(row, rows)
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
                at = pl.ds(yo + row, rows)
                out_ref[at, :] = (p_ref[at, :] - box if minus
                                  else p_ref[at, :] + box)
            for start, stop in ((0, yo), (yo + ny, py)):
                edge = pl.ds(start, stop - start)
                out_ref[edge, :] = (jnp.zeros((stop - start, px), jnp.float32)
                                    if separate_dst else p_ref[edge, :])

    def fresh(s):
        return (s + zo - 1, 0, 0)

    def written(s):
        # the plane step s writes, held at the first owned plane until the
        # ring is full (no write-back happens while the index stands still)
        return (jnp.clip(s - 2, 0, nz - 1) + zo, 0, 0)

    plane = (None, py, px)
    shape = jax.ShapeDtypeStruct(
        (pz, py, px), jnp.float32,
        vma=frozenset(vma) if vma is not None else None)
    in_specs = [pl.BlockSpec(plane, fresh), pl.BlockSpec(plane, written)]
    if separate_dst:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    params = dict(
        grid=(nz + 2,),
        out_shape=shape,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(plane, written),
        scratch_shapes=[pltpu.VMEM((3, ny, px), jnp.float32),
                        pltpu.VMEM((3, ny, px), jnp.float32)],
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
    raise ValueError(f"{name!r} is not a box operator of MG")


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
