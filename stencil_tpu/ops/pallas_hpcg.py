"""Pallas TPU kernels for HPCG on the tight-x layout: the operator alone
(``hpcg_spmv``), the eight-colour Gauss-Seidel sweep (``hpcg_symgs``) and
the transfers between two tight-x levels (``hpcg_restrict``,
``hpcg_prolong``).

HPCG's operator is a 27-point box, 26 at the centre and -1 at every
neighbour INSIDE the grid: ``pallas_mg``'s box at the weights ``(26, -1,
-1, -1)`` on a domain that is fixed on every axis. y and z bring their
Dirichlet face as the block's ghost ring, which holds zero and is never
written; x has no halo on this layout, and the lane roll that forms ``x -+
1`` drops its wrapped lane (``pallas_mg.x_neighbours``). The residual ``r -
A x`` is that builder as it stands (three arrays a call); ``A p`` alone
reads one array and writes one, and is built here from the same ring and
partial sums.

The sweep. A row's colour is ``(ix mod 2) + 2 (iy mod 2) + 4 (iz mod 2)``;
no two rows of a colour are neighbours, so a colour is updated at once,
``x_i <- (r_i + sum of the CURRENT x_j over i's neighbours) / 26``, and a
forward sweep is the colours 0 .. 7 in order, the backward one 7 .. 0.
Colours 0 .. 3 lie on the even-z planes and 4 .. 7 on the odd ones, and
while the planes of one parity are updated those of the other stand still:
one call is HALF a sweep, the planes of one z parity. A grid step takes
one such plane with the two beside it, forms what the two contribute to
every row's sum once (``fixed = r + the 3 x 3 sums of their sum``), and
then updates the plane's four in-plane colours in VMEM, in the sweep's
order, each from the plane as the colour before left it: the 8 in-plane
neighbours by one sublane shift each way and two lane rolls, the colour's
rows picked by a lane and row parity mask. The plane is written in place.
Nothing is dropped or deferred: the order of the eight colours and the
in-place reads are the source's.

The transfers. HPCG's restriction is an injection, ``rc[c] = t[2c]``, and
its prolongation ``x[2c] += xc[c]``: both touch only the fine cells whose
three indices are even. On this layout that is the even owned planes' even
owned rows, a quarter of the level: a grid step takes ONE even fine plane
whole (the odd ones, half the level, are never fetched), picks or updates
its even rows at a row stride of 2 (on a buffer one lane tile wide, the
only kind Mosaic takes a stride on) and its even columns by a product with
a 0/1 matrix on the MXU (``pallas_mg._dot3``: exact, one term a result).
The prolongation works in place on the fine array, so the planes it does
not fetch stay as they are.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..domain.grid import GridSpec
from ..obs import scopes, telemetry
from .pallas_mg import (LANE, box_of_rows, box_stream, box_supported,
                        _chunk_rows, _dot3, keep_plane, transfer_supported,
                        x_neighbours)

DIAGONAL = 26.0
WEIGHTS = (DIAGONAL, -1.0, -1.0, -1.0)      # HPCG's A as a box by class
IN_PLANE_COLOURS = 4
_GROUP_ROWS = 8
_TRIP_VREGS = 64            # of the plane, a trip of a loop over its rows
_PAIRS = 3                  # the ring: (plane updated, plane above) a slot
_VMEM_LIMIT = 64 * 1024 * 1024


def symgs_supported(spec: GridSpec, dtype) -> bool:
    """The box kernel's layout with an even count of owned planes (a call
    takes every second one) and a whole 8-row group of ghost and padding
    rows either side of the owned rows (a group's neighbours are loaded
    whole)."""
    if not box_supported(spec, dtype):
        return False
    p, off, b = spec.padded(), spec.compute_offset(), spec.base
    return (b.z % 2 == 0 and off.y >= _GROUP_ROWS
            and off.y + b.y + _GROUP_ROWS <= p.y and p.y % _GROUP_ROWS == 0)


def make_pallas_hpcg_spmv(spec: GridSpec, interpret: bool = False, vma=None):
    """Build ``fn(q, dst) -> out`` (aliased to ``dst``, which is not read)
    over padded ``(pz, py, px)`` fp32 blocks of a domain fixed on every
    axis: ``out = A q`` on the owned cells, zero on the ghost and padding
    rows of the planes it writes. One array read, one written."""
    if not box_supported(spec, jnp.float32):
        raise ValueError("pallas hpcg spmv unsupported on this spec")
    p, off, b = spec.padded(), spec.compute_offset(), spec.base
    py, px = p.y, p.x
    yo, ny = off.y, b.y
    rows = _chunk_rows(ny, px)
    both = x_neighbours(px, periodic_x=False)

    def kernel(q_ref, _dst, out_ref, c_ring, y_ring):
        s = pl.program_id(0)
        hi = s % 3
        keep_plane(q_ref, c_ring, y_ring, hi, yo, ny, rows)

        @pl.when(s >= 2)
        def _():
            lo, mid = (s + 1) % 3, (s + 2) % 3          # s - 2, s - 1
            for row in range(0, ny, rows):
                out_ref[pl.ds(yo + row, rows), :] = box_of_rows(
                    c_ring, y_ring, lo, mid, hi, pl.ds(row, rows), WEIGHTS,
                    both)
            for start, stop in ((0, yo), (yo + ny, py)):
                out_ref[pl.ds(start, stop - start), :] = jnp.zeros(
                    (stop - start, px), jnp.float32)

    plane, fresh, written, shape, scratch, grid = box_stream(spec, vma)
    return scopes.kernel_call(
        "hpcg_spmv", kernel,
        grid=grid,
        out_shape=shape,
        in_specs=[pl.BlockSpec(plane, fresh),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(plane, written),
        scratch_shapes=scratch,
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )


def _groups_per_trip(groups: int, px: int) -> int:
    """8-row groups a trip of a plane's loops: a trip's groups share one
    load and do not wait for each other, so the long way from a load
    through two lane rolls to the store is walked by several at once, and
    what a trip pays once is shared. ``_TRIP_VREGS`` of the plane a trip,
    from the chip (ms a symmetric sweep at 512^3 by vregs a trip: 4 37.0,
    8 21.9, 16 14.1, 32 10.4, 64 8.48, 128 8.44, 256, the whole plane,
    8.54; at 256^3 16 1.86, 32 1.42, 64 1.29; PERF.md section 6): past 64
    Mosaic's spills eat what the longer trip saves, for a body twice as
    long to trace and lower."""
    want = max(1, _TRIP_VREGS // (px // 128))
    return next(n for n in range(min(want, groups), 0, -1) if groups % n == 0)


def symgs_plan(spec: GridSpec) -> dict:
    """What one half-sweep call does, for the counter ``hpcg.symgs_plan``:
    calls a sweep, the x planes a grid step fetches and holds, the VMEM
    scratch, and a vreg of a colour's update: its lane rolls and the lane
    selects that drop their wrap, its sublane shifts."""
    p, b = spec.padded(), spec.base
    plane = p.y * p.x * 4
    return {
        "passes_per_sweep": 2,
        "planes_in_ring": 2 * _PAIRS,
        "planes_fetched_per_step": 2,
        "planes_per_call": b.z // 2,
        "colours_per_call": IN_PLANE_COLOURS,
        "group_rows": _GROUP_ROWS,
        "groups_per_trip": _groups_per_trip(b.y // _GROUP_ROWS, p.x),
        # the ring of x planes, two result planes, the neighbours' part
        "scratch_bytes": (2 * _PAIRS + 2) * plane + b.y * p.x * 4,
        "lane_rolls_per_vreg_colour": 2,
        "lane_selects_per_vreg_colour": 2,
        "sublane_shifts_per_vreg_colour": 2,
        # the neighbouring planes' part once a plane, then four colours
        "lane_rolls_per_vreg_plane": 2 + 2 * IN_PLANE_COLOURS,
    }


def parity_masks(px: int):
    """``(lane_odd, row_odd)``: int32 (8, px) arrays, 1 where a cell of an
    8-row group of a ONE-block level has an odd x, an odd y (a group starts
    on an even row). Operands of the sweep: what a split level would hand
    its blocks' own origins' parities in."""
    import numpy as np

    lane = np.broadcast_to(np.arange(px, dtype=np.int32) & 1,
                           (_GROUP_ROWS, px))
    row = np.broadcast_to((np.arange(_GROUP_ROWS, dtype=np.int32) & 1)[:, None],
                          (_GROUP_ROWS, px))
    return jnp.asarray(lane), jnp.asarray(row)


def make_pallas_hpcg_symgs(spec: GridSpec, parity: int, reverse: bool,
                           interpret: bool = False, vma=None):
    """Build ``fn(x, r) -> x`` (in place) over padded ``(pz, py, px)``
    fp32 blocks of a domain fixed on every axis: half a Gauss-Seidel sweep,
    the owned planes of z parity ``parity`` (0: colours 0 .. 3, 1: colours
    4 .. 7), their four in-plane colours ascending or (``reverse``)
    descending.

    ``x`` stays in HBM and is read whole, once: a grid step fetches the
    plane it updates and the one above (two planes, one DMA, a step ahead),
    and the plane below is the one above of the step before, kept in a ring
    of three pairs. ``r`` comes through the pipeline on the planes written;
    a result plane goes back by a DMA that the next step's work covers. 8
    bytes a cell of the level a call."""
    if not symgs_supported(spec, jnp.float32):
        raise ValueError("pallas hpcg symgs unsupported on this spec")
    if parity not in (0, 1):
        raise ValueError("a plane's z parity is 0 or 1")
    p, off, b = spec.padded(), spec.compute_offset(), spec.base
    py, px = p.y, p.x
    zo, yo, ny = off.z, off.y, b.y
    steps = b.z // 2
    groups = ny // _GROUP_ROWS
    order = (3, 2, 1, 0) if reverse else (0, 1, 2, 3)
    both = x_neighbours(px, periodic_x=False)
    inv = 1.0 / DIAGONAL
    counted = []

    per_trip = _groups_per_trip(groups, px)
    trips = groups // per_trip

    def rows(t, shift=0, origin=yo, n=per_trip):
        """``n`` aligned groups from group ``t * per_trip + shift`` on."""
        return pl.ds(pl.multiple_of(
            origin + (t * per_trip + shift) * _GROUP_ROWS, _GROUP_ROWS),
            n * _GROUP_ROWS)

    def with_neighbours(ref, t):
        """A trip's groups of ``ref`` with the group before and the one
        after: ONE load, a group each by aligned slices of the value."""
        got = ref[rows(t, -1, n=per_trip + 2), :]
        return [got[i * _GROUP_ROWS:(i + 1) * _GROUP_ROWS]
                for i in range(per_trip + 2)]

    def y_sums(up, c, dn):
        """``(c[y - 1] + c[y + 1], the same + c)`` of a group ``c`` from
        the groups before and after it: one sublane shift each way."""
        sub = jax.lax.broadcasted_iota(jnp.int32, c.shape, 0)
        y_lo = pltpu.roll(jnp.where(sub == _GROUP_ROWS - 1, up, c), 1, 0)
        y_hi = pltpu.roll(jnp.where(sub == 0, dn, c), _GROUP_ROWS - 1, 0)
        return y_lo + y_hi, (y_lo + y_hi) + c

    def kernel(x_hbm, r_ref, lane_ref, row_ref, out_hbm, ring, result, fixed,
               sem_first, sem_in, sem_out):
        if not counted:
            counted.append(True)
            telemetry.get().counter(
                "hpcg.symgs_plan", value=parity, phase="compute",
                grid=[b.z, b.y, b.x], reverse=bool(reverse),
                order=[c + IN_PLANE_COLOURS * parity for c in order],
                **symgs_plan(spec))
        t = pl.program_id(0)
        pair, slot = t % _PAIRS, t % 2

        def first():
            # the plane below the first one updated (the ghost plane for
            # the even planes): into the slot "the step before" would hold
            return pltpu.make_async_copy(
                x_hbm.at[zo + parity - 1], ring.at[_PAIRS - 1, 1], sem_first)

        def fetch(step, into):
            return pltpu.make_async_copy(
                x_hbm.at[pl.ds(zo + 2 * step + parity, 2)], ring.at[into],
                sem_in.at[into])

        def store(step, from_):
            return pltpu.make_async_copy(
                result.at[from_], out_hbm.at[zo + 2 * step + parity],
                sem_out.at[from_])

        @pl.when(t == 0)
        def _():
            first().start()
            fetch(0, 0).start()

        @pl.when(t + 1 < steps)
        def _():
            fetch(t + 1, (t + 1) % _PAIRS).start()

        @pl.when(t == 0)
        def _():
            first().wait()

        fetch(t, pair).wait()

        @pl.when(t >= 2)
        def _():
            store(t - 2, slot).wait()

        below = ring.at[(t + _PAIRS - 1) % _PAIRS, 1]
        here, above = ring.at[pair, 0], ring.at[pair, 1]
        out = result.at[slot]

        # what the planes beside this one bring to every row's sum, once
        def neighbours(i, carry):
            lo, hi = with_neighbours(below, i), with_neighbours(above, i)
            w = [a + b_ for a, b_ in zip(lo, hi)]
            sums = []
            for g in range(per_trip):
                _, y3 = y_sums(w[g], w[g + 1], w[g + 2])
                sums.append(y3 + both(y3))
            fixed[rows(i, origin=0), :] = (
                r_ref[rows(i), :] + jnp.concatenate(sums, axis=0))
            return carry

        jax.lax.fori_loop(0, trips, neighbours, 0)
        for start, stop in ((0, yo), (yo + ny, py)):
            edge = pl.ds(start, stop - start)
            out[edge, :] = here[edge, :]

        # a cell's in-plane colour: x parity + 2 (y parity)
        in_plane = lane_ref[...] + 2 * row_ref[...]
        for n, colour in enumerate(order):
            # the first colour reads the plane as it came and writes every
            # owned row of the result; the others update the result. A
            # trip's rows are loaded before any of them is stored: no row of
            # a colour is another's neighbour, so a trip's groups do not
            # wait for each other
            src = here if n == 0 else out

            def update(i, carry, src=src, colour=colour):
                c = with_neighbours(src, i)
                rest = fixed[rows(i, origin=0), :]
                new = []
                for g in range(per_trip):
                    y2, y3 = y_sums(c[g], c[g + 1], c[g + 2])
                    at = slice(g * _GROUP_ROWS, (g + 1) * _GROUP_ROWS)
                    new.append(jnp.where(
                        in_plane == colour,
                        (rest[at] + (y2 + both(y3))) * inv, c[g + 1]))
                out[rows(i), :] = jnp.concatenate(new, axis=0)
                return carry

            jax.lax.fori_loop(0, trips, update, 0)

        store(t, slot).start()

        @pl.when(t == steps - 1)
        def _():
            store(t, slot).wait()
            if steps >= 2:
                store(t - 1, 1 - slot).wait()

    plane = (None, py, px)
    small = pl.BlockSpec((_GROUP_ROWS, px), lambda t: (0, 0))
    call = scopes.kernel_call(
        "hpcg_symgs", kernel,
        grid=(steps,),
        out_shape=jax.ShapeDtypeStruct(
            (p.z, py, px), jnp.float32,
            vma=frozenset(vma) if vma is not None else None),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(plane, lambda t: (zo + 2 * t + parity, 0, 0)),
                  small, small],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((_PAIRS, 2, py, px), jnp.float32),
                        pltpu.VMEM((2, py, px), jnp.float32),
                        pltpu.VMEM((ny, px), jnp.float32),
                        pltpu.SemaphoreType.DMA(()),
                        pltpu.SemaphoreType.DMA((_PAIRS,)),
                        pltpu.SemaphoreType.DMA((2,))],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )
    lane_odd, row_odd = parity_masks(px)

    def fn(x, r):
        return call(x, r, lane_odd, row_odd)

    return fn


# ------------------------------------------------------------ the transfers


def even_columns(nx: int, dtype):
    """(nx, nx / 2) of 0 and 1: column c takes x = 2c. A stride of 2 along
    x would cut every lane tile in half (XLA makes it a gather of one
    element a cell); as a product the MXU does it, exactly where every
    piece of the value is kept (one term a result, the weight 1). The
    matrix of a whole row is the one of a pair of lane tiles on its
    diagonal: the kernels multiply by that block."""
    import numpy as np

    m = np.zeros((nx, nx // 2), np.float32)
    m[2 * np.arange(nx // 2), np.arange(nx // 2)] = 1
    return jnp.asarray(m, dtype)


def _transfer_geometry(fine: GridSpec, coarse: GridSpec):
    pf, of, bf = fine.padded(), fine.compute_offset(), fine.base
    pc, oc, bc = coarse.padded(), coarse.compute_offset(), coarse.base
    return pf, of, bf, pc, oc, bc


def make_pallas_hpcg_restrict(fine: GridSpec, coarse: GridSpec,
                              interpret: bool = False):
    """Build ``fn(t_fine, rc) -> rc`` (aliased to ``rc``, which is not
    read) over padded fp32 blocks of two tight-x levels: the injection
    ``rc[c] = t[2c]`` onto the coarse level's owned cells, zero on the
    ghost and padding rows of the planes it writes; the coarse ghost planes
    are not written. One coarse plane a grid step from the ONE fine plane
    it reads: the fine level's even owned planes are read whole (half of
    it, for the quarter in their even rows), the coarse level written."""
    if not transfer_supported(fine, coarse, jnp.float32):
        raise ValueError("pallas hpcg restrict unsupported on these specs")
    pf, of, bf, pc, oc, bc = _transfer_geometry(fine, coarse)
    my = bc.y
    tiles = pf.x // LANE
    matrix = even_columns(2 * LANE, jnp.bfloat16)

    def kernel(t_ref, m_ref, _old, out_ref, stage):
        for pair in range(tiles // 2):
            even = []
            for k in (2 * pair, 2 * pair + 1):
                # y: rows 2j of the owned rows, at a row stride of 2 (which
                # Mosaic takes on a buffer one lane tile wide)
                stage[k] = t_ref[pl.ds(of.y, bf.y), pl.ds(k * LANE, LANE)]
                even.append(stage[k, pl.ds(0, my, stride=2), :])
            out_ref[pl.ds(oc.y, my), pl.ds(pair * LANE, LANE)] = _dot3(
                jnp.concatenate(even, axis=1), m_ref[...])
        for start, stop in ((0, oc.y), (oc.y + my, pc.y)):
            out_ref[pl.ds(start, stop - start), :] = jnp.zeros(
                (stop - start, pc.x), jnp.float32)

    call = scopes.kernel_call(
        "hpcg_restrict", kernel,
        grid=(bc.z,),
        out_shape=jax.ShapeDtypeStruct((pc.z, pc.y, pc.x), jnp.float32),
        in_specs=[
            pl.BlockSpec((None, pf.y, pf.x), lambda c: (of.z + 2 * c, 0, 0)),
            pl.BlockSpec(matrix.shape, lambda c: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, pc.y, pc.x),
                               lambda c: (oc.z + c, 0, 0)),
        scratch_shapes=[pltpu.VMEM((tiles, bf.y, LANE), jnp.float32)],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )

    def fn(t, rc):
        return call(t, matrix, rc)

    return fn


def make_pallas_hpcg_prolong(coarse: GridSpec, fine: GridSpec,
                             interpret: bool = False):
    """Build ``fn(xc, xf) -> xf`` (IN PLACE) over padded fp32 blocks of two
    tight-x levels: ``xf[2c] += xc[c]`` over the coarse level's owned
    cells. One even owned fine plane a grid step, read and written back
    whole with the coarse plane spread onto its even rows and columns; the
    odd planes, the ghost planes and every other cell of the block are not
    touched: half the fine level read and written back, the coarse level
    read."""
    if not transfer_supported(fine, coarse, jnp.float32):
        raise ValueError("pallas hpcg prolong unsupported on these specs")
    pf, of, bf, pc, oc, bc = _transfer_geometry(fine, coarse)
    my = bc.y
    tiles = pf.x // LANE
    # the transpose: coarse lane c onto fine lane 2c
    matrix = even_columns(2 * LANE, jnp.bfloat16).T

    def kernel(c_ref, m_ref, old_ref, out_ref, tall):
        @pl.when(pl.program_id(0) == 0)
        def _():
            # the odd rows: what is added to them, every step, is this 0
            tall[...] = jnp.zeros(tall.shape, jnp.float32)

        own = pl.ds(of.y, bf.y)
        for pair in range(tiles // 2):
            wide = _dot3(c_ref[pl.ds(oc.y, my), pl.ds(pair * LANE, LANE)],
                         m_ref[...])
            for half in (0, 1):
                k = 2 * pair + half
                cols = pl.ds(k * LANE, LANE)
                # y: coarse row j onto fine row 2j, at a row stride of 2
                tall[k, pl.ds(0, my, stride=2), :] = wide[
                    :, half * LANE:(half + 1) * LANE]
                out_ref[own, cols] = old_ref[own, cols] + tall[k]
        for start, stop in ((0, of.y), (of.y + bf.y, pf.y)):
            edge = pl.ds(start, stop - start)
            out_ref[edge, :] = old_ref[edge, :]

    def plane(c):
        return (of.z + 2 * c, 0, 0)

    fine_plane = (None, pf.y, pf.x)
    call = scopes.kernel_call(
        "hpcg_prolong", kernel,
        grid=(bc.z,),
        out_shape=jax.ShapeDtypeStruct((pf.z, pf.y, pf.x), jnp.float32),
        in_specs=[
            pl.BlockSpec((None, pc.y, pc.x), lambda c: (oc.z + c, 0, 0)),
            pl.BlockSpec(matrix.shape, lambda c: (0, 0)),
            pl.BlockSpec(fine_plane, plane),
        ],
        out_specs=pl.BlockSpec(fine_plane, plane),
        scratch_shapes=[pltpu.VMEM((tiles, bf.y, LANE), jnp.float32)],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )

    def fn(xc, xf):
        return call(xc, matrix, xf)

    return fn
