"""Pallas TPU kernel for one leapfrog step of iso3dfd (Intel oneAPI samples,
``iso3dfd_dpcpp``): a 49-point star of radius 8 over ``prev``, with ``next``
updated in place and ``vel`` read at the centre.

    lap     = c0 * prev + sum_{r=1..8} c_r * (prev[x+-r] + prev[y+-r] + prev[z+-r])
    next    = 2 * prev - next + lap * vel

One call is one step of one block. The block is walked in (ty)-row strips,
each streamed in z through a RING of ``2 * tz + 16`` planes of ``prev`` held
in VMEM: plane ``m`` of a strip (``m = 0`` is the block's first halo plane)
lives in slot ``m % ring``, a tile reads the ``tz + 16`` planes around its
own, and the ``tz`` fresh planes of the NEXT tile are fetched from HBM
straight into the slots the PREVIOUS tile read last, while this one
computes. So every ``prev`` plane of a strip is read from HBM once, nothing
is copied inside VMEM, and the only input amplification is the y window
``(ty + 16) / ty``. ``next`` and ``vel`` tiles ride small multi-slot buffers
(3 and 2 slots, the lag-1 rule of ``pallas_astaroth.py``), and ``next``'s
write-back drains behind the following tiles.

Rows are whole (``px`` lanes, the x halo inline), and the body walks a
strip's plane a ROW GROUP at a time: 8 rows, one (8, 128) tile deep, so
that every load is on the tile. A group is loaded once; its y pencil reads
it and the groups before and after it, whose rows at +-r are a select of
two groups rotated in registers (a row load off the tile costs Mosaic two
loads, two rotations and a select), and r = 8 is the two neighbours
themselves. The z pencils are the same rows of other slots of the ring.
The x pencils are lane rolls of the centre rows, whose wrapped-in lanes
land only in the halo columns, and those take ``next``'s own value back. A
lane roll is what the body pays most for on a v5e (three units, a roll
each per 6 to 8 bundles: 2 to 2.7 cycles a vreg where a VALU operation is
a quarter), so the 16 shifts are NOT 16 rolls: the centre rows are rolled
by +-1, +-2 and +-8, the terms at +-3 .. +-7 are two sums of the five near
shifts, each weighted by the coefficient of where a roll by +-5 lands it,
rolled once: 8 rolls a group. The sum is the same 49 products in float32
in another order, the x terms multiplied one by one where the y and z
terms of a radius are added first. The walk is unrolled over the strip's
groups (their arithmetic is traced once, ``x_near`` / ``finish``), and a
group's rolls are issued a group ahead so that they fly under the
arithmetic of the one before. The kernel writes owned cells only:
``next``'s halo planes, rows and columns, where the domain's fixed ring
lives, keep what they hold.

Shares nothing with ``pallas_astaroth.py``'s window code but the buffering
discipline: that window is a stack of 8 fields shifted (or ring-indexed)
under derivative pencils of radius 3 that a shared physics module asks for
through a slicing adapter, with the fresh planes staged; this one is one
field whose ring the DMAs fill directly. The tile pick is the same idea on
this kernel's own scratch.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..domain.grid import GridSpec
from ..obs import scopes

RADIUS = 8
# explicit scratch under this many bytes, and a strip of at most this many
# row groups: what a v5e took best of the tiles that divide the block of
# iso3dfd1024x4 (PERF.md section 7 has the sweep: (4, 168), 31.9 MB, 12.75
# ms a call; one strip of 63 groups and 63 MB is 1.6 % faster and doubles
# the body's trace, which is unrolled over a strip's groups)
_SCRATCH_BUDGET = 32 * 1024 * 1024
_STRIP_GROUPS = 32
_ROWS = 8           # rows of a plane the body computes at a time: one tile
_ROW_LOADS = 1      # loads of its own plane a row group: the one group the
                    # walk down the strip has not yet seen, on the tile
_LANE_ROLLS = 8     # whole-row lane rolls a row group: by +-1, +-2, +-8, +-5


def scratch_bytes(spec: GridSpec, tz: int, ty: int) -> int:
    """The ``scratch_shapes`` allocation at (tz, ty): the ring of ``prev``
    planes, three ``next`` tiles and two ``vel`` tiles, whole rows."""
    px = spec.padded().x
    ring = (2 * tz + 2 * RADIUS) * (ty + 2 * RADIUS)
    return 4 * px * (ring + 5 * tz * ty)


def pick_tiles(spec: GridSpec) -> Tuple[int, int]:
    """(tz, ty) under the scratch budget: the tallest strip first (the y
    window is the only read amplification) of no more row groups than the
    body is worth unrolling over, then the deepest tile (fewer grid steps).
    ``tz`` divides 16 so that a tile's fresh planes are one contiguous run
    of ring slots; ``(0, 0)`` where nothing fits."""
    nz, ny = spec.base.z, spec.base.y
    best = None
    for tz in (16, 8, 4, 2, 1):
        if nz % tz:
            continue
        for ty in range(8, min(ny, _STRIP_GROUPS * _ROWS) + 1, 8):
            if ny % ty or scratch_bytes(spec, tz, ty) > _SCRATCH_BUDGET:
                continue
            key = (-ty, -tz)
            if best is None or key < best[0]:
                best = (key, (tz, ty))
    return best[1] if best else (0, 0)


def kernel_plan(spec: GridSpec, tiles: Tuple[int, int] = None) -> dict:
    """What the kernel built at ``tiles`` (the pick's by default) does, for
    the counter ``iso3dfd.step_plan``: the tiles and their grid steps, the
    scratch they take, how often a ``prev`` row is read from HBM, and a row
    group's loads of its own plane and lane rolls."""
    tz, ty = tiles if tiles is not None else pick_tiles(spec)
    return {
        "tiles": [tz, ty],
        "grid_steps": (spec.base.z // tz) * (spec.base.y // ty),
        "scratch_bytes": scratch_bytes(spec, tz, ty),
        "prev_reread": (ty + 2 * RADIUS) / ty,
        "row_loads": _ROW_LOADS,
        "row_loads_off_tile": 0,
        "lane_rolls": _LANE_ROLLS,
    }


def step_supported(spec: GridSpec, dtype) -> bool:
    """Whether the kernel handles this block layout: aligned fp32 blocks of
    a uniform partition, halos of 8 or more on every face, the x halo
    inline in whole rows, compute rows a multiple of the 8-row tile."""
    if not spec.aligned or dtype != jnp.float32 or not spec.is_uniform():
        return False
    r = spec.radius
    if min(r.x(-1), r.x(1), r.y(-1), r.y(1), r.z(-1), r.z(1)) < RADIUS:
        return False
    o, p, b = spec.compute_offset(), spec.padded(), spec.base
    if b.y % 8 or o.y % 8 or o.y + b.y + RADIUS > p.y:
        return False
    return pick_tiles(spec) != (0, 0)


def make_pallas_iso3dfd_step(
    spec: GridSpec,
    coeffs: Sequence[float],
    interpret: bool = False,
    vma=None,
    tiles: Tuple[int, int] = None,
):
    """Build ``fn(prev, nxt, vel) -> nxt`` over padded ``(pz, py, px)`` fp32
    blocks: one step, ``nxt`` updated in place (aliased to the result).
    ``coeffs`` is ``(c0, c_1, ..., c_8)``, the centre weight already summed
    over the three axes."""
    if not step_supported(spec, jnp.float32):
        raise ValueError("pallas iso3dfd step unsupported on this spec")
    if len(coeffs) != RADIUS + 1:
        raise ValueError(f"iso3dfd takes {RADIUS + 1} coefficients")
    c0, cr = float(coeffs[0]), [float(c) for c in coeffs[1:]]
    p, off, b = spec.padded(), spec.compute_offset(), spec.base
    pz, py, px = p.z, p.y, p.x
    zo, yo, xo = off.z, off.y, off.x
    nz, ny, nx = b.z, b.y, b.x
    tz, ty = tiles if tiles is not None else pick_tiles(spec)
    if not (tz >= 1 and 16 % tz == 0 and nz % tz == 0 and ty % 8 == 0
            and ny % ty == 0):
        raise ValueError(
            f"tile sizes ({tz}, {ty}): tz must divide 16 and the block's "
            f"{nz} planes, ty be a multiple of 8 that divides its {ny} rows")
    R = RADIUS
    n_tz, n_ty = nz // tz, ny // ty
    n_tiles = n_tz * n_ty
    rows_in = ty + 2 * R          # y window [y0 - 8, y0 + ty + 8), 8-aligned
    first = tz + 2 * R            # planes a strip's first tile loads
    ring = 2 * tz + 2 * R
    groups = ty // _ROWS

    def xroll(a, t):
        return pltpu.roll(a, t % px, 1)

    # the body's arithmetic on (8, px) row groups, each traced once however
    # many groups the strip has (the kernel calls them a group at a time)

    @jax.jit
    def x_near(ctr):
        """What of a group's x pencil needs no second roll: the terms at
        +-1, +-2 and +-8, and the two weighted sums that ONE more roll each,
        by +-5, turns into the terms at +-3 .. +-7 (a lane roll is the
        body's dearest operation: 8 a row group where 16 shifts make 16)."""
        e1, w1 = xroll(ctr, 1), xroll(ctr, -1)
        e2, w2 = xroll(ctr, 2), xroll(ctr, -2)
        far = xroll(ctr, R) + xroll(ctr, -R)
        c5 = cr[4] * ctr
        qe = ((cr[2] * w2 + cr[3] * w1) + c5) + (cr[5] * e1 + cr[6] * e2)
        qw = ((cr[6] * w2 + cr[5] * w1) + c5) + (cr[3] * e1 + cr[2] * e2)
        near = (cr[0] * (e1 + w1) + cr[1] * (e2 + w2)) + cr[R - 1] * far
        return near, qe, qw

    def x_far(qe, qw):
        return xroll(qe, 5) + xroll(qw, -5)

    @jax.jit
    def finish(ctr, lo, hi, xs, old, vel, owned, takes, zs):
        """A row group's new ``next``: ``lo`` / ``hi`` are the aligned
        groups above and below ``ctr`` in y, ``xs`` its x pencil, ``zs``
        its 16 z neighbours."""
        lap = c0 * ctr
        for r in range(1, R + 1):
            if r < _ROWS:
                # rows +r and -r: a select of two groups, rotated in
                # registers
                up = pltpu.roll(jnp.where(takes[r], ctr, hi), _ROWS - r, 0)
                dn = pltpu.roll(jnp.where(takes[_ROWS - r], lo, ctr), r, 0)
                ys = up + dn
            else:
                ys = hi + lo
            lap = lap + cr[r - 1] * (ys + (zs[2 * r - 2] + zs[2 * r - 1]))
        lap = lap + xs
        new = (2.0 * ctr - old) + lap * vel
        return jnp.where(owned, new, old)

    def kernel(prev_hbm, nin_hbm, vel_hbm, out_hbm, win, nxt_v, vel_v,
               s_win, s_stage, s_nin, s_vel, s_out):
        yi = pl.program_id(0)
        zi = pl.program_id(1)
        t = yi * n_tz + zi
        s3, n3 = t % 3, (t + 1) % 3
        s2, n2 = t % 2, (t + 1) % 2
        y0 = yo + yi * ty

        def tile_zy(ti):
            return zo + (ti % n_tz) * tz, yo + (ti // n_tz) * ty

        def win_dma():
            # a strip's first tile: planes m in [0, tz + 16) into their slots
            return pltpu.make_async_copy(
                prev_hbm.at[pl.ds(zo - R, first), pl.ds(y0 - R, rows_in)],
                win.at[pl.ds(0, first)], s_win)

        def stage_dma(zn):
            # tile zn's fresh planes, m in [zn * tz + 16, zn * tz + 16 + tz):
            # one run of slots (tz divides 16 and the ring), those of the
            # planes tile zn - 2 read last
            m0 = zn * tz + 2 * R
            return pltpu.make_async_copy(
                prev_hbm.at[pl.ds(zo - R + m0, tz), pl.ds(y0 - R, rows_in)],
                win.at[pl.ds(m0 % ring, tz)], s_stage.at[zn % 2])

        def tile_dma(hbm, buf, sem, sl, ti, out=False):
            tz0, ty0 = tile_zy(ti)
            there = hbm.at[pl.ds(tz0, tz), pl.ds(ty0, ty)]
            here = buf.at[sl]
            return pltpu.make_async_copy(
                *((here, there) if out else (there, here)), sem.at[sl])

        def nin_dma(sl, ti):
            return tile_dma(nin_hbm, nxt_v, s_nin, sl, ti)

        def vel_dma(sl, ti):
            return tile_dma(vel_hbm, vel_v, s_vel, sl, ti)

        def out_dma(sl, ti):
            return tile_dma(out_hbm, nxt_v, s_out, sl, ti, out=True)

        @pl.when(zi == 0)
        def _():
            win_dma().start()

        @pl.when(zi + 1 < n_tz)
        def _():
            stage_dma(zi + 1).start()

        @pl.when(t == 0)
        def _():
            nin_dma(s3, 0).start()
            vel_dma(s2, 0).start()

        @pl.when(t + 1 < n_tiles)
        def _():
            # next tile's ``next`` goes into slot n3, whose last write-back
            # (tile t - 2's) has to have drained
            @pl.when(t >= 2)
            def _():
                out_dma(n3, t - 2).wait()

            nin_dma(n3, t + 1).start()
            vel_dma(n2, t + 1).start()

        @pl.when(zi == 0)
        def _():
            win_dma().wait()

        @pl.when(zi > 0)
        def _():
            stage_dma(zi).wait()

        nin_dma(s3, t).wait()
        vel_dma(s2, t).wait()

        lane = lax.broadcasted_iota(jnp.int32, (_ROWS, px), 1)
        owned = (lane >= xo) & (lane < xo + nx)
        sub = lax.broadcasted_iota(jnp.int32, (_ROWS, px), 0)
        takes = {r: sub >= r for r in range(1, _ROWS)}   # sublanes r..7

        def plane(j, carry):
            m = zi * tz + R + j             # this plane's index in the strip
            centre = m % ring
            above = [(m + r) % ring for r in range(1, R + 1)]
            below = [(m - r + ring) % ring for r in range(1, R + 1)]

            def rows(slot, g):
                """Row group g of a plane of the window, on the tile (g =
                -1 and ``groups`` are the window's halo rows)."""
                return win[slot, pl.ds(R + g * _ROWS, _ROWS), :]

            # the walk down the strip, unrolled: a group's x pencil is
            # started a group ahead, its second rolls first of all, so that
            # the rolls of one group fly under the arithmetic of another
            lo, ctr = rows(centre, -1), rows(centre, 0)
            near, qe, qw = x_near(ctr)
            for g in range(groups):
                xs = near + x_far(qe, qw)
                hi = rows(centre, g + 1)
                if g + 1 < groups:
                    near, qe, qw = x_near(hi)
                at = pl.ds(g * _ROWS, _ROWS)
                zs = [rows(s, g) for pair in zip(above, below) for s in pair]
                nxt_v[s3, j, at, :] = finish(
                    ctr, lo, hi, xs, nxt_v[s3, j, at, :], vel_v[s2, j, at, :],
                    owned, takes, zs)
                lo, ctr = ctr, hi
            return carry

        lax.fori_loop(0, tz, plane, 0)

        out_dma(s3, t).start()

        # the write-backs of tiles t - 2, t - 1 and t are still in flight
        @pl.when(t == n_tiles - 1)
        def _():
            if n_tiles >= 3:
                out_dma((t - 2) % 3, t - 2).wait()
            if n_tiles >= 2:
                out_dma((t - 1) % 3, t - 1).wait()
            out_dma(s3, t).wait()

    shape = jax.ShapeDtypeStruct(
        (pz, py, px), jnp.float32,
        vma=frozenset(vma) if vma is not None else None)
    return scopes.kernel_call(
        "iso3dfd_step", kernel,
        grid=(n_ty, n_tz),
        out_shape=shape,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((ring, rows_in, px), jnp.float32),
            pltpu.VMEM((3, tz, ty, px), jnp.float32),
            pltpu.VMEM((2, tz, ty, px), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((3,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((3,)),
        ],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            has_side_effects=True,
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )
