"""Pallas TPU kernel for the 7-point Jacobi sweep.

XLA's codegen for a 3D shifted-slice stencil materializes the shifted
operands (measured ~16 ms per 512^3 fp32 sweep on v5e, vs a ~1.7 ms HBM
roofline at 819 GB/s). This kernel tiles the block into (tz, ty)-plane-row
slabs, streams them HBM->VMEM with *double-buffered* DMA (tile i+1's loads
overlap tile i's compute — the round-1 kernel serialized DMA and compute
and ran at ~64 GB/s), computes the 6-neighbor average in VMEM, and streams
finished tiles back.

Mosaic tiling constraint (the reason for the slab row shapes): VMEM
references are (8, 128)-tiled in their minor two dims, so DMA slices of
VMEM buffers must be tile-aligned there; HBM-side slices are
unconstrained. Row-tiled slabs therefore carry ``ty + 8`` rows (the +-1
halo plus 6 dead rows) instead of ``ty + 2``; z is an untiled dim and
slices freely.

Layout contract: padded blocks with TPU-aligned planes
(GridSpec(aligned=True): py % 8 == 0, px % 128 == 0). The hot/cold sphere
fix-up (reference: bin/jacobi3d.cu:56-63) reads an int32 ``sel`` array
(0 = stencil, 1 = hot, 2 = cold) only for z-tiles that intersect the
sphere z-range.

``wrap`` support: axes whose partition has a single block are periodic
onto themselves; the kernel fills those halos directly from the opposite
face (tiny extra DMAs on edge tiles for z/y, an in-VMEM column copy for
x), replacing the ppermute + halo-update pass entirely for those axes.

Reference parity: computes exactly what ops/jacobi.jacobi_sweep computes
over the compute region (pinned by tests in interpret mode and against the
XLA path on the same device). The output aliases the ``nxt`` buffer;
non-compute cells in the written row range carry the input's values.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..domain.grid import GridSpec
from ..geometry import Dim3
from ..obs import scopes
from .jacobi import COLD_TEMP, HOT_TEMP

# VMEM scratch budget (~16 MB/core on v5e; leave headroom for the compiler)
_VMEM_BUDGET = 12 * 1024 * 1024

# multistep input ring: 3 live planes + 1 in flight
_N_IN = 4

# VMEM the multistep's staging may take: the measured compile ceiling minus
# headroom
MULTISTEP_VMEM_BUDGET = 46 * 1024 * 1024

# row-strip candidates for the row-tiled multistep staging (largest first:
# wider strips mean fewer strip-start pipeline restarts and less overlap
# recompute at uneven splits)
_ROW_CANDS = (512, 384, 256, 192, 128, 96, 64, 48, 32, 24, 16, 8)


def _round8(v: int) -> int:
    return (v + 7) // 8 * 8


def _divisors_desc(n: int, cands) -> list:
    out = [c for c in cands if c <= n and n % c == 0]
    if n not in out:
        out.append(n)
    return out


def _pick_tiles(nz: int, ny: int, yo: int, py: int, px: int) -> Tuple[int, int]:
    """Choose (tz, ty) minimizing read amplification subject to the
    double-buffered scratch fitting in the VMEM budget.

    ``ty == ny`` means full-plane slabs (py rows, arbitrary ny). ``ty < ny``
    requires 8-aligned row tiling: ty % 8 == 0, the compute y-origin on a
    tile boundary (yo % 8 == 0, GridSpec aligned layout), and the slab
    window [y0 - 8, y0 - 8 + ty + 16) inside the padded extent.
    """
    best = None
    for tz in _divisors_desc(nz, (32, 16, 8, 4, 2, 1)):
        for ty in _divisors_desc(ny, (256, 128, 64, 32, 16, 8)):
            if ty == ny:
                rows_in = rows_out = py
            else:
                if ty % 8 or yo % 8 or yo < 8 or yo + ny + 8 > py:
                    continue
                rows_in, rows_out = ty + 16, ty
            need = 4 * (2 * (tz + 2) * rows_in + 4 * tz * rows_out) * px
            if need > _VMEM_BUDGET:
                continue
            amp = ((tz + 2) * rows_in) / (tz * ty)
            key = (amp, -(tz * ty))
            if best is None or key < best[0]:
                best = (key, (tz, ty))
    if best is None:
        return (1, ny)  # tiny blocks always fit
    return best[1]


def _tight_x_layout(wrap_x: bool, nx: int, xo: int, px: int):
    """``(tight, kx, xo_k)`` — whether slabs can carry exactly the nx
    compute columns. Mosaic proves 128-divisibility of minor-dim tile
    indices on BOTH sides of a DMA (offsets and widths), so tight slabs
    require the zero-x-radius layout (``Radius.without_x``: xo == 0,
    px == nx); the periodic x neighborhood then comes from lane rolls.
    Measured 1.36x on the one-step sweep at 512^3 (BASELINE.md round 3,
    scripts/probe_xhalo.py)."""
    tight = wrap_x and nx % 128 == 0 and xo % 128 == 0
    return tight, (nx if tight else px), (0 if tight else xo)


def _roll_x_pair(arr, nx: int, axis: int):
    """Periodic (x-1, x+1) neighbor planes of ``arr`` by lane roll."""
    return pltpu.roll(arr, 1, axis), pltpu.roll(arr, nx - 1, axis)


def make_pallas_jacobi_sweep(
    spec: GridSpec,
    sel_z_range: Tuple[int, int],
    interpret: bool = False,
    vma=None,
    wrap: Tuple[bool, bool, bool] = (False, False, False),
    batch: Optional[int] = None,
):
    """Build ``sweep(curr, nxt, sel) -> new_next`` over one padded block
    (pz, py, px) fp32, writing the compute region of ``nxt`` in place.

    ``sel_z_range`` is the allocation-local [lo, hi) z-range where ``sel``
    may be nonzero (the spheres' bounding planes); tiles outside skip the
    sel DMA and select entirely.

    ``wrap`` = (wz, wy, wx): axes whose periodic halo the kernel fills
    itself from the opposite face (valid only when that mesh axis has a
    single block — the self-wrap case). Jacobi reads only face neighbors,
    so filling faces (no edges/corners) suffices.

    ``batch`` stacks B independent tenant blocks on a leading axis: all
    operands become ``(B, pz, py, px)`` and the grid grows a leading
    batch dimension — one full tile pass per tenant, each tenant's halos
    wrapped onto ITSELF (the multi-tenant campaign's fast path,
    ops/jacobi.make_batched_jacobi_loop). The per-tile pipeline is
    self-contained per batch step: the t==0 prologue re-primes the
    double-buffered DMAs and the final tile drains both outstanding
    stores before the next tenant's pass begins, so no DMA crosses the
    batch axis.
    """
    if not spec.aligned:
        raise ValueError("pallas sweep requires GridSpec(aligned=True)")
    p = spec.padded()
    pz, py, px = p.z, p.y, p.x
    off = spec.compute_offset()
    zo, yo, xo = off.z, off.y, off.x
    nz, ny, nx = spec.base.z, spec.base.y, spec.base.x
    sel_lo, sel_hi = sel_z_range
    wz, wy, wx = wrap

    tight_x, kx, xo_k = _tight_x_layout(wx, nx, xo, px)
    tz, ty = _pick_tiles(nz, ny, yo, py, kx)

    n_tz = nz // tz
    n_ty = ny // ty
    n_tiles = n_tz * n_ty
    full_rows = n_ty == 1
    rows_in = py if full_rows else ty + 16
    rows_out = py if full_rows else ty
    # slab-local row index of the first output row (row-tiled slabs fetch
    # from y0 - 8, the nearest tile boundary carrying the -1 halo row)
    oy = yo if full_rows else 8
    xs = slice(xo_k, xo_k + nx)

    def kernel(curr_hbm, nxt_hbm, sel_hbm, out_hbm, in_v, out_v, sel_v, wy_v, s_in, s_out, s_sel, s_wrap):
        if batch is None:
            t = pl.program_id(0)
        else:
            b = pl.program_id(0)
            t = pl.program_id(1)
        slot = t % 2
        nslot = (t + 1) % 2

        def _ix(*sl):
            # batched operands carry the tenant index on the leading axis
            return sl if batch is None else (b, *sl)

        def tile_zy(ti):
            zi = ti // n_ty
            yi = ti % n_ty
            return zo + zi * tz, yo + yi * ty  # first output plane / row

        def _xsl():
            return pl.ds(xo, nx) if tight_x else slice(None)

        def in_dma(s, ti):
            z0, y0 = tile_zy(ti)
            ys = slice(None) if full_rows else pl.ds(y0 - 8, rows_in)
            src = curr_hbm.at[_ix(pl.ds(z0 - 1, tz + 2), ys, _xsl())]
            return pltpu.make_async_copy(src, in_v.at[s], s_in.at[s])

        def sel_dma(s, ti):
            z0, y0 = tile_zy(ti)
            ys = slice(None) if full_rows else pl.ds(y0, ty)
            src = sel_hbm.at[_ix(pl.ds(z0, tz), ys, _xsl())]
            return pltpu.make_async_copy(src, sel_v.at[s], s_sel.at[s])

        def out_dma(s, ti):
            z0, y0 = tile_zy(ti)
            ys = slice(None) if full_rows else pl.ds(y0, ty)
            dst = out_hbm.at[_ix(pl.ds(z0, tz), ys, _xsl())]
            return pltpu.make_async_copy(out_v.at[s], dst, s_out.at[s])

        def touches_sel(ti):
            z0 = zo + (ti // n_ty) * tz
            return jnp.logical_and(z0 < sel_hi, z0 + tz > sel_lo)

        # pipeline: tile t+1's input DMAs are issued before tile t's compute
        @pl.when(t == 0)
        def _():
            in_dma(slot, t).start()

            @pl.when(touches_sel(t))
            def _():
                sel_dma(slot, t).start()

        @pl.when(t + 1 < n_tiles)
        def _():
            in_dma(nslot, t + 1).start()

            @pl.when(touches_sel(t + 1))
            def _():
                sel_dma(nslot, t + 1).start()

        in_dma(slot, t).wait()

        # self-wrap halo fills (edge tiles only; after the main slab DMA so
        # the writes to in_v cannot race it)
        z0, y0 = tile_zy(t)
        zi = t // n_ty
        yi = t % n_ty
        if wz:

            @pl.when(zi == 0)
            def _():
                ys = slice(None) if full_rows else pl.ds(y0 - 8, rows_in)
                src = curr_hbm.at[_ix(pl.ds(zo + nz - 1, 1), ys, _xsl())]
                cp = pltpu.make_async_copy(src, in_v.at[slot, pl.ds(0, 1)], s_wrap)
                cp.start()
                cp.wait()

            @pl.when(zi == n_tz - 1)
            def _():
                ys = slice(None) if full_rows else pl.ds(y0 - 8, rows_in)
                src = curr_hbm.at[_ix(pl.ds(zo, 1), ys, _xsl())]
                cp = pltpu.make_async_copy(src, in_v.at[slot, pl.ds(tz + 1, 1)], s_wrap)
                cp.start()
                cp.wait()

        if wy and full_rows:
            # the wrapped rows are already resident: in-VMEM copies
            in_v[slot, :, yo - 1, xs] = in_v[slot, :, yo + ny - 1, xs]
            in_v[slot, :, yo + ny, xs] = in_v[slot, :, yo, xs]
        elif wy:
            # wrapped row lives in another tile's rows: stage 8 rows through
            # scratch (VMEM DMA slices must be 8-row aligned), then copy the
            # one needed row in VMEM
            @pl.when(yi == 0)
            def _():
                cp = pltpu.make_async_copy(
                    curr_hbm.at[_ix(pl.ds(z0, tz), pl.ds(yo + ny - 8, 8), _xsl())],
                    wy_v, s_wrap
                )
                cp.start()
                cp.wait()
                in_v[slot, 1 : tz + 1, oy - 1, :] = wy_v[:, 7, :]

            @pl.when(yi == n_ty - 1)
            def _():
                cp = pltpu.make_async_copy(
                    curr_hbm.at[_ix(pl.ds(z0, tz), pl.ds(yo, 8), _xsl())],
                    wy_v, s_wrap
                )
                cp.start()
                cp.wait()
                in_v[slot, 1 : tz + 1, oy + ty, :] = wy_v[:, 0, :]

        if wx and not tight_x:
            in_v[slot, :, :, xo - 1] = in_v[slot, :, :, xo + nx - 1]
            in_v[slot, :, :, xo + nx] = in_v[slot, :, :, xo]

        ctr = slice(oy, oy + ty)  # output rows within the in slab's center
        if tight_x:
            # periodic x neighborhood by lane roll — no halo columns exist
            x_lo, x_hi = _roll_x_pair(in_v[slot, 1 : tz + 1, ctr, :], nx, 2)
        else:
            x_lo = in_v[slot, 1 : tz + 1, ctr, xo - 1 : xo + nx - 1]
            x_hi = in_v[slot, 1 : tz + 1, ctr, xo + 1 : xo + nx + 1]
        avg = (
            x_lo
            + x_hi
            + in_v[slot, 1 : tz + 1, oy - 1 : oy + ty - 1, xs]
            + in_v[slot, 1 : tz + 1, oy + 1 : oy + ty + 1, xs]
            + in_v[slot, 0:tz, ctr, xs]
            + in_v[slot, 2 : tz + 2, ctr, xs]
        ) / 6.0  # divide, not *(1/6): bit-parity with ops.jacobi.jacobi_sweep

        # the same out slot was last used by tile t-2; its store must have
        # drained before we overwrite the buffer
        @pl.when(t >= 2)
        def _():
            out_dma(slot, t - 2).wait()

        # non-compute cells in the written range carry the input's values so
        # the store can cover whole aligned rows (tight-x stores span
        # exactly the compute columns — no x carries exist)
        oys = slice(oy, oy + ty) if full_rows else slice(None)
        if full_rows:
            out_v[slot, :, 0:oy, :] = in_v[slot, 1 : tz + 1, 0:oy, :]
            out_v[slot, :, oy + ty :, :] = in_v[slot, 1 : tz + 1, oy + ty : rows_out, :]
        if not tight_x:
            out_v[slot, :, oys, 0:xo] = in_v[slot, 1 : tz + 1, ctr, 0:xo]
            out_v[slot, :, oys, xo + nx :] = in_v[slot, 1 : tz + 1, ctr, xo + nx : px]

        @pl.when(touches_sel(t))
        def _():
            sel_dma(slot, t).wait()
            sel = sel_v[slot, :, oys, xs] if full_rows else sel_v[slot, :, :, xs]
            out_v[slot, :, oys, xs] = jnp.where(
                sel == 1, HOT_TEMP, jnp.where(sel == 2, COLD_TEMP, avg)
            )

        @pl.when(jnp.logical_not(touches_sel(t)))
        def _():
            out_v[slot, :, oys, xs] = avg

        out_dma(slot, t).start()

        # final tile: drain the last two outstanding stores
        @pl.when(t == n_tiles - 1)
        def _():
            if n_tiles >= 2:
                out_dma(nslot, t - 1).wait()
            out_dma(slot, t).wait()

    shape = (pz, py, px) if batch is None else (batch, pz, py, px)
    if vma is None:
        out_shape = jax.ShapeDtypeStruct(shape, jnp.float32)
    else:
        # inside shard_map, declare the output varying over the mesh axes
        out_shape = jax.ShapeDtypeStruct(shape, jnp.float32, vma=frozenset(vma))
    fn = scopes.kernel_call(
        "jacobi_sweep", kernel,
        grid=(n_tiles,) if batch is None else (batch, n_tiles),
        out_shape=out_shape,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, tz + 2, rows_in, kx), jnp.float32),
            pltpu.VMEM((2, tz, rows_out, kx), jnp.float32),
            pltpu.VMEM((2, tz, rows_out, kx), jnp.int32),
            pltpu.VMEM((tz, 8, kx), jnp.float32),  # wy staging
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA(()),
        ],
        input_output_aliases={1: 0},  # nxt buffer is updated in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                ("arbitrary",) if batch is None
                else ("arbitrary", "arbitrary")
            ),
            has_side_effects=True,
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )
    return fn


def valid_strip_rows(spec: GridSpec, k: int, ty: int) -> bool:
    """Whether ``ty``-row strips can stage the depth-``k`` multistep over
    this block: 8-aligned strips at least one wrap-pad (``round8(k)``)
    tall, and — whenever more than one strip exists — enough slack that
    every slab fetch (edge strips reach ``hp`` rows past their output
    rows; with an overlapped final strip the bound tightens to the last
    interior strip) stays inside the valid [yo, yo + ny) rows."""
    if spec.dim.y > 1:
        return False  # strips replace the y self-wrap ring: single-block y
    ny = spec.base.y
    if ty % 8 or ty > ny:
        return False
    hp = _round8(k)
    if ty < hp:
        return False
    n_ty = -(-ny // ty)
    return n_ty == 1 or (n_ty - 1) * ty + hp <= ny


def plan_multistep_staging(spec: GridSpec, k_want: int, budget: int):
    """``(k, rows)``: the deepest temporal depth <= ``k_want`` whose VMEM
    staging fits ``budget`` bytes, and the row-strip height that achieves
    it (``None`` = full-plane staging, the legacy layout).

    Full planes are preferred while they reach ``k_want`` (no strip
    overlap recompute, no per-strip pipeline restarts). Row tiling engages
    only when full planes self-cap the depth — the 768^3 regime where
    ``(py, px)`` planes held the multistep at k=4 (VERDICT r5 weak #2) —
    and requires a single-block y axis (the strip machinery replaces the
    y self-wrap ring; deep-halo y keeps full planes)."""
    if k_want < 2:
        return k_want, None
    p = spec.padded()
    kx = _staged_columns(spec)
    k_full = (budget // (p.y * kx * 4) - (_N_IN + 2)) // 3 + 1
    if k_full >= k_want or spec.dim.y > 1:
        return max(0, min(k_want, k_full)), None
    for k in range(k_want, max(k_full, 1), -1):
        for ty in _ROW_CANDS:
            if (valid_strip_rows(spec, k, ty)
                    and _staging_bytes(kx, k, ty + 2 * _round8(k), ty)
                    <= budget):
                return k, ty
    return max(0, k_full), None


def temporal_depth_cap() -> int:
    """The deepest temporal multistep any build takes: 12, where the tight-x
    kernels plateau (ops/jacobi.py, the temporal-blocking note);
    ``STENCIL_TEMPORAL_K_CAP`` probes deeper."""
    import os

    try:
        return int(os.environ.get("STENCIL_TEMPORAL_K_CAP", "12"))
    except ValueError as e:
        raise ValueError(
            "STENCIL_TEMPORAL_K_CAP must be an integer, got "
            f"{os.environ['STENCIL_TEMPORAL_K_CAP']!r}"
        ) from e


def pick_temporal_depth(size, partition, chunk: int,
                        budget: int = MULTISTEP_VMEM_BUDGET) -> Tuple[int, str]:
    """``(k, bound)``: how deep an application on the tight-x layout realizes
    its y/z halos so that a dispatch of ``chunk`` steps runs as deep-halo
    multistep passes (one radius-k exchange, then k steps in one pass over
    the block), and which of ``"chunk"`` / ``"cap"`` / ``"vmem"`` /
    ``"block"`` / ``"mesh"`` set it.

    1 (``"mesh"``) where that kernel cannot engage: one block (it wraps in
    the kernel at any radius), a split x axis (side buffers), an uneven
    split. Else the deepest k <= ``chunk`` whose staging
    (:func:`plan_multistep_staging`, the planner the loop builder asks) fits
    ``budget`` at the DEEP plane, which grows with k; the wavefront needs
    nz >= 2k + 1 and a halo comes from one neighbour, so k <= ny. A k that
    divides ``chunk`` is preferred: the dispatch is then passes only, and no
    single step runs its shells over deep halos."""
    from ..geometry import Radius

    g, d = Dim3.of(size), Dim3.of(partition)
    if (d.flatten() == 1 or d.x > 1 or g.x % 128
            or g.y % d.y or g.z % d.z):
        return 1, "mesh"
    ny, nz = g.y // d.y, g.z // d.z
    k_block = min((nz - 1) // 2, ny if d.y > 1 else nz)
    cap = temporal_depth_cap()
    k_geo = min(chunk, cap, k_block)

    def fits(k):
        spec = GridSpec(g, d, Radius.constant(k).without_x())
        return plan_multistep_staging(spec, k, budget)[0] >= k

    fitting = [k for k in range(k_geo, 1, -1) if fits(k)]
    dividing = [k for k in fitting if chunk % k == 0]
    k = (dividing or fitting or [1])[0]
    if k_geo >= 2 and (not fitting or fitting[0] < k_geo):
        return k, "vmem"
    if k_block < min(chunk, cap):
        return k, "block"
    return k, "cap" if cap < chunk else "chunk"


def _multistep_x_layout(spec: GridSpec):
    """``(tight, kx, xo_k)`` of the multistep's staged rows: a single-block
    x axis wraps in the kernel, tightly where the lanes allow."""
    return _tight_x_layout(spec.dim.x == 1, spec.base.x,
                           spec.compute_offset().x, spec.padded().x)


def _staged_columns(spec: GridSpec) -> int:
    """Columns of a staged multistep row: nx under the tight-x layout."""
    return _multistep_x_layout(spec)[1]


def _staging_bytes(kx: int, k: int, rows_staged: int, rows_out: int) -> int:
    """VMEM scratch of the depth-``k`` multistep: the input ring and three
    planes a stage below the last at ``rows_staged`` rows, two output
    planes at ``rows_out``."""
    return 4 * kx * ((_N_IN + 3 * (k - 1)) * rows_staged + 2 * rows_out)


# a stage of the multistep walks its plane in groups of 8 rows (a vreg's
# sublanes), at most this many a trip of its loop. A trip is bound by its
# 2 lane rolls a vreg (7.6 bundles for three, one on each rotate unit) and
# pays once for its addresses and until the first roll returns: some 60
# bundles in the schedule and about 140 cycles on the chip, where the call
# at 512^3 took 0.885 / 0.687 / 0.594 ms a step at 8 / 16 / 32 groups a
# trip (0.49 + 3.2 / groups) and 3.54 / 3.00 / 2.68 at 768^3 on strips. 32
# groups are 128 vregs a trip at 512 lanes and up to 156 at 768, with no
# spill in a plain loop at either width: the scheduler streams them. 64
# would gain 9 % more for twice the program to lower and compile
# (scripts/count_bundles.py on the cells' own kernels, PERF.md section 6)
_GROUP_ROWS = 8
_GROUPS_PER_TRIP = 32


def _stage_rows(spec: GridSpec, k: int, rows: Optional[int],
                s: int) -> Tuple[int, int]:
    """Slab rows ``[lo, hi)`` that stage ``s`` of ``k`` computes: ``k - s``
    rows beyond each side of the strip's own (``rows``) or, in full planes
    (``None``), of a multi-block y axis's; a single block's y ring is not
    computed but filled."""
    if rows is None:
        ey = k - s if spec.dim.y > 1 else 0
        yo = spec.compute_offset().y
        return yo - ey, yo + spec.base.y + ey
    return _round8(k) - (k - s), _round8(k) + rows + (k - s)


def _stage_walk(lo: int, hi: int) -> Tuple[int, int, int, int]:
    """``(g0, n_g, per_trip, trips)``: the aligned 8-row groups
    ``[g0, g0 + n_g)`` that cover rows ``[lo, hi)``, walked in the fewest
    trips of at most ``_GROUPS_PER_TRIP`` groups, all of one size; a last
    trip that would pass the end is re-anchored onto it."""
    g0 = lo // _GROUP_ROWS
    n_g = -(-hi // _GROUP_ROWS) - g0
    trips = -(-n_g // _GROUPS_PER_TRIP)
    return g0, n_g, -(-n_g // trips), trips


def multistep_staging(spec: GridSpec, k: int, rows: Optional[int]) -> dict:
    """What one pass of the depth-``k`` multistep stages and computes along
    y, as the builders below lay it out (``rows``: the strip height, ``None``
    = full planes): ``strips``; ``halo_rows``, the rows a staged strip holds
    beyond the ``rows`` it writes; ``rows_computed``, summed over all stages
    and strips (a strip's stage s computes ``k - s`` rows beyond each side of
    its own, a multi-block y axis in full planes likewise; a re-anchored
    last strip computes its overlap again); ``rows_kept`` = ``k * ny``, what
    a pass with no recompute would compute; ``vmem_bytes`` of scratch. And
    how a stage walks its rows (``body``): in aligned groups of
    ``group_rows``, at most ``groups_per_trip`` a trip of its loop,
    ``rows_walked`` in all (whole groups, a re-anchored last trip's twice),
    with ``lane_rolls_per_vreg`` for ``x -+ 1`` (whole rows are rolled in
    both layouts), between ``stage_buffers`` scratch arrays. And how an edge
    strip gets its periodic y rows: ``wrap_dmas`` DMAs from the opposite
    face a grid step (one strip is both edges: 2; full planes copy in VMEM:
    0), started ``wrap_prefetch`` grid steps ahead of their use, as the slab
    is."""
    ny = spec.base.y
    beyond = k * (k - 1)        # 2 (k - s) rows over the stages s = 1..k
    if rows is None:
        strips, staged, out = 1, spec.padded().y, spec.padded().y
        computed = k * ny + (beyond if spec.dim.y > 1 else 0)
    else:
        strips, staged, out = -(-ny // rows), rows + 2 * _round8(k), rows
        computed = strips * (k * rows + beyond)
    walks = [_stage_walk(*_stage_rows(spec, k, rows, s))
             for s in range(1, k + 1)]
    kx = _staged_columns(spec)
    return {"k": k, "rows": rows or 0, "strips": strips,
            "halo_rows": staged - (rows or ny),
            "rows_computed": computed, "rows_kept": k * ny,
            "vmem_bytes": _staging_bytes(kx, k, staged, out),
            "body": "row_groups", "group_rows": _GROUP_ROWS,
            "groups_per_trip": max(per_trip for _, _, per_trip, _ in walks),
            "rows_walked": strips * _GROUP_ROWS * sum(
                per_trip * trips for _, _, per_trip, trips in walks),
            "lane_rolls_per_vreg": 2,
            "stage_buffers": k - 1,
            "wrap_dmas": 0 if rows is None else 2 if strips == 1 else 1,
            "wrap_prefetch": 0 if rows is None else 1}


def _make_multistep_stage(spec: GridSpec, slab_rows: int, wrap_rows: bool):
    """The ONE stage body of the temporal multistep, for both builders and
    both layouts: ``stage(src, dst, dst_up, rows, zg, row_org, col_org)``
    computes slab rows ``rows = (lo, hi)`` of a plane from the three
    ``(ref, slot)`` planes ``src`` (below, centre, above in z) of the
    stage before, all ``slab_rows`` tall, into the ``(ref, slot)`` plane
    ``dst`` ``dst_up`` rows further up, spheres fixed up from global
    coordinates (slab row r is global row ``r + row_org``, lane c global
    column ``c + col_org``; ``wrap_rows``: periodic in g.y, as a
    multi-block x is in g.x; ``zg`` the plane's global z).

    The plane is walked in aligned 8-row groups, a few a trip of a
    ``fori_loop`` that carries nothing, so that no value is larger than a
    group and all of a trip's stay in registers (a whole plane is four
    register files: Mosaic stored and reloaded every such value, and the
    one store slot a bundle set the pace). Rows are loaded at their tile
    boundary (Mosaic takes no row load at a traced offset off it) and
    ``y -+ 1`` come from the group and its neighbour by ONE sublane
    rotation and a select each. A group that straddles ``lo`` or ``hi`` is
    computed whole: its rows outside hold values nothing reads (the next
    stage reads its own extent + 1, inside this one's); the neighbour
    group is clamped at the slab's two ends.

    Rows are whole in BOTH layouts, and ``x -+ 1`` a lane roll of the row.
    Tight-x (kx == nx, no x halos): the roll IS the periodic neighbourhood.
    Inline x halos: a computed column's neighbours lie inside the row, so
    the wrap lands in columns beyond the stage's x extent, which like the
    rows beyond its y extent hold values nothing reads (a single block's x
    ring is filled after the stage, as its y ring is). A load at the x
    origin's lane offset is shifted by Mosaic before it can meet an iota:
    7.3 lane rolls a vreg where whole rows take 2 (PERF.md section 6)."""
    g = spec.global_size
    mx = spec.dim.x > 1
    kx = _staged_columns(spec)
    hot_c = (g.x // 3, g.y // 2, g.z // 2)
    cold_c = (g.x * 2 // 3, g.y // 2, g.z // 2)
    assert hot_c[1:] == cold_c[1:] and g.y >= _GROUP_ROWS
    thresh = (g.x // 10 + 1) ** 2
    last = slab_rows // _GROUP_ROWS - 1

    # a group's arithmetic, traced ONCE a build however many groups, stages
    # and sphere branches call it (an inner jit: the kernel's trace holds a
    # call a group)

    @jax.jit
    def average(up, c, dn, xs, z_lo, z_hi):
        """A group's new rows from the centre plane's group ``c``, its
        neighbour groups ``up`` and ``dn`` in y, the same rows ``xs`` to
        roll, and the two z neighbours' groups."""
        sub = jax.lax.broadcasted_iota(jnp.int32, c.shape, 0)
        x_lo, x_hi = _roll_x_pair(xs, kx, 1)
        y_lo = pltpu.roll(jnp.where(sub == 7, up, c), 1, 0)
        y_hi = pltpu.roll(jnp.where(sub == 0, dn, c), 7, 0)
        return (
            x_lo
            + x_hi
            + y_lo
            + y_hi
            + z_lo
            + z_hi
        ) / 6.0  # divide: bit-parity with ops.jacobi.jacobi_sweep

    @jax.jit
    def fix_spheres(val, at, hot_x2, cold_x2, dz2):
        """The hot and cold sphere's cells of a group whose first row is
        global row ``at``: exact integer arithmetic on global coordinates;
        the two spheres share their y and z centre."""
        sub = jax.lax.broadcasted_iota(jnp.int32, val.shape, 0)
        if wrap_rows:  # a group wraps once at most
            row = sub + jnp.mod(at, g.y)
            row = jnp.where(row >= g.y, row - g.y, row)
        else:
            row = sub + at
        yz2 = (row - hot_c[1]) ** 2 + dz2
        return jnp.where(yz2 + hot_x2 < thresh, HOT_TEMP,
                         jnp.where(yz2 + cold_x2 < thresh, COLD_TEMP, val))

    def stage(src, dst, dst_up, rows, zg, row_org, col_org):
        g0, n_g, per_trip, trips = _stage_walk(*rows)
        ct, ct_s = src[1]
        dst_ref, dst_s = dst

        def at_group(grp, n=1):
            """``n`` aligned groups from group ``grp`` on."""
            return pl.ds(pl.multiple_of(grp * _GROUP_ROWS, _GROUP_ROWS),
                         n * _GROUP_ROWS)

        def groups(plane, span):
            """A trip's rows of a plane: ONE load at a traced offset, a
            group each by aligned slices of the value."""
            ref, slot = plane
            got = ref[slot, span]
            return [got[i * _GROUP_ROWS:(i + 1) * _GROUP_ROWS]
                    for i in range(per_trip)]

        def walk(spheres: bool):
            if spheres:
                # a column's terms are the same in every group. Halo-
                # extended cells of a multi-block axis can sit beyond the
                # global extent; their true coordinate is the periodic
                # wrap, without which a sphere that crosses the boundary
                # would clamp differently here than on the owning block.
                col = jax.lax.broadcasted_iota(
                    jnp.int32, (_GROUP_ROWS, kx), 1) + col_org
                if mx:
                    col = jnp.mod(col, g.x)
                hot_x2 = (col - hot_c[0]) ** 2
                cold_x2 = (col - cold_c[0]) ** 2
                dz2 = (zg - hot_c[2]) ** 2

            def trip(t, carry):
                first = g0 + jnp.minimum(t * per_trip, n_g - per_trip)
                span = at_group(first, per_trip)
                # the centre plane's group before and after the trip's
                # (held inside the slab: such a row is outside the extent)
                before = first - 1 if g0 > 0 else jnp.maximum(first - 1, 0)
                after = first + per_trip
                if g0 + n_g > last:
                    after = jnp.minimum(after, last)
                ctr = ([ct[ct_s, at_group(before)]] + groups(src[1], span)
                       + [ct[ct_s, at_group(after)]])
                z_lo, z_hi = groups(src[0], span), groups(src[2], span)
                # the rows to roll are loaded a second time, at the same
                # offset formed another way so that the two loads are not
                # merged: the scheduler issues a trip's rolls well ahead,
                # and a row it also had to keep for the y shifts was
                # spilled at 768 lanes
                again = pl.multiple_of(
                    g0 * _GROUP_ROWS + jnp.minimum(
                        t * (per_trip * _GROUP_ROWS),
                        (n_g - per_trip) * _GROUP_ROWS), _GROUP_ROWS)
                x_src = groups(src[1], pl.ds(again, per_trip * _GROUP_ROWS))
                vals = []
                for i in range(per_trip):
                    val = average(*ctr[i:i + 3], x_src[i], z_lo[i], z_hi[i])
                    if spheres:
                        val = fix_spheres(
                            val, (first + i) * _GROUP_ROWS + row_org,
                            hot_x2, cold_x2, dz2)
                    vals.append(val)
                out = pl.multiple_of(first * _GROUP_ROWS - dst_up,
                                     _GROUP_ROWS)
                dst_ref[dst_s, pl.ds(out, per_trip * _GROUP_ROWS)] = (
                    jnp.concatenate(vals, axis=0))
                return carry

            jax.lax.fori_loop(0, trips, trip, 0)

        # sphere fix-up only on planes intersecting the spheres (both share
        # the same z center and radius)
        near = jnp.abs(zg - hot_c[2]) <= g.x // 10

        @pl.when(near)
        def _():
            walk(True)

        @pl.when(jnp.logical_not(near))
        def _():
            walk(False)

    return stage


def _stage_planes(s: int, k: int, v, j, in_v, st_v, out_v):
    """``(src, dst)`` of stage ``s`` at wavefront step ``j``, as
    ``(ref, slot)`` planes: vplanes ``v - 1, v, v + 1`` of the stage before
    (the input ring holds vplane u at ``(u + k) % _N_IN``, a stage's array
    at ``u % 3``) and vplane ``v`` of this one's (the last: the out slot)."""
    if s == 1:
        src = [(in_v, jnp.mod(v + u + k, _N_IN)) for u in (-1, 0, 1)]
    else:
        src = [(st_v[s - 2], jnp.mod(v + u, 3)) for u in (-1, 0, 1)]
    if s == k:
        return src, (out_v, jnp.mod(j, 2))
    return src, (st_v[s - 1], jnp.mod(v, 3))


def make_pallas_jacobi_multistep(
    spec: GridSpec,
    k: int,
    interpret: bool = False,
    vma=None,
    rows: Optional[int] = None,
):
    """Temporal-blocked Jacobi: advance the field ``k`` steps in ONE pass
    over HBM.

    A z-wavefront streams planes through VMEM: when input plane j arrives,
    stage 1 computes plane j-1, stage 2 plane j-2, ..., stage k (the
    output) plane j-k. HBM traffic per step drops from (1 read + 1 write)
    to ((1 + eps) read + 1 write) / k — the communication-avoiding scheme
    that matters on a machine where the stencil is purely memory-bound.

    Axis handling is derived per axis from ``spec.dim``:

    - single-block axes are periodic onto themselves: wrapped plane indices
      on the input fetch (z), in-VMEM ring copies on every stage plane
      (y/x) — no exchange at all, the original single-block behavior;
    - multi-block axes use **deep halos**: the caller exchanges radius-k
      halos ONCE, then stage s computes extents extended (k - s) cells
      into the halo ring, shrinking to the owned region at stage k. One
      exchange per k steps — temporal blocking that survives weak scaling
      (the deep-halo composition of the reference's wrap math,
      dim3.hpp:208-230, with its exchange loop, bin/jacobi3d.cu:296-368).

    Multi-block (uniform partitions only) requires radius >= k on both
    sides of every multi-block axis; the returned ``fn(org, curr, nxt)``
    then takes a (3,) int32 of this block's global (z, y, x) origin
    (scalar prefetch) so the sphere fix-up stays coordinate-exact.
    Single-block keeps the legacy ``fn(curr, nxt)`` signature.

    The hot/cold sphere fix-up is computed inline from integer coordinates:
    the reference's ``int64(sqrtf(d2)) <= R`` (bin/jacobi3d.cu:30-32,49) is
    exactly ``d2 < (R+1)^2`` for exact integer d2 (f32 sqrt of an exact
    integer < 2^24 cannot cross an integer boundary), so no sel array is
    read at all.

    ``rows`` selects **row-tiled staging** (``None`` = the legacy
    full-plane layout): all VMEM staging carries ``rows + 2*round8(k)``-row
    strips instead of full ``(py, px)`` planes, so temporal depth no
    longer collapses with plane size (k>=8 survives 768^3 — VERDICT r5
    weak #2). The grid becomes (n_strips, wavefront): each y-strip runs
    its own z-wavefront; stage s computes ``k - s`` extra rows each side
    (recomputed overlap between strips, the same shrinking-extent math the
    deep-halo ``ext()`` uses), the periodic y neighborhood of edge strips
    arrives via wrap-row DMAs from the opposite face (replacing the y-ring
    fills), and a final strip at ``ny % rows != 0`` is re-anchored to
    ``yo + ny - rows`` — its overlap with the previous strip recomputes
    identical values, so the overlapping writes are idempotent. Requires a
    single-block y axis (use :func:`plan_multistep_staging` /
    :func:`valid_strip_rows` to pick a legal height).

    Both layouts run ONE stage body, :func:`_make_multistep_stage`, between
    a scratch array a stage.
    """
    if rows is not None:
        return _make_multistep_row_tiled(
            spec, k, rows, interpret=interpret, vma=vma
        )
    if not spec.aligned:
        raise ValueError("pallas multistep requires GridSpec(aligned=True)")
    p = spec.padded()
    pz, py, px = p.z, p.y, p.x
    off = spec.compute_offset()
    zo, yo, xo = off.z, off.y, off.x
    nz, ny, nx = spec.base.z, spec.base.y, spec.base.x
    mz, my, mx = spec.dim.z > 1, spec.dim.y > 1, spec.dim.x > 1
    use_org = mz or my or mx
    r = spec.radius
    if use_org:
        if not spec.is_uniform():
            raise ValueError(
                "deep-halo multistep requires a uniform partition")
        for m, rl, rh in (
            (mz, r.z(-1), r.z(1)), (my, r.y(-1), r.y(1)), (mx, r.x(-1), r.x(1))
        ):
            if m and (rl < k or rh < k):
                raise ValueError(
                    "deep-halo multistep needs radius >= k on "
                    "multi-block axes"
                )
    if nz < 2 * k + 1:
        raise ValueError("domain too shallow for this temporal depth")
    J = nz + 2 * k  # pipeline steps: input vplanes -k .. nz+k-1
    g = spec.global_size
    tight_x, kx, xo_k = _multistep_x_layout(spec)
    N_IN = _N_IN  # input ring: 3 live planes + 1 in flight

    def ext(s):
        """(ey, ex) compute-extent extension of stage s into the halo ring
        (stage 0 = the exchanged deep-halo input)."""
        return ((k - s) if my else 0, (k - s) if mx else 0)

    def kernel(*refs):
        if use_org:
            org, *refs = refs
            ozv = org[0] if mz else 0
            oyv = org[1] if my else 0
            oxv = org[2] if mx else 0
        else:
            ozv = oyv = oxv = 0
        curr_hbm, nxt_hbm, out_hbm, in_v, *st_v, out_v, s_in, s_out = refs
        j = pl.program_id(0)

        def _xsl():
            return pl.ds(xo, nx) if tight_x else slice(None)

        def out_dma(step):
            ph = zo + (step - 2 * k)
            return pltpu.make_async_copy(
                out_v.at[pl.ds(jnp.mod(step, 2), 1)],
                out_hbm.at[pl.ds(ph, 1), slice(None), _xsl()],
                s_out.at[jnp.mod(step, 2)],
            )

        def in_dma(step):
            if mz:
                ph = zo - k + step  # deep-halo plane, no wrap
            else:
                ph = zo + jnp.mod(step - k, nz)  # wrapped physical plane
            return pltpu.make_async_copy(
                curr_hbm.at[pl.ds(ph, 1), slice(None), _xsl()],
                in_v.at[pl.ds(jnp.mod(step, N_IN), 1)],
                s_in.at[jnp.mod(step, N_IN)],
            )

        @pl.when(j == 0)
        def _():
            in_dma(0).start()

        @pl.when(j + 1 < J)
        def _():
            in_dma(j + 1).start()

        in_dma(j).wait()

        def fill_wrap(ref, slot, ey, ex):
            """Periodic rings of the self-wrap axes on a plane whose valid
            extents are extended (ey, ex) into the halo (multi-block axes);
            the ring spans the full valid extent so the next stage's
            shifted reads stay within filled cells."""
            xw = slice(xo_k - ex, xo_k + nx + ex)
            if not my:
                ref[slot, yo - 1, xw] = ref[slot, yo + ny - 1, xw]
                ref[slot, yo + ny, xw] = ref[slot, yo, xw]
            if not mx and not tight_x:
                ry = 0 if my else 1
                yw = slice(yo - ey - ry, yo + ny + ey + ry)
                ref[slot, yw, xo - 1] = ref[slot, yw, xo + nx - 1]
                ref[slot, yw, xo + nx] = ref[slot, yw, xo]

        fill_wrap(in_v, jnp.mod(j, N_IN), *ext(0))
        stage = _make_multistep_stage(spec, py, my)

        for s in range(1, k + 1):
            @pl.when(j >= 2 * s)
            def _(s=s):
                v = j - k - s  # this stage's output vplane
                ey, ex = ext(s)
                src, dst = _stage_planes(s, k, v, j, in_v, st_v, out_v)
                if s == k:
                    # the same out slot was last used at step j-2; drain it
                    @pl.when(j >= 2 * k + 2)
                    def _():
                        out_dma(j - 2).wait()

                zg = jnp.mod(ozv + v, g.z) if mz else jnp.mod(v, nz)
                stage(src, dst, 0, _stage_rows(spec, k, None, s), zg,
                      oyv - yo, oxv - xo_k)
                if s < k:
                    fill_wrap(st_v[s - 1], jnp.mod(v, 3), ey, ex)

        @pl.when(j >= 2 * k)
        def _():
            out_dma(j).start()

        @pl.when(j == J - 1)
        def _():
            out_dma(j - 1).wait()
            out_dma(j).wait()

    if vma is None:
        out_shape = jax.ShapeDtypeStruct((pz, py, px), jnp.float32)
    else:
        out_shape = jax.ShapeDtypeStruct((pz, py, px), jnp.float32, vma=frozenset(vma))
    scratch = [
        pltpu.VMEM((N_IN, py, kx), jnp.float32),
        # an array a stage below the last: a stage's loads and stores are
        # then provably apart (in one array at traced slots the scheduler
        # chained every group behind the one before it)
        *[pltpu.VMEM((3, py, kx), jnp.float32) for _ in range(k - 1)],
        pltpu.VMEM((2, py, kx), jnp.float32),
        pltpu.SemaphoreType.DMA((N_IN,)),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        has_side_effects=True,
        vmem_limit_bytes=100 * 1024 * 1024,
    )
    if use_org:
        return scopes.kernel_call(
            "jacobi_multistep", kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(J,),
                in_specs=[
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=pl.BlockSpec(memory_space=pl.ANY),
                scratch_shapes=scratch,
            ),
            out_shape=out_shape,
            input_output_aliases={2: 0},  # (org, curr, nxt) -> nxt
            compiler_params=params,
            interpret=interpret,
        )
    return scopes.kernel_call(
        "jacobi_multistep", kernel,
        grid=(J,),
        out_shape=out_shape,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch,
        input_output_aliases={1: 0},
        compiler_params=params,
        interpret=interpret,
    )


def _make_multistep_row_tiled(
    spec: GridSpec,
    k: int,
    ty: int,
    interpret: bool = False,
    vma=None,
):
    """Row-tiled staging body of :func:`make_pallas_jacobi_multistep`.

    Grid (n_ty, J): strip-major, wavefront-minor. Slab row r of a strip
    anchored at output row ``y0`` holds virtual row ``y0 - hp + r``
    (``hp = round8(k)`` wrap-pad rows each side); virtual rows outside
    [yo, yo + ny) are the periodic wrap, delivered to edge strips by a
    second hp-row DMA from the opposite face that is started and waited
    for WITH the slab's, a grid step ahead of its use (both HBM row offsets
    and the 8-aligned VMEM offsets 0 / hp / hp + ty are DMA-legal, so no
    staged single-row copies are needed; alone after the slab's wait its
    latency was 24 % of the kernel at 768^3). Stage s computes rows
    [hp - (k-s), hp + ty + (k-s)) — interior strips recompute up to k rows
    each side of their output rows instead of reading a neighbor strip,
    which is what unchains the staging footprint from the plane size."""
    assert spec.aligned
    p = spec.padded()
    pz, py, px = p.z, p.y, p.x
    off = spec.compute_offset()
    zo, yo, xo = off.z, off.y, off.x
    nz, ny, nx = spec.base.z, spec.base.y, spec.base.x
    mz, my, mx = spec.dim.z > 1, spec.dim.y > 1, spec.dim.x > 1
    assert not my, "row-tiled multistep staging needs a single-block y axis"
    assert valid_strip_rows(spec, k, ty), (k, ty, ny)
    use_org = mz or mx
    r = spec.radius
    if use_org:
        assert spec.is_uniform(), "deep-halo multistep requires a uniform partition"
        for m, rl, rh in ((mz, r.z(-1), r.z(1)), (mx, r.x(-1), r.x(1))):
            assert not m or (rl >= k and rh >= k), (
                "deep-halo multistep needs radius >= k on multi-block axes"
            )
    assert nz >= 2 * k + 1, "domain too shallow for this temporal depth"
    hp = _round8(k)
    R = ty + 2 * hp
    n_ty = -(-ny // ty)
    J = nz + 2 * k  # wavefront steps per strip: input vplanes -k .. nz+k-1
    g = spec.global_size
    tight_x, kx, xo_k = _multistep_x_layout(spec)

    def kernel(*refs):
        if use_org:
            org, *refs = refs
            ozv = org[0] if mz else 0
            oxv = org[2] if mx else 0
        else:
            ozv = oxv = 0
        (curr_hbm, nxt_hbm, out_hbm, in_v, *st_v, out_v, s_in, s_out,
         s_wrap) = refs
        yi = pl.program_id(0)
        j = pl.program_id(1)
        y0 = yo + jnp.minimum(yi * ty, ny - ty)  # uneven final strip re-anchors

        def _xsl():
            return pl.ds(xo, nx) if tight_x else slice(None)

        def in_plane(step):
            if mz:
                return zo - k + step  # deep-halo plane, no wrap
            return zo + jnp.mod(step - k, nz)  # wrapped physical plane

        def in_event(step, go):
            """Start or wait the DMAs of input ``step``: the main slab and,
            on an edge strip, the opposite face's ``hp`` rows (periodic y)
            with it, on the same ring slot. The slab skips the rows a wrap
            delivers, so the destinations are disjoint, every VMEM
            offset/extent stays 8-row aligned and no fetch leaves the valid
            [yo, yo + ny) rows."""
            ph = in_plane(step)
            slot = jnp.mod(step, _N_IN)

            def cp(sem, src_lo, n_rows, dst_off):
                return pltpu.make_async_copy(
                    curr_hbm.at[pl.ds(ph, 1), pl.ds(src_lo, n_rows), _xsl()],
                    in_v.at[pl.ds(slot, 1), pl.ds(dst_off, n_rows)],
                    sem.at[slot],
                )

            def fetch(slab, *wraps):
                go(cp(s_in, *slab))
                for rows in wraps:
                    go(cp(s_wrap, *rows))

            below = (yo + ny - hp, hp, 0)   # the top face's rows, under row 0
            above = (yo, hp, hp + ty)

            if n_ty == 1:
                fetch((y0, ty, hp), below, above)
                return

            @pl.when(yi == 0)
            def _():
                fetch((y0, ty + hp, hp), below)

            @pl.when(yi == n_ty - 1)
            def _():
                fetch((y0 - hp, hp + ty, 0), above)

            if n_ty > 2:
                @pl.when(jnp.logical_and(yi > 0, yi < n_ty - 1))
                def _():
                    fetch((y0 - hp, R, 0))

        def out_dma(step):
            ph = zo + (step - 2 * k)
            return pltpu.make_async_copy(
                out_v.at[pl.ds(jnp.mod(step, 2), 1)],
                out_hbm.at[pl.ds(ph, 1), pl.ds(y0, ty), _xsl()],
                s_out.at[jnp.mod(step, 2)],
            )

        @pl.when(j == 0)
        def _():
            in_event(0, lambda c: c.start())

        @pl.when(j + 1 < J)
        def _():
            in_event(j + 1, lambda c: c.start())

        in_event(j, lambda c: c.wait())

        def fill_wrap_x(ref, slot, es):
            """Periodic x ring of a plane whose valid row extent is
            [hp - es, hp + ty + es) — covers the next stage's x-shifted
            reads (its rows shrink by one)."""
            if not mx and not tight_x:
                yw = slice(hp - es, hp + ty + es)
                ref[slot, yw, xo - 1] = ref[slot, yw, xo + nx - 1]
                ref[slot, yw, xo + nx] = ref[slot, yw, xo]

        fill_wrap_x(in_v, jnp.mod(j, _N_IN), k)
        stage = _make_multistep_stage(spec, R, True)

        for s in range(1, k + 1):
            @pl.when(j >= 2 * s)
            def _(s=s):
                v = j - k - s  # this stage's output vplane
                es = k - s
                src, dst = _stage_planes(s, k, v, j, in_v, st_v, out_v)
                if s == k:
                    # the same out slot was last used at step j-2; drain it
                    @pl.when(j >= 2 * k + 2)
                    def _():
                        out_dma(j - 2).wait()

                # strip rows (and the wrap-pad of edge strips) sit at their
                # wrapped global y
                zg = jnp.mod(ozv + v, g.z) if mz else jnp.mod(v, nz)
                # the out planes hold a strip's own rows alone
                stage(src, dst, hp if s == k else 0,
                      _stage_rows(spec, k, ty, s), zg, y0 - yo - hp,
                      oxv - xo_k)
                if s < k:
                    fill_wrap_x(st_v[s - 1], jnp.mod(v, 3), es)

        @pl.when(j >= 2 * k)
        def _():
            out_dma(j).start()

        @pl.when(j == J - 1)
        def _():
            out_dma(j - 1).wait()
            out_dma(j).wait()

    if vma is None:
        out_shape = jax.ShapeDtypeStruct((pz, py, px), jnp.float32)
    else:
        out_shape = jax.ShapeDtypeStruct((pz, py, px), jnp.float32, vma=frozenset(vma))
    scratch = [
        pltpu.VMEM((_N_IN, R, kx), jnp.float32),
        *[pltpu.VMEM((3, R, kx), jnp.float32) for _ in range(k - 1)],
        pltpu.VMEM((2, ty, kx), jnp.float32),
        pltpu.SemaphoreType.DMA((_N_IN,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((_N_IN,)),  # the wrap rows of a ring slot
    ]
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        has_side_effects=True,
        vmem_limit_bytes=100 * 1024 * 1024,
    )
    if use_org:
        return scopes.kernel_call(
            "jacobi_multistep_rows", kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(n_ty, J),
                in_specs=[
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=pl.BlockSpec(memory_space=pl.ANY),
                scratch_shapes=scratch,
            ),
            out_shape=out_shape,
            input_output_aliases={2: 0},  # (org, curr, nxt) -> nxt
            compiler_params=params,
            interpret=interpret,
        )
    return scopes.kernel_call(
        "jacobi_multistep_rows", kernel,
        grid=(n_ty, J),
        out_shape=out_shape,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch,
        input_output_aliases={1: 0},
        compiler_params=params,
        interpret=interpret,
    )


def sel_z_range(spec: GridSpec) -> Tuple[int, int]:
    """Allocation-local z-range that may contain sphere cells, valid for
    every block (conservative union over blocks): the spheres span global
    z in [zc - R, zc + R] (reference geometry, bin/jacobi3d.cu:44-49)."""
    global_size = spec.global_size
    zc = global_size.z // 2
    R = global_size.x // 10
    zo = spec.radius.z(-1)
    glo, ghi = zc - R, zc + R + 1
    # conservative: if any block covers part of [glo, ghi), its local range
    # is within [zo, zo + base.z); compute the tightest uniform bound
    lo = spec.padded().z
    hi = 0
    for iz in range(spec.dim.z):
        o = sum(spec.sizes_z[:iz])
        s = spec.sizes_z[iz]
        blo = max(glo - o, 0)
        bhi = min(ghi - o, s)
        if blo < bhi:
            lo = min(lo, zo + blo)
            hi = max(hi, zo + bhi)
    if hi <= lo:
        return (0, 0)
    return (lo, hi)
