"""Pallas TPU kernel for the Astaroth RK3 substep (all 8 fields).

XLA's codegen for the unfused substep materializes the shifted-slice
operands of 60+ derivative pencils in HBM (measured ~266 ms per 256^3 fp32
substep triple on v5e, vs a ~5 GB/substep traffic roofline of ~6 ms). This
kernel walks each (ty)-row strip of the block in z with a **sliding window
of field planes held in VMEM**: per z-tile only the ``tz`` fresh planes are
fetched from HBM (prefetched into a parity-double-buffered stage while the
previous tile computes), the window shifts down in VMEM, and every
derivative and the four MHD right-hand sides are evaluated entirely in
VMEM before the Williamson RK3 stage update streams finished tiles back.

The round-2 version re-fetched the full (tz + 6)-plane halo slab per tile,
a (tz+6)/tz = 4x z-read amplification at the VMEM-forced tz=2 (measured
18.3 ms/substep at 256^3 against a ~7 ms traffic roofline). The sliding
window reads each input plane once per strip, so z-amplification falls to
(nz+6)/nz; the remaining input amplification is the 8-row-aligned y
window ((ty+16)/ty) times the x lane padding px/nx — which the tight-x
layout (Radius.without_x: px == nx, x pencils via lane rolls) reduces
to 1.

The math is NOT duplicated: derivative pencils come from
``astaroth.fd.field_data`` / ``gradient_of_divergence`` and the physics
from ``astaroth.equations`` — the same functions the XLA path executes —
applied to VMEM refs through a window-local view adapter. Parity between
the two paths is therefore structural (pinned by
tests/test_pallas_astaroth.py in interpret mode).

The body walks the tile ONE 8-row group of one plane a trip of a loop that
carries nothing (a vreg or three a value, so what fd and the equations form
stays in registers; on whole (2, 128) x 256 tiles every value was the
register file, 64 vregs, and the one store slot a bundle bound the kernel:
492 spill stores a vreg position of 669 bundles, PERF.md section 6). Rows
are loaded whole at their tile boundary, shifted in y by a select and a
sublane rotation in registers, and everything shifted in x is a lane roll
of a value AFTER it is formed (fd: the x pencil from the centre rows, the
mixed derivatives from the y and z differences the gradient has): 84 rolls
and 120 window reads a vreg position where 144 and 440 were, counted while
the body is traced (counter ``astaroth.substep_plan``).

Layout contract: padded fp32 blocks with TPU-aligned planes
(GridSpec(aligned=True)), face radii >= 3, exchanged halos (including the
xy/yz/xz edge halos the cross-derivatives read — AXIS_COMPOSED phase
composition provides them). The kernel writes compute cells only: out's
halo columns/rows/planes keep their prior contents (refreshed by the next
exchange before any read).

Window discipline — two selectable variants (``variant=``):

- ``"shift"`` (the round-3 kernel): the window is kept physically ordered
  in VMEM; every non-strip-start tile copies the 2*H halo planes down
  (``win[f, 0:2H] = win[f, tz:tz+2H]``) before appending the fresh planes.
- ``"ring"``: shift-free modular-slot rotation — the same math the jacobi
  multistep uses for its plane slots (ops/pallas_stencil.py). Window plane
  j of tile zi lives at physical slot ``(zi*tz + j) % W``; the append
  stores the fresh planes into the recycled slots (planes tile zi-1 read
  last — the lag-1 rule holds trivially for in-body VMEM stores) and the
  compute reads per-plane at dynamic slots, reassembled by concatenation.
  Eliminates NF*2H plane copies per tile at the price of dynamic-index
  addressing; built to settle the round-5 floor contradiction (the
  12.7 ms standalone window-shift leg vs the 0.4 ms in-situ probe —
  VERDICT r5 weak #1, scripts/probe_ring_substep.py is the on-chip A/B).

Buffering discipline (the documented lag-1 rule: a DMA started at grid
step t may write a buffer last touched by compute at step t-1, never one
step t itself reads):

- ``win`` (single buffer, per strip): the strip-start DMA filling it is
  issued at the strip's first tile, one step after the previous strip's
  last compute read it.
- ``stage`` (2 slots by z-tile parity): tile zi's compute consumes slot
  zi%2 while the DMA for tile zi+1 fills slot (zi+1)%2.
- ``out_v`` (3 slots): the out-read DMA of tile t (prefetched at t-1,
  substep > 0), the compute of tile t, and the write-back of tile t which
  drains while tiles t+1/t+2 proceed; slot t%3 is safe to reload once the
  write-back of tile t-3 has drained.

Reference parity: the fused integrate of astaroth/kernels.cu:62-87
(``solve<step>`` over the full subdomain) with the block-size autotuning of
astaroth/integration.cuh:130-215 replaced by the VMEM-budget tile pick.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..domain.grid import GridSpec
from ..geometry import Rect3, Dim3
from ..obs import scopes, telemetry
from ..astaroth.fd import field_data
from ..astaroth.equations import (
    Constants,
    continuity,
    entropy,
    gradient_of_divergence,
    induction,
    momentum,
)

FIELDS = ("lnrho", "uux", "uuy", "uuz", "ax", "ay", "az", "entropy")
NF = len(FIELDS)

# Williamson (1980) low-storage coefficients (reference: integration.cuh:19-21)
RK3_ALPHA = (0.0, -5.0 / 9.0, -153.0 / 128.0)
RK3_BETA = (1.0 / 3.0, 15.0 / 16.0, 8.0 / 15.0)

# VMEM budget for the explicit scratch buffers (v5e-measured: ~34 MB of
# scratch still compiles, ~45 MB does not once Mosaic's expression
# temporaries for the tile DAG are added; see scripts/probe_r03.py).
_SCRATCH_BUDGET = 22 * 1024 * 1024
_HALO = 3  # 6th-order stencils, fixed (reference: astaroth.h STENCIL_ORDER 6)


def _divisors(n: int, cands) -> list:
    return [c for c in cands if c <= n and n % c == 0]


def scratch_bytes(spec: GridSpec, tz: int, ty: int) -> int:
    """Explicit VMEM scratch of the sliding-window substep at (tz, ty):
    all buffers carry full px-wide rows (px == nx under the tight-x
    layout, px == round_up(nx + 6, 128) inline) — exactly the
    ``scratch_shapes`` allocation."""
    px = spec.padded().x
    rows_in = ty + 16
    win = NF * (tz + 2 * _HALO) * rows_in * px
    stage = 2 * NF * tz * rows_in * px
    out = 3 * NF * tz * ty * px
    return 4 * (win + stage + out)


def pick_tiles(spec: GridSpec) -> Tuple[int, int]:
    """(tz, ty) under the scratch budget (the autotuner analogue,
    integration.cuh:130-215). Input amplification is (ty+16)/ty — z reads
    are amortized by the sliding window — so the key prefers the largest
    ty, then the largest tz (fewer tiles: fewer DMA descriptors and less
    window-shift work per output plane)."""
    nz, ny = spec.base.z, spec.base.y
    best = None
    for tz in _divisors(nz, (16, 12, 8, 6, 4, 3, 2, 1)):
        for ty in _divisors(ny, (128, 96, 64, 48, 32, 24, 16, 8)):
            if scratch_bytes(spec, tz, ty) > _SCRATCH_BUDGET:
                continue
            key = (-ty, -tz)
            if best is None or key < best[0]:
                best = (key, (tz, ty))
    return best[1] if best else (0, 0)


def substep_supported(spec: GridSpec, dtype) -> bool:
    """Whether the fused kernel handles this block layout. The tight-x
    layout (Radius.without_x: zero x radius, no halo columns) is supported
    on a single-block lane-aligned x axis — x pencils become lane rolls."""
    if not spec.aligned or dtype != jnp.float32:
        return False
    r = spec.radius
    if min(r.y(-1), r.y(1), r.z(-1), r.z(1)) < _HALO:
        return False
    o = spec.compute_offset()
    p = spec.padded()
    b = spec.base
    if b.y % 8 or o.y % 8 or o.y < 8 or o.y + b.y + 8 > p.y:
        return False
    if o.z < _HALO or o.z + b.z + _HALO > p.z:
        return False
    if r.x(-1) == 0 and r.x(1) == 0:
        if spec.dim.x != 1 or b.x % 128 or o.x != 0:
            return False
    elif min(r.x(-1), r.x(1)) < _HALO:
        return False
    elif o.x < _HALO or o.x + b.x + _HALO > p.x:
        return False
    return pick_tiles(spec) != (0, 0)


class _SlabView:
    """Adapter letting fd.field_data slice ONE 8-row group of ONE plane of
    a field's window in the VMEM scratch ref as if it were a plain
    [z, y, x] array of whole rows: local rows [8, 16) are the group,
    [0, 8) and [16, 24) the groups before and after it; ``row0`` (traced,
    a multiple of 8) is the window row of local row 0 and ``plane0``
    (traced) the window plane of local plane 0.

    Every load is of a whole row group at its own (8-row) tile boundary,
    made once however often fd asks (``loads``); a read at rows +-dy of
    the group is built in registers from two of them: a select and ONE
    sublane rotation (Mosaic takes no load at a traced row offset off
    the tile, and builds a static one from two loads, two to three
    rotations and selects).

    Rows are whole (``px`` columns) in both layouts, and fd shifts what it
    formed from them in x through :meth:`xroll`, an in-VMEM lane roll
    (out[j] = v[(j + d) mod px]). Tight-x (px == nx, no x halos): the
    roll IS the periodic neighborhood. Inline x halos: a compute column's
    neighbours lie inside the row, so the wrap only ever lands in the
    columns the kernel does not write.

    ``zmap``: ring-indexed window — maps a logical window plane j to its
    (traced) physical slot (the slot math of the jacobi multistep,
    ops/pallas_stencil.py).

    ``asked`` counts what the traced body asks of the window, a vreg
    position (the counter ``astaroth.substep_plan``)."""

    __slots__ = ("ref", "pre", "plane0", "row0", "px", "asked", "zmap",
                 "loads")

    def __init__(self, ref, pre, plane0, row0, px, asked, zmap=None):
        self.ref = ref
        self.pre = pre
        self.plane0 = plane0
        self.row0 = row0
        self.px = px
        self.asked = asked
        self.zmap = zmap
        self.loads = {}

    def xroll(self, v, d):
        if d == 0:
            return v
        self.asked["lane_rolls"] += 1
        return pltpu.roll(v, (-d) % self.px, 2)

    def group(self, k, j):
        """Local rows [8k, 8k + 8) of local plane ``j``: one on-tile load."""
        if (k, j) not in self.loads:
            self.asked["window_reads"] += 1
            rows = pl.ds(pl.multiple_of(self.row0 + 8 * k, 8), 8)
            plane = self.plane0 + j
            if self.zmap is not None:
                plane = self.zmap(plane)
            self.loads[k, j] = self.ref[self.pre + (pl.ds(plane, 1), rows)]
        return self.loads[k, j]

    def __getitem__(self, idx):
        assert isinstance(idx, tuple) and idx[0] is Ellipsis, idx
        zsl, ysl, xsl = idx[1:]
        assert (xsl.start, xsl.stop) == (0, self.px), xsl
        assert zsl.stop - zsl.start == 1, zsl
        dy = ysl.start - 8
        assert ysl.stop - ysl.start == 8 and abs(dy) <= _HALO, ysl
        ctr = self.group(1, zsl.start)
        if dy == 0:
            return ctr
        sub = jax.lax.broadcasted_iota(jnp.int32, ctr.shape, 1)
        if dy > 0:
            # rows dy.. of the group, then the first dy of the next one
            mixed = jnp.where(sub >= dy, ctr, self.group(2, zsl.start))
        else:
            # the last -dy rows of the group before, then this one's
            mixed = jnp.where(sub >= 8 + dy, self.group(0, zsl.start), ctr)
        return pltpu.roll(mixed, (-dy) % 8, 1)


def make_pallas_substep(
    spec: GridSpec,
    c: Constants,
    inv_ds: Sequence[float],
    substep: int,
    dt: float,
    interpret: bool = False,
    vma=None,
    tiles: Tuple[int, int] = None,
    _skip_shift: bool = False,  # timing probe only: wrong results
    variant: str = "shift",
):
    """Build ``fn(curr8, out8) -> out8`` over padded (pz, py, px) fp32
    blocks: one RK3 stage for all fields, out buffers updated in place.

    ``curr8``/``out8`` are tuples ordered like :data:`FIELDS`.
    ``variant``: ``"shift"`` (plane-copy window shifts) or ``"ring"``
    (shift-free modular-slot rotation) — see the module docstring."""
    if not substep_supported(spec, jnp.float32):
        raise ValueError("pallas astaroth substep unsupported on this spec")
    if variant not in ("shift", "ring"):
        raise ValueError(f"unknown substep variant {variant!r}")
    ring = variant == "ring"
    if ring and _skip_shift:
        raise ValueError("_skip_shift probes the shift variant")
    p = spec.padded()
    pz, py, px = p.z, p.y, p.x
    off = spec.compute_offset()
    zo, yo, xo = off.z, off.y, off.x
    nz, ny, nx = spec.base.z, spec.base.y, spec.base.x
    tz, ty = tiles if tiles is not None else pick_tiles(spec)
    if not (tz >= 1 and nz % tz == 0 and ny % ty == 0 and ty % 8 == 0):
        raise ValueError(
            f"tile sizes ({tz}, {ty}) must divide block "
            f"({nz}, {ny}) with ty a multiple of 8"
        )
    n_tz, n_ty = nz // tz, ny // ty
    n_tiles = n_tz * n_ty
    rows_in = ty + 16  # y window [y0-8, y0+ty+8): +-3 halo rows, 8-aligned
    H = _HALO
    W = tz + 2 * H  # window planes per field
    # tight-x layout (Radius.without_x, single-block x): px == nx, off.x
    # == 0, no x halo columns exist — slabs are full rows with zero lane
    # padding and the periodic x pencils come from in-VMEM lane rolls
    # (Mosaic requires DMA x-slice offsets AND widths to be 128-aligned,
    # so slicing an inline-halo layout tighter is not expressible; the
    # layout change is)
    tight_x = spec.radius.x(-1) == 0 and spec.radius.x(1) == 0
    beta = RK3_BETA[substep]
    alpha_over_pb = RK3_ALPHA[substep] / RK3_BETA[substep - 1] if substep else 0.0
    ids = tuple(float(v) for v in inv_ds)
    # the region the rates of one 8-row group of one plane are produced
    # over, in the group's frame (_SlabView: local rows [8, 16), whole rows)
    rect = Rect3(Dim3(0, 8, H), Dim3(px, 16, H + 1))
    plan = {}  # what the first trace of the body asked of the window

    def kernel(*refs):
        curr_hbm = refs[:NF]
        oin_hbm = refs[NF : 2 * NF]
        out_hbm = refs[2 * NF : 3 * NF]
        win, stage, out_v, s_win, s_stage, s_oin, s_out = refs[3 * NF :]
        yi = pl.program_id(0)
        zi = pl.program_id(1)
        t = yi * n_tz + zi
        s3 = t % 3  # out_v slot
        n3 = (t + 1) % 3
        y0 = yo + yi * ty
        z0 = zo + zi * tz
        # ring variant: logical window plane j of tile zi lives at physical
        # slot (zi*tz + j) % W; a strip start (zi == 0) is offset 0, so the
        # full-window DMA below needs no variant-specific handling
        zmap = (lambda j: jnp.mod(zi * tz + j, W)) if ring else None

        def tile_zy(ti):
            return zo + (ti % n_tz) * tz, yo + (ti // n_tz) * ty

        def win_dma(f):
            # full window for a strip's first tile: planes [z0-H, z0+tz+H)
            return pltpu.make_async_copy(
                curr_hbm[f].at[pl.ds(z0 - H, W), pl.ds(y0 - 8, rows_in)],
                win.at[f],
                s_win,
            )

        def stage_dma(sl, znext, f):
            # fresh planes for tile znext of this strip: [z0' + H, z0' + tz + H)
            return pltpu.make_async_copy(
                curr_hbm[f].at[
                    pl.ds(zo + znext * tz + H, tz), pl.ds(y0 - 8, rows_in)
                ],
                stage.at[sl, f],
                s_stage.at[sl],
            )

        def oin_dma(sl, ti, f):
            tz0, ty0 = tile_zy(ti)
            return pltpu.make_async_copy(
                oin_hbm[f].at[pl.ds(tz0, tz), pl.ds(ty0, ty)],
                out_v.at[sl, f],
                s_oin.at[sl],
            )

        def out_dma(sl, ti, f):
            tz0, ty0 = tile_zy(ti)
            return pltpu.make_async_copy(
                out_v.at[sl, f],
                out_hbm[f].at[pl.ds(tz0, tz), pl.ds(ty0, ty)],
                s_out.at[sl],
            )

        # input pipeline: strip starts load the whole window; later tiles
        # consume the stage prefetched during the previous tile
        @pl.when(zi == 0)
        def _():
            for f in range(NF):
                win_dma(f).start()

        @pl.when(zi + 1 < n_tz)
        def _():
            for f in range(NF):
                stage_dma((zi + 1) % 2, zi + 1, f).start()

        # oin prefetch (substep > 0): tile t+1's out-read into slot n3,
        # which requires tile t-2's write-back (same slot) drained
        if substep:
            @pl.when(t == 0)
            def _():
                for f in range(NF):
                    oin_dma(s3, 0, f).start()

            @pl.when(t + 1 < n_tiles)
            def _():
                @pl.when(t >= 2)
                def _():
                    for f in range(NF):
                        out_dma(n3, t - 2, f).wait()

                for f in range(NF):
                    oin_dma(n3, t + 1, f).start()

        @pl.when(zi == 0)
        def _():
            for f in range(NF):
                win_dma(f).wait()

        @pl.when(zi > 0)
        def _():
            for f in range(NF):
                stage_dma(zi % 2, zi, f).wait()
            for f in range(NF):
                if ring:
                    # shift-free: store the fresh planes into the recycled
                    # ring slots (planes tile zi-1 read last)
                    for i in range(tz):
                        win[f, zmap(2 * H + i)] = stage[zi % 2, f, i]
                else:
                    # shift the window down by tz planes, then append the
                    # fresh planes (the RHS loads fully before the store,
                    # so the overlapping ranges are safe)
                    if not _skip_shift:
                        win[f, 0 : 2 * H] = win[f, tz : tz + 2 * H]
                    win[f, 2 * H : 2 * H + tz] = stage[zi % 2, f]

        if substep:
            for f in range(NF):
                oin_dma(s3, t, f).wait()
        else:
            # no oin reload: compute itself reuses out_v[t%3], last drained
            # as tile t-3's write-back source
            @pl.when(t >= 3)
            def _():
                for f in range(NF):
                    out_dma(s3, t - 3, f).wait()

        # derivatives + physics over the tile, via the shared fd/equations
        # implementation (reference: solve<step>, user_kernels.h:437-469),
        # ONE 8-row group of one plane a trip: what a group forms is a
        # vreg a lane tile, so it lives in registers (module docstring).
        # The loop carries nothing and its body is traced once.
        n_g = ty // 8

        def group(i, _):
            p0, g = jax.lax.div(i, n_g), jax.lax.rem(i, n_g)
            asked = {"lane_rolls": 0, "window_reads": 0}
            fds = [
                field_data(
                    _SlabView(win, (f,), p0, g * 8, px, asked, zmap=zmap),
                    rect, ids)
                for f in range(NF)
            ]
            lnrho, uux, uuy, uuz, ax, ay, az, ss = fds
            uu = (uux, uuy, uuz)
            aa = (ax, ay, az)
            # the shifted sums of both vectors before the right-hand sides
            # that read them: their rolls fly under the arithmetic between
            gradient_of_divergence(uu)
            gradient_of_divergence(aa)
            rates = [None] * NF
            rates[0] = continuity(uu, lnrho)
            mom = momentum(c, uu, lnrho, ss, aa)
            ind = induction(c, uu, aa)
            rates[1], rates[2], rates[3] = mom
            rates[4], rates[5], rates[6] = ind
            rates[7] = entropy(c, ss, uu, lnrho, aa)
            if not plan:
                plan.update(asked)
                telemetry.get().counter(
                    "astaroth.substep_plan", value=substep, phase="compute",
                    tiles=[tz, ty], tight_x=tight_x, variant=variant,
                    lane_rolls_per_position=asked["lane_rolls"],
                    window_reads_per_position=asked["window_reads"])

            rows = pl.ds(pl.multiple_of(g * 8, 8), 8)  # of the tile
            if not tight_x:
                col = jax.lax.broadcasted_iota(jnp.int32, (1, 8, px), 2)
                computed = (col >= xo) & (col < xo + nx)
            for f in range(NF):
                curr_c = fds[f].value
                if substep:
                    old = out_v[s3, f, pl.ds(p0, 1), rows]
                    new = curr_c + beta * (
                        alpha_over_pb * (curr_c - old) + rates[f] * dt
                    )
                else:
                    new = curr_c + beta * dt * rates[f]
                if not tight_x:
                    # the other columns carry curr, so that the store
                    # covers whole aligned rows
                    new = jnp.where(computed, new, curr_c)
                out_v[s3, f, pl.ds(p0, 1), rows] = new
            return _

        jax.lax.fori_loop(0, tz * n_g, group, 0)

        for f in range(NF):
            out_dma(s3, t, f).start()

        # final drain: write-backs of tiles t-2, t-1, t are still pending
        # (earlier ones were waited in the prefetch / pre-compute paths)
        @pl.when(t == n_tiles - 1)
        def _():
            for f in range(NF):
                if n_tiles >= 3:
                    out_dma((t - 2) % 3, t - 2, f).wait()
                if n_tiles >= 2:
                    out_dma((t - 1) % 3, t - 1, f).wait()
                out_dma(s3, t, f).wait()

    shape = jax.ShapeDtypeStruct(
        (pz, py, px), jnp.float32, vma=frozenset(vma) if vma is not None else None
    )
    fn = scopes.kernel_call(
        "astaroth_substep", kernel,
        grid=(n_ty, n_tz),
        out_shape=(shape,) * NF,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (2 * NF),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * NF,
        scratch_shapes=[
            pltpu.VMEM((NF, W, rows_in, px), jnp.float32),
            pltpu.VMEM((2, NF, tz, rows_in, px), jnp.float32),
            pltpu.VMEM((3, NF, tz, ty, px), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((3,)),
            pltpu.SemaphoreType.DMA((3,)),
        ],
        input_output_aliases={NF + f: f for f in range(NF)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            has_side_effects=True,
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )

    def apply(curr8, out8):
        return fn(*curr8, *out8)

    return apply
