"""In-place periodic halo fills for self-wrap axes (Pallas, TPU).

The TPU-native analogue of the reference's pack/unpack + same-device
``PeerAccessSender`` transport (reference: src/pack_kernel.cu:3-103,
tx_cuda.cuh:41-113): on an axis whose partition has a single block, the
periodic halo source is the block itself, so the exchange phase is a pure
intra-HBM data movement. Expressing it as ``dynamic_update_slice`` makes
XLA materialize tile-padded slab arrays and full-array copies (measured
~22 ms for what is ~50 MB of logical movement at 512^3 r3 x4); these
kernels instead update the halo regions *in place* (``input_output_aliases``)
touching only the affected (8, 128) tiles.

Axis economics per quantity (512^3, r=3, fp32):
- z: halo planes are whole (py, px) slabs — 6 plane copies, ~16 MB.
- y: halo rows live in one 8-row tile per side. Where a block's rows are a
  multiple of 8 neither tile holds an owned row, and the source row-tile
  (the halo rows at the same place in it) is read and written onto it
  whole: 4 row-tile passes, ~43 MB. A tile that does hold owned rows (140
  rows) is read, its halo rows overwritten in VMEM, and written: 6 passes.
  Streamed over z in batches of up to 32 planes, two buffer slots: every
  DMA of a batch is in flight at once and the next batch loads while this
  one is written.
- x: halo columns live inside one 128-lane tile per side — RMW of both
  edge lane-tiles (~0.55 GB; the 128-lane tile is the minimum write
  granularity, a ~42x amplification that any layout storing x halos
  inline must pay).

Used by ``HaloExchange`` for AXIS_COMPOSED phases with a single block on
the axis. A multi-block phase keeps the ppermute + update path on y and z;
on a split x (lane) axis it packs and unpacks with the two edge-tile
kernels at the end of this module (``make_split_x_pack`` /
``make_split_x_unpack``), which share the x fill's geometry, and XLA keeps
only the lane-dense carrier and the ``ppermute``. Phase ordering (x, then
y, then z) is preserved because each axis is a separate kernel call — later
phases read the earlier phases' filled halos.

Each build records the HBM bytes one call reads and writes
(``halo.self_fill.bytes_dma``, see ``_record_dma_bytes``): 0.560 + 0.043 +
0.016 GB a quantity at the size above (the y record also says how many
DMAs a call issues, how many its schedule keeps in flight and how many
destination tiles it does not read); the split-x pair records
``halo.split_x.bytes_dma`` (0.287 + 0.567 GB a quantity there).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..domain.grid import GridSpec
from ..obs import scopes

_LANE = 128
_SUB = 8


# -- quantity grouping / packed carriers --------------------------------------
# Shared between the fused multi-quantity fill kernels below and the
# quantity-batched exchange phases (parallel/exchange.py): a multi-quantity
# state is processed per same-dtype GROUP (never bitcast), and a group's
# boundary slabs ride one packed (Q, ...) carrier per data movement — the
# ppermute analogue of the reference's per-neighbor multi-quantity message
# (reference: packer.cu:10-26, the DevicePacker laying q quantities into one
# contiguous buffer).


def dtype_groups(state):
    """``[(dtype, [keys])]`` of a quantity dict, grouped by dtype in
    first-appearance order. The grouping unit for packed carriers and
    fused fills: quantities in one group share every slab shape and may
    be stacked without bitcasting; distinct dtypes exchange separately."""
    groups = {}
    for k, v in state.items():
        groups.setdefault(jnp.dtype(v.dtype), []).append(k)
    return list(groups.items())


def pack_slabs(slabs):
    """Stack a same-dtype group's boundary slabs into the packed
    ``(Q, ...slab)`` carrier that rides one collective (packer.cu's
    per-neighbor message re-expressed for ``lax.ppermute``).

    A single-slab group degenerates to the slab itself (no leading unit
    axis), so the batched phase bodies at Q=1 compile the exact historical
    per-quantity program — they ARE the per-quantity implementation then."""
    return slabs[0] if len(slabs) == 1 else jnp.stack(slabs)


def unpack_slabs(carrier, nq: int):
    """Scatter a packed ``(Q, ...slab)`` carrier back into per-quantity
    slabs (static leading index — XLA fuses these into the halo updates);
    inverse of :func:`pack_slabs`, including the Q=1 degeneration."""
    return [carrier] if nq == 1 else [carrier[q] for q in range(nq)]


def wire_narrow_dtype(native, wire_dtype):
    """The dtype a wire-crossing carrier of ``native`` data travels as
    under the bf16-on-the-wire compression knob, or None when the
    carrier stays native: compression only ever NARROWS a floating
    carrier (fp32 -> bf16/f16, fp64 -> f32/bf16/...), never widens,
    never touches integer quantities, and never bitcasts — the cast is a
    rounding ``astype`` on the send side and a lossless widen on unpack.
    Local copies (self-wrap fills, resident-neighbor shifts) are never
    compressed: only bytes that actually cross the interconnect pay the
    precision for the bandwidth."""
    if wire_dtype is None:
        return None
    native = jnp.dtype(native)
    wire = jnp.dtype(wire_dtype)
    if not (jnp.issubdtype(native, jnp.floating)
            and jnp.issubdtype(wire, jnp.floating)):
        return None
    if wire.itemsize >= native.itemsize:
        return None
    return wire


def wrap_fill_batched(spec: GridSpec, a):
    """Periodic self-wrap halo fill of every *leading-dim* block: ``a`` is
    ``(..., pz, py, px)`` — e.g. the multi-tenant campaign's stacked
    ``(B, pz, py, px)`` tenant states — and every trailing (pz, py, px)
    block is an INDEPENDENT single-block periodic domain whose halos wrap
    onto itself. Nothing ever crosses the leading axes: the slice
    assignments below touch only the trailing three dims.

    Fill order is the composed x -> y -> z phase order of
    ``parallel/exchange.py`` (AXIS_ORDER), each later axis copying the
    full extent of the earlier axes including their just-filled halos, so
    edges and corners come out identical to a single-block
    ``HaloExchange`` self-wrap — the bit-parity anchor of the batched
    campaign step programs (tests/test_campaign.py)."""
    off = spec.compute_offset()
    b = spec.base
    r = spec.radius
    xo, yo, zo = off.x, off.y, off.z
    nx, ny, nz = b.x, b.y, b.z
    rxm, rxp = r.x(-1), r.x(1)
    rym, ryp = r.y(-1), r.y(1)
    rzm, rzp = r.z(-1), r.z(1)
    if rxm:
        a = a.at[..., :, :, xo - rxm:xo].set(a[..., :, :, xo + nx - rxm:xo + nx])
    if rxp:
        a = a.at[..., :, :, xo + nx:xo + nx + rxp].set(a[..., :, :, xo:xo + rxp])
    if rym:
        a = a.at[..., :, yo - rym:yo, :].set(a[..., :, yo + ny - rym:yo + ny, :])
    if ryp:
        a = a.at[..., :, yo + ny:yo + ny + ryp, :].set(a[..., :, yo:yo + ryp, :])
    if rzm:
        a = a.at[..., zo - rzm:zo, :, :].set(a[..., zo + nz - rzm:zo + nz, :, :])
    if rzp:
        a = a.at[..., zo + nz:zo + nz + rzp, :, :].set(a[..., zo:zo + rzp, :, :])
    return a


def _axis_geom(spec: GridSpec, axis: str) -> Tuple[int, int, int]:
    """(offset, size, (rm, rp)) along one axis."""
    off = spec.compute_offset()
    r = spec.radius
    if axis == "x":
        return off.x, spec.base.x, (r.x(-1), r.x(1))
    if axis == "y":
        return off.y, spec.base.y, (r.y(-1), r.y(1))
    return off.z, spec.base.z, (r.z(-1), r.z(1))


# VMEM scratch budget for a fill kernel (kernels pass vmem_limit_bytes to
# lift the 16 MB default scoped limit; leave headroom for Mosaic).
_VMEM_BUDGET = 24 * 1024 * 1024
# buffer slots of the y kernel: batch i + 1 loads while batch i is written
_Y_SLOTS = 2


def _x_tzb(spec: GridSpec, nq: int = 1, z_stack: int = 1) -> int:
    """z-batch depth of the x kernel: deepest of 16/8/4/2 whose 8 buffers
    (x nq quantities) fit the budget (v5e-measured at 256^3: TZB=16
    4.25 ms vs TZB=4 6.01 ms — bigger DMAs amortize per-batch latency)."""
    p = spec.padded()
    pz = p.z * z_stack
    tzb = 16
    while tzb > 2 and (8 * nq * tzb * p.y * _LANE * 4 > _VMEM_BUDGET or tzb > pz):
        tzb //= 2
    return tzb


def max_fill_group(spec: GridSpec, axis: str = "x") -> int:
    """Largest quantity count a fused x or y fill can carry under the VMEM
    budget at its shallowest z batch (callers chunk larger quantity sets;
    the z fill stages one plane set whatever the count)."""
    if axis == "y":
        one = _y_scratch_bytes(spec, 1, _SUB)
    else:
        one = 8 * 2 * spec.padded().y * _LANE * 4
    return max(1, min(16, _VMEM_BUDGET // one))


class _YSide(NamedTuple):
    """One halo of a y fill: rows ``[dst_at, dst_at + r)`` of the row-tile
    window ``[dst_t, dst_t + dst_span)`` take rows ``[src_at, src_at + r)``
    of the window at ``src_t``. ``rebuilt``: the destination window holds
    no owned row and the source window has its shape and its rows at the
    same place, so the source window as read IS the destination window
    (its halo rows right, its dead rows some owned rows' values) and is
    written there without the destination being read first."""

    r: int
    dst_t: int
    dst_span: int
    dst_at: int
    src_t: int
    src_span: int
    src_at: int
    rebuilt: bool


def _y_sides(spec: GridSpec, wanted=(True, True)) -> Tuple[_YSide, ...]:
    """The active halos of a y fill, low first; ``wanted`` (low, high)
    leaves a side out."""
    o, sz, (rm, rp) = _axis_geom(spec, "y")
    rm, rp = (r if on else 0 for r, on in zip((rm, rp), wanted))
    py = spec.padded().y

    def window(row, r):
        t = (row // _SUB) * _SUB
        return t, min(-(-(row + r - t) // _SUB) * _SUB, py - t), row - t

    sides = []
    # rows [o-rm, o) <- rows [o+sz-rm, o+sz); rows [o+sz, o+sz+rp) <- [o, o+rp)
    for r, dst, src in ((rm, o - rm, o + sz - rm), (rp, o + sz, o)):
        if r:
            d, s = window(dst, r), window(src, r)
            owned = d[0] < o + sz and d[0] + d[1] > o
            sides.append(_YSide(r, *d, *s, not owned and d[1:] == s[1:]))
    return tuple(sides)


def _y_scratch_bytes(spec: GridSpec, nq: int, tzb: int) -> int:
    """VMEM of the y kernel's buffers: a source window a side and, where
    the destination is read too, its window, in ``_Y_SLOTS`` slots."""
    rows = sum(s.src_span + (0 if s.rebuilt else s.dst_span)
               for s in _y_sides(spec))
    return _Y_SLOTS * nq * tzb * rows * spec.padded().x * 4


def _y_tzb(spec: GridSpec, nq: int = 1, z_stack: int = 1) -> int:
    """z-batch depth of the y kernel: the deepest of 32/16/8 whose buffers
    (x nq quantities) fit the budget, then evened out over the batches
    that takes, so that the clamped last batch moves fewer than one plane
    a batch twice (518 planes: 17 batches of 31, not of 32)."""
    pz = spec.padded().z * z_stack
    tzb = 32
    while tzb > _SUB and (
            _y_scratch_bytes(spec, nq, tzb) > _VMEM_BUDGET or tzb > pz):
        tzb //= 2
    return -(-pz // -(-pz // tzb))


def _scratch_bytes(spec: GridSpec, axis: str, z_stack: int = 1) -> int:
    """VMEM scratch the kernel for ``axis`` would allocate at one quantity
    (see make_self_fill)."""
    p = spec.padded()
    o, sz, (rm, rp) = _axis_geom(spec, axis)
    if axis == "z":
        return max(rm, rp, 1) * p.y * p.x * 4
    if axis == "y":
        return _y_scratch_bytes(spec, 1, _y_tzb(spec, 1, z_stack))
    # x (nq=1): 4 double-buffered 2-slot buffers
    return 8 * _x_tzb(spec, z_stack=z_stack) * p.y * _LANE * 4


def self_fill_supported(spec: GridSpec, axis: str, dtype, z_stack: int = 1) -> bool:
    """Whether the in-place fill kernel handles this configuration.

    ``z_stack > 1``: the kernel targets a (z_stack, pz, py, px) resident
    z-stack viewed as one contiguous (z_stack*pz, py, px) array. Valid for
    x/y fills only — they act within each z plane, so resident block
    boundaries along z are transparent; the z fill's plane copies are not.
    """
    if z_stack > 1 and axis == "z":
        return False
    if not spec.aligned or dtype != jnp.float32:
        return False
    o, sz, (rm, rp) = _axis_geom(spec, axis)
    if rm == 0 and rp == 0:
        return False
    p = spec.padded()
    # x/y kernels stream fixed-depth z batches; thinner blocks would slice
    # out of range (z0 = min(i*TZB, pz-TZB) goes negative)
    if axis == "x" and p.z * z_stack < 4:
        return False
    if axis == "y" and p.z * z_stack < 8:
        return False
    if _scratch_bytes(spec, axis, z_stack) > _VMEM_BUDGET:
        return False
    if axis == "x":
        # halo and wrap-source columns must each sit inside the two edge
        # lane-tiles the kernel rewrites
        lo_t = 0
        hi_t = ((o + sz) // _LANE) * _LANE
        if hi_t + _LANE > p.x or hi_t <= lo_t:
            return False
        cols = [(o - rm, o), (o, o + rp), (o + sz - rm, o + sz), (o + sz, o + sz + rp)]
        homes = [lo_t, lo_t, hi_t, hi_t]
        for (a, b), home in zip(cols, homes):
            if a < home or b > home + _LANE:
                return False
        return True
    if axis == "y":
        # halo rows and wrap-source rows each within one 8-row tile span
        return rm <= _SUB and rp <= _SUB
    return True  # z: untiled dim, plane copies always work


def _record_dma_bytes(nq: int, shape, read: int, written: int,
                      name: str = "halo.self_fill.bytes_dma", **tag):
    """The HBM bytes ONE CALL of a halo kernel moves, counted where its
    DMAs are built (HBM -> VMEM: bytes read; VMEM -> HBM: bytes written),
    recorded once per build: ``halo.self_fill.bytes_dma`` tagged ``axis``
    for a self-fill, ``halo.split_x.bytes_dma`` tagged ``part`` (pack or
    unpack; a record's ``kind`` is taken) for the split-x pair. The lane and
    row tiles the x and y kernels rewrite whole are in it, which
    ``HaloExchange.bytes_moved`` leaves out. A y fill's record adds
    ``dmas`` (the DMAs a call issues), ``in_flight`` (the most its
    schedule has started and not yet waited for) and ``dst_read_skipped``
    (destination windows written without being read: 0, 1 or 2).
    ``utils/mosaic_traffic`` derives the same counts from the lowered
    Mosaic module (a test's cross-check)."""
    from ..obs import telemetry

    telemetry.get().counter(
        name, bytes=read + written, phase="exchange", quantities=nq,
        shape=list(shape), bytes_read=read, bytes_written=written, **tag)


def make_self_fill(spec: GridSpec, axis: str, vma=None, interpret: bool = False,
                   nq: int = 1, z_stack: int = 1, sides=(True, True)):
    """Build the in-place periodic fill for one self-wrap axis of fp32
    (pz, py, px) blocks. ``nq == 1``: ``fill(block) -> block``; ``nq > 1``:
    ``fill(b0, .., b{nq-1}) -> (b0', ..)`` — one kernel fills every
    quantity's halo (the multi-quantity pack analogue, packer.cu:10-26),
    amortizing per-kernel and per-batch overheads across quantities.

    ``z_stack > 1`` (x/y axes only): the fill runs over a resident z-stack
    of ``z_stack`` whole padded blocks viewed as one contiguous
    ``(z_stack*pz, py, px)`` array — x/y halos act within each z plane, so
    one kernel fills every resident block's halo in place (VERDICT r4
    item 7; the reference runs its same-GPU fast path under
    oversubscription too, tx_cuda.cuh:41-113).

    ``sides`` (low, high): the halos this group wants filled (a radius a
    quantity, ``HaloExchange(quantity_radius=)``); the other is left as
    it lies and costs no DMA on y and z (x rewrites both edge lane-tiles
    either way: each halo's source is in the other)."""
    if not any(sides):
        raise ValueError("a self-fill that fills neither side")
    if not self_fill_supported(spec, axis, jnp.float32, z_stack):
        raise ValueError(
            f"self-wrap fill unsupported for axis {axis!r} on this spec "
            f"(z_stack={z_stack})"
        )
    if axis != "z" and not 1 <= nq <= max_fill_group(spec, axis):
        raise ValueError(
            f"{axis}-phase fill group size {nq} outside "
            f"[1, {max_fill_group(spec, axis)}]"
        )
    p = spec.padded()
    pz, py, px = p.z * z_stack, p.y, p.x
    o, sz, (rm, rp) = _axis_geom(spec, axis)
    rm, rp = (r if on else 0 for r, on in zip((rm, rp), sides))
    shape = jax.ShapeDtypeStruct(
        (pz, py, px), jnp.float32, vma=frozenset(vma) if vma is not None else None
    )
    _out_shape = (shape,) * nq
    _aliases = {q: q for q in range(nq)}

    def _wrap(fn):
        if nq == 1:
            return lambda block: fn(block)[0]
        return fn

    if axis == "z":
        def kernel(*refs):
            outs = refs[nq : 2 * nq]
            v, sem = refs[2 * nq :]

            def copy(out, src, dst, n):
                cp = pltpu.make_async_copy(out.at[pl.ds(src, n)], v.at[pl.ds(0, n)], sem)
                cp.start()
                cp.wait()
                cp = pltpu.make_async_copy(v.at[pl.ds(0, n)], out.at[pl.ds(dst, n)], sem)
                cp.start()
                cp.wait()

            for q in range(nq):
                if rm:
                    copy(outs[q], o + sz - rm, o - rm, rm)  # top planes -> low halo
                if rp:
                    copy(outs[q], o, o + sz, rp)  # first planes -> high halo

        nstage = max(rm, rp, 1)
        plane = py * px * 4
        _record_dma_bytes(nq, (pz, py, px), nq * (rm + rp) * plane,
                          nq * (rm + rp) * plane, axis="z")
        return _wrap(scopes.kernel_call(
            "self_fill_z", kernel,
            grid=(1,),
            out_shape=_out_shape,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * nq,
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * nq,
            scratch_shapes=[
                pltpu.VMEM((nstage, py, px), jnp.float32),
                pltpu.SemaphoreType.DMA(()),
            ],
            input_output_aliases=_aliases,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                has_side_effects=True,
                vmem_limit_bytes=100 * 1024 * 1024,
            ),
            interpret=interpret,
        ))

    if axis == "y":
        # Every DMA of a z batch flies with its batch, and batches overlap:
        # step i starts batch i + 1's reads (every quantity's source window
        # a side and, where it is read, the destination window, each into a
        # buffer of its own in the next slot), then waits for batch i's,
        # copies the halo rows in VMEM where a destination was read, starts
        # every write of batch i and ends on their wait, so the next step
        # finds its load slot written back. A rebuilt side (_YSide) has no
        # destination read and no copy: its source buffer is written out.
        #
        # z is untiled, so the last batch is clamped and overlaps the one
        # before it when pz % TZB != 0. Unlike in the x kernel its reads may
        # be prefetched past that batch's writes: every row a source window
        # gives is an owned row, which this kernel never changes, and of a
        # destination window that was read the halo rows are overwritten in
        # VMEM and the others written back as read, so whichever of the two
        # writes a prefetched read races, the tile it writes is the same,
        # and the two batches write equal values to the planes they share.
        sides = _y_sides(spec, sides)
        TZB = _y_tzb(spec, nq, z_stack)
        n_b = -(-pz // TZB)

        def kernel(*refs):
            outs = refs[nq : 2 * nq]
            i = pl.program_id(0)
            # a side's scratch: the source buffer, its semaphores, the
            # writes' semaphores and, where the destination is read, its
            # buffer and semaphores
            bufs, rest = [], list(refs[2 * nq :])
            for side in sides:
                n = 3 if side.rebuilt else 5
                bufs.append(rest[:n])
                rest = rest[n:]

            def copy(q, step, t, span, buf, sem, out=False):
                """One DMA of batch ``step``: rows [t, t + span) of its z
                planes of quantity q into the batch's slot of ``buf``, or
                ``out`` of it."""
                slot = jnp.mod(step, _Y_SLOTS)
                z0 = jnp.minimum(step * TZB, pz - TZB)
                hbm = outs[q].at[pl.ds(z0, TZB), pl.ds(t, span)]
                vmem = buf.at[slot, q]
                return pltpu.make_async_copy(
                    *((vmem, hbm) if out else (hbm, vmem)), sem.at[slot])

            def reads(step):
                cps = []
                for side, (src, s_src, _, *dst) in zip(sides, bufs):
                    for q in range(nq):
                        cps.append(copy(q, step, side.src_t, side.src_span,
                                        src, s_src))
                        if dst:
                            cps.append(copy(q, step, side.dst_t,
                                            side.dst_span, *dst))
                return cps

            def writes(step):
                return [copy(q, step, side.dst_t, side.dst_span,
                             dst[0] if dst else src, s_wr, out=True)
                        for side, (src, _, s_wr, *dst) in zip(sides, bufs)
                        for q in range(nq)]

            @pl.when(i == 0)
            def _():
                for cp in reads(i):
                    cp.start()

            @pl.when(i + 1 < n_b)
            def _():
                for cp in reads(i + 1):
                    cp.start()

            for cp in reads(i):
                cp.wait()
            slot = jnp.mod(i, _Y_SLOTS)
            for side, (src, _, _, *dst) in zip(sides, bufs):
                for q in range(nq if dst else 0):
                    dst[0][slot, q, :, side.dst_at : side.dst_at + side.r, :] = (
                        src[slot, q, :, side.src_at : side.src_at + side.r, :])
            written = writes(i)
            for cp in written:
                cp.start()
            for cp in written:
                cp.wait()

        rows = TZB * px * 4     # one row of a z batch
        written = sum(s.dst_span for s in sides)
        read = sum(s.src_span + (0 if s.rebuilt else s.dst_span) for s in sides)
        n_rd = nq * sum(1 if s.rebuilt else 2 for s in sides)
        _record_dma_bytes(nq, (pz, py, px), n_b * nq * read * rows,
                          n_b * nq * written * rows, axis="y",
                          dmas=n_b * (n_rd + nq * len(sides)),
                          in_flight=2 * n_rd if n_b > 1 else n_rd,
                          dst_read_skipped=sum(s.rebuilt for s in sides))
        scratch = []
        for s in sides:
            scratch += [
                pltpu.VMEM((_Y_SLOTS, nq, TZB, s.src_span, px), jnp.float32),
                pltpu.SemaphoreType.DMA((_Y_SLOTS,)),
                pltpu.SemaphoreType.DMA((_Y_SLOTS,)),
            ]
            if not s.rebuilt:
                scratch += [
                    pltpu.VMEM((_Y_SLOTS, nq, TZB, s.dst_span, px), jnp.float32),
                    pltpu.SemaphoreType.DMA((_Y_SLOTS,)),
                ]
        return _wrap(scopes.kernel_call(
            "self_fill_y", kernel,
            grid=(n_b,),
            out_shape=_out_shape,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * nq,
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * nq,
            scratch_shapes=scratch,
            input_output_aliases=_aliases,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                has_side_effects=True,
                vmem_limit_bytes=100 * 1024 * 1024,
            ),
            interpret=interpret,
        ))

    # axis == "x": rewrite both edge lane-tiles, double-buffered over z.
    # 8 buffers (rd/wr x lo/hi x 2 slots); depth picked by the VMEM budget
    TZB = _x_tzb(spec, nq, z_stack)
    n_b = -(-pz // TZB)  # overlapping last batch: z is untiled, restart anywhere
    lo_t = 0
    hi_t = ((o + sz) // _LANE) * _LANE

    # batches are disjoint except the clamped last one, whose z-range
    # overlaps the previous batch's when pz % TZB != 0 — that read must
    # not be prefetched past the overlapping write
    tail_overlaps = (pz % TZB) != 0
    prefetch_limit = n_b - 1 if tail_overlaps else n_b

    def kernel(*refs):
        outs = refs[nq : 2 * nq]
        rd_lo, rd_hi, wr_lo, wr_hi, s_rlo, s_rhi, s_wlo, s_whi = refs[2 * nq :]
        i = pl.program_id(0)
        slot = jnp.mod(i, 2)
        nslot = jnp.mod(i + 1, 2)

        def z_of(step):
            return jnp.minimum(step * TZB, pz - TZB)

        def rd(s, q, step, buf, sem, col):
            return pltpu.make_async_copy(
                outs[q].at[pl.ds(z_of(step), TZB), :, pl.ds(col, _LANE)],
                buf.at[s, q],
                sem.at[s],
            )

        def wr(s, q, step, buf, sem, col):
            return pltpu.make_async_copy(
                buf.at[s, q],
                outs[q].at[pl.ds(z_of(step), TZB), :, pl.ds(col, _LANE)],
                sem.at[s],
            )

        def rd_both(s, step):
            for q in range(nq):
                rd(s, q, step, rd_lo, s_rlo, lo_t).start()
                rd(s, q, step, rd_hi, s_rhi, hi_t).start()

        def wr_start(s, step):
            for q in range(nq):
                wr(s, q, step, wr_lo, s_wlo, lo_t).start()
                wr(s, q, step, wr_hi, s_whi, hi_t).start()

        def wr_wait(s, step):
            for q in range(nq):
                wr(s, q, step, wr_lo, s_wlo, lo_t).wait()
                wr(s, q, step, wr_hi, s_whi, hi_t).wait()

        @pl.when(i == 0)
        def _():
            rd_both(slot, i)

        @pl.when(i + 1 < prefetch_limit)
        def _():
            rd_both(nslot, i + 1)

        if tail_overlaps:
            @pl.when(jnp.logical_and(i == prefetch_limit, i >= 1))
            def _():
                # non-prefetched tail batch: the overlapping previous write
                # must land before reading
                wr_wait(nslot, i - 1)
                rd_both(slot, i)

        for q in range(nq):
            rd(slot, q, i, rd_lo, s_rlo, lo_t).wait()
            rd(slot, q, i, rd_hi, s_rhi, hi_t).wait()

        # the write buffers of batch i-2 (same slot) must have drained
        @pl.when(i >= 2)
        def _():
            wr_wait(slot, i - 2)

        for q in range(nq):
            wr_lo[slot, q] = rd_lo[slot, q]
            wr_hi[slot, q] = rd_hi[slot, q]
            if rm:  # cols [o-rm, o) <- [o+sz-rm, o+sz) (hi tile)
                wr_lo[slot, q, :, :, o - rm - lo_t : o - lo_t] = rd_hi[
                    slot, q, :, :, o + sz - rm - hi_t : o + sz - hi_t
                ]
            if rp:  # cols [o+sz, o+sz+rp) <- [o, o+rp) (lo tile)
                wr_hi[slot, q, :, :, o + sz - hi_t : o + sz + rp - hi_t] = rd_lo[
                    slot, q, :, :, o - lo_t : o + rp - lo_t
                ]
        wr_start(slot, i)

        @pl.when(i == n_b - 1)
        def _():
            # wr(n_b-2): the overlap tail branch waited it; otherwise here
            if n_b >= 2 and not tail_overlaps:
                wr_wait(nslot, i - 1)
            wr_wait(slot, i)

    # every batch reads and rewrites both edge lane-tiles of every row
    tiles = n_b * nq * 2 * TZB * py * _LANE * 4
    _record_dma_bytes(nq, (pz, py, px), tiles, tiles, axis="x")
    return _wrap(scopes.kernel_call(
        "self_fill_x", kernel,
        grid=(n_b,),
        out_shape=_out_shape,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * nq,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * nq,
        scratch_shapes=[
            pltpu.VMEM((2, nq, TZB, py, _LANE), jnp.float32),
            pltpu.VMEM((2, nq, TZB, py, _LANE), jnp.float32),
            pltpu.VMEM((2, nq, TZB, py, _LANE), jnp.float32),
            pltpu.VMEM((2, nq, TZB, py, _LANE), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        input_output_aliases=_aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            has_side_effects=True,
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    ))


# -- split x axis: pack / unpack on the two edge lane-tiles ------------------
# The x phase of a partition that SPLITS x. The halo source is the
# neighbour's block, so a carrier crosses the wire between a pack and an
# unpack, but on the field both are the x self-fill's job: 3 columns of
# every row live in one 128-lane tile a side, and XLA's slice and
# dynamic_update_slice each cost a pass over the whole field to reach them
# (measured 28 of 34 ms an exchange at 4 x 512^3 on (2,2,1)). The two
# kernels below touch the edge lane-tiles only, streamed in the x fill's own
# z batches, and relayout in VMEM between "r lanes of every row" and a
# lane-dense carrier.
#
# The carrier of one side is ``(groups, nq, py, 128)``: batch ``i``, plane
# ``j`` of the batch, column ``c`` sits in group ``i // k`` at lane
# ``((i % k) * TZB + j) * r + c``, with ``k = (128 // r) // TZB`` batches a
# group (126 of 128 lanes in use at r = 3). Pack and unpack walk the same
# batches (``z = min(i * TZB, pz - TZB)``), so a clamped last batch sends
# its overlapping planes twice, to the same halo cells, and the unpack needs
# no tail rule: what two of its batches write to one plane is equal.


class _SplitSide(NamedTuple):
    """One direction of a split-x phase: ``r`` columns leave lanes
    ``[src, src + r)`` of the lane-tile at ``src_tile`` and land in lanes
    ``[dst, dst + r)`` of the tile at ``dst_tile`` on the neighbour."""

    r: int
    src_tile: int
    src: int
    dst_tile: int
    dst: int
    k: int        # z batches that share one 128-lane carrier group
    groups: int


class _SplitX(NamedTuple):
    shape: Tuple[int, int, int]
    tzb: int
    n_b: int
    sides: Tuple[_SplitSide, ...]   # forward (toward +x) first; r > 0 only


def _split_x_geom(spec: GridSpec, nq: int) -> _SplitX:
    p = spec.padded()
    o, sz, (rm, rp) = _axis_geom(spec, "x")
    lo_t = 0
    hi_t = ((o + sz) // _LANE) * _LANE
    tzb = max(1, min(_x_tzb(spec, nq), _LANE // max(rm, rp)))
    n_b = -(-p.z // tzb)
    sides = []
    # forward: my top rm columns fill the +x neighbour's low halo;
    # backward: my first rp columns fill the -x neighbour's high halo
    for r, src, dst in ((rm, o + sz - rm, o - rm), (rp, o, o + sz)):
        if r:
            s_t, d_t = (hi_t, lo_t) if dst < o else (lo_t, hi_t)
            k = (_LANE // r) // tzb
            sides.append(_SplitSide(r, s_t, src - s_t, d_t, dst - d_t, k,
                                    -(-n_b // k)))
    return _SplitX((p.z, p.y, p.x), tzb, n_b, tuple(sides))


def split_x_supported(spec: GridSpec, dtype) -> bool:
    """Whether :func:`make_split_x_pack` / :func:`make_split_x_unpack`
    handle this block: the x self-fill's own gates (fp32, aligned, halo and
    source columns inside the two edge lane-tiles, ``p.z >= 4``, the VMEM
    budget). The carrier staging adds 4 tiles a quantity to the x fill's
    ``8 * TZB``; the kernels' ``vmem_limit_bytes`` has the room."""
    return self_fill_supported(spec, "x", dtype)


def split_x_carrier_shapes(spec: GridSpec, nq: int):
    """The carriers one pack returns and one unpack takes, forward first
    (an inactive direction has none)."""
    g = _split_x_geom(spec, nq)
    return [(s.groups, nq, g.shape[1], _LANE) for s in g.sides]


def _split_x_check(spec: GridSpec, nq: int):
    if not split_x_supported(spec, jnp.float32):
        raise ValueError("split-x pack/unpack unsupported on this spec")
    if not 1 <= nq <= max_fill_group(spec):
        raise ValueError(
            f"split-x group size {nq} outside [1, {max_fill_group(spec)}]")


def _lane_iota(py: int):
    return jax.lax.broadcasted_iota(jnp.int32, (py, _LANE), 1)


def make_split_x_pack(spec: GridSpec, nq: int = 1, vma=None,
                      interpret: bool = False):
    """``pack(b0, .., b{nq-1}) -> [carrier, ..]``: read both edge lane-tiles
    of every fp32 ``(pz, py, px)`` field once and write the lane-dense
    carriers of the active directions (forward first). The fields are plain
    inputs: nothing is written to them. Both sources are interior columns
    the x phase never writes, so both packs may precede both unpacks."""
    _split_x_check(spec, nq)
    g = _split_x_geom(spec, nq)
    (pz, py, px), TZB, n_b = g.shape, g.tzb, g.n_b
    ns = len(g.sides)
    tiles = [(n, q) for n in range(ns) for q in range(nq)]  # one DMA each

    def kernel(*refs):
        fields = refs[:nq]
        carriers = refs[nq : nq + ns]
        scratch = refs[nq + ns :]
        i = pl.program_id(0)
        slot = jnp.mod(i, 2)
        z_of = lambda step: jnp.minimum(step * TZB, pz - TZB)
        lane = _lane_iota(py)

        def rd(n, q, s, step):
            buf, sem = scratch[4 * n], scratch[4 * n + 2]
            return pltpu.make_async_copy(
                fields[q].at[pl.ds(z_of(step), TZB), :,
                             pl.ds(g.sides[n].src_tile, _LANE)],
                buf.at[s, q], sem.at[s])

        @pl.when(i == 0)
        def _():
            for n, q in tiles:
                rd(n, q, slot, i).start()

        @pl.when(i + 1 < n_b)
        def _():
            for n, q in tiles:
                rd(n, q, 1 - slot, i + 1).start()

        for n, q in tiles:
            rd(n, q, slot, i).wait()

        for n, side in enumerate(g.sides):
            buf, cb, s_out = scratch[4 * n], scratch[4 * n + 1], scratch[4 * n + 3]
            grp = i // side.k
            sub = i - grp * side.k
            gs = jnp.mod(grp, 2)

            def out(group, cb=cb, s_out=s_out, n=n):
                s = jnp.mod(group, 2)
                return pltpu.make_async_copy(
                    cb.at[s], carriers[n].at[group], s_out.at[s])

            @pl.when(sub == 0)
            def _():
                # this staging slot last held group grp - 2
                @pl.when(grp >= 2)
                def _():
                    out(grp - 2).wait()

                cb[gs] = jnp.zeros((nq, py, _LANE), jnp.float32)

            for q in range(nq):
                acc = cb[gs, q]
                for j in range(TZB):
                    at = (sub * TZB + j) * side.r
                    moved = pltpu.roll(
                        buf[slot, q, j], jnp.mod(at - side.src + _LANE, _LANE), 1)
                    acc = jnp.where(
                        (lane >= at) & (lane < at + side.r), moved, acc)
                cb[gs, q] = acc

            @pl.when((sub == side.k - 1) | (i == n_b - 1))
            def _():
                out(grp).start()

            @pl.when(i == n_b - 1)
            def _():
                if side.groups >= 2:
                    out(grp - 1).wait()
                out(grp).wait()

    tile_bytes = py * _LANE * 4
    _record_dma_bytes(
        nq, g.shape, len(tiles) * n_b * TZB * tile_bytes,
        sum(s.groups for s in g.sides) * nq * tile_bytes,
        name="halo.split_x.bytes_dma", part="pack")
    scratch = []
    for _ in g.sides:
        scratch += [
            pltpu.VMEM((2, nq, TZB, py, _LANE), jnp.float32),
            pltpu.VMEM((2, nq, py, _LANE), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ]
    vma = frozenset(vma) if vma is not None else None
    return scopes.kernel_call(
        "split_x_pack", kernel,
        grid=(n_b,),
        out_shape=[jax.ShapeDtypeStruct(shp, jnp.float32, vma=vma)
                   for shp in split_x_carrier_shapes(spec, nq)],
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * nq,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * ns,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )


def make_split_x_unpack(spec: GridSpec, nq: int = 1, vma=None,
                        interpret: bool = False):
    """``unpack(b0, .., b{nq-1}, carrier, ..) -> [b0', ..]``: in place
    (``input_output_aliases``), read both edge lane-tiles of every field,
    overwrite the low halo lanes from the carrier that arrived from the -x
    neighbour (its forward carrier) and the high halo lanes from the +x
    neighbour's backward carrier, write the tiles back. Nothing else of a
    field is read or written."""
    _split_x_check(spec, nq)
    g = _split_x_geom(spec, nq)
    (pz, py, px), TZB, n_b = g.shape, g.tzb, g.n_b
    ns = len(g.sides)
    tiles = [(n, q) for n in range(ns) for q in range(nq)]  # one DMA each

    def kernel(*refs):
        carriers = refs[nq : nq + ns]
        outs = refs[nq + ns : 2 * nq + ns]
        scratch = refs[2 * nq + ns :]
        i = pl.program_id(0)
        slot = jnp.mod(i, 2)
        z_of = lambda step: jnp.minimum(step * TZB, pz - TZB)
        lane = _lane_iota(py)

        def tile(n, q, step):
            return outs[q].at[pl.ds(z_of(step), TZB), :,
                              pl.ds(g.sides[n].dst_tile, _LANE)]

        def rd(n, q, s, step):
            rb, s_r = scratch[6 * n], scratch[6 * n + 3]
            return pltpu.make_async_copy(tile(n, q, step), rb.at[s, q],
                                         s_r.at[s])

        def wr(n, q, s, step):
            wb, s_w = scratch[6 * n + 1], scratch[6 * n + 4]
            return pltpu.make_async_copy(wb.at[s, q], tile(n, q, step),
                                         s_w.at[s])

        def cin(n, group):
            cb, s_c = scratch[6 * n + 2], scratch[6 * n + 5]
            s = jnp.mod(group, 2)
            return pltpu.make_async_copy(
                carriers[n].at[group], cb.at[s], s_c.at[s])

        @pl.when(i == 0)
        def _():
            for n, q in tiles:
                rd(n, q, slot, i).start()
            for n in range(ns):
                cin(n, 0).start()

        @pl.when(i + 1 < n_b)
        def _():
            for n, q in tiles:
                rd(n, q, 1 - slot, i + 1).start()

        # the write buffers of batch i - 2 (same slot) must have drained
        @pl.when(i >= 2)
        def _():
            for n, q in tiles:
                wr(n, q, slot, i - 2).wait()

        for n, side in enumerate(g.sides):
            rb, wb, cb = scratch[6 * n : 6 * n + 3]
            grp = i // side.k
            sub = i - grp * side.k
            gs = jnp.mod(grp, 2)

            @pl.when(sub == 0)
            def _():
                cin(n, grp).wait()

                # the other slot's group was last read a step ago
                @pl.when(grp + 1 < side.groups)
                def _():
                    cin(n, grp + 1).start()

            halo = (lane >= side.dst) & (lane < side.dst + side.r)
            for q in range(nq):
                rd(n, q, slot, i).wait()
                arrived = cb[gs, q]
                for j in range(TZB):
                    at = (sub * TZB + j) * side.r
                    moved = pltpu.roll(
                        arrived, jnp.mod(side.dst - at + _LANE, _LANE), 1)
                    wb[slot, q, j] = jnp.where(halo, moved, rb[slot, q, j])
                wr(n, q, slot, i).start()

        @pl.when(i == n_b - 1)
        def _():
            for n, q in tiles:
                if n_b >= 2:
                    wr(n, q, 1 - slot, i - 1).wait()
                wr(n, q, slot, i).wait()

    tile_bytes = py * _LANE * 4
    moved = len(tiles) * n_b * TZB * tile_bytes
    _record_dma_bytes(
        nq, g.shape,
        moved + sum(s.groups for s in g.sides) * nq * tile_bytes, moved,
        name="halo.split_x.bytes_dma", part="unpack")
    scratch = []
    for _ in g.sides:
        scratch += [
            pltpu.VMEM((2, nq, TZB, py, _LANE), jnp.float32),
            pltpu.VMEM((2, nq, TZB, py, _LANE), jnp.float32),
            pltpu.VMEM((2, nq, py, _LANE), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ]
    shape = jax.ShapeDtypeStruct(
        g.shape, jnp.float32, vma=frozenset(vma) if vma is not None else None)
    return scopes.kernel_call(
        "split_x_unpack", kernel,
        grid=(n_b,),
        out_shape=(shape,) * nq,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (nq + ns),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * nq,
        scratch_shapes=scratch,
        input_output_aliases={q: q for q in range(nq)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )
