"""7-point Jacobi heat-diffusion stencil and its fused distributed step.

TPU-native re-design of the reference demo kernel and iteration structure
(reference: bin/jacobi3d.cu:30-85 kernel, :296-377 overlap loop): each
compute cell becomes the average of its six face neighbors; a "hot" sphere
(value 1) fixed at x = 1/3 and a "cold" sphere (value 0) at x = 2/3 of the
global domain, radius X/10, are re-imposed every step. Initial condition is
0.5 everywhere (bin/jacobi3d.cu:25).

The kernel is shifted array slices over the halo-padded block — XLA fuses
the adds, divide, and sphere masks into one elementwise pass (the analogue
of the reference's single CUDA kernel). The comm/compute overlap of the
reference (interior kernel on its own stream, CPU-polled exchange, then
exterior kernels, src/stencil.cu:1002-1186) becomes *dataflow*: inside one
jitted step the interior sweep depends only on pre-exchange data, so XLA is
free to run the halo ``ppermute``s concurrently with it, then the exterior
slabs consume the exchanged halos. No host polling exists.

Sphere masks are precomputed host-side from global coordinates and sharded
alongside the quantity (step-invariant).
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..geometry import Dim3, Radius, Rect3, exterior_regions, interior_region
from ..obs import scopes, telemetry
from ..parallel.exchange import BLOCK_PSPEC, HaloExchange, Method
from ..utils import timer
from . import double_buffer

HOT_TEMP = 1.0
COLD_TEMP = 0.0
INIT_TEMP = (HOT_TEMP + COLD_TEMP) / 2


def _masks(sel):
    """The hot and cold sphere masks of a step, from the packed ``sel``."""
    with scopes.scope(scopes.MASK):
        return sel == 1, sel == 2


def _reshape(a, shape):
    """A reshape round a kernel call (``stencil.carry``)."""
    with scopes.scope(scopes.CARRY):
        return a.reshape(shape)


def _loop_args(ex: HaloExchange):
    """The abstract (curr, nxt, sel) a jacobi loop over ``ex`` is built
    for: what ``obs.scopes.op_map`` lowers it with."""
    shape, sh = ex.spec.stacked_shape_zyx(), ex.sharding()
    f32 = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sh)
    return f32, f32, jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sh)


def _module(iters) -> str:
    """The stable module name of the chunk loop (or the single step)."""
    return scopes.JACOBI_STEP if iters is None else scopes.JACOBI_LOOP


def _jit_jacobi(ex: HaloExchange, iters, fn, **jit_kwargs):
    """The chunk loop (or the single step) under its stable module name,
    registered with its abstract arguments."""
    return scopes.jit_loop(_module(iters), fn, _loop_args(ex), **jit_kwargs)


def _rect_slices(rect: Rect3, dz=0, dy=0, dx=0):
    return (
        slice(rect.lo.z + dz, rect.hi.z + dz),
        slice(rect.lo.y + dy, rect.hi.y + dy),
        slice(rect.lo.x + dx, rect.hi.x + dx),
    )


def jacobi_sweep(src, out, rect: Rect3, masks=None):
    """Write the 6-neighbor average of ``src`` into region ``rect`` of
    ``out`` (allocation-local coords; leading dims allowed). ``masks`` is an
    optional ``(hot, cold)`` pair of bool arrays shaped like ``src``."""
    avg = (
        src[(..., *_rect_slices(rect, dx=-1))]
        + src[(..., *_rect_slices(rect, dx=1))]
        + src[(..., *_rect_slices(rect, dy=-1))]
        + src[(..., *_rect_slices(rect, dy=1))]
        + src[(..., *_rect_slices(rect, dz=-1))]
        + src[(..., *_rect_slices(rect, dz=1))]
    ) / 6
    if masks is not None:
        hot, cold = masks
        sl = (..., *_rect_slices(rect))
        avg = jnp.where(hot[sl], HOT_TEMP, jnp.where(cold[sl], COLD_TEMP, avg))
    return out.at[(..., *_rect_slices(rect))].set(avg.astype(out.dtype))


def _sweep_shell_wrap_x(src, out, rect: Rect3, masks=None):
    """:func:`jacobi_sweep` for a shell rect spanning the FULL x extent of
    a tight-x block (``Radius.without_x``: no x halo columns exist, the x
    axis is single-block periodic): the x neighborhood comes from rolls.
    Operand order matches the Pallas kernel's (x_lo + x_hi + y + z) so
    overlap-patched cells are bit-identical to serialized ones."""
    c = src[(..., *_rect_slices(rect))]
    avg = (
        jnp.roll(c, 1, -1)
        + jnp.roll(c, -1, -1)
        + src[(..., *_rect_slices(rect, dy=-1))]
        + src[(..., *_rect_slices(rect, dy=1))]
        + src[(..., *_rect_slices(rect, dz=-1))]
        + src[(..., *_rect_slices(rect, dz=1))]
    ) / 6
    if masks is not None:
        hot, cold = masks
        sl = (..., *_rect_slices(rect))
        avg = jnp.where(hot[sl], HOT_TEMP, jnp.where(cold[sl], COLD_TEMP, avg))
    return out.at[(..., *_rect_slices(rect))].set(avg.astype(out.dtype))


def _patch_x_edges_sidebuf(src, out, compute: Rect3, xlo, xhi, masks=None):
    """Recompute the two x-edge columns of the compute region from
    exchanged side buffers (multi-block tight-x: the kernel's lane rolls
    wrapped onto the block's OWN columns, wrong at block edges). Operand
    order matches the kernel's x_lo + x_hi + y + z sum for bit parity."""
    lo, hi = compute.lo, compute.hi
    zy = (slice(lo.z, hi.z), slice(lo.y, hi.y))

    def col(x0, dz=0, dy=0):
        return src[(..., slice(lo.z + dz, hi.z + dz),
                    slice(lo.y + dy, hi.y + dy), slice(x0, x0 + 1))]

    for edge, x_lo, x_hi in (
        (lo.x, xlo[(..., *zy, slice(-1, None))], col(lo.x + 1)),
        (hi.x - 1, col(hi.x - 2), xhi[(..., *zy, slice(0, 1))]),
    ):
        avg = (
            x_lo + x_hi
            + col(edge, dy=-1) + col(edge, dy=1)
            + col(edge, dz=-1) + col(edge, dz=1)
        ) / 6
        dst = (..., *zy, slice(edge, edge + 1))
        if masks is not None:
            hot, cold = masks
            avg = jnp.where(hot[dst], HOT_TEMP,
                            jnp.where(cold[dst], COLD_TEMP, avg))
        out = out.at[dst].set(avg.astype(out.dtype))
    return out


def _sweep_slab_dyn(src3, o3, sel3, lo, size):
    """Re-sweep one dynamic-offset boundary shell ``[lo, lo + size)`` of a
    (pz, py, px) block from exchanged data ``src3`` into ``o3``. ``size`` is
    static; ``lo`` entries may be traced (uneven-partition hi-side shells).
    Bit-parity with :func:`jacobi_sweep`: same operand order, same divide."""
    lz, ly, lx = lo
    sz, sy, sx = size
    slab = lax.dynamic_slice(
        src3, (lz - 1, ly - 1, lx - 1), (sz + 2, sy + 2, sx + 2)
    )
    avg = (
        slab[1 : sz + 1, 1 : sy + 1, 0:sx]
        + slab[1 : sz + 1, 1 : sy + 1, 2 : sx + 2]
        + slab[1 : sz + 1, 0:sy, 1 : sx + 1]
        + slab[1 : sz + 1, 2 : sy + 2, 1 : sx + 1]
        + slab[0:sz, 1 : sy + 1, 1 : sx + 1]
        + slab[2 : sz + 2, 1 : sy + 1, 1 : sx + 1]
    ) / 6
    selc = lax.dynamic_slice(sel3, lo, size)
    with scopes.scope(scopes.MASK):
        hot, cold = selc == 1, selc == 2
    avg = jnp.where(hot, HOT_TEMP, jnp.where(cold, COLD_TEMP, avg))
    return lax.dynamic_update_slice(o3, avg.astype(o3.dtype), lo)


def _patch_shells_dyn(spec, src, out, sel, multi_block_only: bool):
    """Patch every boundary shell of an uneven-partition block from the
    exchanged state (the dynamic-extent exterior pass; see ops/shells.py)."""
    from .shells import dyn_block_sizes, include_axes, shell_regions

    p = spec.padded()
    shp = out.shape
    with scopes.scope(scopes.SWEEP_SHELL):
        s3 = src.reshape(p.z, p.y, p.x)
        o3 = out.reshape(p.z, p.y, p.x)
        sel3 = sel.reshape(p.z, p.y, p.x)
        sizes = dyn_block_sizes(spec)
        for lo, size in shell_regions(
                spec, sizes, include_axes(spec, multi_block_only)):
            o3 = _sweep_slab_dyn(s3, o3, sel3, lo, size)
        return o3.reshape(shp)


def jacobi6_block(block, radius: Radius, masks=None):
    """One full-compute-region Jacobi sweep over a padded block, in place of
    the halo ring (reference kernel over the whole region,
    bin/jacobi3d.cu:343-360)."""
    if min(radius.x(-1), radius.x(1), radius.y(-1), radius.y(1),
           radius.z(-1), radius.z(1)) < 1:
        raise ValueError("jacobi needs face radius >= 1")
    *_, pz, py, px = block.shape
    off = Dim3(radius.x(-1), radius.y(-1), radius.z(-1))
    hi = Dim3(px - radius.x(1), py - radius.y(1), pz - radius.z(1))
    return jacobi_sweep(block, block, Rect3(off, hi), masks)


def make_jacobi_step(ex: HaloExchange, overlap: bool = True, use_pallas=None,
                     standard_spheres: bool = True, interpret: bool = False):
    """Build the jitted distributed iteration: exchange + stencil + swap.

    Returns ``step(curr, nxt, sel) -> (new_curr, new_next)`` over stacked
    sharded arrays; both buffers are donated. The double-buffer swap of the
    reference (a pointer swap, src/local_domain.cu:67-84) is realized in two
    halves (ops/double_buffer.py): on the device each buffer keeps its slot
    (the new state is written over ``nxt``, the jitted program returns the
    pair in input order, so donation aliases both and no copy is made), and
    the returned callable swaps the two handles on the host.

    ``overlap=True`` replicates the reference's interior/exterior split
    (bin/jacobi3d.cu:296-368): the interior sweep reads pre-exchange data
    (it never touches halos, src/stencil.cu:878-921), the ≤6 exterior slabs
    read exchanged halos. On an uneven partition the exterior slabs become
    dynamic-offset shells (ops/shells.py) — per-block extents are static per
    block index, so the overlap structure survives uneven splits exactly as
    the reference's per-LocalDomain regions do (src/stencil.cu:878-977).
    """
    # host-side build phase (kernel selection + closure construction); the
    # first invocation's XLA compile lands in the caller's warmup span
    with timer.timed("jacobi.build"), timer.trace_range("jacobi.build"):
        return _compile_jacobi(ex, overlap, iters=None, use_pallas=use_pallas,
                               standard_spheres=standard_spheres,
                               interpret=interpret)


def make_jacobi_loop(ex: HaloExchange, iters: int, overlap: bool = True, use_pallas=None,
                     standard_spheres: bool = True, interpret: bool = False,
                     temporal_k: Optional[int] = None,
                     multistep_rows: Optional[int] = None):
    """Like :func:`make_jacobi_step` but runs ``iters`` iterations inside one
    compiled program — one host dispatch per chunk. Same contract:
    ``loop(curr, nxt, sel) -> (new_curr, new_next)``, inputs donated. A
    ``while`` trip runs TWO steps (or two multistep passes) so that it ends
    with both buffers where it began, the odd step runs after the loop, and
    the handles are swapped on the host when the number of steps (multistep
    passes plus single steps) is odd: no compiled code ever exchanges the
    pair (ops/double_buffer.py; the counter ``loop.pingpong`` says what was
    built).

    This is the ``USE_CUDA_GRAPH`` analogue taken further: where the
    reference graph-captures one exchange (packer.cu:96-103), XLA compiles
    the whole iteration loop, so a chunk costs one host dispatch.

    ``standard_spheres`` declares that the ``sel`` argument will be the
    standard jacobi3d hot/cold spheres (``sphere_sel(global_size)``). Only
    then may the temporal-blocked kernel engage, because it re-derives the
    spheres from coordinates instead of reading ``sel``. Pass ``False``
    when driving the step with a custom or empty ``sel``.

    ``temporal_k`` caps the temporal-blocking depth explicitly. Weak-scaling
    comparisons need it: a single-block mesh has no radius bound and would
    run the full default depth (k=12) while an N-chip deep-halo run is
    capped at the realized radius,
    conflating temporal depth with scaling in the efficiency column
    (ADVICE r3).

    ``multistep_rows`` forces the multistep's row-strip height (None =
    :func:`~stencil_tpu.ops.pallas_stencil.plan_multistep_staging` picks:
    full planes while they reach the depth, row strips beyond) — the
    probing knob behind ``jacobi3d --multistep-rows``.
    """
    # same build-phase accounting as make_jacobi_step: the multistep plan
    # (staging/row-tiling decisions) is constructed here, on the host
    with timer.timed("jacobi.build"), timer.trace_range("jacobi.build"):
        return _compile_jacobi(ex, overlap, iters=iters, use_pallas=use_pallas,
                               standard_spheres=standard_spheres,
                               interpret=interpret, temporal_k=temporal_k,
                               multistep_rows=multistep_rows)


def _want_pallas(ex: HaloExchange, use_pallas) -> bool:
    if use_pallas is not None:
        return bool(use_pallas)
    devs = ex.mesh.devices.flatten()
    # resident (oversubscribed) shards stack whole padded blocks along the
    # leading block dims: the per-block kernels run once per resident
    # (VERDICT r4 item 7). Uneven + resident keeps the XLA path (the
    # dynamic-shell machinery is single-resident).
    if ex.oversubscribed and not ex.spec.is_uniform():
        return False
    return ex.spec.aligned and all(d.platform == "tpu" for d in devs)


def _compile_jacobi_auto(ex: HaloExchange, overlap: bool, iters,
                         temporal_k: Optional[int] = None,
                         multistep_rows: Optional[int] = None):
    """The AUTO_SPMD iteration: ONE global jitted program over the sharded
    stacked arrays, with no shard_map and no hand-written collectives — the
    halo fill is the exchange's :meth:`~HaloExchange.auto_fill` slab program
    and the sweep is the same shifted-slice kernel applied with its leading
    block dims intact, so the SPMD partitioner synthesizes every
    collective-permute (the bench_mpi_pack question asked of the whole
    step, not just the exchange). The reference overlap structure survives
    as dataflow exactly as in the manual path: on uniform partitions the
    interior sweep reads pre-exchange data and only the exterior slabs
    consume the exchanged halos; uneven partitions serialize (the dynamic
    shells need per-device axis_index, a shard_map concept). Bit parity
    with the AXIS_COMPOSED XLA path is pinned in tests/test_auto_spmd.py.
    """
    spec = ex.spec
    r = spec.radius
    assert min(
        r.x(-1), r.x(1), r.y(-1), r.y(1), r.z(-1), r.z(1)
    ) >= 1, (
        "the AUTO_SPMD jacobi path needs face radius >= 1 on every side "
        "(no Pallas in-kernel x wrap exists in the global program)"
    )
    if temporal_k is not None or multistep_rows is not None:
        # an explicit temporal request must never be conflated with the
        # per-step program this path compiles (the ADVICE-r3 rule the
        # temporal_k knob exists for)
        from ..utils import logging as log

        log.warn(
            f"temporal_k={temporal_k} multistep_rows={multistep_rows} "
            "ignored: the temporal multistep is a Pallas/shard_map "
            "construct; the AUTO_SPMD path runs per-step global sweeps"
        )
    off = spec.compute_offset()
    compute = Rect3(off, off + spec.base)
    interior = interior_region(compute, r)
    exteriors = exterior_regions(compute, interior)
    use_overlap = overlap and spec.is_uniform()

    def body(curr, nxt, sel):
        masks = _masks(sel)
        if use_overlap:
            # overlap as dataflow: the interior never touches halos, so the
            # partitioner is free to run its synthesized permutes
            # concurrently with it; the exterior slabs read exchanged halos
            out = jacobi_sweep(curr, nxt, interior, masks)
            cur2 = ex.auto_fill(curr)
            with scopes.scope(scopes.SWEEP_SHELL):
                for rect in exteriors:
                    out = jacobi_sweep(cur2, out, rect, masks)
        else:
            # serialized (or uneven): exchange, then sweep the full base
            # extent — cells past an uneven block's true size are dead pad
            cur2 = ex.auto_fill(curr)
            out = jacobi_sweep(cur2, nxt, compute, masks)
        return out, cur2

    def entry_fn(curr, nxt, sel):
        if iters is None:
            return body(curr, nxt, sel)
        return jax.lax.fori_loop(
            0, iters, lambda _, cn: body(cn[0], cn[1], sel), (curr, nxt)
        )

    sh = ex.sharding()
    return _jit_jacobi(
        ex, iters, entry_fn, in_shardings=(sh,) * 3, out_shardings=(sh, sh),
        donate_argnums=(0, 1),
    )


def _compile_jacobi(ex: HaloExchange, overlap: bool, iters, use_pallas=None,
                    standard_spheres: bool = True, interpret: bool = False,
                    temporal_k: Optional[int] = None,
                    multistep_rows: Optional[int] = None):
    spec = ex.spec
    r = spec.radius
    if ex.method == Method.AUTO_SPMD:
        return _compile_jacobi_auto(ex, overlap, iters, temporal_k,
                                    multistep_rows)
    assert min(r.y(-1), r.y(1), r.z(-1), r.z(1)) >= 1, (
        "jacobi needs face radius >= 1 on every side"
    )
    tight_x = min(r.x(-1), r.x(1)) < 1
    # tight-x on a MULTI-BLOCK x axis: kernels still roll x block-locally
    # (wrong at block edges) and the exchange delivers the true neighbor
    # columns as side buffers, from which the two x-edge columns are
    # patched (VERDICT r3 item 5; reference pack-to-buffer economics,
    # src/pack_kernel.cu:3-54)
    side_x = tight_x and spec.dim.x > 1
    if tight_x:
        # zero-x-radius layout (Radius.without_x): no x halo columns exist;
        # only the Pallas kernels can form the x neighborhood (lane rolls).
        # Single-block x wraps periodically in-kernel; multi-block x takes
        # side buffers. Multi-block y/z overlap shells span the full x
        # extent and take the roll-aware sweep (_sweep_shell_wrap_x).
        assert spec.base.x % 128 == 0, (
            "zero x radius requires lane-aligned per-block x extents"
        )
        assert spec.is_uniform(), (
            "tight-x with multi-block axes requires uniform splits (dynamic "
            "shells keep inline halos)"
        )
        assert _want_pallas(ex, use_pallas), (
            "zero x radius requires the Pallas fast path (in-kernel x wrap)"
        )
        assert ex.resident.x == 1, (
            "tight-x does not support x residency (side buffers are "
            "single-resident along x)"
        )
    off = spec.compute_offset()
    compute = Rect3(off, off + spec.base)
    interior = interior_region(compute, r)
    exteriors = exterior_regions(compute, interior)
    use_overlap = overlap and spec.is_uniform()
    # uneven partitions overlap too — via dynamic-offset shells instead of
    # static exterior rects (per-block extents are static per block index).
    # Resident (oversubscribed) shards carry a stacked leading block dim the
    # shell machinery's (pz,py,px) reshape cannot express — those fall back
    # to the serialized exchange-then-sweep path instead of crashing at
    # trace time (ADVICE r3).
    use_dyn_overlap = overlap and not spec.is_uniform() and not ex.oversubscribed

    pallas_sweep = None
    pallas_axes = None
    if _want_pallas(ex, use_pallas):
        from .pallas_stencil import make_pallas_jacobi_sweep, sel_z_range
        from ..parallel.mesh import AXIS_X, AXIS_Y, AXIS_Z, MESH_AXES

        # axes with a single block are periodic onto themselves: the kernel
        # fills those halos from the opposite face (wrap), and the exchange
        # runs only on the multi-block axes (engages exchange_block's axis
        # subsetting, AXIS_COMPOSED only). On one chip the exchange
        # vanishes entirely.
        if ex.method == Method.AXIS_COMPOSED:
            # side_x: the kernel rolls x block-locally exactly like a
            # self-wrap axis; the block-edge columns are patched from the
            # exchanged side buffers afterwards
            wrap = (spec.dim.z == 1, spec.dim.y == 1,
                    spec.dim.x == 1 or side_x)
            pallas_axes = tuple(
                name for name, w in zip((AXIS_Z, AXIS_Y, AXIS_X), wrap) if not w
            )
        else:
            assert not side_x, (
                "multi-block tight-x requires Method.AXIS_COMPOSED "
                "(side buffers compose with axis phases)"
            )
            wrap = (False, False, False)
            pallas_axes = None  # DIRECT26 has no axis phases to subset
        # interpret mode (CI integration tests): the pallas HLO interpreter
        # cannot propagate varying-manual-axes metadata
        pallas_sweep = make_pallas_jacobi_sweep(
            spec, sel_z_range(spec),
            vma=None if interpret else MESH_AXES,
            wrap=wrap, interpret=interpret,
        )

    # shells to re-sweep from exchanged halos when the Pallas fast path
    # overlaps comm with compute: only the sides whose axis actually has
    # multiple blocks (self-wrap sides are filled inside the kernel). The
    # redundant compute is the shell volume (~6 r-thick faces, <1% at
    # benchmark sizes) — the price of making the full-region kernel the
    # "interior" of the reference's overlap structure
    # (bin/jacobi3d.cu:296-368) without a second kernel variant.
    pallas_shells = []
    if pallas_sweep is not None and pallas_axes:
        shrink_lo = Dim3(
            r.x(-1) if spec.dim.x > 1 else 0,
            r.y(-1) if spec.dim.y > 1 else 0,
            r.z(-1) if spec.dim.z > 1 else 0,
        )
        shrink_hi = Dim3(
            r.x(1) if spec.dim.x > 1 else 0,
            r.y(1) if spec.dim.y > 1 else 0,
            r.z(1) if spec.dim.z > 1 else 0,
        )
        inner = Rect3(compute.lo + shrink_lo, compute.hi - shrink_hi)
        pallas_shells = exterior_regions(compute, inner)

    nres = ex.resident.flatten()

    def body(curr, nxt, sel):
        if pallas_sweep is not None:
            p = spec.padded()

            def sweep3(c, n):
                if nres == 1:
                    p3 = (p.z, p.y, p.x)
                    return _reshape(
                        pallas_sweep(_reshape(c, p3), _reshape(n, p3),
                                     _reshape(sel, p3)),
                        nxt.shape)
                # resident (oversubscribed) shard: the leading block dims
                # stack whole padded blocks, each with exchange-filled
                # halos — the per-block kernel runs once per resident
                p4 = (nres, p.z, p.y, p.x)
                cf, nf, sf = _reshape(c, p4), _reshape(n, p4), _reshape(sel, p4)
                outs = [pallas_sweep(cf[j], nf[j], sf[j]) for j in range(nres)]
                with scopes.scope(scopes.CARRY):
                    return jnp.stack(outs).reshape(nxt.shape)

            if pallas_axes is None:  # DIRECT26: no axis phases to subset
                cur2 = ex.exchange_block(curr)
                return sweep3(cur2, nxt), cur2
            if side_x:
                # multi-block x without inline halos: the kernel's x rolls
                # wrap onto the block's own columns; the exchange delivers
                # the true neighbor columns as side buffers and the two
                # edge columns are re-swept from them (after any y/z
                # shells, so edge cells inside shells are also correct)
                masks = _masks(sel)
                if use_overlap:
                    out = sweep3(curr, nxt)
                    cur2 = ex.exchange_block(curr)
                    xlo, xhi = ex.x_side_buffers(curr, 1)
                    with scopes.scope(scopes.SWEEP_SHELL):
                        for rect in pallas_shells:
                            out = _sweep_shell_wrap_x(cur2, out, rect, masks)
                else:
                    # FULL exchange (self-wrap fills included): the edge
                    # patch reads y/z halo rows of the edge columns, which
                    # the axis-subset exchange would leave stale
                    cur2 = ex.exchange_block(curr)
                    xlo, xhi = ex.x_side_buffers(cur2, 1)
                    out = sweep3(cur2, nxt)
                with scopes.scope(scopes.SWEEP_SHELL):
                    out = _patch_x_edges_sidebuf(
                        cur2, out, compute, xlo, xhi, masks)
                return out, cur2
            if not pallas_axes:  # every axis self-wraps: no exchange at all
                return sweep3(curr, nxt), curr
            if use_overlap:
                # overlap as dataflow (reference: interior kernel concurrent
                # with the exchange, src/stencil.cu:1002-1186): the full
                # sweep reads PRE-exchange data — XLA is free to schedule
                # the ppermutes concurrently — then the multi-block-axis
                # shells are re-swept from the exchanged halos. The shells'
                # stencils also read self-wrap-axis halos, which the kernel
                # normally wraps internally, so this path runs the FULL
                # exchange (self-wrap fills included), not the subset
                out = sweep3(curr, nxt)
                cur2 = ex.exchange_block(curr)
                masks = _masks(sel)
                shell_sweep = _sweep_shell_wrap_x if tight_x else jacobi_sweep
                with scopes.scope(scopes.SWEEP_SHELL):
                    for rect in pallas_shells:
                        out = shell_sweep(cur2, out, rect, masks)
                return out, cur2
            if use_dyn_overlap:
                # same structure, uneven partition: the kernel still wraps
                # self-wrap axes internally, so only multi-block-axis shells
                # need patching — at dynamic offsets (hi side of an uneven
                # axis sits at off + this_block_size - r)
                out = sweep3(curr, nxt)
                cur2 = ex.exchange_block(curr)
                out = _patch_shells_dyn(spec, cur2, out, sel, multi_block_only=True)
                return out, cur2
            cur2 = ex.exchange_block(curr, axes=pallas_axes)
            return sweep3(cur2, nxt), cur2
        masks = _masks(sel)
        if use_overlap:
            out = jacobi_sweep(curr, nxt, interior, masks)
            cur2 = ex.exchange_block(curr)
            with scopes.scope(scopes.SWEEP_SHELL):
                for rect in exteriors:
                    out = jacobi_sweep(cur2, out, rect, masks)
        elif use_dyn_overlap:
            # uneven: full-region sweep on PRE-exchange data (cells within r
            # of a boundary read stale halos and are re-swept below; jacobi
            # never reads the out buffer, so the over-write is harmless),
            # exchange concurrent by dataflow, then dynamic-offset shells on
            # every side (self-wrap halos are stale pre-exchange too)
            out = jacobi_sweep(curr, nxt, compute, masks)
            cur2 = ex.exchange_block(curr)
            out = _patch_shells_dyn(spec, cur2, out, sel, multi_block_only=False)
        else:
            cur2 = ex.exchange_block(curr)
            out = jacobi_sweep(cur2, nxt, compute, masks)
        # swap: computed buffer becomes curr, old curr becomes scratch
        return out, cur2

    # temporal blocking: advance k steps per HBM pass when the loop is
    # fused — the stencil is purely memory-bound, so HBM traffic drops
    # ~1/k. The depth cap is re-measured whenever the kernels change
    # (STENCIL_TEMPORAL_K_CAP probes deeper): the pre-tight-x kernels
    # plateaued at k=10 (3.20 ms/step, round 2); the tight-x kernels
    # plateau at k=12 (512^3 round 5: k=10 1.752, k=12 1.695, k=13 1.696
    # ms/iter — log deleted in PR 21; older unverified figure). Depth is
    # further bounded by
    # the z extent (pipeline needs nz >= 2k+1) and by the staging planes
    # fitting the VMEM budget ((k-1)*3 + 6 full planes). On a single
    # block every axis self-wraps in-kernel; on a uniform multi-block
    # mesh the same kernel runs in deep-halo mode — one radius-k exchange
    # per k steps (the communication-avoiding scheme; k is then also
    # bounded by the realized multi-block-axis radii, so drivers opt in
    # by realizing with radius k).
    multistep = None
    deep_halo = False
    TEMPORAL_K = 0
    STRIP_ROWS = None
    # side_x is excluded: its empty/partial pallas_axes would read as
    # "self-wrap" to the multistep, whose in-kernel x wrap is wrong at
    # block edges (deep-halo x needs radius >= k, which tight-x lacks)
    if (pallas_sweep is not None and pallas_axes is not None and not side_x
            and standard_spheres and iters and spec.is_uniform()):
        from .pallas_stencil import (MULTISTEP_VMEM_BUDGET,
                                     plan_multistep_staging,
                                     temporal_depth_cap)

        k_want = max(0, min(temporal_depth_cap(), (spec.base.z - 1) // 2,
                            iters))
        if temporal_k is not None:
            k_want = min(k_want, temporal_k)
        if pallas_axes:
            # multi-block: the fused multistep subsumes the overlap
            # structure, so it only engages when overlap was requested —
            # overlap=False must keep timing the serialized reference
            # structure (the A/B knob the benchmarks rely on)
            r_mb = [
                rr for m, rl, rh in (
                    (spec.dim.z > 1, r.z(-1), r.z(1)),
                    (spec.dim.y > 1, r.y(-1), r.y(1)),
                    (spec.dim.x > 1, r.x(-1), r.x(1)),
                ) if m for rr in (rl, rh)
            ]
            k_want = min(k_want, *r_mb)
        # staging plan: full planes while they reach k_want, row strips
        # when the plane size would otherwise self-cap the depth (the
        # 768^3 regime: k=4 full-plane -> k=12 row-tiled)
        k_cap, STRIP_ROWS = plan_multistep_staging(spec, k_want,
                                                   MULTISTEP_VMEM_BUDGET)
        if multistep_rows is not None:
            from .pallas_stencil import valid_strip_rows

            assert valid_strip_rows(spec, k_cap, multistep_rows), (
                f"multistep_rows={multistep_rows} illegal for k={k_cap}, "
                f"ny={spec.base.y}"
            )
            STRIP_ROWS = multistep_rows
        if pallas_axes:
            deep_halo = overlap and k_cap >= 2
            TEMPORAL_K = k_cap if deep_halo else 0
        else:
            TEMPORAL_K = k_cap
    if multistep_rows is not None and TEMPORAL_K < 2:
        # a probe run must never attribute legacy-path numbers to row
        # tiling because the multistep quietly failed to engage
        from ..utils import logging as log

        log.warn(
            f"multistep_rows={multistep_rows} ignored: the temporal "
            "multistep did not engage (overlap off, non-uniform partition, "
            "side-buffer tight-x, iters/radius too small, or non-Pallas "
            "path) — timings reflect the per-step kernels"
        )
    if TEMPORAL_K >= 2:
        from .pallas_stencil import (make_pallas_jacobi_multistep,
                                     multistep_staging)
        from ..parallel.mesh import MESH_AXES

        multistep = make_pallas_jacobi_multistep(
            spec, TEMPORAL_K,
            vma=None if interpret else MESH_AXES, interpret=interpret,
            rows=STRIP_ROWS,
        )
        # what was staged, once per build (full planes: rows 0)
        telemetry.get().counter(
            "kernel.multistep.staging", value=TEMPORAL_K, phase="compute",
            module=_module(iters),
            **multistep_staging(spec, TEMPORAL_K, STRIP_ROWS))

    # the static runs of exchanging steps this program makes, in order:
    # entry_fn makes them, double_buffer.jit_in_place derives trips and the
    # host swap from them
    if multistep is not None:
        n_multi, n_single = divmod(iters, TEMPORAL_K)
        counts = (n_multi,) + (1,) * n_single  # passes, then single steps
    else:
        counts = (1 if iters is None else iters,)

    def entry_fn(curr, nxt, sel):
        def step(cn):
            return body(*cn, sel)

        if multistep is not None:
            p = spec.padded()
            res = (ex.resident.z, ex.resident.y, ex.resident.x)
            if deep_halo:
                from ..parallel.mesh import AXIS_X, AXIS_Y, AXIS_Z

                idx = [
                    lax.axis_index(n) if d > 1 else 0
                    for n, d in ((AXIS_Z, spec.dim.z), (AXIS_Y, spec.dim.y),
                                 (AXIS_X, spec.dim.x))
                ]

                def origin(jz, jy, jx):
                    # global block index = device index * residents + j
                    # (leading block dims shard in contiguous chunks)
                    with scopes.scope(scopes.CARRY):
                        return jnp.stack([
                            jnp.asarray((idx[0] * res[0] + jz) * spec.base.z, jnp.int32),
                            jnp.asarray((idx[1] * res[1] + jy) * spec.base.y, jnp.int32),
                            jnp.asarray((idx[2] * res[2] + jx) * spec.base.x, jnp.int32),
                        ])

            def run_multi(c, x):
                p3 = (p.z, p.y, p.x)
                if nres == 1:
                    org = (origin(0, 0, 0),) if deep_halo else ()
                    return _reshape(
                        multistep(*org, _reshape(c, p3), _reshape(x, p3)),
                        c.shape)
                # resident shard: one multistep per stacked block, each at
                # its own global origin (residency implies multi-block axes,
                # so this is always the deep-halo form)
                assert deep_halo
                cf, xf = _reshape(c, (nres,) + p3), _reshape(x, (nres,) + p3)
                outs = []
                for j in range(nres):
                    jz, rem = divmod(j, res[1] * res[2])
                    jy, jx = divmod(rem, res[2])
                    outs.append(multistep(origin(jz, jy, jx), cf[j], xf[j]))
                with scopes.scope(scopes.CARRY):
                    return jnp.stack(outs).reshape(c.shape)

            def mbody(cn):
                c, x = cn
                if deep_halo:
                    # one radius-k exchange feeds k fused steps; self-wrap
                    # axes are still wrapped inside the kernel
                    c = ex.exchange_block(c, axes=pallas_axes)
                return (run_multi(c, x), c)

            cn = double_buffer.repeat(mbody, counts[0], (curr, nxt))
            for n in counts[1:]:
                cn = double_buffer.repeat(step, n, cn)
            return cn
        return double_buffer.repeat(step, counts[0], (curr, nxt))

    fn = jax.shard_map(
        entry_fn,
        mesh=ex.mesh,
        in_specs=(BLOCK_PSPEC,) * 3,
        out_specs=(BLOCK_PSPEC, BLOCK_PSPEC),
        check_vma=not interpret,
    )
    return double_buffer.jit_in_place(_module(iters), fn, _loop_args(ex),
                                      counts)


def make_batched_jacobi_loop(spec, iters: int, *, sharding=None,
                             sel_sharding=None, use_pallas: bool = False,
                             batch: Optional[int] = None,
                             interpret: bool = False):
    """The multi-tenant batched iteration: ``loop(curr, nxt, sel) ->
    (new_curr, new_next)`` over ``(B, pz, py, px)`` stacked tenant states,
    advancing every tenant ``iters`` steps inside ONE compiled program.

    ``spec`` describes ONE tenant as a single-block domain
    (``GridSpec(size, Dim3(1, 1, 1), radius)``); the leading batch axis
    stacks B independent tenants. Each tenant is its own periodic box:
    halos self-wrap per tenant (ops/halo_fill.wrap_fill_batched — the
    composed x->y->z fill order of a single-block HaloExchange), NEVER
    across the batch axis, and the sweep is the same
    :func:`jacobi_sweep` (leading dims ride the ``...`` slices), so each
    lane is bit-identical to running that tenant through the standard
    single-domain machinery (pinned by tests/test_campaign.py).

    The program is embarrassingly batch-parallel — zero collectives —
    so ``sharding`` (a ``NamedSharding`` splitting axis 0 over a 1-D
    device mesh) serves B tenants across the whole mesh under one jit:
    the serving program of the campaign driver
    (stencil_tpu/campaign/driver.py). ``sel_sharding`` covers the sel
    argument (pass a replicated sharding for a shared ``(pz, py, px)``
    sel, or reuse ``sharding`` for per-tenant sel).

    ``use_pallas=True`` swaps the XLA shifted-slice sweep for the Pallas
    kernel with a leading batch grid dimension and all-axes in-kernel
    wrap (``make_pallas_jacobi_sweep(batch=...)``) — the TPU fast path;
    it requires ``batch`` (static), an aligned spec, and a per-tenant
    ``(B, pz, py, px)`` sel. Buffers are NOT donated: the campaign
    driver keeps live references across rollbacks (fault/recover.py
    stash semantics), which donation would invalidate.
    """
    from ..geometry import Dim3 as _D3

    if spec.dim != _D3(1, 1, 1):
        raise ValueError(
            "batched tenants are single-block domains; got partition "
            f"{spec.dim} (spatial decomposition and tenant batching do "
            "not compose yet)"
        )
    r = spec.radius
    if min(r.x(-1), r.x(1), r.y(-1), r.y(1), r.z(-1), r.z(1)) < 1:
        raise ValueError("jacobi needs face radius >= 1 on every side")
    off = spec.compute_offset()
    compute = Rect3(off, off + spec.base)

    pallas_sweep = None
    if use_pallas:
        from .pallas_stencil import make_pallas_jacobi_sweep, sel_z_range

        if batch is None or batch < 1:
            raise ValueError("use_pallas needs the static batch size")
        pallas_sweep = make_pallas_jacobi_sweep(
            spec, sel_z_range(spec), wrap=(True, True, True),
            batch=batch, interpret=interpret,
        )

    from .halo_fill import wrap_fill_batched

    def body(curr, nxt, sel):
        if pallas_sweep is not None:
            # all three axes wrap in-kernel (each tenant is periodic onto
            # itself); jacobi reads only face halos, which the kernel
            # fills — no separate fill pass exists on this path
            out = pallas_sweep(curr, nxt, sel)
            return out, curr
        cur2 = wrap_fill_batched(spec, curr)
        masks = _masks(sel)
        out = jacobi_sweep(cur2, nxt, compute, masks)
        return out, cur2

    def entry_fn(curr, nxt, sel):
        if iters == 1:
            return body(curr, nxt, sel)
        return jax.lax.fori_loop(
            0, iters, lambda _, cn: body(cn[0], cn[1], sel), (curr, nxt)
        )

    with timer.timed("jacobi.build"), timer.trace_range("jacobi.build"):
        # named like the single-domain loop; the batch is the caller's, so
        # nothing is registered for op_map
        if sharding is None:
            return scopes.jit_loop(scopes.JACOBI_LOOP, entry_fn)
        return scopes.jit_loop(
            scopes.JACOBI_LOOP, entry_fn,
            in_shardings=(sharding, sharding, sel_sharding or sharding),
            out_shardings=(sharding, sharding),
        )


def sphere_masks(global_size) -> Tuple[np.ndarray, np.ndarray]:
    """Hot/cold sphere masks over the global [z,y,x] grid.

    Bit-parity with the reference's integer-truncated distance
    (bin/jacobi3d.cu:30-32,49): dist = int64(sqrtf(dx^2+dy^2+dz^2)),
    hot iff dist(hotCenter) <= X/10."""
    g = Dim3.of(global_size)
    hot_c = (g.x // 3, g.y // 2, g.z // 2)
    cold_c = (g.x * 2 // 3, g.y // 2, g.z // 2)
    rad = g.x // 10
    # sparse (broadcastable) coordinate axes: only the final dense d2 array
    # is full-size, not three int64 coordinate cubes
    z, y, x = np.meshgrid(
        np.arange(g.z), np.arange(g.y), np.arange(g.x), indexing="ij", sparse=True
    )

    def dist(c):
        d2 = (x - c[0]) ** 2 + (y - c[1]) ** 2 + (z - c[2]) ** 2
        return np.sqrt(d2.astype(np.float32)).astype(np.int64)

    hot = dist(hot_c) <= rad
    cold = (~hot) & (dist(cold_c) <= rad)
    return hot, cold


def sphere_sel(global_size) -> np.ndarray:
    """Hot/cold spheres packed into one int32 array: 0 stencil, 1 hot,
    2 cold — the layout both compute paths consume."""
    hot, cold = sphere_masks(global_size)
    sel = np.zeros(hot.shape, np.int32)
    sel[hot] = 1
    sel[cold] = 2
    return sel


def jacobi_reference(field: np.ndarray, masks, iters: int) -> np.ndarray:
    """Slow numpy reference with periodic wrap for correctness checks
    (the CPU reference of BASELINE.json config 1)."""
    hot, cold = masks
    f = field.astype(np.float64)
    for _ in range(iters):
        avg = (
            np.roll(f, 1, 2) + np.roll(f, -1, 2)
            + np.roll(f, 1, 1) + np.roll(f, -1, 1)
            + np.roll(f, 1, 0) + np.roll(f, -1, 0)
        ) / 6
        f = np.where(hot, HOT_TEMP, np.where(cold, COLD_TEMP, avg))
    return f
