"""Persistent whole-chunk mega-kernel: a k-step chunk in ONE kernel.

PR 14's fused kernel moved one exchange+substep into a single
``pallas_call``, but every step still pays a kernel launch and a host
dispatch round-trip — the floor that bounds small-domain and campaign
throughput (ROADMAP #7: the B=64 32^3 campaign p50 sits in
dispatch-dominated territory). This module takes the §5.8
kernel-initiated idea to its endpoint: ONE persistent kernel per k-step
chunk, with deep-halo (radius*k) staging trading redundant boundary
compute for k-fold fewer wire rounds — the classic communication-
avoiding temporal fusion. Launch count drops from O(steps) to
O(chunks).

The chunk schedule (both lowerings):

1. exchange radius*k-deep halos ONCE — in-kernel per-direction
   ``pltpu.make_async_remote_copy``s behind a neighbor barrier
   semaphore on TPU, the host-orchestrated plain REMOTE_DMA emulation
   elsewhere (``parallel/remote_emu.RemoteDmaEmulation`` at the deep
   radius the driver realized);
2. run k substeps with NO further exchange: substep s sweeps the
   region grown ``k - 1 - s`` cells beyond the compute region on every
   side — the shrinking valid strip of ``plan_multistep_staging``'s
   deep-halo math (ops/pallas_stencil.py). Grown-region cells are
   REDUNDANT recomputes of neighbor cells: the halo coordinate system
   is seamless (a halo cell at index ``off + n + j`` IS neighbor cell
   ``j``), and the sweep expression/operand order is byte-for-byte
   :func:`~stencil_tpu.ops.jacobi.jacobi_sweep`'s, so every redundant
   cell reproduces the neighbor's value bit-exactly — which is why the
   chunk output is bit-identical to the composed per-step baseline
   (tests/test_persistent_stencil.py pins it, uneven partitions and
   guarded rollbacks included).

Inter-chunk safety on TPU: the barrier semaphore at kernel start means
a neighbor's NEXT chunk cannot begin landing slabs into our halos
until every ring neighbor (including us) has entered its next kernel —
by which point this chunk's reads are done. Between substeps no data
crosses devices at all (the deep halo covers the whole chunk), so no
in-chunk barrier exists — that is the communication avoidance.

The ``sel`` contract: both lowerings read hot/cold sel values at
GROWN-region cells, so ``sel`` must arrive with its halos filled to
the realized radius — one ``ex(sel)`` per loop build (sel is
step-invariant; the step compilers in ops/jacobi.py do this).

The mega-kernel has not run on a chip (CHANGES.md PR 21, "not run on
the chip"). In the tests the all-self-wrap (single device)
form runs in interpret mode, parity-pinned against
the XLA chunk program including uneven z extents whose mod-3 plane
ring wraps mid-window; the crossing form waits for
``scripts/probe_persistent.py`` on hardware. Correctness on the CPU
mesh is owned by :func:`make_persistent_chunk_body` + the plain
REMOTE_DMA emulation (ops/jacobi._compile_jacobi_persistent).

First-cut scope, loud: uniform partitions and one resident block per
device for the TPU kernel (the CPU emulation owns uneven); multistep
depth k >= 2 (k == 1 IS the fused kernel — plan/ir.build_plan refuses
the degenerate combination).
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..geometry import DIRECTIONS_26, Dim3, Rect3
from ..obs import scopes


def persistent_kernel_supported(spec, resident) -> bool:
    """What the persistent TPU mega-kernel handles today: UNIFORM
    partitions, one resident block per device (static per-direction
    extents in-kernel). Uneven single-resident chunks run the
    host-orchestrated emulation; oversubscription is loud infeasibility
    at HaloExchange construction."""
    return spec.is_uniform() and resident == Dim3(1, 1, 1)


def chunk_schedule(iters: int, k: int) -> List[int]:
    """The chunk depths a ``iters``-step persistent loop runs: full
    depth-``k`` chunks plus one shallower tail chunk for the remainder
    (the tail reuses the same machinery at a smaller depth — still one
    exchange + one chunk program). Drives both the step loops and the
    launch-count census (2 host dispatches per entry)."""
    if k < 1:
        raise ValueError(f"persistent chunk depth must be >= 1, got {k}")
    if iters < 0:
        raise ValueError(f"iters must be >= 0, got {iters}")
    n, rem = divmod(iters, k)
    return [k] * n + ([rem] if rem else [])


def check_chunk_depth(spec, depth: int) -> None:
    """Loud refusal when the realized halo cannot feed a depth-``depth``
    chunk: substep 0 reads ``depth`` cells into the halo on every side,
    so every face radius must be >= depth. The planner refuses the same
    configurations statically (plan/cost.py ``feasible``'s scaled-radius
    check); this guards direct driver use."""
    r = spec.radius
    rmin = min(r.x(-1), r.x(1), r.y(-1), r.y(1), r.z(-1), r.z(1))
    if rmin < depth:
        raise ValueError(
            f"persistent chunk depth {depth} needs radius >= {depth} on "
            f"every side (realized min face radius is {rmin}): realize "
            "the spec at radius*k before building the chunk"
        )
    if min(spec.base.x, spec.base.y, spec.base.z) < depth:
        raise ValueError(
            f"persistent chunk depth {depth} exceeds a {spec.base} block "
            "interior: the shrinking valid strip would go negative "
            "(plan/cost.py prices this infeasible)"
        )


def make_persistent_chunk_body(spec, depth: int):
    """The XLA chunk program body: ``chunk(curr, nxt, sel) -> (out,
    scratch)`` over one exchange-filled padded block inside
    ``shard_map`` — ``depth`` substeps, NO exchange, substep ``s``
    sweeping the region grown ``depth - 1 - s`` cells per side. This is
    what ``_compile_jacobi_persistent`` compiles per mesh (ONE program
    dispatch per chunk) and what the interpret-mode mega-kernel is
    parity-pinned against.

    Works on uneven partitions with the SAME static base-extent rects:
    halo cells sit immediately adjacent to a block's true extent, so a
    grown sweep recomputes neighbor cells at the right coordinates;
    cells beyond ``true_size + grow`` compute garbage that nothing ever
    reads (the next substep's reads stop exactly at the valid edge, and
    the next chunk's exchange rewrites the halos)."""
    from .jacobi import jacobi_sweep

    check_chunk_depth(spec, depth)
    off = spec.compute_offset()
    base = spec.base

    def chunk(curr, nxt, sel):
        masks = (sel == 1, sel == 2)
        c, n = curr, nxt
        for s in range(depth):
            g = depth - 1 - s
            rect = Rect3(
                Dim3(off.x - g, off.y - g, off.z - g),
                Dim3(off.x + base.x + g, off.y + base.y + g,
                     off.z + base.z + g),
            )
            n = jacobi_sweep(c, n, rect, masks)
            c, n = n, c
        return c, n

    return chunk


def _deep_dir_phases(spec, mesh_dim):
    """Per-direction message records at the spec's FULL (deep) radius on
    a uniform partition: ``(direction, src, dst, shape, crossing)`` in
    (z, y, x) block-local coordinates — the DIRECT26 exact-extent
    geometry (faces, edges, AND corners: grown substeps read corner
    halos, unlike the per-step jacobi). Exact extents fill disjoint
    halo regions, so message order is free and all remote copies start
    concurrently (the fused kernel's argument, ops/fused_stencil.py)."""
    r = spec.radius
    off = spec.compute_offset()
    b = spec.base
    multi = {"z": mesh_dim.z > 1, "y": mesh_dim.y > 1, "x": mesh_dim.x > 1}
    out = []
    for d in DIRECTIONS_26:
        if spec.radius.dir(-d) == 0:
            continue
        src, dst, shape = [], [], []
        for axis, dc, o, s, rm, rp in (
            ("z", d.z, off.z, b.z, r.z(-1), r.z(1)),
            ("y", d.y, off.y, b.y, r.y(-1), r.y(1)),
            ("x", d.x, off.x, b.x, r.x(-1), r.x(1)),
        ):
            if dc == 1:
                src.append(o + s - rm)
                dst.append(o - rm)
                shape.append(rm)
            elif dc == -1:
                src.append(o)
                dst.append(o + s)
                shape.append(rp)
            else:
                src.append(o)
                dst.append(o)
                shape.append(s)
        crossing = any(
            comp != 0 and multi[axis]
            for axis, comp in (("z", d.z), ("y", d.y), ("x", d.x))
        )
        out.append((d, tuple(src), tuple(dst), tuple(shape), crossing))
    return out


def make_persistent_jacobi_kernel(spec, plan, k: int, dtype=jnp.float32,
                                  collective_id: int = 0,
                                  interpret: bool = False):
    """The whole-chunk mega-kernel: ``fn(curr, nxt, sel) -> (curr',
    out', sel')`` — ONE ``pallas_call`` per k-step chunk:

    barrier with every ring neighbor → start every per-direction deep
    (radius*k) remote copy concurrently + local self-wrap hand-offs →
    wait the recv semaphores → k plane-streamed substeps over the
    shrinking grown regions, with a mod-3 ring-indexed 3-plane VMEM
    window per substep (PR 1's modular-slot machinery: each input plane
    loads exactly once per substep, no plane copies) and the substeps
    ping-ponging between the two aliased HBM buffers.

    ``curr'``/``out'``/``sel'`` alias ``curr``/``nxt``/``sel`` in
    place; after k substeps the final field sits in ``out'`` when k is
    odd and in ``curr'`` when k is even — the host wrapper in
    ops/jacobi.py resolves the parity. ``sel`` must arrive halo-filled
    (see the module docstring); the kernel never exchanges it.

    In interpret mode only the all-self-wrap (single device) form runs
    — no remote copies exist — which parity-pins the substep ring, the
    shrinking extents, and the deep self-wrap fills against
    :func:`make_persistent_chunk_body` on any host, including z extents
    that wrap the mod-3 plane ring mid-window (``nz % 3 != 0``)."""
    from .jacobi import COLD_TEMP, HOT_TEMP

    if not spec.is_uniform():
        raise ValueError(
            "the persistent TPU mega-kernel takes uniform partitions "
            "today; uneven persistent runs the host-orchestrated chunk "
            "(ops/jacobi._compile_jacobi_persistent)"
        )
    if k < 2:
        raise ValueError(
            "persistent chunks need k >= 2 (a depth-1 chunk IS the "
            "fused substep kernel — use kernel_variant='fused')"
        )
    check_chunk_depth(spec, k)
    p = spec.padded()
    pz, py, px = p.z, p.y, p.x
    off = spec.compute_offset()
    b = spec.base
    nz, ny, nx = b.z, b.y, b.x
    zo, yo, xo = off.z, off.y, off.x
    md = Dim3(plan.mesh_dim[0], plan.mesh_dim[1], plan.mesh_dim[2]) \
        if not isinstance(plan.mesh_dim, Dim3) else plan.mesh_dim
    phases = _deep_dir_phases(spec, md)
    crossing = [ph for ph in phases if ph[4]]
    local = [ph for ph in phases if not ph[4]]
    n_cross = len(crossing)
    if interpret and n_cross:
        raise ValueError(
            "interpret mode runs the all-self-wrap (single device) "
            "persistent kernel only — remote copies have no interpreter"
        )
    multi = {"z": md.z > 1, "y": md.y > 1, "x": md.x > 1}

    def dslice(starts, shape):
        return tuple(pl.ds(s, w) for s, w in zip(starts, shape))

    def kernel(curr, nxt, sel, curr_o, out_o, sel_o, *scratch):
        sends = scratch[0:n_cross]
        lands = scratch[n_cross: 2 * n_cross]
        (planes, sel_pl, out_pl, send_sems, recv_sems, copy_sem) = \
            scratch[2 * n_cross: 2 * n_cross + 6]

        idx = {a: lax.axis_index(a) if multi[a] else 0
               for a in ("z", "y", "x")}
        ring = {"z": md.z, "y": md.y, "x": md.x}

        def neighbor(d):
            out = {}
            for axis, comp in (("z", d.z), ("y", d.y), ("x", d.x)):
                if comp and multi[axis]:
                    out[axis] = (idx[axis] + comp) % ring[axis]
            return out

        rdmas = []
        if n_cross:
            # 1. barrier: one signal per crossing direction — a
            # neighbor entering its chunk kernel proves our previous
            # chunk's reads of its landings are complete (launch order
            # per device is serial), so the deep slabs may land
            barrier = pltpu.get_barrier_semaphore()
            for d, _s, _d2, _sh, _c in crossing:
                pltpu.semaphore_signal(
                    barrier, inc=1, device_id=neighbor(d),
                    device_id_type=pltpu.DeviceIdType.MESH,
                )
            pltpu.semaphore_wait(barrier, n_cross)

            # 2. stage + START every deep remote copy concurrently
            for i, (d, src, _dst, shape, _c) in enumerate(crossing):
                cp = pltpu.make_async_copy(
                    curr.at[dslice(src, shape)], sends[i], copy_sem)
                cp.start()
                cp.wait()
                rdma = pltpu.make_async_remote_copy(
                    src_ref=sends[i], dst_ref=lands[i],
                    send_sem=send_sems.at[i], recv_sem=recv_sems.at[i],
                    device_id=neighbor(d),
                    device_id_type=pltpu.DeviceIdType.MESH,
                )
                rdma.start()
                rdmas.append(rdma)

        # self-wrap hand-offs: deep local copies behind the sends
        for _d, src, dst, shape, _c in local:
            cp = pltpu.make_async_copy(
                curr.at[dslice(src, shape)],
                curr_o.at[dslice(dst, shape)], copy_sem)
            cp.start()
            cp.wait()

        # sel rides through aliased (already halo-filled by the caller)
        if n_cross:
            for rdma in rdmas:
                rdma.wait()
            for i, (_d, _src, dst, shape, _c) in enumerate(crossing):
                cp = pltpu.make_async_copy(
                    lands[i], curr_o.at[dslice(dst, shape)], copy_sem)
                cp.start()
                cp.wait()

        def substep(src_ref, dst_ref, g):
            """One grown-region plane-streamed sweep: z planes
            [zo - g, zo + nz + g), y/x extents grown g per side, with
            the mod-3 ring window — plane z+1 loads into slot
            (z+1) % 3 while z-1/z are already resident (each plane
            loads once; the ring offset wraps mid-window whenever the
            grown z extent is not a multiple of 3)."""
            z0 = zo - g
            z1 = zo + nz + g
            ys = slice(yo - g, yo + ny + g)
            xs = slice(xo - g, xo + nx + g)
            ysm = slice(yo - g - 1, yo + ny + g - 1)
            ysp = slice(yo - g + 1, yo + ny + g + 1)
            xsm = slice(xo - g - 1, xo + nx + g - 1)
            xsp = slice(xo - g + 1, xo + nx + g + 1)

            def load_plane(z):
                slot = lax.rem(z, 3)
                cp = pltpu.make_async_copy(
                    src_ref.at[pl.ds(z, 1)], planes.at[slot], copy_sem)
                cp.start()
                cp.wait()

            load_plane(z0 - 1)
            load_plane(z0)

            def body(i, _):
                z = z0 + i
                load_plane(z + 1)
                cp = pltpu.make_async_copy(
                    sel.at[pl.ds(z, 1)], sel_pl, copy_sem)
                cp.start()
                cp.wait()
                cp = pltpu.make_async_copy(
                    dst_ref.at[pl.ds(z, 1)], out_pl, copy_sem)
                cp.start()
                cp.wait()
                c = planes[lax.rem(z, 3), 0]
                lo = planes[lax.rem(z - 1 + 3, 3), 0]
                hi = planes[lax.rem(z + 1, 3), 0]
                avg = (
                    c[ys, xsm] + c[ys, xsp]
                    + c[ysm, xs] + c[ysp, xs]
                    + lo[ys, xs] + hi[ys, xs]
                ) / 6
                sl = sel_pl[0][ys, xs]
                avg = jnp.where(sl == 1, HOT_TEMP,
                                jnp.where(sl == 2, COLD_TEMP, avg))
                out_pl[0, ys, xs] = avg.astype(dtype)
                cp = pltpu.make_async_copy(
                    out_pl, dst_ref.at[pl.ds(z, 1)], copy_sem)
                cp.start()
                cp.wait()
                return 0

            lax.fori_loop(0, z1 - z0, body, 0)

        # k substeps, unrolled (static grown extents per substep),
        # ping-ponging the aliased HBM buffers: even substeps read the
        # exchanged curr_o, odd read out_o
        for s in range(k):
            g = k - 1 - s
            if s % 2 == 0:
                substep(curr_o, out_o, g)
            else:
                substep(out_o, curr_o, g)

    block = jax.ShapeDtypeStruct((pz, py, px), dtype)
    sel_block = jax.ShapeDtypeStruct((pz, py, px), jnp.int32)
    scratch_shapes = (
        [pltpu.VMEM(sh, dtype) for _d, _s, _d2, sh, _c in crossing]  # sends
        + [pltpu.VMEM(sh, dtype) for _d, _s, _d2, sh, _c in crossing]  # lands
        + [
            pltpu.VMEM((3, 1, py, px), dtype),   # mod-3 plane ring
            pltpu.VMEM((1, py, px), jnp.int32),  # sel plane
            pltpu.VMEM((1, py, px), dtype),      # out plane (RMW)
            pltpu.SemaphoreType.DMA((max(1, n_cross),)),
            pltpu.SemaphoreType.DMA((max(1, n_cross),)),
            pltpu.SemaphoreType.DMA(()),
        ]
    )
    return scopes.kernel_call(
        "persistent_jacobi", kernel,
        grid=(1,),
        out_shape=(block, block, sel_block),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        scratch_shapes=scratch_shapes,
        input_output_aliases={0: 0, 1: 1, 2: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            has_side_effects=True,
            collective_id=collective_id,
        ),
        interpret=interpret,
    )
