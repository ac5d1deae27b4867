"""Fused compute+exchange mega-kernel: overlap REMOTE_DMA behind tiles.

The §5.8 endgame of the kernel-initiated transport (ops/remote_dma.py,
PR 10): that carrier runs as a SEPARATE ``pallas_call`` serialized with
the sweep, so its zero-ppermute DMAs buy zero overlap. This module fuses
them — ONE Pallas kernel per exchange+sweep substep that

1. barriers with every ring neighbor, packs the boundary slabs, and
   STARTs all per-neighbor ``pltpu.make_async_remote_copy``s
   boundary-first (every send is in flight before any compute);
2. computes interior tiles while the DMAs fly;
3. waits the recv semaphores and unpacks the landings into the halos
   (``input_output_aliases`` — in-place, the reference's peer-access
   write);
4. computes the boundary tiles from the freshly exchanged halos.

So wire time hides behind interior FLOPs instead of preceding them — the
TPU analogue of the reference's L5 colocated peer-access transports and
the comm/compute-overlap thesis of the whole paper (src/stencil.cu:
1002-1186 overlap engine + tx_colocated.cu concurrent per-neighbor
writes).

Geometry: the composed x→y→z slab phases CANNOT start boundary-first (a
y slab carries x-halo data, so phase y's send depends on phase x's
receive). The fused schedule therefore moves one EXACT-extent message
per active direction — the plan's ``FusedPhaseIR`` records (plan/ir.py),
the DIRECT26 geometry re-transported as kernel-initiated copies: every
message reads only sender compute-region cells, so all of them start
concurrently and together they fill every declared halo cell
bit-identically to AXIS_COMPOSED. ``wire_dtype`` (bf16 or the fp8
``float8_e4m3fn`` tier) narrows wire-crossing carriers exactly like the
axis carrier; self-wrap hand-offs stay lossless.

The cross-device kernels here have not run on a chip (CHANGES.md PR 21,
"not run on the chip"; ``scripts/probe_remote_dma.py``'s fused leg is the
hardware probe). The host-orchestrated emulation
(``parallel/remote_emu.FusedRemoteEmulation``) pins the fused
schedule's semantics bit-identically to AXIS_COMPOSED on the CPU mesh
(tests/test_fused_stencil.py, scripts/ci_fused_gate.py). The one piece
the tests DO run is the all-self-wrap (single device) form of the
jacobi mega-kernel in interpret mode: no remote copies exist, so the
interior/boundary split and in-kernel wrap fills are parity-pinned
against the XLA step on any host.

First-cut scope (loud, never silent): single resident block per device;
the jacobi mega-kernel additionally wants uniform partitions (the
emulation owns uneven); the boundary pass re-streams whole planes —
exact but unturned, the hardware session's refinement. The astaroth
multistep folds in host-side (astaroth/integrate.make_fused_astaroth_loop
slots the ring-indexed substep kernels between the fused start/wait).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import scopes
from ..ops.halo_fill import wire_narrow_dtype


def fused_kernel_supported(spec, resident) -> bool:
    """What the fused TPU kernels handle today: UNIFORM partitions, one
    resident block per device (the per-direction extents are static in
    the kernel). Uneven single-resident fused runs the host-orchestrated
    schedule (``HaloExchange._fused_host_schedule`` — the step loops use
    it directly); oversubscription is loud infeasibility at HaloExchange
    construction. Extending the TPU carrier to uneven size-tables, like
    ops/remote_dma.py's axis carrier, is the hardware session's
    follow-up."""
    from ..geometry import Dim3

    return spec.is_uniform() and resident == Dim3(1, 1, 1)


def _dir_geometry(spec, phase):
    """Static (src starts, dst starts, extents) in (z, y, x) block-local
    coordinates for one FusedPhaseIR on a UNIFORM partition."""
    assert phase.src is not None and phase.dst is not None, (
        "fused TPU kernels take uniform partitions (the emulation owns "
        "uneven geometry)"
    )
    return phase.src, phase.dst, phase.shape


def _device_id_for(phase):
    """Mesh-axis device_id dict targeting the +direction neighbor."""
    dx, dy, dz = phase.direction
    out = {}
    for axis, comp in (("z", dz), ("y", dy), ("x", dx)):
        if comp:
            out[axis] = comp  # resolved to axis_index + comp in-kernel
    return out


def make_fused_exchange_kernel(spec, plan, nq: int, dtype,
                               wire_dtype: Optional[str] = None,
                               collective_id: int = 0):
    """The exchange-only fused carrier: ``fn(*blocks) -> blocks`` over
    ``nq`` same-dtype (pz, py, px) padded blocks inside ``shard_map``,
    delivering EVERY active direction's message in one kernel — all
    remote copies started before any local work, local hand-offs and
    unpacks behind them. This is what ``HaloExchange(fused=True)``
    compiles per dtype group on TPU (exchange loops, probes); the
    compute-fused jacobi form is :func:`make_fused_jacobi_kernel`."""
    if not spec.is_uniform():
        raise ValueError(
            "the fused TPU carrier takes uniform partitions today; "
            "uneven fused stays with the CPU emulation until the "
            "hardware session extends it"
        )
    p = spec.padded()
    pz, py, px = p.z, p.y, p.x
    wire = wire_narrow_dtype(dtype, wire_dtype)
    wdt = wire if wire is not None else dtype
    phases = list(plan.fused_phases)
    crossing = [ph for ph in phases if ph.crossing]
    local = [ph for ph in phases if not ph.crossing]
    n_cross = len(crossing)
    if n_cross == 0:
        raise ValueError(
            "fused exchange kernel needs at least one wire-crossing "
            "direction (an all-self-wrap mesh exchanges locally)"
        )

    def dslice(starts, shape):
        return tuple(pl.ds(s, w) for s, w in zip(starts, shape))

    def kernel(*refs):
        ins = refs[:nq]
        outs = refs[nq: 2 * nq]
        scratch = refs[2 * nq:]
        sends = scratch[0:n_cross]
        lands = scratch[n_cross: 2 * n_cross]
        stages = scratch[2 * n_cross: 3 * n_cross] if wire is not None else ()
        base = 3 * n_cross if wire is not None else 2 * n_cross
        send_sems, recv_sems, copy_sem = scratch[base: base + 3]

        idx = {a: lax.axis_index(a) for a in ("z", "y", "x")}
        ring = {"z": plan.mesh_dim[2], "y": plan.mesh_dim[1],
                "x": plan.mesh_dim[0]}

        def neighbor(ph):
            did = {}
            for axis, comp in _device_id_for(ph).items():
                did[axis] = (idx[axis] + comp) % ring[axis]
            return did

        # 1. barrier: every neighbor this kernel writes into must be
        # quiescent; each device receives exactly one signal per
        # crossing direction (wrap rings make the count symmetric)
        barrier = pltpu.get_barrier_semaphore()
        for ph in crossing:
            pltpu.semaphore_signal(
                barrier, inc=1, device_id=neighbor(ph),
                device_id_type=pltpu.DeviceIdType.MESH,
            )
        pltpu.semaphore_wait(barrier, n_cross)

        # 2. stage + START every remote copy, boundary-first
        rdmas = []
        for i, ph in enumerate(crossing):
            src, _dst, shape = _dir_geometry(spec, ph)
            for q in range(nq):
                if wire is None:
                    cp = pltpu.make_async_copy(
                        ins[q].at[dslice(src, shape)], sends[i].at[q],
                        copy_sem)
                    cp.start()
                    cp.wait()
                else:
                    cp = pltpu.make_async_copy(
                        ins[q].at[dslice(src, shape)], stages[i].at[q],
                        copy_sem)
                    cp.start()
                    cp.wait()
                    sends[i][q] = stages[i][q].astype(wdt)
            rdma = pltpu.make_async_remote_copy(
                src_ref=sends[i], dst_ref=lands[i],
                send_sem=send_sems.at[i], recv_sem=recv_sems.at[i],
                device_id=neighbor(ph),
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            rdma.start()
            rdmas.append(rdma)

        # self-wrap hand-offs: pure local copies, lossless, overlapped
        # behind the in-flight sends
        for ph in local:
            src, dst, shape = _dir_geometry(spec, ph)
            for q in range(nq):
                cp = pltpu.make_async_copy(
                    ins[q].at[dslice(src, shape)],
                    outs[q].at[dslice(dst, shape)], copy_sem)
                cp.start()
                cp.wait()

        # 3. wait + unpack (widen) into the halos, in place
        for rdma in rdmas:
            rdma.wait()
        for i, ph in enumerate(crossing):
            _src, dst, shape = _dir_geometry(spec, ph)
            for q in range(nq):
                if wire is None:
                    cp = pltpu.make_async_copy(
                        lands[i].at[q], outs[q].at[dslice(dst, shape)],
                        copy_sem)
                    cp.start()
                    cp.wait()
                else:
                    stages[i][q] = lands[i][q].astype(dtype)
                    cp = pltpu.make_async_copy(
                        stages[i].at[q], outs[q].at[dslice(dst, shape)],
                        copy_sem)
                    cp.start()
                    cp.wait()

    block = jax.ShapeDtypeStruct((pz, py, px), dtype)
    scratch_shapes = (
        [pltpu.VMEM((nq,) + ph.shape, wdt) for ph in crossing]    # sends
        + [pltpu.VMEM((nq,) + ph.shape, wdt) for ph in crossing]  # lands
        + ([pltpu.VMEM((nq,) + ph.shape, dtype) for ph in crossing]
           if wire is not None else [])                           # cast stage
        + [
            pltpu.SemaphoreType.DMA((n_cross,)),
            pltpu.SemaphoreType.DMA((n_cross,)),
            pltpu.SemaphoreType.DMA(()),
        ]
    )
    return scopes.kernel_call(
        "fused_exchange", kernel,
        grid=(1,),
        out_shape=(block,) * nq,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * nq,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * nq,
        scratch_shapes=scratch_shapes,
        input_output_aliases={q: q for q in range(nq)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            has_side_effects=True,
            collective_id=collective_id,
        ),
    )


def make_fused_jacobi_kernel(spec, plan, dtype=jnp.float32,
                             wire_dtype: Optional[str] = None,
                             collective_id: int = 0,
                             interpret: bool = False):
    """The jacobi mega-kernel: ``fn(curr, nxt, sel) -> (curr', out)`` —
    ONE ``pallas_call`` per substep running the full fused schedule:

    barrier → stage+start every remote copy → local self-wrap fills →
    full-region sweep on pre-exchange data (the "interior": its stencil
    reads stale wire halos only at boundary cells, re-swept below) →
    wait recv semaphores + unpack → re-sweep the boundary planes.

    ``curr'`` is the exchanged state (halos filled, aliased in place),
    ``out`` the swept field (aliased to ``nxt``). Plane-streamed: whole
    padded (py, px) planes ride HBM↔VMEM DMAs (tile-aligned by
    construction), the 6-neighbor average runs vector-side. The boundary
    pass re-streams the affected planes whole — exact (re-swept interior
    cells recompute identical values) but untuned; shell-extent staging
    is the hardware session's refinement.

    In interpret mode only the all-self-wrap (single device) form runs —
    no remote copies exist there — which parity-pins the sweep and the
    in-kernel wrap fills against the XLA step on any host
    (tests/test_fused_stencil.py)."""
    from ..geometry import Dim3
    from .jacobi import COLD_TEMP, HOT_TEMP

    if not spec.is_uniform():
        raise ValueError(
            "the fused jacobi mega-kernel takes uniform partitions "
            "today; uneven fused jacobi runs the host-orchestrated "
            "schedule (ops/jacobi._compile_jacobi_fused)"
        )
    r = spec.radius
    if min(r.x(-1), r.x(1), r.y(-1), r.y(1), r.z(-1), r.z(1)) < 1:
        raise ValueError("jacobi needs face radius >= 1")
    p = spec.padded()
    pz, py, px = p.z, p.y, p.x
    off = spec.compute_offset()
    b = spec.base
    nz, ny, nx = b.z, b.y, b.x
    zo, yo, xo = off.z, off.y, off.x
    wire = wire_narrow_dtype(dtype, wire_dtype)
    wdt = wire if wire is not None else dtype
    phases = list(plan.fused_phases)
    crossing = [ph for ph in phases if ph.crossing]
    local = [ph for ph in phases if not ph.crossing]
    n_cross = len(crossing)
    if interpret and n_cross:
        raise ValueError(
            "interpret mode runs the all-self-wrap (single device) fused "
            "kernel only — remote copies have no interpreter"
        )
    multi = {"z": plan.mesh_dim[2] > 1, "y": plan.mesh_dim[1] > 1,
             "x": plan.mesh_dim[0] > 1}

    def dslice(starts, shape):
        return tuple(pl.ds(s, w) for s, w in zip(starts, shape))

    def kernel(curr, nxt, sel, curr_o, out_o, *scratch):
        sends = scratch[0:n_cross]
        lands = scratch[n_cross: 2 * n_cross]
        stages = scratch[2 * n_cross: 3 * n_cross] if wire is not None else ()
        base = 3 * n_cross if wire is not None else 2 * n_cross
        (planes, sel_pl, out_pl, send_sems, recv_sems, copy_sem) = \
            scratch[base: base + 6]

        idx = {a: lax.axis_index(a) if multi[a] else 0
               for a in ("z", "y", "x")}
        ring = {"z": plan.mesh_dim[2], "y": plan.mesh_dim[1],
                "x": plan.mesh_dim[0]}

        def neighbor(ph):
            return {axis: (idx[axis] + comp) % ring[axis]
                    for axis, comp in _device_id_for(ph).items()}

        rdmas = []
        if n_cross:
            # 1. barrier with every neighbor this kernel writes into
            barrier = pltpu.get_barrier_semaphore()
            for ph in crossing:
                pltpu.semaphore_signal(
                    barrier, inc=1, device_id=neighbor(ph),
                    device_id_type=pltpu.DeviceIdType.MESH,
                )
            pltpu.semaphore_wait(barrier, n_cross)

            # 2. stage + START every remote copy, boundary-first
            for i, ph in enumerate(crossing):
                src, _dst, shape = _dir_geometry(spec, ph)
                if wire is None:
                    cp = pltpu.make_async_copy(
                        curr.at[dslice(src, shape)], sends[i], copy_sem)
                    cp.start()
                    cp.wait()
                else:
                    cp = pltpu.make_async_copy(
                        curr.at[dslice(src, shape)], stages[i], copy_sem)
                    cp.start()
                    cp.wait()
                    sends[i][...] = stages[i][...].astype(wdt)
                rdma = pltpu.make_async_remote_copy(
                    src_ref=sends[i], dst_ref=lands[i],
                    send_sem=send_sems.at[i], recv_sem=recv_sems.at[i],
                    device_id=neighbor(ph),
                    device_id_type=pltpu.DeviceIdType.MESH,
                )
                rdma.start()
                rdmas.append(rdma)

        # self-wrap hand-offs: local, lossless, behind the in-flight sends
        for ph in local:
            src, dst, shape = _dir_geometry(spec, ph)
            cp = pltpu.make_async_copy(
                curr.at[dslice(src, shape)],
                curr_o.at[dslice(dst, shape)], copy_sem)
            cp.start()
            cp.wait()

        def load_plane(slot, z):
            cp = pltpu.make_async_copy(
                curr_o.at[pl.ds(z, 1)], planes.at[slot], copy_sem)
            cp.start()
            cp.wait()

        def sweep_plane(z):
            """One full compute plane: load z-1, z, z+1 + sel + the out
            plane, average vector-side, merge, store the plane back."""
            for s, dz in enumerate((-1, 0, 1)):
                load_plane(s, z + dz)
            cp = pltpu.make_async_copy(
                sel.at[pl.ds(z, 1)], sel_pl, copy_sem)
            cp.start()
            cp.wait()
            cp = pltpu.make_async_copy(
                nxt.at[pl.ds(z, 1)], out_pl, copy_sem)
            cp.start()
            cp.wait()
            c = planes[1, 0]
            ys = slice(yo, yo + ny)
            xs = slice(xo, xo + nx)
            avg = (
                c[ys, slice(xo - 1, xo + nx - 1)]
                + c[ys, slice(xo + 1, xo + nx + 1)]
                + c[slice(yo - 1, yo + ny - 1), xs]
                + c[slice(yo + 1, yo + ny + 1), xs]
                + planes[0, 0][ys, xs]
                + planes[2, 0][ys, xs]
            ) / 6
            sl = sel_pl[0][ys, xs]
            avg = jnp.where(sl == 1, HOT_TEMP,
                            jnp.where(sl == 2, COLD_TEMP, avg))
            out_pl[0, ys, xs] = avg.astype(dtype)
            cp = pltpu.make_async_copy(
                out_pl, out_o.at[pl.ds(z, 1)], copy_sem)
            cp.start()
            cp.wait()

        # interior: the full-region sweep on pre-exchange data — every
        # plane whose stencil never reads a wire halo is final here
        def body(i, _):
            sweep_plane(zo + i)
            return 0

        lax.fori_loop(0, nz, body, 0)

        if n_cross:
            # 3. wait + unpack the landings into the halos, in place
            for rdma in rdmas:
                rdma.wait()
            for i, ph in enumerate(crossing):
                _src, dst, shape = _dir_geometry(spec, ph)
                if wire is None:
                    cp = pltpu.make_async_copy(
                        lands[i], curr_o.at[dslice(dst, shape)], copy_sem)
                    cp.start()
                    cp.wait()
                else:
                    stages[i][...] = lands[i][...].astype(dtype)
                    cp = pltpu.make_async_copy(
                        stages[i], curr_o.at[dslice(dst, shape)], copy_sem)
                    cp.start()
                    cp.wait()

            # 4. boundary: re-sweep the planes whose stencils read wire
            # halos. Re-swept interior cells recompute identical values,
            # so whole-plane re-sweeps are exact; z-only meshes (the
            # z-heavy NodePartition default) touch just 2 planes.
            if multi["x"] or multi["y"]:
                lax.fori_loop(0, nz, body, 0)
            else:
                sweep_plane(zo)
                sweep_plane(zo + nz - 1)

    block = jax.ShapeDtypeStruct((pz, py, px), dtype)
    sel_block = jax.ShapeDtypeStruct((pz, py, px), jnp.int32)
    scratch_shapes = (
        [pltpu.VMEM(ph.shape, wdt) for ph in crossing]    # sends
        + [pltpu.VMEM(ph.shape, wdt) for ph in crossing]  # lands
        + ([pltpu.VMEM(ph.shape, dtype) for ph in crossing]
           if wire is not None else [])                   # cast staging
        + [
            pltpu.VMEM((3, 1, py, px), dtype),   # in-plane window
            pltpu.VMEM((1, py, px), jnp.int32),  # sel plane
            pltpu.VMEM((1, py, px), dtype),      # out plane (RMW)
            pltpu.SemaphoreType.DMA((max(1, n_cross),)),
            pltpu.SemaphoreType.DMA((max(1, n_cross),)),
            pltpu.SemaphoreType.DMA(()),
        ]
    )
    return scopes.kernel_call(
        "fused_jacobi", kernel,
        grid=(1,),
        out_shape=(block, block),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        scratch_shapes=scratch_shapes,
        input_output_aliases={0: 0, 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            has_side_effects=True,
            collective_id=collective_id,
        ),
        interpret=interpret,
    )


class FusedRemoteDmaExchange:
    """The all-TPU FUSED transport of one ``HaloExchange(fused=True)``:
    a jitted ``shard_map`` program whose wire movement is ONE
    :func:`make_fused_exchange_kernel` call per dtype group — every
    direction's copy in flight concurrently, zero ``lax.ppermute``
    anywhere (the same census pin as ops/remote_dma.RemoteDmaExchange,
    which this replaces when the plan carries the fused variant). The
    compute-fused jacobi substep wires the same schedule through
    :func:`make_fused_jacobi_kernel` instead (ops/jacobi)."""

    def __init__(self, ex):
        from ..parallel.mesh import BLOCK_PSPEC

        if not fused_kernel_supported(ex.spec, ex.resident):
            raise ValueError(
                "the fused TPU carrier supports uniform single-resident "
                "partitions today (uneven fused runs the "
                "host-orchestrated schedule via the fused step loops; "
                "use AXIS_COMPOSED for oversubscription)"
            )
        self.ex = ex
        self._pspec = BLOCK_PSPEC
        self._kernels = {}

    def _group_kernel(self, nq, dtype, cid):
        key = (nq, str(jnp.dtype(dtype)))
        if key not in self._kernels:
            self._kernels[key] = make_fused_exchange_kernel(
                self.ex.spec, self.ex.plan, nq, dtype,
                wire_dtype=self.ex.wire_dtype, collective_id=cid,
            )
        return self._kernels[key]

    def _blocks_body(self, state):
        from ..ops.halo_fill import dtype_groups

        ex = self.ex
        p = ex.spec.padded()
        if not isinstance(state, dict):
            state = {0: state}
            unwrap = True
        else:
            unwrap = False
        out = dict(state)
        if ex.batch_quantities:
            groups = dtype_groups(out)
        else:
            groups = [(out[k].dtype, [k]) for k in out]
        for cid, (dt, keys) in enumerate(groups):
            kern = self._group_kernel(len(keys), dt, cid)
            shaped = [out[k].reshape(p.z, p.y, p.x) for k in keys]
            res = kern(*shaped)
            # a tuple out_shape comes back as a tuple even at length 1 —
            # wrap only a bare array, never double-wrap
            if not isinstance(res, (tuple, list)):
                res = (res,)
            for k, blk in zip(keys, res):
                out[k] = blk.reshape(state[k].shape)
        return out[0] if unwrap else out

    def __call__(self, state):
        return self._compiled(state)

    @property
    def _compiled(self):
        if "_compiled_fn" not in self.__dict__:
            fn = jax.shard_map(
                self._blocks_body, mesh=self.ex.mesh,
                in_specs=self._pspec, out_specs=self._pspec,
            )
            self.__dict__["_compiled_fn"] = jax.jit(fn, donate_argnums=0)
        return self.__dict__["_compiled_fn"]

    def make_loop(self, iters: int):
        def many(state):
            return lax.fori_loop(
                0, iters, lambda _, s: self._blocks_body(s), state)

        fn = jax.shard_map(many, mesh=self.ex.mesh,
                           in_specs=self._pspec, out_specs=self._pspec)
        return jax.jit(fn, donate_argnums=0)

    def collective_census(self, state):
        from ..utils.hlo_check import collective_census

        txt = self._compiled.lower(state).compile().as_text()
        return collective_census(txt)
