"""Kernel-initiated halo exchange: per-neighbor async remote DMA (TPU).

The TPU analogue of the reference's fastest transport family —
``tx_colocated`` / ``ColocatedDirectAccessSender`` (PAPER.md L5, §5.8):
one GPU writes directly into its neighbor's halo, skipping the MPI
staging entirely. Here the staging being skipped is the XLA collective
path: instead of handing boundary slabs to ``lax.ppermute`` (one ~0.66 ms
dispatch per collective on the recorded CPU-mesh economics, and the
round-7/10 censuses showed per-collective overhead — not bytes —
dominates this stack), the carrier kernel below issues
``pltpu.make_async_remote_copy`` from INSIDE the kernel, so a compiled
``Method.REMOTE_DMA`` exchange contains ZERO collective-permutes.

Per axis phase (the composed x→y→z slab geometry, straight from the
plan's ``RemoteDmaPhaseIR``), every device runs the same kernel:

1. barrier with its two ring neighbors (their landing buffers must be
   quiescent before anyone writes into them);
2. stage its outbound boundary slabs into VMEM and START the remote
   copies toward both neighbors — boundary-first: the sends are in
   flight before anything else runs, so interior compute scheduled
   around the kernel overlaps the wire time;
3. wait the inbound copies and write the received slabs into its own
   halo (``input_output_aliases`` — the in-place unpack of the
   reference's peer-access writes).

The packed ``(Q, …slab)`` carrier is PR-5's per-dtype batching: the DMA
count per exchange is Q-independent (≤ 2 per phase per dtype group).
``wire_dtype`` (bf16-on-the-wire) narrows the staged carrier before the
send and widens on unpack — only wire-crossing bytes pay precision.

These kernels have not run on a chip (CHANGES.md PR 21, "not run on
the chip"); ``scripts/probe_remote_dma.py`` is the hardware probe. The
tests run the CPU emulation (``parallel/remote_emu.py``), which pins the
semantics bit-identically to AXIS_COMPOSED, and the plan-level claims
(0 ppermutes, wire bytes) are pinned against the emulation's census in
tests/test_remote_dma.py. Running the real kernels under the Pallas TPU
interpreter inside ``shard_map`` is untried (ROADMAP C3).
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


def remote_kernel_supported(spec, resident) -> bool:
    """What the carrier kernel handles: one resident block per device.
    Uniform AND uneven (remainder) partitions are supported — on an
    uneven ring the slab extents (rm/rp × full padded orthogonals) are
    identical across participants and only the hi-side slab's start
    offset varies, so the kernel reads it from the static per-ring size
    table at its own ``axis_index`` (the same size-table discipline as
    the dynamic overlap shells). Oversubscribed REMOTE_DMA stays with
    the CPU emulation's geometry until a hardware session extends the
    kernel — loud infeasibility, never a silent fallback."""
    from ..geometry import Dim3

    return resident == Dim3(1, 1, 1)


def make_remote_axis_kernel(spec, phase, nq: int, dtype,
                            wire_dtype: Optional[str] = None,
                            collective_id: int = 0):
    """Build the per-phase carrier kernel: ``fn(*blocks) -> blocks`` over
    ``nq`` same-dtype (pz, py, px) padded blocks inside ``shard_map``,
    delivering both boundary slabs of one axis phase via remote DMA.
    ``phase`` is the plan's RemoteDmaPhaseIR; ``phase.ring > 1`` required
    (self-wrap phases are pure local copies — no DMA to issue)."""
    if not (phase.ring > 1 and phase.active):
        raise ValueError(
            "remote axis kernel needs a multi-device active phase "
            "(self-wrap phases are pure local copies — no DMA to issue)"
        )
    p = spec.padded()
    pz, py, px = p.z, p.y, p.x
    rm, rp, off = phase.rm, phase.rp, phase.offset
    # uneven rings share every slab EXTENT (rm/rp x full padded
    # orthogonals); only the hi-side start offset depends on this
    # device's block size, read from the static size table in-kernel
    uniform = phase.uniform
    sz = phase.sizes[0]
    sizes_tbl = phase.sizes  # static per-ring ints from the plan IR
    axis = phase.axis
    # slab shapes (z, y, x) with the phase axis narrowed to the radius
    def slab_shape(r):
        return {
            "x": (nq, pz, py, r),
            "y": (nq, pz, r, px),
            "z": (nq, r, py, px),
        }[axis]

    # data-dim index of the phase axis within a (pz, py, px) block
    ddim = {"z": 0, "y": 1, "x": 2}[axis]
    wire = wire_narrow_dtype(dtype, wire_dtype)
    wdt = wire if wire is not None else dtype

    def dslice(start, width):
        idx = [slice(None)] * 3
        idx[ddim] = pl.ds(start, width)
        return tuple(idx)

    def kernel(*refs):
        ins = refs[:nq]
        outs = refs[nq: 2 * nq]
        (comm_lo, comm_hi, send_lo, send_hi, stage_rm, stage_rp,
         send_sems, recv_sems, copy_sem) = refs[2 * nq:]
        my = lax.axis_index(axis)
        m = phase.ring
        fwd = (my + 1) % m
        bwd = (my - 1 + m) % m
        sz_my = (sz if uniform
                 else jnp.asarray(sizes_tbl, jnp.int32)[my])

        def stage_in(src_ref, sl, dst_buf, stage, q):
            """HBM slab -> wire-dtype VMEM staging. A DMA cannot cast,
            so the compression path round-trips through a native-dtype
            staging buffer (sized per SIDE — rm and rp slabs differ
            under asymmetric radii) and casts vector-side."""
            if wire is None:
                cp = pltpu.make_async_copy(src_ref.at[sl], dst_buf.at[q],
                                           copy_sem)
                cp.start()
                cp.wait()
            else:
                cp = pltpu.make_async_copy(src_ref.at[sl], stage.at[q],
                                           copy_sem)
                cp.start()
                cp.wait()
                dst_buf[q] = stage[q].astype(wdt)

        def stage_out(src_buf, stage, q, dst_ref, sl):
            """Wire-dtype VMEM landing -> HBM halo (widen on unpack)."""
            if wire is None:
                cp = pltpu.make_async_copy(src_buf.at[q], dst_ref.at[sl],
                                           copy_sem)
                cp.start()
                cp.wait()
            else:
                stage[q] = src_buf[q].astype(dtype)
                cp = pltpu.make_async_copy(stage.at[q], dst_ref.at[sl],
                                           copy_sem)
                cp.start()
                cp.wait()

        # 1. neighbor barrier: both landing buffers quiescent
        barrier = pltpu.get_barrier_semaphore()
        for nbr in (fwd, bwd):
            pltpu.semaphore_signal(
                barrier, inc=1, device_id={axis: nbr},
                device_id_type=pltpu.DeviceIdType.MESH,
            )
        pltpu.semaphore_wait(barrier, 2)

        # 2. stage + SEND, boundary-first: both remote copies are in
        # flight before any local work below
        rdmas = []
        if rm:
            for q in range(nq):
                stage_in(ins[q], dslice(off + sz_my - rm, rm), send_hi,
                         stage_rm, q)
            rdma = pltpu.make_async_remote_copy(
                src_ref=send_hi, dst_ref=comm_lo,
                send_sem=send_sems.at[0], recv_sem=recv_sems.at[0],
                device_id={axis: fwd},
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            rdma.start()
            rdmas.append(rdma)
        if rp:
            for q in range(nq):
                stage_in(ins[q], dslice(off, rp), send_lo, stage_rp, q)
            rdma = pltpu.make_async_remote_copy(
                src_ref=send_lo, dst_ref=comm_hi,
                send_sem=send_sems.at[1], recv_sem=recv_sems.at[1],
                device_id={axis: bwd},
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            rdma.start()
            rdmas.append(rdma)

        # 3. wait + unpack into the halos (in place)
        for rdma in rdmas:
            rdma.wait()
        if rm:
            for q in range(nq):
                stage_out(comm_lo, stage_rm, q, outs[q],
                          dslice(off - rm, rm))
        if rp:
            for q in range(nq):
                stage_out(comm_hi, stage_rp, q, outs[q],
                          dslice(off + sz_my, rp))

    block = jax.ShapeDtypeStruct((pz, py, px), dtype)
    return scopes.kernel_call(
        "remote_dma", kernel,
        grid=(1,),
        out_shape=(block,) * nq,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * nq,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * nq,
        scratch_shapes=[
            # packed (Q, …slab) carriers: landing buffers (what the
            # neighbors' remote copies write) and send staging; the
            # native cast-staging buffers are PER SIDE — rm and rp slab
            # shapes differ under asymmetric radii, and a DMA requires
            # identical src/dst shapes
            pltpu.VMEM(slab_shape(max(rm, 1)), wdt),   # comm_lo landing
            pltpu.VMEM(slab_shape(max(rp, 1)), wdt),   # comm_hi landing
            pltpu.VMEM(slab_shape(max(rp, 1)), wdt),   # send_lo staging
            pltpu.VMEM(slab_shape(max(rm, 1)), wdt),   # send_hi staging
            pltpu.VMEM(slab_shape(max(rm, 1)), dtype),  # rm cast staging
            pltpu.VMEM(slab_shape(max(rp, 1)), dtype),  # rp cast staging
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA(()),
        ],
        input_output_aliases={q: q for q in range(nq)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            has_side_effects=True,
            collective_id=collective_id,
        ),
    )


class RemoteDmaExchange:
    """The all-TPU REMOTE_DMA transport of one :class:`HaloExchange`:
    a jitted ``shard_map`` program whose wire movement is carrier
    kernels (above) on ring phases and plain local slab copies on
    self-wrap phases — no ``lax.ppermute`` anywhere, so the compiled
    census reads 0 collective-permutes (the same pin the CPU emulation
    carries)."""

    def __init__(self, ex):
        from ..parallel.mesh import BLOCK_PSPEC

        if not remote_kernel_supported(ex.spec, ex.resident):
            raise ValueError(
                "Method.REMOTE_DMA's TPU carrier kernel supports uniform "
                "single-resident partitions today (uneven/oversubscribed "
                "REMOTE_DMA is staged for a hardware session; use "
                "AXIS_COMPOSED there)"
            )
        self.ex = ex
        self._pspec = BLOCK_PSPEC
        self._kernels = {}

    def _phase_kernel(self, phase, nq, dtype, cid):
        key = (phase.axis, nq, str(jnp.dtype(dtype)))
        if key not in self._kernels:
            self._kernels[key] = make_remote_axis_kernel(
                self.ex.spec, phase, nq, dtype,
                wire_dtype=self.ex.wire_dtype, collective_id=cid,
            )
        return self._kernels[key]

    def _blocks_body(self, state):
        """Per-block body (inside shard_map): composed x→y→z phase
        order, each phase's wire movement a remote-DMA kernel call."""
        from ..ops.halo_fill import dtype_groups

        ex = self.ex
        p = ex.spec.padded()
        if not isinstance(state, dict):
            state = {0: state}
            unwrap = True
        else:
            unwrap = False
        out = dict(state)
        # per-dtype packed carriers (PR-5 geometry, Q-independent DMA
        # count); with batching off, each quantity is its own carrier —
        # the per-quantity baseline the plan's dmas_per_exchange models
        # and the CPU emulation mirrors
        if ex.batch_quantities:
            groups = dtype_groups(out)
        else:
            groups = [(out[k].dtype, [k]) for k in out]
        for cid, (rphase, aphase) in enumerate(
                zip(ex.plan.remote_phases, ex.plan.axis_phases)):
            if not rphase.active:
                continue
            for dt, keys in groups:
                if rphase.ring <= 1:
                    # self-wrap: pure local slab copy — the composed
                    # batched body at n == 1 IS that program (no permute)
                    blocks = ex._axis_phase_batched(
                        [out[k] for k in keys], aphase)
                else:
                    kern = self._phase_kernel(rphase, len(keys), dt, cid)
                    shaped = [out[k].reshape(p.z, p.y, p.x) for k in keys]
                    res = kern(*shaped)
                    # a tuple out_shape comes back as a tuple even at
                    # length 1 — wrap only a bare array, never double-wrap
                    if not isinstance(res, (tuple, list)):
                        res = (res,)
                    blocks = [r.reshape(out[k].shape)
                              for r, k in zip(res, keys)]
                for k, b in zip(keys, blocks):
                    out[k] = b
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
