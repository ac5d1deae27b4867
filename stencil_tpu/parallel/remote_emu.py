"""Semantics-exact CPU emulation of the REMOTE_DMA halo exchange.

``Method.REMOTE_DMA``'s real transport issues per-neighbor async remote
copies from inside the compute kernel (``pltpu.make_async_remote_copy``,
ops/remote_dma.py) — data movement the XLA collective path never sees.
The tests run on the CPU mesh and do not drive those kernels through a
cross-device Pallas interpreter (untried, ROADMAP C3), so correctness is
pinned here instead: the SAME
per-neighbor copy schedule, executed as host-initiated device-to-device
transfers (``jax.device_put`` of the packed boundary carrier straight to
the neighbor device — the closest thing a CPU backend has to a remote
DMA: a point-to-point copy that no collective compiler arbitrates).

Each axis phase (composed x→y→z geometry, straight from the plan's
``RemoteDmaPhaseIR`` records) runs as three stages:

1. **take** (compiled per device, ZERO collectives): slice the boundary
   slabs of the device's resident stack and pack the same-dtype group
   into one ``(Q, …slab)`` carrier (PR-5 geometry — the transfer count
   is Q-independent), narrowing to ``wire_dtype`` when the bf16-on-the-
   wire knob is set;
2. **transfer** (no program at all): ``device_put`` each carrier to its
   ring neighbor — the emulated remote DMA (a self-wrap ring degenerates
   to a local hand-off, exactly like the kernel's loopback copy);
3. **update** (compiled per device, ZERO collectives): widen + unpack
   the received carriers and write every halo slab — the incoming
   boundary plus the resident-neighbor shifts, which never left the
   device (the same split ``_axis_phase_resident_batched`` lowers).

Because a halo exchange is pure data movement, copying the same cells
makes the result bit-identical to ``AXIS_COMPOSED`` by construction —
tests/test_remote_dma.py pins it across uniform/uneven/oversubscribed
partitions and mixed-dtype states. ``collective_census`` here censuses
EVERY compiled piece of one exchange; the pinned verdict is 0
collective-permutes (the REMOTE_DMA claim, honest on both lowerings).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.halo_fill import pack_slabs, unpack_slabs, wire_narrow_dtype
from ..utils import timer


class RemoteDmaEmulation:
    """Host-orchestrated REMOTE_DMA lowering for non-TPU meshes."""

    def __init__(self, ex):
        from .exchange import HaloExchange  # noqa: F401 — typing only

        self.ex = ex
        self.mesh = ex.mesh
        self.plan = ex.plan
        if jax.process_count() > 1:
            raise NotImplementedError(
                "the REMOTE_DMA CPU emulation is single-process (every "
                "shard must be addressable for host-initiated neighbor "
                "copies); multi-host REMOTE_DMA is the TPU kernel's job"
            )
        # mesh coords per device: mesh.devices is (mz, my, mx) in the
        # ('z', 'y', 'x') axis order of parallel/mesh.py
        self._coords: Dict[int, Tuple[int, int, int]] = {}
        md = self.mesh.devices
        for iz in range(md.shape[0]):
            for iy in range(md.shape[1]):
                for ix in range(md.shape[2]):
                    self._coords[md[iz, iy, ix].id] = (iz, iy, ix)
        self._jits: Dict[tuple, object] = {}
        self._avals: Dict[tuple, tuple] = {}
        self.last_transfer_count = 0  # emulated remote copies, last exchange

    # -- compiled pieces ------------------------------------------------------
    def _jit(self, key, build):
        """Cache one jitted piece per static geometry key, remembering
        its argument avals so :meth:`collective_census` can lower it."""
        if key not in self._jits:
            self._jits[key] = jax.jit(build())
        return self._jits[key]

    def _remember(self, key, args) -> None:
        if key not in self._avals:
            self._avals[key] = tuple(
                jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args
            )

    def _device_sizes(self, phase, i: int) -> Tuple[int, ...]:
        c = phase.resident
        return tuple(int(phase.sizes[i * c + j]) for j in range(c))

    def _take_fn(self, phase, sizes, shard_shape, dtype, nq, wire):
        """take(*shards) -> (hi_carrier?, lo_carrier?): the boundary
        slabs this device sends (+axis: its LAST resident's top rm slab;
        -axis: its FIRST resident's bottom rp slab), packed per group and
        narrowed to the wire dtype when compression is on."""
        rm, rp, off, adim, bdim, c = (phase.rm, phase.rp, phase.offset,
                                      phase.adim, phase.bdim, phase.resident)
        sz_last = sizes[c - 1]

        def slab(s, j, start, width):
            idx = [slice(None)] * len(shard_shape)
            idx[bdim] = slice(j, j + 1)
            idx[adim] = slice(start, start + width)
            return s[tuple(idx)]

        def take(*shards):
            out = []
            if rm:
                hi = pack_slabs([slab(s, c - 1, off + sz_last - rm, rm)
                                 for s in shards])
                out.append(hi.astype(wire) if wire is not None else hi)
            if rp:
                lo = pack_slabs([slab(s, 0, off, rp) for s in shards])
                out.append(lo.astype(wire) if wire is not None else lo)
            return tuple(out)

        return take

    def _update_fn(self, phase, sizes, shard_shape, dtype, nq, wire):
        """update(*shards, recv...) -> new shards: write every halo slab
        of this device's resident stack — lane 0's low halo from the
        received -axis carrier, lane c-1's high halo from the +axis one,
        interior lanes from their resident neighbors (local, lossless)."""
        rm, rp, off, adim, bdim, c = (phase.rm, phase.rp, phase.offset,
                                      phase.adim, phase.bdim, phase.resident)

        def slab(s, j, start, width):
            idx = [slice(None)] * len(shard_shape)
            idx[bdim] = slice(j, j + 1)
            idx[adim] = slice(start, start + width)
            return s[tuple(idx)]

        def put(s, piece, j, start, width):
            idx = [slice(None)] * len(shard_shape)
            idx[bdim] = slice(j, j + 1)
            idx[adim] = slice(start, start + width)
            return s.at[tuple(idx)].set(piece)

        def update(*args):
            shards = list(args[:nq])
            rest = list(args[nq:])
            recv_lo = recv_hi = None
            if rm:
                recv_lo = rest.pop(0)
                if wire is not None:
                    recv_lo = recv_lo.astype(dtype)
            if rp:
                recv_hi = rest.pop(0)
                if wire is not None:
                    recv_hi = recv_hi.astype(dtype)
            lo_q = unpack_slabs(recv_lo, nq) if rm else None
            hi_q = unpack_slabs(recv_hi, nq) if rp else None
            out = []
            for q, s in enumerate(shards):
                o = s
                if rm:
                    for j in range(c):
                        piece = (lo_q[q] if j == 0 else
                                 slab(s, j - 1, off + sizes[j - 1] - rm, rm))
                        o = put(o, piece, j, off - rm, rm)
                if rp:
                    for j in range(c):
                        piece = (hi_q[q] if j == c - 1 else
                                 slab(s, j + 1, off, rp))
                        o = put(o, piece, j, off + sizes[j], rp)
                out.append(o)
            return tuple(out)

        return update

    # -- one exchange ---------------------------------------------------------
    def _phase_groups(self, leaves) -> List[Tuple[object, List[int]]]:
        """Same-dtype leaf groups in first-appearance order (PR-5's
        packing unit); per-leaf groups when batching is off — the
        transfer count then scales with Q, like the per-quantity
        ppermute program it mirrors."""
        if not self.ex.batch_quantities:
            return [(leaves[i].dtype, [i]) for i in range(len(leaves))]
        groups: Dict[object, List[int]] = {}
        for i, leaf in enumerate(leaves):
            groups.setdefault(jnp.dtype(leaf.dtype), []).append(i)
        return list(groups.items())

    def _shards_by_coords(self, leaf):
        out = {}
        for sh in leaf.addressable_shards:
            out[self._coords[sh.device.id]] = sh.data
        return out

    def __call__(self, state):
        with timer.timed("exchange.remote_emu"), \
                timer.trace_range("exchange.remote-dma.emulated"):
            return self._exchange_once(state)

    def _exchange_once(self, state):
        leaves, treedef = jax.tree.flatten(state)
        self.last_transfer_count = 0
        sharding = self.ex.sharding()
        for phase in self.plan.remote_phases:
            if not phase.active:
                continue
            leaves = self._run_phase(leaves, phase, sharding)
        return jax.tree.unflatten(treedef, leaves)

    def _run_phase(self, leaves, phase, sharding):
        mdevs = self.mesh.devices
        axis_of = {"z": 0, "y": 1, "x": 2}[phase.axis]
        m = phase.ring
        leaves = list(leaves)
        for dtype, idxs in self._phase_groups(leaves):
            nq = len(idxs)
            # only wire-crossing carriers compress (ring > 1): a
            # self-wrap phase's hand-off never leaves the device and
            # stays lossless, matching the composed lowering's policy
            wire = (wire_narrow_dtype(dtype, self.ex.wire_dtype)
                    if m > 1 else None)
            shards = [self._shards_by_coords(leaves[i]) for i in idxs]
            coords_list = list(shards[0])
            # 1. take: pack each device's outbound boundary carriers
            sent: Dict[Tuple[int, int, int], tuple] = {}
            for coords in coords_list:
                i = coords[axis_of]
                sizes = self._device_sizes(phase, i)
                args = tuple(s[coords] for s in shards)
                key = ("take", phase.axis, sizes, args[0].shape,
                       str(dtype), nq, str(wire))
                fn = self._jit(key, lambda: self._take_fn(
                    phase, sizes, args[0].shape, dtype, nq, wire))
                self._remember(key, args)
                sent[coords] = fn(*args)
            # 2. transfer: each carrier rides straight to its ring
            # neighbor — the emulated per-neighbor remote DMA (self-wrap
            # rings hand the carrier back to the same device)
            recv: Dict[Tuple[int, int, int], list] = {c: [] for c in coords_list}
            for coords in coords_list:
                i = coords[axis_of]
                out = list(sent[coords])
                if phase.rm:
                    # +axis send: this device's top slab fills the low
                    # halo of ring neighbor i+1 (the composed fwd pair)
                    dst = list(coords)
                    dst[axis_of] = (i + 1) % m
                    dst = tuple(dst)
                    carrier = out.pop(0)
                    if dst != coords:
                        carrier = jax.device_put(carrier, mdevs[dst])
                        self.last_transfer_count += 1
                    recv[dst].insert(0, ("lo", carrier))
                if phase.rp:
                    dst = list(coords)
                    dst[axis_of] = (i - 1) % m
                    dst = tuple(dst)
                    carrier = out.pop(0)
                    if dst != coords:
                        carrier = jax.device_put(carrier, mdevs[dst])
                        self.last_transfer_count += 1
                    recv[dst].append(("hi", carrier))
            # 3. update: write every halo slab from the received
            # carriers + the local resident-neighbor shifts
            new_shards: Dict[Tuple[int, int, int], tuple] = {}
            for coords in coords_list:
                i = coords[axis_of]
                sizes = self._device_sizes(phase, i)
                args = tuple(s[coords] for s in shards)
                carriers = [c for tag, c in sorted(
                    recv[coords], key=lambda t: 0 if t[0] == "lo" else 1)]
                key = ("upd", phase.axis, sizes, args[0].shape,
                       str(dtype), nq, str(wire))
                fn = self._jit(key, lambda: self._update_fn(
                    phase, sizes, args[0].shape, dtype, nq, wire))
                self._remember(key, tuple(args) + tuple(carriers))
                new_shards[coords] = fn(*args, *carriers)
            # reassemble each leaf from its updated shards
            order = [self._coords[d.id] for d in mdevs.flat]
            for q, li in enumerate(idxs):
                leaves[li] = jax.make_array_from_single_device_arrays(
                    leaves[li].shape, sharding,
                    [new_shards[c][q] for c in order],
                )
        return leaves

    # -- loops / census -------------------------------------------------------
    def make_loop(self, iters: int):
        """``iters`` back-to-back exchanges. A host loop (the emulation
        has no single compiled program to fuse) — correct, not fast; the
        fused-loop economics belong to the TPU carrier kernel."""

        def loop(state):
            for _ in range(iters):
                state = self(state)
            return state

        return loop

    def collective_census(self, state) -> Dict[str, Tuple[int, int]]:
        """Census over EVERY compiled piece one exchange of ``state``
        runs (all take/update programs): op counts summed across pieces.
        The REMOTE_DMA pin is that this comes back with no
        ``collective-permute`` entry at all."""
        from ..utils.hlo_check import collective_census

        # make sure every piece this state needs exists (and is recorded)
        self._exchange_once(state)
        total: Dict[str, Tuple[int, int]] = {}
        for key, fn in self._jits.items():
            avals = self._avals.get(key)
            if avals is None:
                continue
            txt = fn.lower(*avals).compile().as_text()
            for kind, (c, b) in collective_census(txt).items():
                c0, b0 = total.get(kind, (0, 0))
                total[kind] = (c0 + c, b0 + b)
        return total


class FusedRemoteEmulation(RemoteDmaEmulation):
    """Host-orchestrated FUSED compute+exchange schedule (ROADMAP #5).

    The fused mega-kernel's order — (1) pack boundary slabs and START
    every per-neighbor copy boundary-first, (2) compute interior tiles
    while the DMAs fly, (3) wait the recv semaphores, (4) compute the
    boundary tiles — executed host-side for non-TPU meshes, with the
    caller owning steps 2 and 4 (``_compile_jacobi_fused`` /
    ``make_fused_astaroth_loop`` slot their compiled collective-free
    sweeps between :meth:`fused_start` and :meth:`fused_finish`).

    The composed x→y→z slab geometry cannot start boundary-first (a y
    slab carries x-halo data, so phase y's send depends on phase x's
    receive); the fused schedule therefore moves one EXACT-extent
    message per active direction — the plan's ``FusedPhaseIR`` records,
    the DIRECT26 geometry re-transported. Every message reads only
    sender compute-region cells, so all of them start concurrently, and
    together they fill every declared halo cell bit-identically to
    AXIS_COMPOSED (the same data-movement argument that pins DIRECT26;
    tests/test_fused_stencil.py pins it here, wire compression
    included — a carrier rounds exactly once either way). Every compiled
    piece (per-device take/update programs) censuses ZERO
    collective-permutes, the same pin as the serialized emulation."""

    def __init__(self, ex):
        from ..geometry import Dim3

        super().__init__(ex)
        if ex.resident != Dim3(1, 1, 1):
            raise ValueError(
                "the fused compute+exchange schedule supports "
                "single-resident partitions only (got resident "
                f"{ex.resident}); use the plain REMOTE_DMA carrier or "
                "AXIS_COMPOSED for oversubscription"
            )
        if not self.plan.fused:
            raise RuntimeError(
                "fused emulation needs a fused plan (HaloExchange built "
                "without fused=True?)"
            )

    # -- geometry -------------------------------------------------------------
    def _block_sizes(self, coords) -> Tuple[int, int, int]:
        iz, iy, ix = coords
        s = self.ex.spec.block_size((ix, iy, iz))
        return (s.z, s.y, s.x)

    def _dir_slices(self, sizes, outbound: bool):
        """Per-phase static (z, y, x) slices into a padded shard: the
        outbound compute-region slab a device sends toward each
        direction, or the halo region the received carrier fills —
        exact extents, so no write overlaps another (no layering
        needed). ``sizes`` are THIS device's block sizes (ring-sharing
        makes the orthogonal extents match the sender's)."""
        spec = self.ex.spec
        r = spec.radius
        off = spec.compute_offset()
        out = []
        for ph in self.plan.fused_phases:
            dx, dy, dz = ph.direction
            sl = [slice(None), slice(None), slice(None)]
            for i, (dc, s, rmin, rplus, o) in enumerate(zip(
                (dz, dy, dx), sizes,
                (r.z(-1), r.y(-1), r.x(-1)),
                (r.z(1), r.y(1), r.x(1)),
                (off.z, off.y, off.x),
            )):
                if dc == 1:
                    sl.append(slice(o + s - rmin, o + s) if outbound
                              else slice(o - rmin, o))
                elif dc == -1:
                    sl.append(slice(o, o + rplus) if outbound
                              else slice(o + s, o + s + rplus))
                else:
                    sl.append(slice(o, o + s))
            out.append((tuple(sl), ph.crossing))
        return out

    def _fused_take_fn(self, sizes, shard_shape, dtype, nq, wire):
        """take(*shards) -> one packed carrier per direction (phase
        order), narrowed to the wire dtype on wire-crossing directions
        (self-wrap hand-offs stay lossless — the composed policy)."""
        specs = self._dir_slices(sizes, outbound=True)

        def take(*shards):
            out = []
            for sl, crossing in specs:
                car = pack_slabs([s[sl] for s in shards])
                if wire is not None and crossing:
                    car = car.astype(wire)
                out.append(car)
            return tuple(out)

        return take

    def _fused_update_fn(self, sizes, shard_shape, dtype, nq, wire):
        """update(*shards, *carriers) -> new shards: widen + unpack every
        received carrier into its exact halo region."""
        specs = self._dir_slices(sizes, outbound=False)

        def update(*args):
            shards = list(args[:nq])
            carriers = args[nq:]
            for (sl, crossing), car in zip(specs, carriers):
                if wire is not None and crossing:
                    car = car.astype(dtype)
                for q, slab in enumerate(unpack_slabs(car, nq)):
                    shards[q] = shards[q].at[sl].set(slab)
            return tuple(shards)

        return update

    # -- the fused schedule ---------------------------------------------------
    def fused_start(self, state):
        """Stages 1+2: pack every device's per-direction carriers
        (compiled takes, zero collectives) and START the emulated remote
        copies — ``device_put`` toward the neighbor, issued but not
        synced, so the caller's interior compute dispatches while they
        fly. Returns the pending structure for :meth:`fused_wait` /
        :meth:`fused_finish`."""
        leaves, treedef = jax.tree.flatten(state)
        self.last_transfer_count = 0
        mdevs = self.mesh.devices
        mz, my, mx = mdevs.shape
        phases = self.plan.fused_phases
        pending = {"treedef": treedef, "leaves": leaves,
                   "sharding": self.ex.sharding(), "groups": []}
        for dtype, idxs in self._phase_groups(leaves):
            nq = len(idxs)
            wire = wire_narrow_dtype(dtype, self.ex.wire_dtype)
            shards = [self._shards_by_coords(leaves[i]) for i in idxs]
            coords_list = list(shards[0])
            recv: Dict[Tuple[int, int, int], list] = {
                c: [None] * len(phases) for c in coords_list}
            for coords in coords_list:
                sizes = self._block_sizes(coords)
                args = tuple(s[coords] for s in shards)
                key = ("ftake", sizes, args[0].shape, str(dtype), nq,
                       str(wire))
                fn = self._jit(key, lambda: self._fused_take_fn(
                    sizes, args[0].shape, dtype, nq, wire))
                self._remember(key, args)
                carriers = fn(*args)
                iz, iy, ix = coords
                for pi, ph in enumerate(phases):
                    dx, dy, dz = ph.direction
                    dst = ((iz + dz) % mz, (iy + dy) % my,
                           (ix + dx) % mx)
                    car = carriers[pi]
                    if dst != coords:
                        car = jax.device_put(car, mdevs[dst])
                        self.last_transfer_count += 1
                    recv[dst][pi] = car
            pending["groups"].append((dtype, idxs, shards, recv))
        return pending

    def fused_wait(self, pending) -> None:
        """Stage 3: the recv-semaphore wait — block until every started
        carrier has landed on its destination device."""
        for _dt, _idxs, _shards, recv in pending["groups"]:
            for per_dev in recv.values():
                for car in per_dev:
                    if car is not None:
                        jax.block_until_ready(car)

    def fused_finish(self, pending):
        """Stage 4's data half: widen + unpack every received carrier
        into the halos (compiled updates, zero collectives) and
        reassemble the exchanged state; the caller's boundary compute
        reads the result."""
        leaves = list(pending["leaves"])
        order = [self._coords[d.id] for d in self.mesh.devices.flat]
        for dtype, idxs, shards, recv in pending["groups"]:
            nq = len(idxs)
            wire = wire_narrow_dtype(dtype, self.ex.wire_dtype)
            new_shards: Dict[Tuple[int, int, int], tuple] = {}
            for coords in recv:
                sizes = self._block_sizes(coords)
                args = tuple(s[coords] for s in shards)
                carriers = tuple(recv[coords])
                key = ("fupd", sizes, args[0].shape, str(dtype), nq,
                       str(wire))
                fn = self._jit(key, lambda: self._fused_update_fn(
                    sizes, args[0].shape, dtype, nq, wire))
                self._remember(key, args + carriers)
                new_shards[coords] = fn(*args, *carriers)
            for q, li in enumerate(idxs):
                leaves[li] = jax.make_array_from_single_device_arrays(
                    leaves[li].shape, pending["sharding"],
                    [new_shards[c][q] for c in order],
                )
        return jax.tree.unflatten(pending["treedef"], leaves)

    def _exchange_once(self, state):
        """One standalone fused exchange (no compute slotted in): the
        same pack → start → wait → update schedule, back to back."""
        pending = self.fused_start(state)
        self.fused_wait(pending)
        return self.fused_finish(pending)


def run_fused_substep(emu, state, interior, boundary, rec=None):
    """One host-orchestrated fused substep — THE shared overlap
    protocol of the fused step loops (ops/jacobi._compile_jacobi_fused,
    astaroth/integrate.make_fused_astaroth_loop): start every emulated
    copy, dispatch the caller's interior compute while they fly, wait,
    unpack, then the caller's boundary compute, each stage under its
    variant-tagged ``fused.*`` span so every fused loop reports the same
    overlap semantics.

    ``interior()`` returns the interior-computed output; ``boundary
    (exchanged_state, out)`` returns the finished output. Both must be
    collective-free compiled programs. Returns ``(exchanged_state, out,
    interior_seconds, total_seconds)`` — the caller accumulates the two
    times into its ``fused.overlap_fraction`` gauge."""
    import time as _time

    from ..obs import telemetry

    rec = rec or telemetry.get()
    t0 = _time.perf_counter()
    with rec.span("fused.pack", phase="exchange", variant="fused"):
        pending = emu.fused_start(state)
    t1 = _time.perf_counter()
    with rec.span("fused.interior", phase="compute", variant="fused"):
        out = interior()
        jax.block_until_ready(out)
    t2 = _time.perf_counter()
    with rec.span("fused.dma_wait", phase="exchange", variant="fused"):
        emu.fused_wait(pending)
    cur2 = emu.fused_finish(pending)
    with rec.span("fused.boundary", phase="compute", variant="fused"):
        out = boundary(cur2, out)
        jax.block_until_ready(out)
    t3 = _time.perf_counter()
    return cur2, out, t2 - t1, t3 - t0
