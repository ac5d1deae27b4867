"""26-neighbor halo exchange over a TPU device mesh, periodic unless an axis
is fixed.

This single module replaces the reference's entire transport zoo — the eight
``Method`` transports, the pack/unpack kernels, the staged/pinned-buffer MPI
state machines, and the CPU polling engine (reference: include/stencil/
method.hpp:5-16, tx_cuda.cuh, tx_colocated.cu, src/stencil.cu:1002-1186).
On TPU all of it collapses into collective permutes compiled by XLA onto the
ICI torus: ``lax.ppermute`` of boundary slabs inside a ``shard_map``-ped,
jitted function (SURVEY.md §5.8). "CUDA graph capture" of the exchange
(packer.cu:96-103) corresponds to the one-time XLA compilation of that jit.

Three exchange strategies are kept (the analogue of the reference's method
selection, src/stencil.cu:372-412):

- ``Method.AXIS_COMPOSED`` (default): three phases, one per axis, two
  ``ppermute``s each. Each phase's slabs span the *full padded extent* of
  the other axes, so edge and corner halos are composed from consecutive
  phases (x fills faces; y slabs carry x-halo data into xy-edges; z slabs
  carry both into xz/yz-edges and corners). 6 collectives total,
  independent of radius shape; supports uneven (remainder) partitions via
  per-device dynamic slab offsets.
- ``Method.DIRECT26``: one ``ppermute`` per active direction (the literal
  translation of the reference's 26 messages) — exact extents on uniform
  partitions; on uneven (remainder) partitions the orthogonal extents are
  padded to the base block size and messages apply in face→edge→corner
  order so every halo cell still ends correct (blocks in the same ring
  share orthogonal-axis sizes, so the valid slab region always aligns).
  Useful for verification and collective-count ablation.
- ``Method.AUTO_SPMD``: NO hand-written collectives at all. The halo fill
  is expressed as a jitted program over the globally-sharded stacked array
  — shifted slices rolled along the *block* dims — and XLA's SPMD
  partitioner synthesizes the collective-permutes. This is the repo's
  analogue of the reference's ``bench_mpi_pack`` question (bin/
  bench_mpi_pack.cu:18-80): does hand-built data-movement machinery beat
  the toolchain's built-in path? Same send-extent rule, periodic wrap,
  radius shapes, uneven partitions, and oversubscription as AXIS_COMPOSED
  (the partitioner turns shard-internal shifts into local copies and
  shard-boundary shifts into permutes on its own); results are required
  bit-identical (tests/test_auto_spmd.py, bench_exchange --ablate).

Boundaries and extents other than the defaults (AXIS_COMPOSED, one block a
device; everything else refuses them): ``periodic=(x, y, z)`` with a
``False`` fixes an axis. Its permute pairs leave the wrap out, its two edge
blocks keep their outer halo (the application's ghost: seeded once, never
written here), and a fixed axis of one block has no phase at all.
``faces_only`` says the stencil is a star: a slab is cut to the compute
region of its orthogonal leading axes (whole rows stay whole), so no edge
or corner halo is filled and fewer bytes move. Which QUANTITIES move is the
caller's: every entry point takes the state it is to exchange, and
``DistributedDomain`` hands over those declared ``exchanged``.
``quantity_radius`` (``{state key: Radius}``, every key of the state) is a
radius A QUANTITY: which of the 26 directions' halos are FILLED for it,
among those the spec allocates. A direction of an axis phase then carries
only the quantities that want that side (a lattice-Boltzmann population,
read at one offset, wants one side an axis), whether the phase is a
permute, an XLA slab copy or a self-fill kernel
(``make_self_fill(sides=)``); a z slab keeps the y halo rows only where a
carried quantity's edge gate asks for them. ``None``: the plan and the
program there always were.

The wire's schedule on the slab path (``_slab_phases``): both directions
of an axis phase are packed from the blocks as the phase finds them, both
permutes follow with no data dependence between them, both slabs are
placed last. Where a block has ONE neighbour on the axis (a fixed axis of
two blocks: ``AxisPhaseIR.merged``) the phase sends one carrier in one
``ppermute`` over ``fwd + bwd``, the slab's and the halo's start picked by
``lax.axis_index``. The phases of a faces-only plan that read nothing of
each other (y and z: each slab is cut to the other's compute region) leave
in one wave (``_waves``). The counter ``halo.wire_schedule`` records what
a composed body issued.

Send-extent rule pinned from the reference: the data sent toward direction
``d`` fills the receiver's ``-d``-side halo, so its extent is
``halo_extent(-d)`` and a direction is active iff ``radius.dir(-d) != 0``
(reference: src/stencil.cu:344,358-360, test_cuda_local_domain.cu "case1").

Quantity batching (default on, ``batch_quantities=``): a multi-quantity
dict state exchanges per same-dtype group — each collective carries ONE
packed ``(Q, ...slab)`` carrier holding every quantity's boundary slab, so
the collective count per exchange is independent of the quantity count
(6 composed permutes or ≤26 direct ones total, not per quantity). This is
the ``ppermute`` analogue of the reference's multi-quantity per-neighbor
message (packer.cu:10-26) and the answer to the per-collective-overhead
economics the Round-7 ablation measured (DIRECT26 moved 1.9× fewer bytes
but ran 4.2× slower purely on collective count, BASELINE.md).

Every strategy lowers from the declarative ExchangePlan IR
(``stencil_tpu/plan/ir.py``): :attr:`HaloExchange.plan` holds the phase
list (axis phases with permute pairs and size tables; direct26 direction
messages with carrier extents), and the lowering bodies below consume
phase records instead of recomputing the geometry inline. The partition/
method autotuner (``stencil_tpu/plan/``) searches those plans — not code
paths — and this module is required to compile each plan bit-identically
to the historical method branches (census pins in tests/test_plan_ir.py).
"""

from __future__ import annotations

import enum
from functools import cached_property
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding

from ..domain.grid import GridSpec
from ..geometry import DIRECTIONS_26, Dim3, halo_extent
from ..obs import scopes
from ..plan.ir import RETIRED_METHODS, build_plan, spec_axis as _spec_axis
from ..utils import timer
from .mesh import AXIS_X, AXIS_Y, AXIS_Z, BLOCK_PSPEC, block_sharding, mesh_dim


class Method(enum.Enum):
    """Exchange strategy (TPU analogue of method.hpp:5-16)."""

    AXIS_COMPOSED = "axis-composed"
    DIRECT26 = "direct26"
    AUTO_SPMD = "auto-spmd"


def direction_bytes(spec: GridSpec, direction, itemsize: int) -> int:
    """Logical bytes received across all blocks for one direction's halos —
    the accounting the reference Allreduces into per-method counters
    (reference: src/stencil.cu:139-161,620-627)."""
    d = Dim3.of(direction)
    if spec.radius.dir(d) == 0:
        return 0
    total = 0
    for iz in range(spec.dim.z):
        for iy in range(spec.dim.y):
            for ix in range(spec.dim.x):
                ext = halo_extent(d, spec.block_size((ix, iy, iz)), spec.radius)
                total += ext.flatten() * itemsize
    return total


class HaloExchange:
    """A compiled halo-exchange over stacked-block arrays.

    State layout: each quantity is an array of shape
    ``(bz, by, bx, pz, py, px)`` sharded ``P('z','y','x')`` over a grid
    mesh; ``__call__`` fills every halo cell whose direction is active and
    returns the updated pytree (donated, so XLA reuses the buffers —
    the in-place halo write of the reference's unpack kernels).

    ``batch_quantities`` (default on): multi-quantity dict states exchange
    per same-dtype GROUP — each collective carries one packed ``(Q, ...)``
    carrier of every quantity's boundary slab, so the collective count per
    exchange is independent of the quantity count (one ``ppermute`` pair
    per composed axis phase / one permute per DIRECT26 direction — the
    multi-quantity message of the reference's DevicePacker, packer.cu:
    10-26, re-expressed for ``lax.ppermute``). ``False`` keeps the
    historical one-collective-per-quantity program (the A/B baseline:
    ``bench_exchange --batched-ab``). Results are bit-identical either
    way — the exchange is pure data movement.
    """

    def __init__(self, spec: GridSpec, mesh: Mesh, method: Method = Method.AXIS_COMPOSED,
                 batch_quantities: bool = True, wire_dtype=None,
                 periodic=(True, True, True), faces_only: bool = False,
                 quantity_radius=None):
        if method in RETIRED_METHODS:
            raise ValueError(
                f"exchange method {method!r} is retired: the halo has one "
                f"transport (choose from {[m.value for m in Method]})")
        method = Method(method)
        md = mesh_dim(mesh)
        # oversubscription (reference: dd.set_gpus({0,0}), stencil.hpp:154,
        # test_exchange.cu:52): more partition blocks than devices — the
        # extra blocks are RESIDENT: stacked along the block dims of each
        # shard, exchanged by intra-device slab shifts (see
        # _axis_phase_resident_batched). Any axis may stack (mixed (cz,cy,cx)
        # stacking included) and splits may be uneven — per-resident sizes
        # come from traced lookups into the static per-axis size tables,
        # the same machinery as the dynamic overlap shells (ops/shells.py).
        if spec.dim.x % md.x or spec.dim.y % md.y or spec.dim.z % md.z:
            raise ValueError(
                f"mesh {dict(mesh.shape)} does not divide partition {spec.dim}"
            )
        self.resident = Dim3(
            spec.dim.x // md.x, spec.dim.y // md.y, spec.dim.z // md.z
        )
        self.resident_z = self.resident.z
        for name in (AXIS_X, AXIS_Y, AXIS_Z):
            sizes, rm, rp, _off = _spec_axis(spec, name)
            if min(sizes) < max(rm, rp):
                # halos come from the adjacent block only (one neighbor per
                # direction, like the reference's 26-message plan)
                raise ValueError(
                    f"{name}-axis block size {min(sizes)} < radius {max(rm, rp)}: "
                    "halo would span multiple blocks"
                )
        self.spec = spec
        self.mesh = mesh
        self.method = method
        self.batch_quantities = bool(batch_quantities)
        # bf16-on-the-wire halo compression: wire-crossing packed
        # carriers narrow to this dtype before the send and widen on
        # unpack (ops/halo_fill.wire_narrow_dtype owns the policy: only
        # floating carriers ever narrow; local copies stay lossless).
        # Lossy by design — parity gates run with it off; bench_exchange
        # --wire-ab measures the error it buys the bandwidth with.
        if wire_dtype is not None:
            wire_dtype = str(jnp.dtype(wire_dtype))
            if method == Method.AUTO_SPMD:
                from ..utils import logging as log

                log.warn("wire_dtype is ignored for Method.AUTO_SPMD: the "
                         "SPMD partitioner owns the collective schedule "
                         "and packs no carriers")
                wire_dtype = None
        self.wire_dtype = wire_dtype
        # fixed axes and a star's faces-only slabs (module docstring): both
        # are AXIS_COMPOSED lowerings with one block a device, and building
        # the plan here refuses anything else at once
        self.periodic = tuple(bool(v) for v in periodic)
        self.faces_only = bool(faces_only)
        # axis -> (permutes, stages) while a composed body is being built
        # (:meth:`_permute_wire`), for the counter ``halo.wire_schedule``
        self._wired = None
        # a radius a quantity (module docstring): ``{state key: Radius}``
        self.quantity_radius = dict(quantity_radius) if quantity_radius else None
        if (not all(self.periodic) or self.faces_only
                or self.quantity_radius is not None):
            self.plan

    @property
    def oversubscribed(self) -> bool:
        """More partition blocks than devices on at least one axis."""
        return self.resident != Dim3(1, 1, 1)

    def _on_tpu(self) -> bool:
        return all(d.platform == "tpu" for d in self.mesh.devices.flatten())

    @cached_property
    def plan(self):
        """The declarative ExchangePlan this exchange lowers from
        (phases, directions, pack groups, permute pairs — plan/ir.py).
        The autotuner scores these same plans without compiling them."""
        return build_plan(
            self.spec, mesh_dim(self.mesh), self.method,
            batch_quantities=self.batch_quantities, resident=self.resident,
            wire_dtype=self.wire_dtype, periodic=self.periodic,
            faces_only=self.faces_only,
            quantity_radius=self.quantity_radius,
        )

    # -- public API ----------------------------------------------------------
    def __call__(self, state):
        return self._compiled(state)

    def exchange_block(self, block, axes=None):
        """Per-block exchange body for composing into larger shard_map'd
        steps (e.g. fused compute/exchange overlap): takes and returns one
        (1,1,1,pz,py,px) block inside a ``shard_map`` over this mesh.

        ``axes`` (AXIS_* names) restricts the composed method to a subset of
        axis phases — used by fused kernels that handle self-wrap axes
        internally. Only valid for AXIS_COMPOSED."""
        if self.method == Method.AUTO_SPMD:
            raise RuntimeError(
                "Method.AUTO_SPMD has no per-block exchange body: its "
                "collectives are synthesized by the SPMD partitioner from "
                "the global program (use __call__/make_loop/auto_fill, or a "
                "manual method for shard_map composition)"
            )
        if self.method == Method.DIRECT26:
            if axes is not None:
                raise ValueError("axis subsetting requires AXIS_COMPOSED")
            return self._direct26_blocks(block)
        if self.quantity_radius is not None:
            raise RuntimeError(
                "this exchange has a radius a quantity: hand "
                "exchange_blocks the quantity dict, so that each block is "
                "known by its key")
        return self._composed_blocks(block, axes)

    def x_side_buffers(self, block, r: int):
        """Out-of-line x halos for a tight-x layout on a MULTI-BLOCK x axis
        (``Radius.without_x`` with dim.x > 1): the halo columns that would
        live inline are delivered as thin side buffers instead. Returns
        ``(xlo, xhi)``: ``xlo[..., j]`` holds the cell at global
        ``x = x0 - r + j`` (the -x neighbor's top columns), ``xhi[..., j]``
        at ``x0 + nx + j``. Per-block, inside ``shard_map``. The kernels
        roll the interior periodically and the x-edge columns are patched
        from these buffers — the reference's pack-to-buffer transport
        economics (src/pack_kernel.cu:3-54) re-expressed: dense side
        buffers instead of strided inline halo writes."""
        if self.spec.radius.x(-1) != 0 or self.spec.radius.x(1) != 0:
            raise ValueError(
                "x_side_buffers is the tight-x (zero x radius) transport"
            )
        sizes = self.spec.sizes_x
        if len(set(sizes)) != 1:
            raise ValueError("side buffers require a uniform x split")
        if self.resident.x != 1:
            raise ValueError("side buffers do not support x residency")
        n = len(sizes)
        nx = sizes[0]
        with scopes.scope(scopes.HALO_PACK):
            hi_cols = block[..., nx - r : nx]
            lo_cols = block[..., 0:r]
        if n > 1:
            fwd = [(i, (i + 1) % n) for i in range(n)]
            bwd = [(i, (i - 1) % n) for i in range(n)]
            with scopes.scope(scopes.HALO_WIRE):
                return (
                    lax.ppermute(hi_cols, AXIS_X, fwd),
                    lax.ppermute(lo_cols, AXIS_X, bwd),
                )
        return hi_cols, lo_cols

    def exchange_blocks(self, state):
        """Per-block exchange of a whole quantity dict inside ``shard_map``.

        Unlike mapping :meth:`exchange_block` per quantity, the dict is
        processed per same-dtype group (never bitcast): with
        ``batch_quantities`` each collective moves ONE packed ``(Q, ...)``
        carrier of the whole group's boundary slabs — a Q-independent
        collective count per exchange — and fp32 quantities on self-wrap
        axes share the fused multi-quantity fill kernels (the
        multi-quantity-pack analogue, packer.cu:10-26) — one kernel per
        axis phase instead of one per quantity. Non-fp32 groups on
        self-wrap axes take a packed slab fill: one fused slice/update
        pair per phase for the group (the fp64 analogue of the fused
        fills; ROADMAP #5)."""
        if self.method == Method.AUTO_SPMD:
            raise RuntimeError(
                "Method.AUTO_SPMD has no per-block exchange body "
                "(see exchange_block); use __call__/make_loop instead"
            )
        if not isinstance(state, dict):
            return jax.tree.map(self.exchange_block, state)
        if self.quantity_radius is not None and (
                set(state) != set(self.quantity_radius)):
            raise ValueError(
                f"the state's keys {sorted(state)} are not the quantities "
                f"this exchange has a radius for "
                f"({sorted(self.quantity_radius)})")
        from ..ops.halo_fill import dtype_groups

        groups = dtype_groups(state)
        if self.method == Method.DIRECT26:
            if not self.batch_quantities:
                return jax.tree.map(self.exchange_block, state)
            out = dict(state)
            for _dt, keys in groups:
                blocks = self._direct26_batched([out[k] for k in keys])
                for k, b in zip(keys, blocks):
                    out[k] = b
            return out
        return self._composed_quantities(state, groups)

    def _composed_quantities(self, state, groups, axes=None):
        """AXIS_COMPOSED over a quantity dict, one same-dtype group at a
        time per wave of axis phases (:meth:`_waves`: as a rule one phase;
        the phases of a faces-only plan that read nothing of each other
        leave together): fused Pallas fills for fp32 self-wrap axes,
        packed-carrier phases (one ppermute pair per phase per group)
        elsewhere, per-quantity carriers when ``batch_quantities`` is off.
        ``axes`` restricts the phases (:meth:`exchange_block`). Records
        ``halo.wire_schedule``, once a build. Under a radius a quantity
        each phase moves what its two directions carry
        (:meth:`_carried_phase`)."""
        fills = self._self_fills
        out = dict(state)
        waves = self._waves([ph for ph in self.plan.axis_phases if ph.active
                             and (axes is None or ph.axis in axes)])
        self._wired = {}
        try:
            for wave in waves:
                for dt, keys in groups:
                    if self.quantity_radius is not None:
                        (ph,) = wave    # nothing of such a plan is cut to
                        self._carried_phase(out, dt, keys, ph)  # leave together
                        continue
                    fill = (len(wave) == 1 and wave[0].blocks == 1
                            and wave[0].axis in fills and dt == jnp.float32)
                    for batch in ([keys] if fill or self.batch_quantities
                                  else [[k] for k in keys]):
                        blocks = [out[k] for k in batch]
                        if fill:
                            blocks = self._self_fill_group(
                                wave[0].axis, blocks)
                        elif len(wave) == 1:
                            blocks = self._axis_phase_batched(blocks, wave[0])
                        else:
                            blocks = self._slab_phases(blocks, wave)
                        out.update(zip(batch, blocks))
        finally:
            wired, self._wired = self._wired, None
        from ..obs import telemetry

        def tally(ph):                  # (permutes, stages) of a phase
            return wired.get(ph.axis, (0, 0))

        telemetry.get().counter(
            "halo.wire_schedule", phase="exchange",
            value=sum(count for count, _stages in wired.values()),
            phases=[{"axis": ph.axis, "permutes": tally(ph)[0],
                     "merged": ph.merged}
                    for wave in waves for ph in wave],
            waves=sum(max(tally(ph)[1] for ph in wave) for wave in waves))
        return out

    @staticmethod
    def _waves(phases):
        """The phases in the plan's order, grouped into waves that leave
        together: a phase joins the wave before it where it reads nothing
        that wave's phases write. Its slab is then cut (``trim``) to the
        compute region along each of their axes, which is what a
        faces-only plan does to its y and z slabs (x rides whole: y and z
        carry the x halos, so they wait for an x phase). Every member
        crosses the wire on evenly split blocks (on an uneven split the
        static cut reaches into a smaller block's halo). Any other phase
        is a wave of its own."""
        waves = []
        for ph in phases:
            cut = {dim for dim, _lo, _w in ph.trim}
            if waves and ph.ring > 1 and ph.uniform and all(
                    m.ring > 1 and m.uniform and m.adim in cut
                    for m in waves[-1]):
                waves[-1].append(ph)
            else:
                waves.append([ph])
        return waves

    def _self_fill_group(self, name: str, blocks, sides=(True, True)):
        """One fp32 group on a self-wrap axis: the Pallas fill writes the
        halos in place, touching only the edge tiles. The x and y kernels'
        scratch scales with the quantity count, so a group larger than
        their VMEM budget carries goes in chunks; the z fill carries every
        quantity in one kernel. ``sides``: the (low, high) halos this
        group wants filled."""
        from ..ops.halo_fill import max_fill_group

        fshape = self._fill_shape()
        step = (len(blocks) if name == AXIS_Z
                else max_fill_group(self.spec, name))
        out = []
        for i in range(0, len(blocks), step):
            chunk = blocks[i : i + step]
            fill = self._multi_fill(name, len(chunk), sides)
            with scopes.scope(scopes.HALO_SELF_FILL):
                res = fill(*[b.reshape(fshape) for b in chunk])
                res = (res,) if len(chunk) == 1 else res
                out += [v.reshape(b.shape) for v, b in zip(res, chunk)]
        return out

    def _multi_fill(self, axis: str, nq: int, sides=(True, True)):
        cache = self.__dict__.setdefault("_multi_fills", {})
        key = (axis, nq) if all(sides) else (axis, nq, tuple(sides))
        if key not in cache:
            if nq == 1 and all(sides):
                cache[key] = self._self_fills[axis]
            else:
                from ..ops.halo_fill import make_self_fill
                from .mesh import MESH_AXES

                one_sided = {} if all(sides) else {"sides": tuple(sides)}
                cache[key] = make_self_fill(
                    self.spec, axis, vma=MESH_AXES, nq=nq,
                    z_stack=self.resident.z, **one_sided)
        return cache[key]

    @cached_property
    def _compiled(self):
        if self.method == Method.AUTO_SPMD:
            sh = self.sharding()
            return jax.jit(
                lambda state: jax.tree.map(self.auto_fill, state),
                in_shardings=sh, out_shardings=sh, donate_argnums=0,
            )
        fn = jax.shard_map(
            self.exchange_blocks,
            mesh=self.mesh,
            in_specs=BLOCK_PSPEC,
            out_specs=BLOCK_PSPEC,
        )
        return jax.jit(fn, donate_argnums=0)

    def sharding(self) -> NamedSharding:
        return block_sharding(self.mesh)

    def make_loop(self, iters: int, like=None):
        """``iters`` back-to-back exchanges in one compiled program — for
        benchmarking without per-dispatch host overhead (the analogue of the
        reference's timed exchange loop, bin/exchange_weak.cu:168-177).
        Loops are cached per ``iters``, so repeated calls reuse the jitted
        program instead of retracing. The program is the module
        ``stencil_exchange_loop``; with ``like`` (the state it will be
        called with, arrays or structs) it is registered for
        ``obs.scopes.op_map``."""
        cache = self.__dict__.setdefault("_loops", {})
        if iters not in cache:
            args = None if like is None else (
                scopes.abstract(like, self.sharding()),)
            # build-phase accounting for all strategies (the
            # flight-recorder bucket; jax.profiler sees the same range)
            with timer.timed("exchange.build"), \
                    timer.trace_range(f"exchange.{self.method.value}.build"):
                if self.method == Method.AUTO_SPMD:
                    def many(state):
                        return lax.fori_loop(
                            0, iters,
                            lambda _, s: jax.tree.map(self.auto_fill, s), state,
                        )

                    sh = self.sharding()
                    cache[iters] = scopes.jit_loop(
                        scopes.EXCHANGE_LOOP, many, args, in_shardings=sh,
                        out_shardings=sh, donate_argnums=0,
                    )
                    return cache[iters]

                def many(state):
                    return lax.fori_loop(
                        0, iters, lambda _, s: self.exchange_blocks(s), state
                    )

                fn = jax.shard_map(
                    many, mesh=self.mesh, in_specs=BLOCK_PSPEC,
                    out_specs=BLOCK_PSPEC,
                )
                cache[iters] = scopes.jit_loop(
                    scopes.EXCHANGE_LOOP, fn, args, donate_argnums=0)
        return cache[iters]

    def collective_census(self, state) -> Dict[str, Tuple[int, int]]:
        """``{op kind: (count, bytes)}`` of ONE compiled exchange of
        ``state`` — the per-method data-movement census the bench_mpi_pack
        ablation tables row out (see utils/hlo_check.collective_census).
        Static counts over the post-SPMD-partitioning HLO: what each
        strategy actually asks the interconnect to move, counted the same
        way for hand-written ppermutes and partitioner-synthesized ones."""
        from ..utils.hlo_check import collective_census

        with timer.timed("exchange.census"), \
                timer.trace_range(f"exchange.{self.method.value}.census"):
            txt = self._compiled.lower(state).compile().as_text()
            return collective_census(txt)

    def _by_key(self, itemsizes, keys) -> Dict:
        if keys is None or len(keys) != len(itemsizes):
            raise ValueError(
                "an exchange with a radius a quantity counts bytes by "
                "quantity: pass keys= aligned with itemsizes")
        return dict(zip(keys, itemsizes))

    def bytes_logical(self, itemsizes: Sequence[int], keys=None) -> int:
        """Total halo bytes delivered per exchange (reference-parity count),
        for the quantities whose ``itemsizes`` are given (the exchanged
        ones). A fixed axis delivers nothing across the domain's edge and a
        faces-only exchange nothing to edges and corners; under a radius a
        quantity (``keys``: the state keys the itemsizes belong to) each
        quantity counts the directions its own radius has."""
        spec = self.spec
        if self.quantity_radius is not None:
            total = 0
            for key, size in self._by_key(itemsizes, keys).items():
                r = self.quantity_radius[key]
                total += size * sum(
                    halo_extent(d, spec.block_size((ix, iy, iz)),
                                spec.radius).flatten()
                    for d in DIRECTIONS_26 if r.dir(d)
                    for iz in range(spec.dim.z) for iy in range(spec.dim.y)
                    for ix in range(spec.dim.x))
            return total
        per_item = 0
        for d in DIRECTIONS_26:
            if spec.radius.dir(d) == 0 or (
                    self.faces_only and abs(d.x) + abs(d.y) + abs(d.z) > 1):
                continue
            for iz in range(spec.dim.z):
                for iy in range(spec.dim.y):
                    for ix in range(spec.dim.x):
                        if all(wraps or 0 <= i + c < n for wraps, i, c, n in zip(
                                self.periodic, (ix, iy, iz), (d.x, d.y, d.z),
                                (spec.dim.x, spec.dim.y, spec.dim.z))):
                            per_item += halo_extent(
                                d, spec.block_size((ix, iy, iz)),
                                spec.radius).flatten()
        return per_item * sum(itemsizes)

    def bytes_moved(self, itemsizes: Sequence[int], keys=None) -> int:
        """Bytes relocated by the exchange implementation: composed slabs
        span full padded extents, so this is >= bytes_logical. On a
        self-wrap (single-block) axis no collective carries data — the same
        slab bytes move in place, via the Pallas fill kernel on TPU (whose
        x/y lane/row-tile RMW amplification is not counted here: the
        ``halo.self_fill.bytes_dma`` counter each fill build records holds
        the HBM bytes its DMAs really read and write, as
        ``halo.split_x.bytes_dma`` does for a split x phase's edge-tile
        kernels) or via slice+update elsewhere. AUTO_SPMD expresses the
        composed slab
        program, so it shares the composed accounting (the partitioner may
        move less; collective_census counts what it actually emitted).
        Uneven DIRECT26 pads orthogonal extents to the base block size."""
        if self.method == Method.DIRECT26:
            if self.spec.is_uniform():
                return self.bytes_logical(itemsizes)
            r = self.spec.radius
            b = self.spec.base
            total = 0
            for d in DIRECTIONS_26:
                if r.dir(-d) == 0:
                    continue
                ext = 1
                for dc, rm, rp, base in (
                    (d.z, r.z(-1), r.z(1), b.z),
                    (d.y, r.y(-1), r.y(1), b.y),
                    (d.x, r.x(-1), r.x(1), b.x),
                ):
                    ext *= rm if dc == 1 else rp if dc == -1 else base
                total += ext
            return total * sum(itemsizes) * self.spec.num_blocks()
        if self.quantity_radius is not None:
            sizes = self._by_key(itemsizes, keys)
            return self.plan.wire_bytes(sizes) + self.plan.local_bytes(sizes)
        # the plan's own slab extents (x, y and z phase: both radii times
        # the padded orthogonal extent, every block; no wrap slab on a
        # fixed axis, orthogonal axes cut to the compute region for a star)
        return sum(ph.wire_cells + ph.local_cells
                   for ph in self.plan.axis_phases) * sum(itemsizes)

    # -- axis-composed implementation ---------------------------------------
    def _composed_blocks(self, block, axes=None):
        """One quantity: the dict body's one-key degeneration."""
        return self._composed_quantities(
            {0: block}, [(block.dtype, [0])], axes)[0]

    @cached_property
    def _self_fills(self):
        """axis name -> in-place Pallas halo-fill kernel, for single-block
        (self-wrap) axes on TPU (the pack/unpack-kernel analogue; see
        ops/halo_fill.py). Empty off-TPU or for unsupported layouts.

        Pure z-stack residency ((cz, 1, 1) oversubscription) keeps the
        fills: the x/y kernels act within each z plane, so the stacked
        shard viewed as one (cz*pz, py, px) array is filled by ONE kernel
        (VERDICT r4 item 7 — the reference's same-GPU fast path also runs
        under oversubscription, tx_cuda.cuh:41-113). Mixed x/y residency
        stacks non-z block dims the contiguous reshape can't express —
        those keep the XLA slab path."""
        devs = self.mesh.devices.flatten()
        if not all(d.platform == "tpu" for d in devs):
            return {}
        if self.resident.x != 1 or self.resident.y != 1:
            return {}
        from ..ops.halo_fill import make_self_fill, self_fill_supported
        from .mesh import MESH_AXES

        fills = {}
        for name, wraps in zip((AXIS_X, AXIS_Y, AXIS_Z), self.periodic):
            sizes, _rm, _rp, _o = _spec_axis(self.spec, name)
            if wraps and len(sizes) == 1 and self_fill_supported(
                self.spec, name, jnp.float32, z_stack=self.resident.z
            ):
                fills[name] = make_self_fill(
                    self.spec, name, vma=MESH_AXES, z_stack=self.resident.z
                )
        return fills

    def _fill_shape(self) -> Tuple[int, int, int]:
        """The contiguous 3-d view a self-fill kernel runs over: the padded
        block, with any resident z-stack folded into the leading dim."""
        p = self.spec.padded()
        return (self.resident.z * p.z, p.y, p.x)

    def _resident_sizes(self, name: str, c: int):
        """This device's ``c`` resident block sizes along one axis: static
        ints on a uniform split, traced lookups into the static size table
        otherwise (global block index = axis_index * c + j — jax shards the
        leading block dims in contiguous chunks)."""
        sizes, _rm, _rp, _off = _spec_axis(self.spec, name)
        if len(set(sizes)) == 1:
            return [sizes[0]] * c
        tbl = jnp.asarray(sizes, jnp.int32)
        idx = lax.axis_index(name)
        return [tbl[idx * c + j] for j in range(c)]

    def _permute_wire(self, carrier, name, pairs, stage: int = 0):
        """One wire-crossing ``ppermute`` of a packed carrier, paying the
        optional bf16-on-the-wire compression: the carrier narrows to
        ``wire_dtype`` on the send side and widens back after the permute
        (rounding ``astype``, never a bitcast). ONLY data that actually
        crosses the interconnect comes through here — self-wrap copies
        and resident-neighbor shifts never do, so they stay lossless.
        ``stage``: which of its phase's groups of permutes this one is in;
        a later group packs from what an earlier one delivered (only the
        resident body has two). A composed body under construction tallies
        both for ``halo.wire_schedule``."""
        from ..ops.halo_fill import wire_narrow_dtype

        if self._wired is not None:
            count, stages = self._wired.get(name, (0, 0))
            self._wired[name] = (count + 1, max(stages, stage + 1))
        w = wire_narrow_dtype(carrier.dtype, self.wire_dtype)
        with scopes.scope(scopes.HALO_WIRE):
            if w is None:
                return lax.ppermute(carrier, name, pairs)
            native = carrier.dtype
            # optimization_barrier on BOTH sides: XLA's convert-mover
            # happily hoists a narrowing convert across a collective-permute
            # (and fuses the pair back into a sender-side rounding), which
            # keeps the rounding but puts full-width bytes back on the wire —
            # the barriers pin narrow-before-send / widen-after-receive so
            # the permute payload (what the census bytes count) really is
            # the wire dtype
            wired = lax.optimization_barrier(carrier.astype(w))
            out = lax.optimization_barrier(lax.ppermute(wired, name, pairs))
            return out.astype(native)

    # -- quantity-batched phases (packed carriers) ---------------------------
    def _axis_phase_batched(self, blocks, phase):
        """One composed axis phase for a same-dtype quantity group: every
        quantity's boundary slab is gathered and stacked into one packed
        ``(Q, ...slab)`` carrier, and ONE ``ppermute`` a direction moves
        the whole group — the collective count per phase is independent
        of Q (the DevicePacker's per-neighbor multi-quantity message,
        packer.cu:10-26, as a ppermute payload). Self-wrap axes (n == 1)
        skip the permute: the packed carrier is a single fused slab copy,
        which is also the non-fp32 fill path (fp32 self-wrap axes use the
        Pallas fills upstream). Bit-identical to per-quantity phases —
        the exchange is pure data movement. Q=1 is the per-quantity
        program (pack_slabs is the identity then). All geometry (size
        table, permute pairs, radii, offsets) comes from the phase record
        of the ExchangePlan IR. Two phases leave the slab body
        (:meth:`_slab_phases`): blocks resident on this axis
        (:meth:`_axis_phase_resident_batched`), and a split x (lane) axis
        that :meth:`_split_x` accepts, which packs and unpacks on the two
        edge lane-tiles (:meth:`_split_x_phase`), same carrier count, same
        bits."""
        if not phase.active:
            return blocks
        if phase.resident > 1:
            return self._axis_phase_resident_batched(blocks, phase)
        if self._split_x(phase, blocks[0].dtype):
            return self._split_x_phase(blocks, phase)
        return self._slab_phases(blocks, (phase,))

    def _sides(self, phase):
        """THE slab geometry of an axis phase, one entry a carrier:
        ``(source start, halo start, width, permute pairs, edge)``. The
        low halo ``[off - rm, off)`` is filled from the top ``rm`` owned
        cells of the block below (``fwd``), the high halo at ``off + sz``
        from the bottom ``rp`` of the block above (``bwd``); sources are
        owned cells, which no side writes. On a fixed axis the block at
        ``edge`` hears nothing on that side and keeps what its halo holds
        (the domain's ghost); ``None``: every block hears. Where the phase
        is ``merged`` a block has one neighbour, so ONE side whose two
        starts depend on which end the block is: the start of a slice,
        not a select over data."""
        rm, rp, off, n = phase.rm, phase.rp, phase.offset, phase.ring
        if phase.uniform:
            sz = phase.sizes[0]
        else:
            sz = jnp.asarray(phase.sizes, dtype=jnp.int32)[
                lax.axis_index(phase.axis)]
        low = (off + sz - rm, off - rm, rm, phase.fwd,
               None if phase.periodic else 0)
        high = (off, off + sz, rp, phase.bwd,
                None if phase.periodic else n - 1)
        if phase.merged:
            (lower, _upper), = phase.fwd
            # the lower block sends up the axis and hears from above
            up = lax.axis_index(phase.axis) == lower
            return [(jnp.where(up, low[0], high[0]),
                     jnp.where(up, high[1], low[1]), rm,
                     phase.fwd + phase.bwd, None)]
        return [side for side in (low, high) if side[2] > 0]

    def _slab_phases(self, blocks, phases):
        """Axis phases that read nothing of each other (:meth:`_waves`;
        as a rule ONE phase), for a same-dtype group, one block a device:
        every carrier of every phase is packed from the blocks as they
        come in, then every permute is issued with no data dependence on
        another, then every slab is placed. Bit for bit what one side
        after another gives: the sources are cells no side writes and the
        placements are disjoint."""
        from ..ops.halo_fill import pack_slabs, unpack_slabs

        nq = len(blocks)
        flights = []
        for ph in phases:
            with scopes.scope(scopes.HALO_PACK):
                for src, dst, width, pairs, edge in self._sides(ph):
                    carrier = pack_slabs([
                        _slice_in_dim(b, src, width, ph.adim, ph.trim)
                        for b in blocks])
                    flights.append((ph, carrier, pairs, dst, edge))
        flights = [
            (ph, self._permute_wire(carrier, ph.axis, pairs)
             if ph.ring > 1 else carrier, dst, edge)   # ONE for the group
            for ph, carrier, pairs, dst, edge in flights]
        for ph, carrier, dst, edge in flights:
            with scopes.scope(scopes.HALO_UNPACK):
                slabs = unpack_slabs(carrier, nq)
                if edge is not None:
                    kept = lax.axis_index(ph.axis) == edge
                    slabs = [
                        jnp.where(kept, _slice_in_dim(
                            b, dst, s.shape[ph.adim], ph.adim, ph.trim), s)
                        for b, s in zip(blocks, slabs)]
                blocks = [_update_in_dim(b, s, dst, ph.adim, ph.trim)
                          for b, s in zip(blocks, slabs)]
        return blocks

    def _carried_phase(self, out, dtype, keys, phase):
        """One axis phase of a plan with a radius a quantity, for the
        same-dtype group ``keys`` of the state ``out`` (updated in place),
        one block a device: each direction moves the quantities its
        :class:`~stencil_tpu.plan.ir.SideIR` names and nothing else. On a
        self-wrap axis with a fill kernel the group goes in up to three
        calls, by which of the two halos a quantity wants (the kernels
        move whole planes and row windows, so they cut no rows); elsewhere
        both directions' carriers are packed, both permutes follow, both
        slabs are placed, as :meth:`_slab_phases` does, a z slab's rows
        cut where the plan says so."""
        from ..ops.halo_fill import pack_slabs, unpack_slabs

        low, high = phase.sides
        if (phase.blocks == 1 and phase.axis in self._self_fills
                and dtype == jnp.float32):
            for want in ((True, True), (True, False), (False, True)):
                batch = [k for k in keys
                         if (k in low.keys, k in high.keys) == want]
                if batch:
                    out.update(zip(batch, self._self_fill_group(
                        phase.axis, [out[k] for k in batch], sides=want)))
            return
        flights = []
        # :meth:`_sides` leaves a direction of no width out: so here
        carried = [side for side, width in zip(
            phase.sides, (phase.rm, phase.rp)) if width > 0]
        with scopes.scope(scopes.HALO_PACK):
            for (src, dst, width, pairs, _edge), side in zip(
                    self._sides(phase), carried):
                batch = [k for k in keys if k in side.keys]
                for group in ([batch] if self.batch_quantities
                              else [[k] for k in batch]):
                    if group:
                        flights.append((group, pack_slabs([
                            _slice_in_dim(out[k], src, width, phase.adim,
                                          side.trim) for k in group]),
                            pairs, dst, side.trim))
        flights = [
            (group, self._permute_wire(carrier, phase.axis, pairs)
             if phase.ring > 1 else carrier, dst, trim)
            for group, carrier, pairs, dst, trim in flights]
        with scopes.scope(scopes.HALO_UNPACK):
            for group, carrier, dst, trim in flights:
                for k, slab in zip(group, unpack_slabs(carrier, len(group))):
                    out[k] = _update_in_dim(out[k], slab, dst, phase.adim,
                                            trim)

    def _split_x(self, phase, dtype) -> bool:
        """Whether this phase packs and unpacks with the edge-tile kernels
        of ops/halo_fill.py: the lane axis, split evenly with one block a
        device, fp32, on TPUs, the halo and source columns inside the two
        edge lane-tiles. Only there does placing a slab cost XLA a pass
        over the whole field; every other phase keeps the XLA slab path."""
        if not (phase.axis == AXIS_X and phase.blocks > 1 and phase.uniform
                and dtype == jnp.float32 and phase.periodic and not phase.trim
                and self.resident == Dim3(1, 1, 1)):
            return False
        from ..ops.halo_fill import split_x_supported

        return self._on_tpu() and split_x_supported(self.spec, dtype)

    def _split_x_kernel(self, nq: int):
        """(pack, unpack) for a group of ``nq`` fields, built once."""
        cache = self.__dict__.setdefault("_split_x_kernels", {})
        if nq not in cache:
            from ..ops.halo_fill import make_split_x_pack, make_split_x_unpack
            from .mesh import MESH_AXES

            cache[nq] = (
                make_split_x_pack(self.spec, nq, vma=MESH_AXES),
                make_split_x_unpack(self.spec, nq, vma=MESH_AXES),
            )
        return cache[nq]

    def _split_x_phase(self, blocks, phase):
        """The x phase on a split lane axis: one kernel packs both
        directions' columns from the two edge lane-tiles into lane-dense
        carriers, the two permutes fly, one kernel unpacks both in place.
        Both sources are interior columns that the phase never writes, so
        the result is the slab path's, bit for bit."""
        from ..ops.halo_fill import max_fill_group

        fshape = self._fill_shape()
        pairs = [pr for pr, r in ((phase.fwd, phase.rm), (phase.bwd, phase.rp))
                 if r]
        gmax = max_fill_group(self.spec)
        out = []
        for i in range(0, len(blocks), gmax):
            chunk = [b.reshape(fshape) for b in blocks[i : i + gmax]]
            pack, unpack = self._split_x_kernel(len(chunk))
            with scopes.scope(scopes.HALO_PACK):
                carriers = pack(*chunk)
            carriers = [self._permute_wire(c, phase.axis, pr)
                        for c, pr in zip(carriers, pairs)]
            with scopes.scope(scopes.HALO_UNPACK):
                res = unpack(*chunk, *carriers)
            out += [v.reshape(b.shape) for v, b in zip(res, blocks[i:])]
        return out

    def _axis_phase_resident_batched(self, blocks, phase):
        """Axis phase with partition blocks resident per device along
        this axis (oversubscription), for a same-dtype group. Neighbor
        slabs between resident blocks shift along the stacked block dim —
        a pure local copy, per quantity, the analogue of the reference's
        same-GPU ``PeerAccessSender`` short-circuit (tx_cuda.cuh:41-113)
        — and only the two boundary slabs of ALL quantities ride one
        packed carrier per ``ppermute``: one collective pair per phase
        regardless of Q. Works on any axis, uneven splits included
        (per-resident sizes may be traced scalars). The high side packs
        from what the low side placed: two stages."""
        from ..ops.halo_fill import pack_slabs, unpack_slabs

        name, adim, bdim = phase.axis, phase.adim, phase.bdim
        rm, rp, off, c = phase.rm, phase.rp, phase.offset, phase.resident
        m = phase.ring
        fwd, bwd = phase.fwd, phase.bwd
        sz = self._resident_sizes(name, c)
        nq = len(blocks)

        def take_j(b, j, start, width):
            starts = _starts(b.ndim, start, adim)
            starts = starts[:bdim] + (jnp.asarray(j, jnp.int32),) + starts[bdim + 1:]
            shp = list(b.shape)
            shp[bdim] = 1
            shp[adim] = width
            with scopes.scope(scopes.HALO_PACK):
                return lax.dynamic_slice(b, starts, tuple(shp))

        def put_j(b, slab, j, start):
            starts = _starts(b.ndim, start, adim)
            starts = starts[:bdim] + (jnp.asarray(j, jnp.int32),) + starts[bdim + 1:]
            with scopes.scope(scopes.HALO_UNPACK):
                return lax.dynamic_update_slice(b, slab, starts)

        blocks = list(blocks)
        if rm > 0:
            srcs = [
                [take_j(b, j, off + sz[j] - rm, rm) for j in range(c)]
                for b in blocks
            ]
            incoming = [s[c - 1] for s in srcs]
            if m > 1:
                with scopes.scope(scopes.HALO_PACK):
                    carrier = pack_slabs(incoming)
                carrier = self._permute_wire(carrier, name, fwd)
                with scopes.scope(scopes.HALO_UNPACK):
                    incoming = unpack_slabs(carrier, nq)
            for q in range(nq):
                for j in range(c):
                    blocks[q] = put_j(
                        blocks[q], incoming[q] if j == 0 else srcs[q][j - 1],
                        j, off - rm,
                    )
        if rp > 0:
            srcs = [[take_j(b, j, off, rp) for j in range(c)] for b in blocks]
            incoming = [s[0] for s in srcs]
            if m > 1:
                with scopes.scope(scopes.HALO_PACK):
                    carrier = pack_slabs(incoming)
                carrier = self._permute_wire(
                    carrier, name, bwd, stage=1 if rm > 0 else 0)
                with scopes.scope(scopes.HALO_UNPACK):
                    incoming = unpack_slabs(carrier, nq)
            for q in range(nq):
                for j in range(c):
                    blocks[q] = put_j(
                        blocks[q],
                        incoming[q] if j == c - 1 else srcs[q][j + 1],
                        j, off + sz[j],
                    )
        return blocks

    # -- auto-SPMD implementation -------------------------------------------
    def auto_fill(self, arr):
        """One halo exchange of a stacked GLOBAL array, with no explicit
        collectives: each axis phase slices the send extents and shifts them
        one step along the (sharded) block dim with ``jnp.roll`` — the SPMD
        partitioner decides what actually moves (shard-internal shifts
        become local copies, shard-boundary shifts become
        collective-permutes). Phase order and extents match
        :meth:`_composed_blocks` exactly, so the result is bit-identical to
        AXIS_COMPOSED; corner/edge halos compose across phases the same way.

        Called under ``jax.jit`` on ``P('z','y','x')``-sharded arrays (see
        :attr:`_compiled`); also safe to trace inside larger global jitted
        steps (ops/jacobi.py's AUTO_SPMD path)."""
        for phase in self._auto_plan.axis_phases:
            arr = self._auto_axis_phase(arr, phase)
        return arr

    @cached_property
    def _auto_plan(self):
        """Axis phases in synthesized form (ring spans the FULL per-axis
        block table — the global roll program has no resident concept; the
        partitioner turns shard-internal shifts into local copies on its
        own). :attr:`plan` equals this when the method IS auto-spmd; the
        manual methods still need it for :meth:`auto_fill` composition."""
        if self.method == Method.AUTO_SPMD:
            return self.plan
        return build_plan(
            self.spec, mesh_dim(self.mesh), Method.AUTO_SPMD,
            batch_quantities=self.batch_quantities, resident=self.resident,
        )

    def _auto_axis_phase(self, arr, phase):
        sizes, rm, rp, off = phase.sizes, phase.rm, phase.rp, phase.offset
        if rm == 0 and rp == 0:
            return arr
        adim, bdim = phase.adim, phase.bdim
        n = len(sizes)
        if phase.uniform:
            sz = sizes[0]
            if rm > 0:
                # every block's top rm planes -> its +neighbor's low halo:
                # globally, a roll of the slab one step up the block dim
                with scopes.scope(scopes.HALO_PACK):
                    slab = lax.slice_in_dim(
                        arr, off + sz - rm, off + sz, axis=adim)
                with scopes.scope(scopes.HALO_WIRE):
                    slab = jnp.roll(slab, 1, axis=bdim)
                with scopes.scope(scopes.HALO_UNPACK):
                    arr = _update_in_dim(arr, slab, off - rm, adim)
            if rp > 0:
                with scopes.scope(scopes.HALO_PACK):
                    slab = lax.slice_in_dim(arr, off, off + rp, axis=adim)
                with scopes.scope(scopes.HALO_WIRE):
                    slab = jnp.roll(slab, -1, axis=bdim)
                with scopes.scope(scopes.HALO_UNPACK):
                    arr = _update_in_dim(arr, slab, off + sz, adim)
            return arr
        # uneven axis: per-block source/dest offsets. The source gather and
        # the dest blend are elementwise along (block dim x data dim) pairs,
        # so the partitioner still sees exactly one cross-block movement per
        # side — the roll.
        ndim = arr.ndim
        bshape = [1] * ndim
        bshape[bdim] = n
        sz_b = jnp.asarray(sizes, jnp.int32).reshape(bshape)
        if rm > 0:
            # block i sends [off + sizes[i] - rm, off + sizes[i]); the
            # receiver's low-side halo sits at the static [off - rm, off)
            ashape = [1] * ndim
            ashape[adim] = rm
            with scopes.scope(scopes.HALO_PACK):
                gidx = sz_b + (off - rm) + jnp.arange(
                    rm, dtype=jnp.int32).reshape(ashape)
                slab = jnp.take_along_axis(arr, gidx, axis=adim)
            with scopes.scope(scopes.HALO_WIRE):
                slab = jnp.roll(slab, 1, axis=bdim)
            with scopes.scope(scopes.HALO_UNPACK):
                arr = _update_in_dim(arr, slab, off - rm, adim)
        if rp > 0:
            # the sender side is static ([off, off + rp), the compute
            # origin); the receiver's high-side halo starts at the
            # per-block off + sizes[i] — a masked blend places it
            with scopes.scope(scopes.HALO_PACK):
                slab = lax.slice_in_dim(arr, off, off + rp, axis=adim)
            with scopes.scope(scopes.HALO_WIRE):
                slab = jnp.roll(slab, -1, axis=bdim)
            with scopes.scope(scopes.HALO_UNPACK):
                ashape = [1] * ndim
                ashape[adim] = arr.shape[adim]
                rel = jnp.arange(
                    arr.shape[adim], dtype=jnp.int32).reshape(ashape) - (
                    sz_b + off
                )
                vals = jnp.take_along_axis(
                    slab, jnp.clip(rel, 0, rp - 1), axis=adim)
                arr = jnp.where((rel >= 0) & (rel < rp), vals, arr)
        return arr

    # -- direct-26 implementation -------------------------------------------
    def _direct26_blocks(self, block):
        """One quantity's 26-message exchange — the batched body's Q=1
        degeneration (pack_slabs is the identity there), so the direction
        geometry lives in exactly one place."""
        return self._direct26_batched([block])[0]

    def _direct26_batched(self, blocks):
        """DIRECT26 with quantity batching: per active direction, every
        quantity's exact-extent slab packs into one ``(Q, ...)`` carrier
        and ONE permute (or resident roll) moves the whole same-dtype
        group — ≤ 26 collectives per exchange regardless of Q (vs 26·Q
        per-quantity). Q=1 degenerates to the exact historical
        per-quantity program (identity pack, no leading carrier axis) —
        :meth:`_direct26_blocks` delegates here."""
        if not self.spec.is_uniform():
            return self._direct26_batched_uneven(blocks)
        from ..ops.halo_fill import pack_slabs, unpack_slabs

        cz, cy, cx = self.resident.z, self.resident.y, self.resident.x
        nq = len(blocks)
        boff = 1 if nq > 1 else 0  # the packed carrier's leading Q axis
        updates = []
        for ph in self.plan.direct_phases:
            with scopes.scope(scopes.HALO_PACK):
                carrier = pack_slabs([
                    lax.dynamic_slice(
                        b, (0, 0, 0) + ph.src, (cz, cy, cx) + ph.shape
                    )
                    for b in blocks
                ])
            carrier = self._roll_blocks(carrier, ph, boff=boff)
            updates.append((carrier, ph.dst))
        out = list(blocks)
        with scopes.scope(scopes.HALO_UNPACK):
            for carrier, dsts in updates:
                for q, piece in enumerate(unpack_slabs(carrier, nq)):
                    out[q] = lax.dynamic_update_slice(
                        out[q], piece, (0, 0, 0) + dsts
                    )
        return out

    def _direct26_batched_uneven(self, blocks):
        """DIRECT26 on a remainder (uneven) partition: the same 26
        messages, with slab extents padded to the base block size along
        each direction's orthogonal (zero-component) axes — every
        ``ppermute`` participant needs ONE static shape, and blocks in the
        same ring share their orthogonal-axis sizes (grid.py), so the
        valid slab region always aligns sender→receiver. Messages apply in
        face→edge→corner order: a padded write can spill only into a band
        belonging to a direction with MORE nonzero components (or into
        dead pad), so every halo cell's true message lands last — and the
        apply order is preserved per direction across the whole group, so
        the layered-overwrite argument covers packed carriers unchanged.
        Per-block compute extents come from traced lookups into the static
        per-axis size tables, the same machinery as
        :meth:`_axis_phase_resident_batched` (VERDICT r5 "Next" #5; ROADMAP #4).
        Q=1 degenerates to the per-quantity program (identity pack)."""
        from ..ops.halo_fill import pack_slabs, unpack_slabs

        spec = self.spec
        r = spec.radius
        off = spec.compute_offset()
        base = spec.base
        cz, cy, cx = self.resident.z, self.resident.y, self.resident.x
        sz = {
            AXIS_Z: self._resident_sizes(AXIS_Z, cz),
            AXIS_Y: self._resident_sizes(AXIS_Y, cy),
            AXIS_X: self._resident_sizes(AXIS_X, cx),
        }
        nq = len(blocks)
        boff = 1 if nq > 1 else 0  # the packed carrier's leading Q axis
        out = list(blocks)
        # plan phases arrive pre-sorted face -> edge -> corner with zero-
        # extent directions dropped and base-padded static carrier shapes
        for ph in self.plan.direct_phases:
            d = Dim3.of(ph.direction)
            info = tuple(zip(
                (d.z, d.y, d.x),
                (off.z, off.y, off.x),
                (r.z(-1), r.y(-1), r.x(-1)),
                (r.z(1), r.y(1), r.x(1)),
                (base.z, base.y, base.x),
            ))
            shape = ph.shape

            def gather(block):
                parts_z = []
                for jz in range(cz):
                    parts_y = []
                    for jy in range(cy):
                        parts_x = []
                        for jx in range(cx):
                            s3 = (sz[AXIS_Z][jz], sz[AXIS_Y][jy], sz[AXIS_X][jx])
                            src = tuple(
                                o + s - rm if dc == 1 else o
                                for (dc, o, rm, _rp, _b), s in zip(info, s3)
                            )
                            parts_x.append(lax.dynamic_slice(
                                block, _starts6((jz, jy, jx), src),
                                (1, 1, 1) + shape,
                            ))
                        parts_y.append(_concat(parts_x, 2))
                    parts_z.append(_concat(parts_y, 1))
                return _concat(parts_z, 0)

            with scopes.scope(scopes.HALO_PACK):
                carrier = pack_slabs([gather(b) for b in out])
            carrier = self._roll_blocks(carrier, ph, boff=boff)
            for q, slab in enumerate(unpack_slabs(carrier, nq)):
                for jz in range(cz):
                    for jy in range(cy):
                        for jx in range(cx):
                            s3 = (sz[AXIS_Z][jz], sz[AXIS_Y][jy], sz[AXIS_X][jx])
                            dst = tuple(
                                o - rm if dc == 1 else o + s if dc == -1 else o
                                for (dc, o, rm, _rp, _b), s in zip(info, s3)
                            )
                            with scopes.scope(scopes.HALO_UNPACK):
                                piece = lax.dynamic_slice(
                                    slab, _starts6((jz, jy, jx), (0, 0, 0)),
                                    (1, 1, 1) + shape,
                                )
                                out[q] = lax.dynamic_update_slice(
                                    out[q], piece, _starts6((jz, jy, jx), dst)
                                )
        return out

    def _roll_blocks(self, slab, ph, boff: int = 0):
        """Send each resident block's slab to its ``+direction`` neighbor
        in the GLOBAL block grid: without oversubscription this is the
        single diagonal 26-neighbor permute (the phase record carries the
        flattened pairs); with residents each axis shifts the stacked
        block dim locally and only the wrap-around boundary rides an axis
        permute (the per-axis composition of the same move). ``boff``:
        leading batch axes before the block dims (the packed ``(Q, ...)``
        carrier of the quantity-batched path)."""
        d = Dim3.of(ph.direction)
        if not self.oversubscribed:
            return self._permute_wire(slab, (AXIS_Z, AXIS_Y, AXIS_X), ph.pairs)
        md = mesh_dim(self.mesh)
        with scopes.scope(scopes.HALO_WIRE):  # resident shifts are the wire
            for name, bdim, comp, m, c in (
                (AXIS_Z, boff + 0, d.z, md.z, self.resident.z),
                (AXIS_Y, boff + 1, d.y, md.y, self.resident.y),
                (AXIS_X, boff + 2, d.x, md.x, self.resident.x),
            ):
                if comp == 0:
                    continue
                if c == 1:
                    if m > 1:
                        pairs = [(i, (i + comp) % m) for i in range(m)]
                        slab = self._permute_wire(slab, name, pairs)
                    continue
                if comp == 1:
                    last = lax.slice_in_dim(slab, c - 1, c, axis=bdim)
                    if m > 1:
                        last = self._permute_wire(
                            last, name, [(i, (i + 1) % m) for i in range(m)])
                    slab = jnp.concatenate(
                        [last, lax.slice_in_dim(slab, 0, c - 1, axis=bdim)], axis=bdim
                    )
                else:
                    first = lax.slice_in_dim(slab, 0, 1, axis=bdim)
                    if m > 1:
                        first = self._permute_wire(
                            first, name, [(i, (i - 1) % m) for i in range(m)])
                    slab = jnp.concatenate(
                        [lax.slice_in_dim(slab, 1, c, axis=bdim), first], axis=bdim
                    )
            return slab


def _starts(ndim: int, start, adim: int):
    """Per-dim start indices, uniformly int32 (mixed Python-int / traced-scalar
    starts trip dynamic_slice's same-dtype requirement under x64)."""
    s = [jnp.asarray(0, jnp.int32)] * ndim
    s[adim] = jnp.asarray(start, jnp.int32)
    return tuple(s)


def _starts6(bidx, data_starts):
    """Start indices of one resident block's slab in the stacked layout:
    (jz, jy, jx) block dims + (z, y, x) data starts, uniformly int32
    (data starts may be traced size-table lookups)."""
    return tuple(jnp.asarray(v, jnp.int32) for v in (*bidx, *data_starts))


def _concat(parts, axis: int):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=axis)


def _trimmed_starts(ndim: int, start, adim: int, trim):
    starts = list(_starts(ndim, start, adim))
    for dim, lo, _width in trim:
        starts[dim] = jnp.asarray(lo, jnp.int32)
    return tuple(starts)


def _slice_in_dim(block, start, width: int, adim: int, trim=()):
    """dynamic_slice along one data dim of a (1,1,1,pz,py,px) block;
    ``trim`` ((dim, start, width), ...) cuts orthogonal dims too (a star
    stencil's faces-only slab)."""
    sizes = list(block.shape)
    sizes[adim] = width
    for dim, _lo, w in trim:
        sizes[dim] = w
    return lax.dynamic_slice(
        block, _trimmed_starts(block.ndim, start, adim, trim), tuple(sizes))


def _update_in_dim(block, slab, start, adim: int, trim=()):
    return lax.dynamic_update_slice(
        block, slab, _trimmed_starts(block.ndim, start, adim, trim))


# -- host <-> stacked-block conversion ---------------------------------------

def shard_blocks(
    global_zyx: np.ndarray, spec: GridSpec, mesh: Mesh, dtype=None
) -> jax.Array:
    """Scatter a global [z,y,x] host array into the stacked padded layout.

    Halo and pad-tail cells are zero-initialized (garbage until the first
    exchange, like fresh cudaMalloc in local_domain.cu:159-220).
    """
    g = spec.global_size
    if global_zyx.shape != (g.z, g.y, g.x):
        raise ValueError(
            f"global array shape {global_zyx.shape} != grid "
            f"({g.z}, {g.y}, {g.x})"
        )
    stacked = np.zeros(spec.stacked_shape_zyx(), dtype=dtype or global_zyx.dtype)
    off = spec.compute_offset()
    for iz in range(spec.dim.z):
        for iy in range(spec.dim.y):
            for ix in range(spec.dim.x):
                o = spec.block_origin((ix, iy, iz))
                s = spec.block_size((ix, iy, iz))
                stacked[
                    iz, iy, ix,
                    off.z : off.z + s.z,
                    off.y : off.y + s.y,
                    off.x : off.x + s.x,
                ] = global_zyx[o.z : o.z + s.z, o.y : o.y + s.y, o.x : o.x + s.x]
    # each device receives only its own blocks, straight from the host
    # array (no whole-array staging on the first device)
    return jax.make_array_from_callback(
        stacked.shape, NamedSharding(mesh, BLOCK_PSPEC), lambda idx: stacked[idx]
    )


def unshard_blocks(stacked, spec: GridSpec) -> np.ndarray:
    """Gather the compute regions of a stacked array back into a global
    [z,y,x] host array (halos dropped)."""
    g = spec.global_size
    arr = np.asarray(jax.device_get(stacked))
    out = np.empty((g.z, g.y, g.x), dtype=arr.dtype)
    off = spec.compute_offset()
    for iz in range(spec.dim.z):
        for iy in range(spec.dim.y):
            for ix in range(spec.dim.x):
                o = spec.block_origin((ix, iy, iz))
                s = spec.block_size((ix, iy, iz))
                out[o.z : o.z + s.z, o.y : o.y + s.y, o.x : o.x + s.x] = arr[
                    iz, iy, ix,
                    off.z : off.z + s.z,
                    off.y : off.y + s.y,
                    off.x : off.x + s.x,
                ]
    return out
