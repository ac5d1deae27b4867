"""DistributedDomain — the top-level user API.

TPU-native re-design of the reference orchestrator
(reference: include/stencil/stencil.hpp:33-225, src/stencil.cu). The surface
is kept: ``set_radius`` → ``add_data`` → ``realize`` → loop
{compute / ``exchange`` / ``swap``} → ``write_paraview``. What changed
underneath:

- Subdomain-per-GPU ``LocalDomain`` allocations become one stacked,
  halo-padded array per quantity, sharded ``P('z','y','x')`` over a 3D
  device mesh (all blocks of all "ranks" in one jit-visible value).
- ``realize``'s transport planning (the 26-direction goto-cascade,
  src/stencil.cu:327-464, and sender/recver construction :651-759) becomes
  the construction + compilation of one :class:`HaloExchange`.
- ``exchange``'s CPU polling engine (src/stencil.cu:1002-1186) is one call
  into the compiled collective program; overlap is XLA's job (SURVEY §7.5).
- Placement (``do_placement``, src/stencil.cu:201-239) becomes device-mesh
  layout; the partition is still the comm-minimizing NodePartition.

Setup/exchange statistics mirror STENCIL_SETUP_STATS / STENCIL_EXCHANGE_STATS
(reference: CMakeLists.txt:17-22) but are always on — they cost one host
timestamp per call.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from .domain import DataHandle, GridSpec
from .geometry import (
    DIRECTIONS_26,
    Dim3,
    NodePartition,
    Radius,
    Rect3,
    exterior_regions,
    halo_extent,
    interior_region,
)
from .parallel import HaloExchange, Method, grid_mesh
from .parallel.exchange import direction_bytes, shard_blocks, unshard_blocks
from .parallel.mesh import sharded_full
from .utils import logging as log
from .utils import timer
from .utils.sync import hard_sync


# moved to geometry/partition.py so the plan cost model predicts the same
# mesh realize() would build; kept as an alias for callers/tests
from .geometry import stack_residents as _stack_residents


class DistributedDomain:
    """A multi-quantity 3D domain distributed over a TPU device mesh."""

    def __init__(self, x: int, y: int, z: int, plan=None,
                 autotune: bool = False, plan_db: Optional[str] = None):
        self.size = Dim3(x, y, z)
        self.radius = Radius.constant(0)
        self._names: List[str] = []
        self._dtypes: List[str] = []
        # per quantity: whether exchange() fills its halos, and whether it
        # holds a second (``next``) buffer
        self._exchanged: List[bool] = []
        self._buffered: List[bool] = []
        # per quantity: the halos exchange() fills for it (None: the
        # domain's radius)
        self._radii: List[Optional[Radius]] = []
        # per axis (x, y, z): periodic unless the application says fixed;
        # and whether the stencil is a star (faces-only slabs)
        self._periodic = (True, True, True)
        self._faces_only = False
        self._method = Method.AXIS_COMPOSED
        self._batch_quantities = True
        self._wire_dtype: Optional[str] = None
        self._devices: Optional[Sequence] = None
        self._partition_dim: Optional[Dim3] = None
        self._placement = None
        # exchange planning (stencil_tpu/plan/): an explicit tuned choice,
        # or realize()-time autotuning against the on-disk plan DB
        self._plan_choice = None
        self._autotune_opts: Optional[dict] = None
        self.autotune_result = None
        if plan is not None:
            self.set_plan(plan)
            if autotune:
                log.warn("explicit plan= suppresses autotune=: the given "
                         "choice is applied as-is (drop plan= to re-tune)")
        if autotune:
            self.enable_autotune(db_path=plan_db)
        self._output_prefix = os.environ.get("STENCIL_OUTPUT_PREFIX", "")
        self._realized = False
        # data (after realize): handle.idx -> stacked array
        self._curr: Dict[int, jax.Array] = {}
        self._next: Dict[int, jax.Array] = {}
        # setup stats (reference: stencil.hpp:103-112)
        self.time_plan = 0.0
        self.time_realize = 0.0
        self.time_create = 0.0
        # exchange stats (reference: stencil.hpp:96-101)
        self.time_exchange = 0.0
        self.time_swap = 0.0
        self.num_exchanges = 0

    # -- configuration (pre-realize) ----------------------------------------
    def set_radius(self, r) -> None:
        """Uniform or per-direction radius (reference: stencil.hpp:124-137)."""
        self.radius = Radius.constant(r) if isinstance(r, int) else r

    def set_boundary(self, periodic=(True, True, True),
                     faces_only: bool = False) -> None:
        """What lies beyond the domain and what the stencil reads of it.

        ``periodic`` (x, y, z): ``False`` makes an axis FIXED. The halo
        beyond the domain's edge on that axis is then the application's
        ghost (a Dirichlet ring: seed it once, the exchange never writes
        it): a split axis exchanges between neighbours without the wrap,
        an axis of one block is not exchanged at all. ``faces_only``: the
        stencil is a star (``Radius.face_edge_corner(r, 0, 0)``), so no
        edge or corner halo is filled and slabs carry no orthogonal halo
        planes or rows. A domain that says nothing is periodic on every
        axis and carries edges and corners. Both need the default
        ``Method.AXIS_COMPOSED`` and one block a device."""
        if self._realized:
            raise RuntimeError("set_boundary after realize()")
        self._periodic = tuple(bool(v) for v in periodic)
        self._faces_only = bool(faces_only)

    def add_data(self, name: str = "", dtype="float32",
                 exchanged: bool = True, buffered: bool = True,
                 radius: Optional[Radius] = None) -> DataHandle:
        """Register a quantity (reference: stencil.hpp:128).

        ``exchanged=False``: a quantity the stencil reads at the centre
        only (a coefficient field). :meth:`exchange` leaves its halos
        alone and the byte counts leave it out. ``buffered=False``: it
        holds no second buffer (:meth:`get_next` has none and
        :meth:`swap` passes it by): a read-only field, or one whose two
        time levels are quantities of their own. ``radius``: which of the
        26 directions' halos :meth:`exchange` FILLS for this quantity (a
        lattice-Boltzmann population is read at one offset, so it wants
        the halo of one side an axis and one edge): each direction 0 or
        what :meth:`set_radius` gave the domain, which stays what every
        quantity ALLOCATES. ``None``: the domain's radius; a domain whose
        quantities all pass none builds the plan it always did. Needs the
        default ``Method.AXIS_COMPOSED``, one block a device and periodic
        axes."""
        if self._realized:
            raise RuntimeError("add_data after realize()")
        if radius is not None and not exchanged:
            raise ValueError("a radius for a quantity that is not exchanged")
        self._radii.append(radius)
        idx = len(self._names)
        self._names.append(name or f"data{idx}")
        self._dtypes.append(str(jnp.dtype(dtype)))
        self._exchanged.append(bool(exchanged))
        self._buffered.append(bool(buffered))
        return DataHandle(idx, self._names[-1], self._dtypes[-1])

    def is_exchanged(self, h: DataHandle) -> bool:
        return self._exchanged[h.idx]

    def set_methods(self, method: Method) -> None:
        """Exchange strategy (reference: stencil.hpp:139)."""
        self._method = method

    def set_plan(self, choice) -> None:
        """Apply a tuned exchange plan (a ``plan.ir.PlanChoice`` or its
        JSON dict): partition shape, exchange method, and quantity
        batching are applied at realize(); the choice's ``multistep_k``
        and ``kernel_variant`` ride along for the apps that own those
        knobs (:attr:`plan_choice`). An explicit :meth:`set_partition`
        still wins over the plan's partition (with a warning)."""
        from .plan.ir import PlanChoice

        if isinstance(choice, dict):
            choice = PlanChoice.from_json(choice)
        self._plan_choice = choice

    def enable_autotune(self, db_path: Optional[str] = None,
                        probe: bool = True, top_n: int = 3,
                        probe_iters: int = 4, ks: Sequence[int] = (1,),
                        force: bool = False) -> None:
        """Autotune the exchange plan at realize() time (plan/autotune):
        consult the plan DB first (a hit replays with zero probes), else
        rank the (partition x method x batching x k) space statically and
        refine the top ``top_n`` with measured probes, persisting the
        winner to ``db_path``. The result lands in
        :attr:`autotune_result`; telemetry gets the ``plan.cache_hit``
        gauge + ``plan.probes_run`` counter either way."""
        self._autotune_opts = dict(
            db_path=db_path, probe=probe, top_n=top_n,
            probe_iters=probe_iters, ks=tuple(ks), force=force,
        )

    @property
    def plan_choice(self):
        """The effective tuned choice (None on a plan-less domain)."""
        return self._plan_choice

    def set_quantity_batching(self, enabled: bool) -> None:
        """Quantity-batched exchange (default on): per collective, all
        same-dtype quantities' boundary slabs ride ONE packed ``(Q, ...)``
        carrier, so the collective count per exchange is independent of
        the quantity count (parallel/exchange.py module docstring). Off =
        the historical one-collective-per-quantity program — the A/B
        baseline of ``bench_exchange --batched-ab``."""
        self._batch_quantities = bool(enabled)

    def set_wire_dtype(self, dtype) -> None:
        """bf16-on-the-wire halo compression (``None`` = off): boundary
        carriers that actually cross the interconnect narrow to this
        dtype before the send and widen on unpack
        (``HaloExchange(wire_dtype=...)``; ops/halo_fill.wire_narrow_dtype
        owns the policy — only floating carriers ever narrow, local
        copies stay lossless). LOSSY by design: the exchanged halos
        round to the wire precision, so checkpoints/parity comparisons
        across the knob differ. ``bench_exchange --wire-ab`` measures
        the error the bandwidth is bought with."""
        self._wire_dtype = None if dtype in (None, "") else str(jnp.dtype(dtype))

    def set_devices(self, devices: Sequence) -> None:
        """Restrict to specific devices (reference ``set_gpus``,
        stencil.hpp:154)."""
        self._devices = list(devices)

    def set_placement(self, placement) -> None:
        """Device-placement strategy (reference: stencil.hpp:146)."""
        self._placement = placement

    def set_partition(self, dim) -> None:
        """Override the automatic partition grid (testing/ablation)."""
        self._partition_dim = Dim3.of(dim)

    def set_output_prefix(self, prefix: str) -> None:
        self._output_prefix = prefix

    # -- realize -------------------------------------------------------------
    def realize(self) -> None:
        """Partition, build the mesh, allocate quantities, compile exchange
        (reference: src/stencil.cu:241-850)."""
        t0 = time.perf_counter()
        with timer.timed("setup.plan"), timer.trace_range("stencil.plan"):
            devices = (
                list(self._devices) if self._devices is not None else jax.devices()
            )
            n = len(devices)
            if self._autotune_opts is not None and self._plan_choice is None:
                if not self._dtypes:
                    log.warn("autotune: no quantities declared; skipping")
                else:
                    from .plan.autotune import autotune as _plan_autotune

                    opts = self._autotune_opts
                    self.autotune_result = _plan_autotune(
                        self.size, self.radius, self._dtypes,
                        devices=devices, db_path=opts["db_path"],
                        probe=opts["probe"], top_n=opts["top_n"],
                        probe_iters=opts["probe_iters"], ks=opts["ks"],
                        force=opts["force"],
                    )
                    self._plan_choice = self.autotune_result.choice
            if self._plan_choice is not None:
                ch = self._plan_choice
                if (self._partition_dim is not None
                        and self._partition_dim != Dim3.of(ch.partition)):
                    # the choice was tuned as a UNIT (its method/batching
                    # were measured on its partition); an explicit
                    # partition overrides the whole plan, not pieces of it
                    log.warn(
                        f"explicit partition {self._partition_dim} overrides "
                        f"the tuned plan {ch.label()}; the plan's method/"
                        "batching are NOT applied (re-tune with the pinned "
                        "partition instead)"
                    )
                    self._plan_choice = None
                else:
                    self._method = Method(ch.method)
                    self._batch_quantities = ch.batch_quantities
                    if self._partition_dim is None:
                        self._partition_dim = Dim3.of(ch.partition)
            if self._partition_dim is not None:
                dim = self._partition_dim
            else:
                # comm-minimizing two-level split: hosts x devices-per-host
                # (reference: do_placement -> NodeAware, src/stencil.cu:201-239)
                hosts = max(1, jax.process_count())
                part = NodePartition(self.size, self.radius, hosts, max(1, n // hosts))
                dim = part.dim()
            mesh_dim = dim
            if dim.flatten() != n:
                # oversubscription (reference: dd.set_gpus({0,0}),
                # stencil.hpp:154): run any partition on fewer devices by
                # stacking c = blocks/devices resident blocks per device;
                # the exchange shifts resident-neighbor slabs locally
                # (exchange.py _axis_phase_resident_batched). Stacking may mix
                # axes — prefer z-heavy (the cheapest slab geometry), then
                # y, then x.
                c, rem = divmod(dim.flatten(), n)
                if rem:
                    raise ValueError(
                        f"partition {dim} has {dim.flatten()} blocks, not a "
                        f"multiple of {n} devices"
                    )
                mesh_dim = _stack_residents(dim, c)
            self.spec = GridSpec(self.size, dim, self.radius)
            ordered = False
            if self._placement is not None and mesh_dim != dim:
                log.warn(
                    "placement strategies assume one block per device; "
                    "ignoring set_placement for the oversubscribed partition"
                )
            elif self._placement is not None:
                devices = self._placement.arrange(devices, self.spec)
                ordered = True
            ch = self._plan_choice
            if ch is not None and ch.placement is not None:
                # the tuned topology-aware placement: mesh position i
                # (row-major z, y, x — residents stack WITHIN a
                # position, so oversubscription composes) is hosted by
                # devices[placement[i]]. An explicit set_placement
                # strategy wins, with a warning — like set_partition
                # over the tuned partition.
                if ordered:
                    log.warn(
                        "explicit set_placement overrides the tuned "
                        f"plan's placement {list(ch.placement)}; probes "
                        "measured the tuned assignment, not this one"
                    )
                else:
                    from .plan.ir import validate_placement

                    err = validate_placement(ch.placement, n)
                    if err is not None:
                        raise ValueError(f"tuned plan placement: {err}")
                    devices = [devices[ch.placement[i]] for i in range(n)]
                    ordered = True
            self.mesh = grid_mesh(mesh_dim, devices, ordered=ordered)
        self.time_plan = time.perf_counter() - t0

        t0 = time.perf_counter()
        with timer.timed("setup.realize"), timer.trace_range("stencil.realize"):
            shape = self.spec.stacked_shape_zyx()
            self._exchange = HaloExchange(
                self.spec, self.mesh, self._method,
                batch_quantities=self._batch_quantities,
                wire_dtype=self._wire_dtype,
                periodic=self._periodic,
                faces_only=self._faces_only,
                quantity_radius=self._quantity_radius(),
            )
            sharding = self._exchange.sharding()
            zeros = {dt: sharded_full(shape, 0, dt, sharding)
                     for dt in set(self._dtypes)}
            for idx, dt in enumerate(self._dtypes):
                self._curr[idx] = zeros[dt]()
                if self._buffered[idx]:
                    self._next[idx] = zeros[dt]()
        self.time_realize = time.perf_counter() - t0

        t0 = time.perf_counter()
        with timer.timed("setup.create"), timer.trace_range("stencil.create"):
            self._exchange._compiled  # build + trace now, like two-phase prepare
        self.time_create = time.perf_counter() - t0
        self._realized = True
        log.debug(
            f"realized {self.size} over {dim} blocks of {self.spec.base}, "
            f"padded {self.spec.padded()}"
        )
        if self._output_prefix:
            self.write_plan(self._output_prefix)

    # -- data access ---------------------------------------------------------
    def get_curr(self, h: DataHandle) -> jax.Array:
        return self._curr[h.idx]

    def get_next(self, h: DataHandle) -> jax.Array:
        return self._next[h.idx]

    def set_curr(self, h: DataHandle, stacked: jax.Array) -> None:
        self._curr[h.idx] = stacked

    def set_next(self, h: DataHandle, stacked: jax.Array) -> None:
        self._next[h.idx] = stacked

    def curr_state(self) -> Dict[int, jax.Array]:
        return dict(self._curr)

    def next_state(self) -> Dict[int, jax.Array]:
        return dict(self._next)

    def set_curr_global(self, h: DataHandle, global_zyx: np.ndarray) -> None:
        """Scatter a host array [z,y,x] into the sharded layout."""
        self._curr[h.idx] = shard_blocks(
            global_zyx.astype(self._dtypes[h.idx]), self.spec, self.mesh
        )

    def get_curr_global(self, h: DataHandle) -> np.ndarray:
        """Gather the compute region to a host array [z,y,x]."""
        return unshard_blocks(self._curr[h.idx], self.spec)

    def sharding(self):
        return self._exchange.sharding()

    # -- the iteration API (reference: stencil.hpp:182-215) ------------------
    @property
    def halo_exchange(self) -> HaloExchange:
        """The compiled halo-exchange op, for composing into larger jitted
        steps (fused compute/exchange overlap, custom loops). Public: this
        is how apps embed the exchange inside their own shard_map'd step
        (the reference's equivalent is handing its senders the app streams,
        bin/jacobi3d.cu:296-368)."""
        return self._exchange

    def _exchanged_state(self) -> Dict[int, jax.Array]:
        """The quantities an exchange moves (all of them unless
        ``add_data(exchanged=False)`` took some out)."""
        if all(self._exchanged):
            return self._curr
        return {i: a for i, a in self._curr.items() if self._exchanged[i]}

    def _exchanged_itemsizes(self) -> List[int]:
        return [jnp.dtype(dt).itemsize
                for dt, ex in zip(self._dtypes, self._exchanged) if ex]

    def _exchanged_keys(self) -> List[int]:
        return [i for i, ex in enumerate(self._exchanged) if ex]

    def _quantity_radius(self) -> Optional[Dict[int, Radius]]:
        """``{index: Radius}`` of every exchanged quantity where one gave
        its own (the others take the domain's), else ``None``."""
        if all(r is None for r in self._radii):
            return None
        return {i: self._radii[i] or self.radius
                for i in self._exchanged_keys()}

    def exchange(self) -> None:
        """Fill every halo of every exchanged quantity from the neighbors,
        periodic unless :meth:`set_boundary` fixed an axis
        (reference: src/stencil.cu:1002-1186).

        Synchronizes with the device each call, so every call pays a full
        host round-trip. For iteration loops use :meth:`exchange_loop` /
        :attr:`halo_exchange` instead."""
        t0 = time.perf_counter()
        with timer.timed("exchange"), timer.trace_range("stencil.exchange"):
            self._curr = {**self._curr,
                          **self._exchange(self._exchanged_state())}
            hard_sync(self._curr)
        self.time_exchange += time.perf_counter() - t0
        self.num_exchanges += 1

    def exchange_loop(self, iters: int):
        """``iters`` fused back-to-back exchanges as one compiled program
        acting on a quantity pytree (see :meth:`curr_state`): amortizes
        dispatch cost the way the reference's timed loops amortize launch
        overhead (reference: bin/exchange_weak.cu:168-177)."""
        return self._exchange.make_loop(iters)

    def run_exchanges(self, iters: int) -> None:
        """Run ``iters`` fused exchanges on the domain's current state."""
        t0 = time.perf_counter()
        with timer.timed("exchange"), timer.trace_range("stencil.exchange_loop"):
            self._curr = {**self._curr, **self.exchange_loop(iters)(
                self._exchanged_state())}
            hard_sync(self._curr)
        self.time_exchange += time.perf_counter() - t0
        self.num_exchanges += iters

    def swap(self) -> None:
        """Swap curr/next (reference: src/stencil.cu:852-872)."""
        t0 = time.perf_counter()
        with timer.timed("swap"):
            if all(self._buffered):
                self._curr, self._next = self._next, self._curr
            else:
                for idx in list(self._next):
                    self._curr[idx], self._next[idx] = (
                        self._next[idx], self._curr[idx])
        self.time_swap += time.perf_counter() - t0

    def get_interior(self) -> List[Rect3]:
        """Per-block interior compute region, allocation-local coordinates
        (reference: src/stencil.cu:878-921)."""
        out = []
        off = self.spec.compute_offset()
        for i in range(self.spec.num_blocks()):
            idx = self._block_idx(i)
            sz = self.spec.block_size(idx)
            compute = Rect3(off, off + sz)
            out.append(interior_region(compute, self.radius))
        return out

    def get_exterior(self) -> List[List[Rect3]]:
        """Per-block exterior slabs (reference: src/stencil.cu:927-977)."""
        out = []
        off = self.spec.compute_offset()
        interiors = self.get_interior()
        for i in range(self.spec.num_blocks()):
            idx = self._block_idx(i)
            sz = self.spec.block_size(idx)
            compute = Rect3(off, off + sz)
            out.append(exterior_regions(compute, interiors[i]))
        return out

    def _block_idx(self, i: int) -> Dim3:
        d = self.spec.dim
        return Dim3(i % d.x, (i // d.x) % d.y, i // (d.x * d.y))

    # -- accounting (reference: src/stencil.cu:139-161) ----------------------
    def exchange_bytes_for_method(self, method: Method) -> int:
        """Logical halo bytes per exchange attributed to ``method``."""
        if method != self._method:
            return 0
        return self._exchange.bytes_logical(self._exchanged_itemsizes(),
                                            keys=self._exchanged_keys())

    def exchange_bytes_moved(self) -> int:
        return self._exchange.bytes_moved(self._exchanged_itemsizes(),
                                          keys=self._exchanged_keys())

    def plan_meta(self) -> dict:
        """The EFFECTIVE exchange plan of this realized domain — what the
        ckpt manifests record so ``--resume`` can warn when a snapshot
        tuned under one plan is revived under another (the state restores
        bit-identically either way — elasticity — but the compiled
        programs, and any recorded performance, differ)."""
        from .plan.ir import PlanChoice, PlanConfig

        if not self._realized:
            raise RuntimeError("plan_meta requires realize()")
        devs = self.mesh.devices.flatten()
        cfg = PlanConfig.make(self.size, self.radius, self._dtypes,
                              len(devs), devs[0].platform)
        ch = self._plan_choice
        choice = PlanChoice(
            partition=(self.spec.dim.x, self.spec.dim.y, self.spec.dim.z),
            method=self._method.value,
            batch_quantities=self._batch_quantities,
            multistep_k=ch.multistep_k if ch is not None else 1,
            kernel_variant=ch.kernel_variant if ch is not None else None,
            placement=ch.placement if ch is not None else None,
        )
        return {"key": cfg.to_json(), "choice": choice.to_json(),
                "tuned": ch is not None,
                "wire_dtype": self._wire_dtype}

    def _warn_plan_mismatch(self, manifest: dict) -> None:
        from .plan.ir import retired_choice_key

        saved = (manifest.get("meta") or {}).get("plan")
        if not saved:
            return  # pre-plan snapshot: nothing to compare
        here = self.plan_meta()
        saved_ch = dict(saved.get("choice") or {})
        here_ch = dict(here["choice"])
        # pre-placement snapshots never wrote the field: an absent
        # placement IS the identity assignment (the plan-DB migration
        # rule), so normalize both sides before comparing — a build
        # upgrade must not make every old snapshot warn
        saved_ch.setdefault("placement", None)
        here_ch.setdefault("placement", None)
        # manifests written by PRs 17 to 29 carry the retired keys of
        # the two-level exchange (hierarchy, host_placement; the host
        # index per block beside the choice): the block geometry never
        # depended on them, so a restore ignores them
        for k in ("hierarchy", "host_placement"):
            saved_ch.pop(k, None)
        if not (saved.get("tuned") or here["tuned"]):
            # neither side went through the tuner: a partition-only delta
            # is the supported elastic mesh-reshape resume (PR 4) and must
            # stay quiet (and so must a placement-only one — both are
            # realize()-time layout facts, not tuned verdicts);
            # method/batching deltas still mix programs
            saved_ch.pop("partition", None)
            here_ch.pop("partition", None)
            saved_ch.pop("placement", None)
            here_ch.pop("placement", None)
        # the comparison is data-driven (plain dicts), so a snapshot
        # written under a method this build does not know — the retired
        # kernel-initiated transport of PRs 10 to 45, or any future
        # one — still WARNS instead of crashing on an unknown enum name;
        # name the methods in the message so the operator sees what moved
        saved_m = saved_ch.get("method")
        here_m = here_ch.get("method")
        known = {m.value for m in Method}
        retired = retired_choice_key(saved_ch)
        unknown = (f" (its {retired} {saved_ch[retired]!r} is retired)"
                   if retired is not None else
                   f" (method {saved_m!r} is unknown to this build)"
                   if saved_m is not None and saved_m not in known else "")
        wire_delta = saved.get("wire_dtype") != here.get("wire_dtype")
        if saved_ch != here_ch or wire_delta:
            detail = (f" (exchange method {saved_m} -> {here_m})"
                      if saved_m != here_m else "")
            if wire_delta:
                detail += (f" (wire_dtype {saved.get('wire_dtype')} -> "
                           f"{here.get('wire_dtype')}: halos exchanged "
                           "after restore round to the NEW wire precision)")
            log.warn(
                "ckpt: snapshot was written under exchange plan "
                f"{saved.get('choice')} but this run uses {here['choice']}"
                f"{detail}{unknown} — the elastic restore is still "
                "bit-exact, but the compiled programs differ; re-tune "
                "(--autotune) or pass the snapshot's plan to keep "
                "measurements comparable"
            )

    def replan(self, choice) -> None:
        """Hot-swap the exchange plan of a REALIZED domain, in place —
        the mid-run half of ROADMAP #6 (the PR-12 ``replan.requested``
        hook's consumer, driven by :class:`stencil_tpu.plan.replan.
        ReplanController` between guarded-loop chunks).

        ``choice`` (a ``plan.ir.PlanChoice`` or its JSON dict) is applied
        as a UNIT — partition, method, batching, kernel variant, and
        block placement; any explicit ``set_partition`` pin is cleared,
        exactly like a fresh tuned realize. The swap is the elastic
        ckpt restore without the disk: gather every quantity's global
        interior (pure host copies — bit-exact), re-realize under the
        new plan (the compile cache of already-seen programs makes this
        cheap), re-scatter, and rebuild the exteriors with one halo
        exchange. State after the swap is bit-identical to before it."""
        from .plan.ir import PlanChoice

        if not self._realized:
            raise RuntimeError(
                "replan() requires a realized domain (use set_plan "
                "before realize() for the initial choice)")
        if isinstance(choice, dict):
            choice = PlanChoice.from_json(choice)
        with timer.timed("setup.replan"), timer.trace_range("stencil.replan"):
            globs = {
                idx: unshard_blocks(self._curr[idx], self.spec)
                for idx in self._curr
            }
            old_choice = self._plan_choice
            old_partition = self._partition_dim

            def _install(ch):
                self._plan_choice = ch
                self._realized = False
                self._curr = {}
                self._next = {}
                self.realize()
                for idx, g in globs.items():
                    self._curr[idx] = shard_blocks(
                        g.astype(self._dtypes[idx]), self.spec, self.mesh)
                if self.radius.max_radius() > 0:
                    # one exchange rebuilds every exterior on the new
                    # layout (idempotent on exchanged data — the
                    # elastic-restore rule)
                    self.exchange()

            self._partition_dim = None
            try:
                _install(choice)
            except Exception:
                # a choice that cannot realize (bad tuned placement, a
                # partition the live device set no longer divides) must
                # not leave the domain torn: the ReplanController's
                # "rejected — continuing on the old plan" contract is
                # only true if the old plan is actually back. Re-realize
                # the old choice, re-shard the gathered state, and let
                # the original exception propagate as the rejection.
                self._partition_dim = old_partition
                _install(old_choice)
                raise

    # -- checkpoint / restart (ckpt/ subsystem) ------------------------------
    def save_checkpoint(self, ckpt_dir: str, step: int, *, keep: int = 3,
                        asynchronous: bool = True) -> None:
        """Snapshot every quantity's ``curr`` state at ``step`` into
        ``ckpt_dir`` (sharded per-block npz + manifest; crash-safe rename
        protocol — see ckpt/snapshot.py).

        ``asynchronous=True`` (default) fetches the snapshot copy on this
        thread, then hashes/serializes/fsyncs on a writer thread so the
        step loop keeps running; a second save drains the first (double
        buffering). Call :meth:`finish_checkpoints` before exiting."""
        from .ckpt import AsyncCheckpointer, host_snapshot, write_snapshot

        if jax.process_count() > 1:
            # cross-host shards are not addressable from this process;
            # per-host sharded writes + manifest merge are a ROADMAP #7
            # follow-up — degrade loudly ONCE, and count every skip so a
            # campaign with zero durable state is alertable from its
            # metrics (ckpt.save_skipped), never kill the run
            from .obs import telemetry

            telemetry.get().counter(
                "ckpt.save_skipped", value=1, phase="ckpt", step=int(step),
                reason="multi-process writes unsupported")
            if not getattr(self, "_ckpt_skip_warned", False):
                self._ckpt_skip_warned = True
                log.warn("ckpt: multi-process checkpoint writes are not "
                         "supported yet; skipping save (every skip is "
                         "counted as ckpt.save_skipped; this warning is "
                         "not repeated)")
            return
        arrays = {name: self._curr[i] for i, name in enumerate(self._names)}
        dtypes = dict(zip(self._names, self._dtypes))
        extra_meta = {"plan": self.plan_meta()}
        if not asynchronous:
            with timer.timed("ckpt.save"), timer.trace_range("ckpt.save"):
                write_snapshot(ckpt_dir, step, self.spec,
                               host_snapshot(self.spec, arrays),
                               dtypes=dtypes, keep=keep,
                               extra_meta=extra_meta)
            return
        cp = getattr(self, "_checkpointer", None)
        if cp is None or cp.ckpt_dir != ckpt_dir:
            if cp is not None:
                cp.close()
            cp = self._checkpointer = AsyncCheckpointer(
                ckpt_dir, keep=keep, dtypes=dtypes
            )
        cp.keep = keep
        cp.save(self.spec, arrays, step, extra_meta=extra_meta)

    def flush_checkpoints(self) -> None:
        """Block until the in-flight async snapshot (if any) is durable,
        keeping the writer alive — what the fault/recovery engine calls
        before reading the checkpoint dir back (rollback restore, the
        ckpt-truncate injection): disk must reflect every save already
        handed off."""
        cp = getattr(self, "_checkpointer", None)
        if cp is not None:
            cp.flush()

    def finish_checkpoints(self) -> None:
        """Drain the async checkpoint writer (every handed-off snapshot is
        durable when this returns)."""
        cp = getattr(self, "_checkpointer", None)
        if cp is not None:
            cp.close()
            self._checkpointer = None

    def restore_checkpoint(self, ckpt_dir: str) -> Optional[int]:
        """Materialize the newest valid snapshot under ``ckpt_dir`` onto
        THIS domain — elastic: the snapshot's partition/mesh/device count
        may differ from the saver's (global reassembly + re-split + halo
        exchange; ckpt/restore.py). Returns the restored step, or None
        when no compatible snapshot exists (logged, never raised — the
        auto-resume path must degrade to a fresh start)."""
        from .ckpt import assemble_global, check_compatible, find_resume
        from .obs import telemetry

        if not self._realized:
            raise RuntimeError("restore_checkpoint requires realize()")
        if jax.process_count() > 1:
            telemetry.get().counter(
                "ckpt.restore_skipped", value=1, phase="ckpt",
                reason="multi-process restore unsupported")
            log.warn("ckpt: multi-process restore is not supported yet; "
                     "starting fresh")
            return None
        # compatibility joins validity in the fallback: a newer intact
        # snapshot from a DIFFERENT domain shape must not shadow an older
        # compatible one
        found = find_resume(
            ckpt_dir,
            accept=lambda m: check_compatible(
                m, self.size, self._names, self._dtypes),
        )
        if found is None:
            log.info(f"ckpt: no valid compatible snapshot under {ckpt_dir}")
            return None
        snap, manifest = found
        # plan provenance: resuming under a different tuned plan is legal
        # (elastic restore) but must never be silent
        self._warn_plan_mismatch(manifest)
        rec = telemetry.get()
        with rec.span("ckpt.restore", phase="ckpt", step=manifest["step"]):
            nbytes = 0
            for idx, name in enumerate(self._names):
                g = assemble_global(snap, manifest, name,
                                    dtype=self._dtypes[idx])
                nbytes += g.nbytes
                self.set_curr_global(DataHandle(idx, name, self._dtypes[idx]), g)
            if self.radius.max_radius() > 0:
                # rebuild every exterior on the CURRENT partition — after
                # this the restored state is indistinguishable from a live
                # one (halo exchange is idempotent on exchanged data)
                self.exchange()
        rec.counter("ckpt.bytes_read", bytes=nbytes, phase="ckpt",
                    step=manifest["step"])
        rec.meta("ckpt.resumed", step=manifest["step"], snapshot=snap)
        log.info(f"ckpt: restored step {manifest['step']} from {snap}")
        return manifest["step"]

    # -- numerical health (fault/ subsystem) ---------------------------------
    def check_health(self, max_abs: Optional[float] = None,
                     step: Optional[int] = None) -> None:
        """One fused ``isfinite`` reduction (plus an optional ``max|u|``
        divergence ceiling) over every quantity's current state — raises
        :class:`stencil_tpu.fault.NumericalFault` naming the offending
        quantity and records the per-check cost as a ``health.check``
        span. The step program is untouched (the guard is a separate
        compiled reduction): with no check calls there is zero HLO
        change. The loop-integrated version (periodic checks + rollback)
        is :func:`stencil_tpu.fault.run_guarded`, wired as the apps'
        ``--health-every`` / ``--max-rollbacks`` knobs."""
        from .fault.health import HealthGuard

        if not self._realized:
            raise RuntimeError("check_health requires realize()")
        g = getattr(self, "_health_guard", None)
        if g is None:
            g = self._health_guard = HealthGuard(every=1, max_abs=max_abs)
        # the ceiling is a host-side comparison, not part of the compiled
        # reduction — mutate it rather than rebuilding (and re-jitting) the
        # guard when callers alternate ceilings
        g.max_abs = float(max_abs) if max_abs else None
        g.check({self._names[i]: a for i, a in self._curr.items()},
                step=-1 if step is None else int(step))

    # -- observability -------------------------------------------------------
    def write_plan(self, prefix: str) -> None:
        """Dump the exchange plan and the block-comm matrix — the analogue of
        plan_<rank>.txt / mat_npy_loadtxt.txt (reference:
        src/stencil.cu:482-637)."""
        path = f"{prefix}plan_{jax.process_index()}.txt"
        with open(path, "w") as f:
            f.write(f"global {self.size} dim {self.spec.dim} base {self.spec.base}\n")
            f.write(f"radius {self.radius}\n")
            f.write(f"method {self._method.value}\n")
            f.write(f"mesh {dict(self.mesh.shape)}\n")
            itemsizes = [jnp.dtype(dt).itemsize for dt in self._dtypes]
            for d in DIRECTIONS_26:
                b = direction_bytes(self.spec, d, sum(itemsizes))
                f.write(f"dir ({d.x},{d.y},{d.z}) bytes {b}\n")
        # block-to-block byte matrix for numpy loadtxt
        nb = self.spec.num_blocks()
        mat = np.zeros((nb, nb), dtype=np.int64)
        itemsize = sum(jnp.dtype(dt).itemsize for dt in self._dtypes)
        for i in range(nb):
            src = self._block_idx(i)
            for d in DIRECTIONS_26:
                if self.radius.dir(d) == 0:
                    continue
                dst = (src + d).wrap(self.spec.dim)
                j = dst.x + dst.y * self.spec.dim.x + dst.z * self.spec.dim.x * self.spec.dim.y
                ext = halo_extent(d, self.spec.block_size(src), self.radius)
                mat[i, j] += ext.flatten() * itemsize
        np.savetxt(f"{prefix}mat_npy_loadtxt.txt", mat, fmt="%d")

    def write_paraview(self, prefix: str, zero_nans: bool = False) -> None:
        """Per-block CSV dump of the interior — same columns as the reference
        (Z,Y,X,<quantity names>; reference: src/stencil.cu:1188-1264).

        Rows stream from the native writer (native/paraview.cpp — the
        reference's writer is C++ too, and a Python row loop is minutes of
        interpreter time at flagship sizes); the pure-Python loop is the
        byte-identical fallback when the shared library cannot be built."""
        off = self.spec.compute_offset()
        hosts = {
            idx: np.asarray(jax.device_get(arr)) for idx, arr in self._curr.items()
        }
        try:
            from .native import paraview_write
        except ImportError as e:
            log.warn(f"{e}; paraview rows take the Python loop")
            paraview_write = None
        for i in range(self.spec.num_blocks()):
            idx3 = self._block_idx(i)
            sz = self.spec.block_size(idx3)
            origin = self.spec.block_origin(idx3)
            path = f"{prefix}_{i}.txt"
            header = ",".join(["Z", "Y", "X"] + list(self._names))
            qs = []
            for qi in range(len(self._names)):
                block = hosts[qi][idx3.z, idx3.y, idx3.x]
                q = block[
                    off.z : off.z + sz.z, off.y : off.y + sz.y, off.x : off.x + sz.x
                ]
                if zero_nans:
                    q = np.nan_to_num(q, nan=0.0)
                qs.append(q)
            if paraview_write is not None:
                paraview_write(
                    path, header,
                    (origin.z, origin.y, origin.x), (sz.z, sz.y, sz.x), qs,
                )
            else:
                with open(path, "w") as f:
                    f.write(header + "\n")
                    for lz in range(sz.z):
                        for ly in range(sz.y):
                            for lx in range(sz.x):
                                pos = origin + Dim3(lx, ly, lz)
                                row = [str(pos.z), str(pos.y), str(pos.x)]
                                row += [repr(float(q[lz, ly, lx])) for q in qs]
                                f.write(",".join(row) + "\n")
            log.info(f"wrote paraview file {path}")
