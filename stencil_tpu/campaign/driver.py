"""Multi-tenant batched campaigns: one compiled program, thousands of
small domains.

Every other layer of this repo scales ONE big domain; production traffic
from many users is the inverse workload — floods of small-to-medium
*independent* simulations (ROADMAP #4). This driver serves that shape:

- **Queue -> slots.** Tenant jobs queue FIFO; the driver packs them into
  fixed-size batch slots of ``slot_size`` lanes, bucketed by shape
  (grid, dtype): a slot's compiled program depends only on the bucket,
  never on the tenants in it. When the queue drains below a full slot,
  the empty lanes are DEAD tenants (zeros — finite, never attributed).
- **Batched stepping.** A slot's state is one ``(B, pz, py, px)`` stacked
  array sharded over a 1-D device mesh on the batch axis
  (``ops/jacobi.make_batched_jacobi_loop``): each tenant is its own
  periodic box (halos self-wrap per tenant, never across the batch
  axis), the program has ZERO collectives, and one jit serves every
  same-shape slot through the :class:`~.compile_cache.CompileCache`
  (``compile.cache_hit`` / ``compile.build_s`` telemetry).
- **Guarded slots.** Each slot segment runs through
  ``fault/recover.run_guarded`` — the SAME engine the apps use — with a
  per-lane :class:`~.health.SlotHealthGuard` and an optional per-tenant
  :class:`~.inject.SlotInjector`. A transient fault rolls the whole slot
  back to the last health-checked stash (deterministic recompute keeps
  every lane bit-identical); a tenant that exhausts ``max_rollbacks``
  raises through as the rc-43 ``fault`` outcome and is EVICTED: its
  evidence bundle moves into its tenant dir, its last healthy state is
  written as a revivable snapshot, its lane is backfilled from the queue
  (or dies), and the surviving lanes resume from the stash — the slot
  never stalls, and survivors finish bit-identical to an uninjected
  campaign (tests/test_campaign.py, scripts/ci_campaign_gate.py).
- **Per-tenant durable state.** Every tenant owns a snapshot dir
  ``<campaign_dir>/tenants/<tid>`` (ckpt/ subsystem: crash-safe rename
  protocol, manifests, retention). ``ckpt_every`` > 0 checkpoints every
  active lane at the cadence; completion and eviction always persist a
  final/last-healthy snapshot, so evicted tenants are revivable
  (``resume=True`` packs a tenant from its newest valid snapshot).

The sequential baseline (:func:`run_sequential`) serves the same jobs
one tenant at a time through the standard ``DistributedDomain`` +
``make_jacobi_loop`` machinery on the same devices — the A/B behind the
tracked ``campaign_batched_over_sequential`` bench leg (aggregate
Mcells/s and p50/p99 per-tenant step latency, utils/statistics
percentiles).
"""

from __future__ import annotations

import os
import shutil
import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..ckpt import assemble_global, check_compatible, find_resume, write_snapshot
from ..domain.grid import GridSpec
from ..fault import RecoveryExhausted, RecoveryPolicy, chunk_plan, run_guarded
from ..fault.inject import FaultPlan
from ..geometry import Dim3, Radius
from ..obs import telemetry
from ..obs.watchdog import FAULT_RC
from ..ops.jacobi import INIT_TEMP, make_batched_jacobi_loop, sphere_sel
from ..utils import logging as log
from ..utils.statistics import percentile
from ..utils.sync import hard_sync
from .compile_cache import CompileCache, cache_key
from .health import SlotHealthGuard, TenantFault
from .inject import SlotInjector

QUANTITY = "temperature"


@dataclass
class TenantJob:
    """One queued simulation: an independent periodic box of one
    workload — ``"jacobi"`` (single-quantity heat) or ``"astaroth"``
    (8-field MHD through ``make_batched_astaroth_step``)."""

    tid: str
    size: Tuple[int, int, int]      # (x, y, z)
    steps: int
    dtype: str = "float32"
    seed: int = 0
    workload: str = "jacobi"
    # Optional per-step latency SLO (milliseconds): while the tenant's
    # lane is live, its ONLINE p99 step latency is tracked against this
    # deadline and a breach emits one `slo.violation` record (the
    # SLO-aware scheduling of ROADMAP #4 consumes these; here the
    # tracking + evidence land). Never joins the bucket — a deadline is
    # a contract, not a shape.
    deadline_ms: Optional[float] = None

    def bucket(self) -> Tuple[Tuple[int, int, int], str, str]:
        """The shape bucket: jobs in one slot must share it (the compiled
        program and the compile-cache key depend on nothing else).
        Workload joins the bucket — a slot's program is the workload's."""
        return (tuple(int(v) for v in self.size), str(self.dtype),
                str(self.workload))


@dataclass
class TenantResult:
    tid: str
    outcome: str                    # "done" | "fault"
    steps: int                      # tenant steps completed
    snapshot_dir: str
    evidence: Optional[str] = None
    final: Optional[np.ndarray] = None   # global [z,y,x] interior ("done",
    #                                      the workload's FIRST quantity)
    finals: Optional[Dict[str, np.ndarray]] = None  # every quantity ("done")


@dataclass
class Lane:
    """One slot position: which tenant occupies it and the step anchors
    mapping the slot clock to the tenant clock (backfilled lanes run
    offset from the slot's step counter)."""

    idx: int
    tenant: Optional[TenantJob] = None
    start_slot_step: int = 0
    start_tenant_step: int = 0

    def tenant_step(self, slot_step: int) -> int:
        return self.start_tenant_step + (slot_step - self.start_slot_step)

    def end_slot_step(self) -> int:
        if self.tenant is None:
            raise RuntimeError("end_slot_step on an empty (dead) lane")
        return self.start_slot_step + (self.tenant.steps
                                       - self.start_tenant_step)


def tenant_init_field(job: TenantJob) -> np.ndarray:
    """The ONE authority for a tenant's initial temperature field
    (``[z, y, x]``): the jacobi lukewarm baseline plus a seeded
    perturbation so tenants are distinguishable — the driver, the
    sequential baseline, revival, and the parity tests all regenerate a
    tenant's step-0 state from this."""
    x, y, z = job.size
    rng = np.random.RandomState(job.seed & 0x7FFFFFFF)
    f = INIT_TEMP + 0.05 * rng.standard_normal((z, y, x))
    return f.astype(job.dtype)


def astaroth_init_state(job: TenantJob) -> Dict[str, np.ndarray]:
    """The one authority for an astaroth tenant's step-0 fields: small
    seeded perturbations per field, lnrho offset to a positive density —
    the same fixture shape the batched-step parity suite uses. Any code
    path (driver, revival, parity tests) regenerates a tenant from
    this."""
    from ..astaroth.integrate import FIELDS

    x, y, z = job.size
    rng = np.random.RandomState((job.seed ^ 0x5A57A407) & 0x7FFFFFFF)
    state = {}
    for k in FIELDS:
        f = rng.standard_normal((z, y, x)) * 0.05
        if k == "lnrho":
            f = f + 0.5
        state[k] = f.astype(job.dtype)
    return state


class _JacobiWorkload:
    """The original campaign workload: single-quantity periodic heat."""

    name = "jacobi"
    default_radius = 1
    needs_sel = True

    def quantity_names(self, job_dtype: str):
        return [QUANTITY]

    def init_state(self, job: TenantJob) -> Dict[str, np.ndarray]:
        return {QUANTITY: tenant_init_field(job)}

    def build_loop(self, spec, iters: int, sharding, sel_sharding,
                   batch: int, use_pallas: bool):
        return make_batched_jacobi_loop(
            spec, iters, sharding=sharding, sel_sharding=sel_sharding,
            use_pallas=use_pallas, batch=batch if use_pallas else None)

    def step(self, loop, state: Dict, scratch: Dict, sel) -> Dict:
        c, _scratch = loop(state[QUANTITY], scratch[QUANTITY], sel)
        return {QUANTITY: c}


class _AstarothWorkload:
    """8-field MHD tenants through ``make_batched_astaroth_step`` —
    the ROADMAP #4 follow-up: the batched astaroth step existed (PR 9);
    this routes whole astaroth campaigns through the same queue/slot/
    guard/evict machinery the jacobi tenants use. No sel (no sphere
    sources), radius 3 (6th-order cross stencils), one reference
    swap-per-iteration RK3 step per slot step."""

    name = "astaroth"
    default_radius = 3
    needs_sel = False
    dt = 1e-8

    def quantity_names(self, job_dtype: str):
        from ..astaroth.integrate import FIELDS

        return list(FIELDS)

    def init_state(self, job: TenantJob) -> Dict[str, np.ndarray]:
        return astaroth_init_state(job)

    def _info(self, spec):
        from ..astaroth import config as ac_config

        info = ac_config.AcMeshInfo()
        conf = os.path.join(os.path.dirname(__file__), "..", "astaroth",
                            "astaroth.conf")
        with open(conf) as f:
            ac_config.parse_config(f.read(), info)
        b = spec.base
        info.int_params["AC_nx"] = int(b.x)
        info.int_params["AC_ny"] = int(b.y)
        info.int_params["AC_nz"] = int(b.z)
        info.update_builtin_params()
        return info

    def build_loop(self, spec, iters: int, sharding, sel_sharding,
                   batch: int, use_pallas: bool):
        from ..astaroth.integrate import make_batched_astaroth_step

        if use_pallas:
            raise ValueError(
                "astaroth campaigns run the XLA batched step (the batched "
                "Pallas substep is a hardware-session follow-up)"
            )
        return make_batched_astaroth_step(spec, self._info(spec),
                                          dt=self.dt, iters=iters,
                                          sharding=sharding)

    def step(self, loop, state: Dict, scratch: Dict, sel) -> Dict:
        curr, _out = loop(state, scratch)
        return curr


WORKLOADS = {"jacobi": _JacobiWorkload(), "astaroth": _AstarothWorkload()}


def pick_slot(queue: deque,
              slot_size: int) -> Tuple[Tuple, List[TenantJob], deque]:
    """Pop the next slot's jobs: the queue head's bucket, same-bucket
    jobs pulled forward FIFO until the slot fills. Returns ``(bucket,
    picked, remaining-queue)`` — the ONE packing policy, shared by the
    driver and the :func:`plan_slots` preview."""
    bucket = queue[0].bucket()
    picked: List[TenantJob] = []
    rest: List[TenantJob] = []
    for j in queue:
        if j.bucket() == bucket and len(picked) < slot_size:
            picked.append(j)
        else:
            rest.append(j)
    return bucket, picked, deque(rest)


def plan_slots(jobs: Sequence[TenantJob],
               slot_size: int) -> List[Tuple[Tuple, List[str]]]:
    """Deterministic packing preview: ``[(bucket, [tids...]), ...]`` in
    the order the driver forms slots (:func:`pick_slot`). Pure (no
    devices, no state): the packing-determinism pin of
    tests/test_campaign.py."""
    queue = deque(jobs)
    out: List[Tuple[Tuple, List[str]]] = []
    while queue:
        bucket, picked, queue = pick_slot(queue, slot_size)
        out.append((bucket, [j.tid for j in picked]))
    return out


def batch_devices(slot_size: int, devices: Sequence) -> List:
    """The largest device prefix that divides the batch axis evenly."""
    for n in range(min(slot_size, len(devices)), 0, -1):
        if slot_size % n == 0:
            return list(devices[:n])
    return list(devices[:1])


class CampaignDriver:
    """Serve a queue of tenant jobs through fixed-size batch slots."""

    def __init__(
        self,
        jobs: Sequence[TenantJob],
        slot_size: int,
        campaign_dir: str,
        *,
        devices: Optional[Sequence] = None,
        radius: Optional[int] = None,
        chunk: int = 2,
        ckpt_every: int = 0,
        ckpt_keep: int = 3,
        health_every: int = 0,
        max_abs: Optional[float] = None,
        max_rollbacks: int = 2,
        rollback_backoff: float = 0.05,
        inject: Optional[str] = None,
        inject_seed: Optional[int] = None,
        resume: bool = False,
        cache: Optional[CompileCache] = None,
        use_pallas: bool = False,
        sentinel=None,
        status=None,
        slo_min_samples: int = 3,
        replan=None,
    ):
        if slot_size < 1:
            raise ValueError(f"slot_size must be >= 1, got {slot_size}")
        tids = [j.tid for j in jobs]
        if len(set(tids)) != len(tids):
            raise ValueError("tenant ids must be unique")
        self.jobs = list(jobs)
        self.slot_size = int(slot_size)
        self.campaign_dir = campaign_dir
        self.devices = (list(devices) if devices is not None
                        else jax.devices())
        # None = each slot uses its workload's default (jacobi 1,
        # astaroth 3 — the 6th-order cross stencils)
        self.radius = None if radius is None else int(radius)
        for j in self.jobs:
            if j.workload not in WORKLOADS:
                raise ValueError(
                    f"tenant {j.tid}: unknown workload {j.workload!r} "
                    f"(known: {sorted(WORKLOADS)})")
        self.chunk = max(1, int(chunk))
        self.ckpt_every = int(ckpt_every)
        self.ckpt_keep = int(ckpt_keep)
        self.health_every = int(health_every) or self.chunk
        self.max_abs = max_abs
        self.policy = RecoveryPolicy(max_rollbacks=max_rollbacks,
                                     backoff_s=rollback_backoff)
        self.inject_spec = inject or None
        self.inject_seed = inject_seed
        self.resume = bool(resume)
        self.cache = cache if cache is not None else CompileCache()
        self.use_pallas = bool(use_pallas)
        # live observability (obs/live.py + obs/status.py): the sentinel
        # watches per-slot chunk-cycle latencies (keyed per bucket — two
        # shapes legitimately run at different cadences), the status
        # writer gets the per-lane tenant table each chunk
        self.sentinel = sentinel
        self.status = status
        # the campaign's plan hot-swap (ROADMAP #6, between slots): a
        # slot's compiled program is bucket-keyed and must not change
        # under a running slot, so the swap point is the slot boundary —
        # a latched replan.requested re-tunes there and the next slot's
        # programs consult the re-tuned plan (plan/replan.py)
        self.replan = replan
        # a tenant's online p99 is judged against its deadline only once
        # this many latency samples exist (a single cold-cache chunk must
        # not condemn a tenant)
        self.slo_min_samples = max(1, int(slo_min_samples))
        # per-tenant online latency samples (bounded — streaming p50/p99
        # over recent history, the obs/live window discipline) and the
        # once-per-tenant violation latch
        self._lane_lat: Dict[str, deque] = {}
        self._slo_violated: set = set()
        # the RUNNING slot's lanes and width, published for the serving
        # layer's chunk-boundary capacity decisions (preemption pricing
        # needs the victims; per-width latency pricing needs the B that
        # produced each sample). Batch campaigns run at slot_size.
        self._cur_lanes: List[Lane] = []
        self._cur_width: int = self.slot_size

    # -- serving extension points (stencil_tpu/serve/) ------------------------
    # The always-on scheduler (serve/scheduler.py) subclasses the driver
    # and overrides these hooks; the batch campaign is the degenerate
    # case (a queue fixed at launch, no intake, no parking). Every hook
    # sits at a point the slot machinery already treats as safe: queue
    # scans, chunk boundaries, result assignment, segment boundaries.

    def _refresh_queue(self, queue) -> None:
        """Grow ``queue`` IN PLACE from an external intake. Called before
        every backfill scan and once per chunk — the point where
        backfill stops being a drain-time convenience and becomes
        steady-state continuous batching: a job admitted here lands in a
        RUNNING slot's next freed lane, never behind a slot barrier."""

    def _observe_chunk(self, bucket, per: float, done_now: int) -> None:
        """Per-chunk serving observation (latency pricing, SLO pressure,
        queue status staging). ``per`` is the chunk's per-step wall."""

    def _publish(self, results: Dict[str, "TenantResult"],
                 r: "TenantResult") -> None:
        """The ONE place a tenant's terminal result lands — every retire
        / evict / revived-complete path funnels through here so a
        serving layer can stream results as they happen."""
        results[r.tid] = r
        self._on_result(r)

    def _on_result(self, r: "TenantResult") -> None:
        """A tenant result just published (serve streams it to disk)."""

    def _on_backfill(self, job: "TenantJob", lane_idx: int,
                     slot_step: int) -> None:
        """A queued tenant just took over a freed lane mid-slot."""

    def _backfill_gate(self, bucket) -> bool:
        """May a freed lane refill from the queue right now? Serving
        vetoes (False) when a job of a DIFFERENT bucket has aged past
        its starvation bound: continuous batching would otherwise keep
        a sustained same-bucket stream's slot alive forever, and the
        waiting job could never enter. A veto lets the lane die so the
        slot drains and the next packing pass serves the overdue job."""
        return True

    def _segment_end(self, slot_step: int, end: int) -> int:
        """Cap a guarded segment's end step (must return in
        ``(slot_step, end]``). The batch campaign runs each segment to
        the earliest lane event; serving caps it to one fused chunk so
        a drain request parks at the next CHUNK boundary instead of
        waiting out a whole tenant."""
        return end

    def _should_park(self) -> bool:
        """True = stop the slot at the next segment boundary and park
        every live lane as a revivable snapshot (graceful drain)."""
        return False

    def _on_park(self, job: "TenantJob", tenant_step: int) -> None:
        """A live lane was parked at ``tenant_step`` (snapshot already
        durable) — the serving layer re-queues it for a later daemon."""

    # -- per-tenant durable state ---------------------------------------------
    def tenant_dir(self, tid: str) -> str:
        return os.path.join(self.campaign_dir, "tenants", tid)

    def _write_tenant_snapshot(self, job: TenantJob, spec: GridSpec,
                               lane_state: Dict[str, np.ndarray],
                               step: int) -> None:
        p = spec.padded()
        arrs = {
            name: np.ascontiguousarray(a.reshape(1, 1, 1, p.z, p.y, p.x))
            for name, a in lane_state.items()
        }
        write_snapshot(self.tenant_dir(job.tid), step, spec, arrs,
                       dtypes={name: job.dtype for name in arrs},
                       keep=self.ckpt_keep)

    def _resume_tenant(self, job: TenantJob
                       ) -> Optional[Tuple[int, Dict[str, np.ndarray]]]:
        """The newest valid compatible snapshot of a revived tenant:
        ``(tenant_step, {quantity: global [z,y,x]})`` or None (fresh)."""
        if not self.resume:
            return None
        names = WORKLOADS[job.workload].quantity_names(job.dtype)
        x, y, z = job.size
        found = find_resume(
            self.tenant_dir(job.tid),
            accept=lambda m: check_compatible(
                m, Dim3(x, y, z), names, [job.dtype] * len(names)),
        )
        if found is None:
            return None
        snap, manifest = found
        g = {name: assemble_global(snap, manifest, name, dtype=job.dtype)
             for name in names}
        log.info(f"campaign: revived tenant {job.tid} from step "
                 f"{manifest['step']} ({snap})")
        return int(manifest["step"]), g

    # -- compiled programs ----------------------------------------------------
    def _loop(self, spec: GridSpec, bucket, iters: int, sharding,
              sel_sharding, devs: Sequence, batch: Optional[int] = None):
        from ..plan.ir import PlanConfig

        (size, dtype, workload) = bucket
        wl = WORKLOADS[workload]
        b = int(batch) if batch else self.slot_size
        nq = len(wl.quantity_names(dtype))
        cfg = PlanConfig.make(Dim3(*size), spec.radius, [dtype] * nq,
                              len(devs), self.devices[0].platform)
        # device IDENTITY joins the key, not just the count: the jitted
        # loop's in_shardings pin a concrete mesh, and a shared cache
        # serving two drivers on disjoint same-sized device sets must
        # never hand one the other's program. batch= keys the slot
        # WIDTH, so an elastic daemon holds one program per (bucket,
        # width) rung and a width revisit is a cache hit by construction
        key = cache_key(cfg, workload=f"{workload}-batched",
                        batch=b, iters=int(iters),
                        pallas=self.use_pallas,
                        devices=[d.id for d in devs])
        return self.cache.get(key, lambda: wl.build_loop(
            spec, iters, sharding, sel_sharding,
            batch=b, use_pallas=self.use_pallas))

    # -- the campaign ---------------------------------------------------------
    def run(self) -> dict:
        rec = telemetry.get()
        os.makedirs(self.campaign_dir, exist_ok=True)
        queue = deque(self.jobs)
        results: Dict[str, TenantResult] = {}
        lat: List[float] = []        # per-chunk per-step wall samples
        cell_steps = 0
        wall = 0.0
        slot_idx = 0
        t0 = time.perf_counter()
        while queue:
            bucket, picked, queue = pick_slot(queue, self.slot_size)
            stats = self._run_slot(slot_idx, bucket, picked, queue, results)
            lat.extend(stats["latency_samples"])
            cell_steps += stats["cell_steps"]
            wall += stats["wall_s"]
            slot_idx += 1
            if self.replan is not None and self.replan.pending:
                # between slots: the same swap the guarded single-domain
                # loop performs between chunks (run_guarded's replan=),
                # at the campaign's own safe boundary
                self.replan.maybe_swap(None, slot_idx)
        agg = cell_steps / wall / 1e6 if wall > 0 else 0.0
        summary = {
            "results": results,
            "tenants": len(self.jobs),
            "slots": slot_idx,
            "cell_steps": cell_steps,
            "step_wall_s": wall,
            "total_wall_s": time.perf_counter() - t0,
            "aggregate_mcells_per_s": agg,
            "p50_step_s": percentile(lat, 50) if lat else float("nan"),
            "p99_step_s": percentile(lat, 99) if lat else float("nan"),
            "evicted": sorted(t for t, r in results.items()
                              if r.outcome == "fault"),
            "slo_violations": sorted(self._slo_violated),
            "anomalies": (self.sentinel.detected_total
                          if self.sentinel is not None else 0),
            "cache": self.cache.stats(),
        }
        if self.sentinel is not None:
            # the campaign's in-run instability lands in the ledger via
            # the standard gauge-trimean ingest (perf_tool)
            rec.gauge("live.anomaly_count",
                      float(self.sentinel.detected_total), phase="live")
        rec.meta("campaign.summary", slots=slot_idx,
                 tenants=len(self.jobs), evicted=len(summary["evicted"]),
                 slo_violations=len(summary["slo_violations"]),
                 cache_hits=self.cache.hits, cache_misses=self.cache.misses)
        return summary

    def _run_slot(self, slot_idx: int, bucket, initial: List[TenantJob],
                  queue: deque, results: Dict[str, TenantResult],
                  width: Optional[int] = None) -> dict:
        """Run one slot. ``width`` overrides ``slot_size`` for THIS slot
        only — the elastic serving path sizes each slot to its queue
        depth; batch campaigns never pass it."""
        rec = telemetry.get()
        (size, dtype, workload) = bucket
        wl = WORKLOADS[workload]
        names = wl.quantity_names(dtype)
        radius = (self.radius if self.radius is not None
                  else wl.default_radius)
        x, y, z = size
        cells = x * y * z
        spec = GridSpec(Dim3(x, y, z), Dim3(1, 1, 1),
                        Radius.constant(radius),
                        aligned=self.use_pallas)
        p = spec.padded()
        off = spec.compute_offset()
        B = int(width) if width else self.slot_size
        devs = batch_devices(B, self.devices)
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.asarray(devs), ("b",))
        sh = NamedSharding(mesh, P("b"))
        shr = NamedSharding(mesh, P())

        # sel (jacobi only): the standard hot/cold spheres, shared across
        # lanes (every tenant of one bucket sees the same geometry); the
        # Pallas path wants the per-tenant stacked layout its kernel
        # indexes. Astaroth has no source geometry — no sel at all.
        sel = None
        sel_sh = shr
        if wl.needs_sel:
            sel_np = np.zeros((p.z, p.y, p.x), np.int32)
            sel_np[off.z:off.z + z, off.y:off.y + y, off.x:off.x + x] = (
                sphere_sel((x, y, z)))
            if self.use_pallas:
                sel = jax.device_put(
                    jnp.asarray(np.broadcast_to(sel_np, (B,) + sel_np.shape)
                                .copy()), sh)
                sel_sh = sh
            else:
                sel = jax.device_put(jnp.asarray(sel_np), shr)
                sel_sh = shr

        lanes = [Lane(i) for i in range(B)]
        self._cur_lanes = lanes
        self._cur_width = B

        def interior(padded: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
            return {
                name: np.ascontiguousarray(
                    a[off.z:off.z + z, off.y:off.y + y, off.x:off.x + x])
                for name, a in padded.items()
            }

        def lane_init(job: TenantJob) -> Tuple[int, Dict[str, np.ndarray]]:
            revived = self._resume_tenant(job)
            t0_step, g = revived if revived is not None else (
                0, wl.init_state(job))
            padded = {}
            for name in names:
                a = np.zeros((p.z, p.y, p.x), dtype)
                a[off.z:off.z + z, off.y:off.y + y, off.x:off.x + x] = g[name]
                padded[name] = a
            return t0_step, padded

        curr_np = {name: np.zeros((B, p.z, p.y, p.x), dtype)
                   for name in names}
        for i, job in enumerate(initial):
            t0_step, padded = lane_init(job)
            if t0_step >= job.steps:
                # revived past its target: report done, leave the lane to
                # a later backfill pass
                fins = interior(padded)
                self._publish(results, TenantResult(
                    job.tid, "done", job.steps, self.tenant_dir(job.tid),
                    final=fins[names[0]], finals=fins))
                continue
            lanes[i].tenant = job
            lanes[i].start_slot_step = 0
            lanes[i].start_tenant_step = t0_step
            for name in names:
                curr_np[name][i] = padded[name]
        curr = {name: jax.device_put(jnp.asarray(a), sh)
                for name, a in curr_np.items()}
        scratch = {name: jax.device_put(jnp.zeros_like(curr[name]), sh)
                   for name in names}
        del curr_np

        guard = SlotHealthGuard(every=self.health_every, max_abs=self.max_abs)
        guard.bind(
            lambda lane: (lanes[lane].tenant.tid
                          if lanes[lane].tenant is not None else None),
            lambda lane, step: lanes[lane].tenant_step(step),
        )
        injector = None
        if self.inject_spec:
            plan = FaultPlan.from_spec(self.inject_spec,
                                       seed=self.inject_seed)
            if plan is not None:
                injector = SlotInjector(plan, spec, lambda: lanes,
                                        known_tenants=[j.tid
                                                       for j in self.jobs])
        rec.meta("campaign.slot", slot=slot_idx,
                 tenants=[l.tenant.tid for l in lanes if l.tenant],
                 bucket={"size": list(size), "dtype": dtype,
                         "workload": workload},
                 devices=len(devs), width=B)

        def backfill(lane: Lane, slot_step: int, state: Dict):
            """Replace a retired/evicted lane from the queue (same bucket
            only) or mark it dead (zeros). Takes and returns the whole
            quantity dict — every quantity's lane moves together."""
            self._refresh_queue(queue)
            job = None
            if self._backfill_gate(bucket):
                for cand in list(queue):
                    if cand.bucket() == bucket:
                        job = cand
                        queue.remove(cand)
                        break
            if job is None:
                lane.tenant = None
                return {
                    name: state[name].at[lane.idx].set(
                        jnp.zeros((p.z, p.y, p.x), dtype))
                    for name in names
                }
            t0_step, padded = lane_init(job)
            if t0_step >= job.steps:
                fins = interior(padded)
                self._publish(results, TenantResult(
                    job.tid, "done", job.steps, self.tenant_dir(job.tid),
                    final=fins[names[0]], finals=fins))
                return backfill(lane, slot_step, state)
            lane.tenant = job
            lane.start_slot_step = slot_step
            lane.start_tenant_step = t0_step
            rec.meta("campaign.backfill", tenant=job.tid, lane=lane.idx,
                     slot=slot_idx, slot_step=int(slot_step))
            self._on_backfill(job, lane.idx, int(slot_step))
            return {
                name: state[name].at[lane.idx].set(
                    jnp.asarray(padded[name]))
                for name in names
            }

        # -- the guarded slot loop -------------------------------------------
        slot_step = 0
        stash: Tuple[int, dict] = (0, dict(curr))
        lat: List[float] = []
        cell_steps = 0
        wall = 0.0

        def step_fn(st, k):
            loop = self._loop(spec, bucket, k, sh, sel_sh, devs, B)
            out = wl.step(loop, st, scratch, sel)
            hard_sync(out)
            return out

        def lane_stats(lane: Lane):
            """(p50_ms, p99_ms) of the lane's tenant over its online
            latency window, or (None, None) before any sample."""
            if lane.tenant is None:
                return None, None
            samples = self._lane_lat.get(lane.tenant.tid)
            if not samples:
                return None, None
            return (percentile(samples, 50) * 1e3,
                    percentile(samples, 99) * 1e3)

        def check_slo(done_now: int) -> None:
            """Judge every live lane's online p99 against its deadline;
            a breach emits ONE slo.violation (latched per tenant — the
            evidence record, not a siren)."""
            for l in lanes:
                job = l.tenant
                if job is None or job.deadline_ms is None:
                    continue
                samples = self._lane_lat.get(job.tid)
                if (not samples or len(samples) < self.slo_min_samples
                        or job.tid in self._slo_violated):
                    continue
                p50_ms, p99_ms = lane_stats(l)
                if p99_ms > job.deadline_ms:
                    self._slo_violated.add(job.tid)
                    rec.meta("slo.violation", tenant=job.tid,
                             step=int(l.tenant_step(done_now)),
                             lane=l.idx, slot=slot_idx, phase="slo",
                             deadline_ms=float(job.deadline_ms),
                             p99_ms=p99_ms, p50_ms=p50_ms,
                             samples=len(samples))
                    log.warn(
                        f"campaign: SLO VIOLATION tenant {job.tid} "
                        f"(lane {l.idx}): online p99 {p99_ms:.3g} ms > "
                        f"deadline {job.deadline_ms:g} ms")

        def lane_table(done_now: int):
            rows = []
            for l in lanes:
                job = l.tenant
                p50_ms, p99_ms = lane_stats(l)
                rows.append({
                    "lane": l.idx,
                    "tenant": job.tid if job else None,
                    "step": int(l.tenant_step(done_now)) if job else None,
                    "steps": job.steps if job else None,
                    "p50_ms": p50_ms,
                    "p99_ms": p99_ms,
                    "deadline_ms": job.deadline_ms if job else None,
                    "slo": (None if job is None or job.deadline_ms is None
                            else ("violated" if job.tid in self._slo_violated
                                  else "ok")),
                })
            return rows

        def on_chunk(st, k, per, done_now):
            nonlocal cell_steps, wall
            n_active = sum(1 for l in lanes if l.tenant is not None)
            lat.append(per)
            cell_steps += k * n_active * cells
            wall += per * k
            rec.gauge("campaign.step_latency_s", per, phase="step",
                      unit="s", mode="batched", slot=slot_idx, iters=k)
            # per-tenant online latency: every live lane of the slot
            # stepped together, so the chunk's per-step wall is each
            # live tenant's sample
            for l in lanes:
                if l.tenant is not None:
                    self._lane_lat.setdefault(
                        l.tenant.tid, deque(maxlen=256)).append(per)
            # steady-state serving: pull any newly-arrived jobs into the
            # LIVE queue every chunk (so a retire later in this same
            # slot backfills them — no slot-wide barrier), then let the
            # serving layer observe the chunk (pricing, SLO pressure)
            self._refresh_queue(queue)
            self._observe_chunk(bucket, per, done_now)
            check_slo(done_now)
            if self.status is not None:
                # stage only: run_guarded's per-chunk update (which runs
                # right after on_chunk) flushes these sections in the
                # same atomic write
                self.status.set(
                    lanes=lane_table(done_now),
                    slo={"violations": sorted(self._slo_violated)})

        def save_fn(s, st):
            nonlocal stash
            stash = (s, dict(st))
            host = {name: np.asarray(jax.device_get(st[name]))
                    for name in names}
            for l in lanes:
                if l.tenant is None:
                    continue
                self._write_tenant_snapshot(
                    l.tenant, spec,
                    {name: host[name][l.idx] for name in names},
                    l.tenant_step(s))

        def restore_fn():
            s, st = stash
            return s, dict(st)

        while any(l.tenant is not None for l in lanes):
            if self._should_park():
                # graceful drain: every live lane's current state becomes
                # a revivable snapshot (the eviction persistence path,
                # minus the eviction) and the slot ends here — a later
                # daemon resumes each tenant from exactly this step
                host = {name: np.asarray(jax.device_get(curr[name]))
                        for name in names}
                for l in lanes:
                    if l.tenant is None:
                        continue
                    tstep = l.tenant_step(slot_step)
                    self._write_tenant_snapshot(
                        l.tenant, spec,
                        {name: host[name][l.idx] for name in names}, tstep)
                    self._on_park(l.tenant, tstep)
                    l.tenant = None
                break
            end = min(l.end_slot_step() for l in lanes
                      if l.tenant is not None)
            end = self._segment_end(slot_step, end)
            state = dict(curr)
            stash = (slot_step, dict(state))

            def plan_fn(s):
                return chunk_plan(
                    s, end, self.chunk,
                    every=(self.ckpt_every, guard.every),
                    at=injector.steps() if injector is not None else (),
                )

            try:
                state, done = run_guarded(
                    state, start=slot_step, iters=end, plan_fn=plan_fn,
                    step_fn=step_fn, guard=guard, injector=injector,
                    policy=self.policy,
                    save_fn=save_fn if self.ckpt_every > 0 else None,
                    ckpt_every=self.ckpt_every, restore_fn=restore_fn,
                    on_chunk=on_chunk, spec=None,
                    ckpt_dir=self.campaign_dir,
                    evidence_dir=self.campaign_dir, app="campaign",
                    sentinel=self.sentinel,
                    # per-bucket key: two shape buckets run at honestly
                    # different cadences; base_metric() strips the tag so
                    # "*"/"step.latency_s" config still applies
                    sentinel_key=("step.latency_s["
                                  f"{x}x{y}x{z},{dtype},{workload}]"),
                    status=self.status,
                )
            except RecoveryExhausted as e:
                curr = self._evict(e, spec, lanes, stash, backfill,
                                   results, slot_idx, names)
                slot_step = stash[0]
                continue
            slot_step = done
            curr = dict(state)
            # segment end passed a health check (run_guarded checks at
            # done >= iters): retire every lane whose tenant is complete
            host = {name: np.asarray(jax.device_get(curr[name]))
                    for name in names}
            for l in lanes:
                if l.tenant is None:
                    continue
                if l.tenant_step(slot_step) < l.tenant.steps:
                    continue
                job = l.tenant
                lane_host = {name: host[name][l.idx] for name in names}
                self._write_tenant_snapshot(job, spec, lane_host,
                                            job.steps)
                fins = interior(lane_host)
                self._publish(results, TenantResult(
                    job.tid, "done", job.steps, self.tenant_dir(job.tid),
                    final=fins[names[0]], finals=fins))
                rec.meta("campaign.retire", tenant=job.tid,
                         step=int(job.steps), lane=l.idx, slot=slot_idx)
                curr = backfill(l, slot_step, curr)

        self._cur_lanes = []
        return {"latency_samples": lat, "cell_steps": cell_steps,
                "wall_s": wall}

    def _evict(self, e: RecoveryExhausted, spec: GridSpec,
               lanes: List[Lane], stash, backfill, results,
               slot_idx: int, names: Sequence[str]):
        """The rc-43 eviction path: evidence moves to the tenant dir, the
        tenant's last healthy state becomes a revivable snapshot, the
        lane is backfilled, and the slot resumes from the stash."""
        rec = telemetry.get()
        f = e.fault
        if not isinstance(f, TenantFault):
            raise e  # unattributable: nothing sane to evict
        lane = lanes[f.lane]
        if lane.tenant is None or lane.tenant.tid != f.tenant:
            raise e  # the lane moved under us: refuse to evict blindly
        job = lane.tenant
        tdir = self.tenant_dir(job.tid)
        os.makedirs(tdir, exist_ok=True)
        evidence = None
        if e.evidence_path and os.path.isfile(e.evidence_path):
            evidence = os.path.join(tdir, "fault-evidence.json")
            shutil.move(e.evidence_path, evidence)
        sstep, sstate = stash
        host = {name: np.asarray(jax.device_get(sstate[name]))
                for name in names}
        healthy_tstep = lane.tenant_step(sstep)
        # revivable: persist the last health-checked state BEFORE the
        # lane is overwritten by the backfill
        self._write_tenant_snapshot(
            job, spec, {name: host[name][lane.idx] for name in names},
            healthy_tstep)
        self._publish(results, TenantResult(
            job.tid, "fault", healthy_tstep, tdir, evidence=evidence))
        rec.meta("campaign.evict", tenant=job.tid,
                 step=int(f.tenant_step), lane=lane.idx, slot=slot_idx,
                 rc=FAULT_RC, healthy_step=int(healthy_tstep),
                 evidence=evidence)
        log.warn(f"campaign: evicted tenant {job.tid} (lane {lane.idx}) "
                 f"after {e.rollbacks} rollback(s) at tenant step "
                 f"{f.tenant_step}; slot resumes from step {sstep}")
        return backfill(lane, sstep, dict(sstate))


# -- the sequential baseline ---------------------------------------------------


def run_sequential(jobs: Sequence[TenantJob], *,
                   devices: Optional[Sequence] = None, radius: int = 1,
                   chunk: int = 2,
                   cache: Optional[CompileCache] = None) -> dict:
    """Serve the same jobs one tenant at a time through the standard
    single-domain machinery (``DistributedDomain`` partitioned over ALL
    the given devices + ``make_jacobi_loop``): the honest baseline of
    ``campaign_batched_over_sequential``. One domain + compiled loop is
    reused per shape bucket (sequential serving amortizes compiles too —
    the ratio measures batching, not compilation); timing covers the
    stepping loop, and per-chunk per-step latencies feed the same
    p50/p99 statistics as the batched driver."""
    from ..api import DistributedDomain
    from ..ops.jacobi import make_jacobi_loop
    from ..parallel.exchange import shard_blocks
    from ..plan.ir import PlanConfig

    devices = list(devices) if devices is not None else jax.devices()
    cache = cache if cache is not None else CompileCache()
    rec = telemetry.get()
    results: Dict[str, TenantResult] = {}
    lat: List[float] = []
    cell_steps = 0
    wall = 0.0
    t0 = time.perf_counter()
    for j in jobs:
        if j.workload != "jacobi":
            raise NotImplementedError(
                f"run_sequential serves jacobi tenants only (tenant "
                f"{j.tid} is {j.workload!r}); the astaroth sequential "
                "baseline is a B=1 slot through the batched driver"
            )

    by_bucket: Dict[Tuple, List[TenantJob]] = {}
    order: List[Tuple] = []
    for j in jobs:
        b = j.bucket()
        if b not in by_bucket:
            by_bucket[b] = []
            order.append(b)
        by_bucket[b].append(j)

    for bucket in order:
        (size, dtype, _workload) = bucket
        x, y, z = size
        cells = x * y * z
        dd = DistributedDomain(x, y, z)
        dd.set_radius(radius)
        dd.set_devices(devices)
        h = dd.add_data(QUANTITY, dtype)
        dd.realize()
        sel = shard_blocks(sphere_sel((x, y, z)), dd.spec, dd.mesh)
        shape = dd.spec.stacked_shape_zyx()
        cfg = PlanConfig.make(Dim3(x, y, z), dd.spec.radius, [dtype],
                              len(devices), devices[0].platform)

        def loop_for(k):
            key = cache_key(cfg, workload="jacobi-sequential",
                            iters=int(k),
                            partition=[dd.spec.dim.x, dd.spec.dim.y,
                                       dd.spec.dim.z],
                            devices=[d.id for d in devices])
            return cache.get(
                key, lambda: make_jacobi_loop(dd.halo_exchange, k))

        for job in by_bucket[bucket]:
            dd.set_curr_global(h, tenant_init_field(job))
            c = dd.get_curr(h)
            n2 = jax.device_put(jnp.zeros(shape, dtype), dd.sharding())
            done = 0
            for k in chunk_plan(0, job.steps, chunk):
                loop = loop_for(k)
                t1 = time.perf_counter()
                c, n2 = loop(c, n2, sel)
                hard_sync(c)
                per = (time.perf_counter() - t1) / k
                done += k
                lat.append(per)
                cell_steps += k * cells
                wall += per * k
                rec.gauge("campaign.step_latency_s", per, phase="step",
                          unit="s", mode="sequential", iters=k)
            dd.set_curr(h, c)
            fin = np.ascontiguousarray(dd.get_curr_global(h))
            results[job.tid] = TenantResult(
                job.tid, "done", done, "", final=fin,
                finals={QUANTITY: fin})

    agg = cell_steps / wall / 1e6 if wall > 0 else 0.0
    return {
        "results": results,
        "tenants": len(jobs),
        "slots": 0,
        "cell_steps": cell_steps,
        "step_wall_s": wall,
        "total_wall_s": time.perf_counter() - t0,
        "aggregate_mcells_per_s": agg,
        "p50_step_s": percentile(lat, 50) if lat else float("nan"),
        "p99_step_s": percentile(lat, 99) if lat else float("nan"),
        "evicted": [],
        "cache": cache.stats(),
    }
