"""jacobi3d — 7-point Jacobi heat diffusion, weak-scaled.

TPU-native port of the reference's main demo app (reference:
bin/jacobi3d.cu): a hot and a cold sphere fixed in a periodic box, 6-neighbor
averaging, interior/exterior comm overlap, optional ParaView CSV dumps, and
a one-line CSV result:

  jacobi3d,<method>,<processes>,<devices>,<x>,<y>,<z>,<exchBytes>,<minIter>,<trimeanIter>

(reference prints per-method byte columns, bin/jacobi3d.cu:386-391; here the
single collective transport's logical bytes are printed once.)

Usage: python -m stencil_tpu.apps.jacobi3d --x 512 --y 512 --z 512 --iters 5
"""

from __future__ import annotations

import argparse
import os
import time
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..api import DistributedDomain
from ..geometry import Dim3, prime_factors
from ..obs import scopes, telemetry
from ..ops.jacobi import INIT_TEMP, make_jacobi_loop, make_jacobi_step, sphere_sel
from ..utils import timer
from ..parallel import Method
from ..parallel.exchange import shard_blocks
from ..parallel.mesh import sharded_full
from ..utils.statistics import Statistics
from ..utils.sync import hard_sync, timed_chunk
from ..utils import logging as log


def weak_scale(x: int, y: int, z: int, num_subdomains: int) -> Dim3:
    """Grow the domain to keep points/subdomain constant: multiply prime
    factors of N into the smallest axis (reference: bin/jacobi3d.cu:190-205)."""
    for pf in prime_factors(num_subdomains):
        if x <= y and x <= z:
            x *= pf
        elif y <= z:
            y *= pf
        else:
            z *= pf
    return Dim3(x, y, z)


def _on_tpu(devices) -> bool:
    """Whether the Pallas path is the one that runs: the tight-x layout and
    the temporal depth are chosen on TPUs only."""
    return all(d.platform == "tpu" for d in devices)


def run(
    x: int,
    y: int,
    z: int,
    iters: int = 5,
    overlap: bool = True,
    method: Method = Method.AXIS_COMPOSED,
    devices=None,
    weak: bool = True,
    paraview: bool = False,
    paraview_every: int = -1,
    prefix: str = "",
    partition=None,
    warmup: int = 1,
    chunk: Optional[int] = None,
    deep_halo: Optional[int] = None,
    multistep_rows: Optional[int] = None,
    metrics_dma: bool = False,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 0,
    ckpt_keep: int = 3,
    resume: bool = False,
    autotune: bool = False,
    plan_db: Optional[str] = None,
    health_every: int = 0,
    max_abs: Optional[float] = None,
    max_rollbacks: int = 3,
    rollback_backoff: float = 0.25,
    inject: Optional[str] = None,
    wire_dtype: Optional[str] = None,
    sentinel=None,
    status=None,
    replan: bool = False,
    replan_probe: bool = False,
) -> dict:
    """One jacobi3d campaign: realize the domain, warm up, run ``iters``
    steps in dispatches of ``chunk`` (default ``min(iters, 10)``), report.

    ``deep_halo`` is the depth K of the realized halos, and with it the
    temporal depth of the fused loop across chips. ``None`` (the default):
    the application picks. On TPUs, on a tight-x mesh with more than one
    block (x not split, an even split, one block a device), with overlap
    on and ``Method.AXIS_COMPOSED``, that is
    :func:`~stencil_tpu.ops.pallas_stencil.pick_temporal_depth` of the
    global size, the partition and ``chunk``: the deepest K <= ``chunk``
    whose multistep staging fits VMEM, a divisor of ``chunk`` where one
    fits, so that a dispatch is one radius-K exchange and one K-step pass
    at a time. Everywhere else it is 1, the layout every run had before.
    An integer (1 included) overrides the pick. What was chosen is the
    counter ``jacobi.temporal_depth`` (``chunk``, ``passes``,
    ``single_steps``, ``halo_zyx``, ``bound``)."""
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    # run() is covered by spans without a hole: realize, init, warmup
    # (build, compile or cache load, first call), steps
    rec = telemetry.get()
    end_realize = rec.open_span("jacobi.realize", phase="init")
    if (weak and n > 1 and partition is None
            and x % 128 == 0
            and _on_tpu(devices)):
        # TPU-first weak scaling: grow + split over z/y only
        # (geometry.decompose_zy) so every chip keeps the tight-x layout
        # and the mesh is a 2D ICI-friendly z x y grid; the reference's
        # smallest-axis weak_scale + 3-axis partition stays for CPU and
        # explicit partitions
        from ..geometry import decompose_zy

        d3 = decompose_zy(n)
        size = Dim3(x, y * d3.y, z * d3.z)
        partition = d3
    else:
        size = weak_scale(x, y, z, n) if weak else Dim3(x, y, z)

    dd = DistributedDomain(size.x, size.y, size.z)
    # the steps a dispatch runs: the temporal depth below is cut to it
    stepwise = paraview and paraview_every > 0
    if chunk is None:
        chunk = 1 if stepwise else min(iters, 10)
    chunk = min(chunk, iters)
    pdim = None
    if partition is not None:
        pdim = Dim3.of(partition)
    elif n == 1:
        pdim = Dim3(1, 1, 1)
    tight_x = (
        pdim is not None and pdim.x == 1 and pdim.flatten() == n
        and size.x % 128 == 0
        and size.y % pdim.y == 0 and size.z % pdim.z == 0
        # no in-kernel x wrap in the global AUTO_SPMD program
        and method != Method.AUTO_SPMD
        and not autotune  # the tuner may pick AUTO_SPMD, which cannot
                          # run the tight-x no-x-halo layout
        and _on_tpu(devices))
    # radius-k halos let the fused loop take the communication-avoiding
    # multistep on multi-block meshes (one radius-k exchange per k steps);
    # the workload stays radius-1 jacobi. The application picks k from what
    # it observes; an explicit deep_halo overrides the pick
    if deep_halo is not None:
        depth_bound = "explicit"
    elif tight_x and overlap and method == Method.AXIS_COMPOSED:
        from ..ops.pallas_stencil import pick_temporal_depth

        deep_halo, depth_bound = pick_temporal_depth(size, pdim, chunk)
    else:
        deep_halo, depth_bound = 1, "mesh"
    if tight_x:
        # tight-x layout: a single-BLOCK x axis wraps x in-kernel (lane
        # rolls), so no x halo columns are allocated — every slab DMA
        # sheds the px/nx lane padding (1.36x at 512^3, BASELINE.md round
        # 3). Multi-block y/z axes keep their inline halos and exchange
        # normally; their overlap shells take the roll-aware sweep. An
        # x-split, uneven, or oversubscribed partition keeps inline halos
        # everywhere (the Pallas fast path disengages there).
        from ..geometry import Radius

        dd.set_radius(Radius.constant(deep_halo).without_x())
    else:
        dd.set_radius(deep_halo)
    dd.set_methods(method)
    dd.set_devices(devices)
    if wire_dtype:
        dd.set_wire_dtype(wire_dtype)
    if partition is not None:
        dd.set_partition(partition)
    if autotune:
        # plan/ subsystem: choose (partition x method x batching) from the
        # DB or by static-rank + measured probes; an explicit --partition
        # or tight-x radius pin above still wins (realize() warns)
        dd.enable_autotune(db_path=plan_db)
    h = dd.add_data("temperature", "float32")
    dd.realize()
    end_realize()
    rad = dd.spec.radius
    # once a run(): the depth the halos were realized for, and how a
    # dispatch of `chunk` steps divides into deep-halo passes at it
    passes, single_steps = (divmod(chunk, deep_halo) if deep_halo >= 2
                            else (0, chunk))
    rec.counter("jacobi.temporal_depth", value=deep_halo, phase="init",
                chunk=chunk, passes=passes, single_steps=single_steps,
                halo_zyx=[rad.z(1), rad.y(1), rad.x(1)], bound=depth_bound)
    if autotune:
        method = dd._method  # the tuned method labels the CSV row

    # init: uniform lukewarm field (reference: bin/jacobi3d.cu:18-27)
    with rec.span("jacobi.init", phase="init"):
        sharding = dd.sharding()
        shape = dd.spec.stacked_shape_zyx()
        dd.set_curr(h, sharded_full(shape, INIT_TEMP, jnp.float32, sharding)())
        sel = shard_blocks(sphere_sel(size), dd.spec, dd.mesh)

    if paraview:
        dd.write_paraview(prefix + "jacobi3d_init")

    # checkpoint/restart (ckpt/): resume replaces the fresh init with the
    # newest durable snapshot's state — elastically, so a run revived on a
    # different partition/device count continues the same campaign
    start = 0
    if ckpt_dir and resume:
        from ._bench_common import resume_from_checkpoint

        start = resume_from_checkpoint(dd, ckpt_dir, iters)
    kill_after = int(os.environ.get("STENCIL_CKPT_KILL_AFTER_SAVE", "-1") or -1)

    def save_ckpt(step: int, state) -> None:
        dd.set_curr(h, state)
        dd.save_checkpoint(ckpt_dir, step, keep=ckpt_keep)
        if 0 <= kill_after <= step:
            # injected-kill hook (CI checkpoint gate / tests): die hard
            # right after this snapshot is durable — the revival must
            # continue from it, not from step 0
            dd.finish_checkpoints()
            log.warn(f"STENCIL_CKPT_KILL_AFTER_SAVE: dying after step {step}")
            os._exit(17)

    curr, nxt = dd.get_curr(h), dd.get_next(h)
    # the halos' depth pins the temporal depth at k=deep_halo on EVERY
    # device count — a single-block run would otherwise take the full
    # default depth (no radius bound) and poison weak-scaling columns
    # against radius-capped N-chip runs (ADVICE r3)
    tk = deep_halo if deep_halo >= 2 else None

    loops = {}  # iters-per-call -> compiled fn

    def get_loop(k: int):
        if k not in loops:
            loops[k] = (
                make_jacobi_loop(dd.halo_exchange, k, overlap=overlap,
                                 temporal_k=tk,
                                 multistep_rows=multistep_rows)
                if k > 1
                else make_jacobi_step(dd.halo_exchange, overlap=overlap)
            )
        return loops[k]

    # Self-healing layer (fault/): the periodic fused health check, the
    # injection schedule, and the rollback policy the guarded loop runs
    # under. All default OFF — the step-loop programs are identical
    # either way (the guard is a separate compiled reduction; pinned by
    # tests/test_fault_health.py).
    from ..fault import (FaultPlan, HealthGuard, RecoveryPolicy, chunk_plan,
                         run_guarded)

    guard = (HealthGuard(every=health_every, max_abs=max_abs)
             if health_every > 0 else None)
    injector = FaultPlan.from_spec(inject)

    # The exact fused-chunk sizes the measured loop will dispatch
    # (checkpoint / health-check boundaries clamp them; injections land
    # at their exact step): ONE schedule drives both warmup and the timed
    # loop, so warmup compiles precisely what runs and no XLA compile can
    # land inside a timed region.
    def plan_fn(s: int):
        return chunk_plan(
            s, iters, chunk,
            every=(ckpt_every if (ckpt_dir and ckpt_every > 0) else 0,
                   health_every if guard is not None else 0),
            at=injector.steps() if injector is not None else (),
        )

    plan = plan_fn(start)

    with rec.span("jacobi.warmup", phase="compile", iters=warmup * chunk):
        if ckpt_dir:
            # checkpointed runs are step-exact by contract (save at k,
            # resume, continue to n == uninterrupted n): warm the compile
            # caches on throwaway copies so warmup never advances the
            # state (the loops donate their inputs, so fresh buffers are
            # needed anyway) — one throwaway call per distinct chunk size
            # in the plan
            if warmup:
                for k in dict.fromkeys(plan):
                    get_loop(k)(curr + 0, nxt + 0, sel)
                hard_sync(curr)
        else:
            # benchmark path: warmup ADVANCES the state (content is
            # irrelevant without checkpoints), so only the main chunk
            # size is warmed — tail/boundary sizes compile in the timed
            # region exactly as they always did
            loop = get_loop(chunk)
            for _ in range(warmup):  # compile + warm caches, excluded from timing
                curr, nxt = loop(curr, nxt, sel)
            if warmup:
                hard_sync(curr)

    end_steps = rec.open_span("jacobi.steps", phase="step")
    # Iterations run in fused chunks: one dispatch + one hard sync per chunk
    # (utils/sync.py). The per-iteration statistic is each chunk's mean,
    # trimean'd over chunks like the reference's per-iter times
    # (bin/jacobi3d.cu:370-372). A short final chunk keeps the
    # total at exactly `iters`. The loop itself runs under the fault/
    # recovery engine: per chunk, step -> inject -> health check ->
    # checkpoint (the check precedes the save, so a poisoned state is
    # never persisted), and a NumericalFault rolls back to the newest
    # valid snapshot with exponential backoff.
    iter_time = Statistics()

    marks = None

    def step_fn(st, k):
        nonlocal nxt, marks
        (c, nxt), marks = timed_chunk(
            scopes.JACOBI_LOOP if k > 1 else scopes.JACOBI_STEP, get_loop(k),
            st["temperature"], nxt, sel)
        return {"temperature": c}

    def on_chunk(st, k, per, done_now):
        iter_time.insert(per)
        rec.chunk_span("jacobi.iter", marks, k, per=per)
        if stepwise and done_now % paraview_every == 0:
            dd.set_curr(h, st["temperature"])
            dd.write_paraview(f"{prefix}jacobi3d_{done_now}")

    save_fn = restore_fn = quarantine_fn = flush_fn = None
    if ckpt_dir:
        if ckpt_every > 0:
            save_fn = lambda s, st: save_ckpt(s, st["temperature"])  # noqa: E731
        flush_fn = dd.flush_checkpoints

        def restore_fn():
            s = dd.restore_checkpoint(ckpt_dir)
            if s is None:
                return None
            return s, {"temperature": dd.get_curr(h)}

        def quarantine_fn(s):
            from ..ckpt import quarantine_snapshot, snapshot_name

            quarantine_snapshot(ckpt_dir, snapshot_name(s),
                                reason="restored state failed health check")

    # The mid-run plan hot-swap (ROADMAP #6, the half PR 12's sentinel
    # was waiting for): when the live sentinel fires replan.requested,
    # the controller re-probes the autotuner between chunks and installs
    # the winning compiled plan via DistributedDomain.replan — the
    # in-memory elastic reshard, bit-identical by construction. Needs the
    # sentinel (the trigger) and a full-radius layout (the tight-x pin
    # realizes no x halos, which only the pinned partition can run).
    controller = None
    if replan and sentinel is None:
        log.warn("--replan needs --live-sentinel (replan.requested is "
                 "the trigger); ignoring")
    elif replan and tight_x:
        log.warn("--replan is unavailable under the tight-x no-x-halo "
                 "layout (a retuned x-split partition could not realize "
                 "it); ignoring")
    elif replan:
        from ..parallel.topology import link_cost_matrix
        from ..plan.ir import PlanChoice, PlanConfig
        from ..plan.replan import ReplanController

        def retune_fn():
            from ..plan.autotune import autotune as _plan_autotune

            res = _plan_autotune(
                dd.size, dd.radius, list(dd._dtypes), devices=devices,
                db_path=plan_db, probe=replan_probe, force=True,
            )
            return res.choice

        def apply_replan(choice, st):
            nonlocal sel, nxt
            dd.set_curr(h, st["temperature"])
            dd.replan(choice)
            loops.clear()  # the old plan's compiled loops are stale
            sel = shard_blocks(sphere_sel(size), dd.spec, dd.mesh)
            nxt = dd.get_next(h)
            return {"temperature": dd.get_curr(h)}

        controller = ReplanController(
            retune_fn, apply_replan, sentinel=sentinel,
            current_choice=PlanChoice.from_json(dd.plan_meta()["choice"]),
            config=PlanConfig.make(dd.size, dd.radius, list(dd._dtypes),
                                   n, devices[0].platform),
            link_costs=link_cost_matrix(devices),
        )
        sentinel.on_replan = controller.request

    loop_t0 = time.perf_counter()
    state, done = run_guarded(
        {"temperature": curr},
        start=start, iters=iters, plan_fn=plan_fn, step_fn=step_fn,
        guard=guard, injector=injector,
        policy=RecoveryPolicy(max_rollbacks=max_rollbacks,
                              backoff_s=rollback_backoff),
        save_fn=save_fn, ckpt_every=ckpt_every, restore_fn=restore_fn,
        quarantine_fn=quarantine_fn, flush_fn=flush_fn, on_chunk=on_chunk,
        spec=dd.spec, ckpt_dir=ckpt_dir, app="jacobi3d",
        sentinel=sentinel, status=status, replan=controller,
    )
    # whole-loop wall clock, INCLUDING what the per-chunk spans exclude
    # (health checks, checkpoint saves, injected faults, backoff and
    # rollback recovery) — the ledger gate's wall-level regression leg
    # (scripts/ci_perf_gate.py trips it with an injected slow: fault)
    loop_wall_s = time.perf_counter() - loop_t0
    curr = state["temperature"]
    if controller is not None and controller.swaps:
        # the CSV row and byte accounting must describe the plan that
        # FINISHED the run, not the one it started on
        method = dd._method
    if ckpt_dir:
        if done > start or start == 0:
            # the final state is always durable (step == iters), so a
            # revived campaign that already finished resumes directly to
            # the report
            save_ckpt(iters, curr)
        # resumed past the end without stepping: the durable snapshot
        # already covers (and may EXCEED) this run's target — re-labeling
        # it as step `iters` would corrupt the campaign's step accounting
        dd.finish_checkpoints()
    if rec.enabled:
        # per-phase split + the compiled programs' static truth. The step
        # fuses exchange+compute, so the exchange share is measured as a
        # standalone fused loop on the same state (halo exchange is
        # idempotent on exchanged data — the astaroth exchElapsed idiom);
        # the census pins the exact on-wire bytes of one exchange.
        itemsizes = [jnp.dtype(jnp.float32).itemsize]
        telemetry.record_exchange_truth(
            dd.halo_exchange, {h.idx: curr}, itemsizes)
        n_ex = max(1, min(chunk, 10))
        exch_loop = dd.halo_exchange.make_loop(n_ex)
        st = {h.idx: curr}
        with rec.span("jacobi.exchange_warmup", phase="compile"):
            st = exch_loop(st)
            hard_sync(st)
        # slow@ injections scheduled PAST the step loop land inside the
        # timed exchange window below (steps iters+1..iters+3, one per
        # sample): `--inject slow@{iters+k}:seconds=S` inflates exactly
        # one measured sample — the drift sentinel's trip-proof knob
        # (scripts/ci_attrib_gate.py). Only slow faults fire here; state
        # corruption stays confined to the guarded step loop.
        slow_tail = None
        if injector is not None:
            tail = [i for i in injector.injections
                    if i.kind == "slow" and i.step > iters]
            if tail:
                slow_tail = FaultPlan(tail, seed=injector.seed)
        exch_samples = []

        def exchange(st, i):
            st = exch_loop(st)
            return st if slow_tail is None else slow_tail.fire_due(
                st, iters + i, iters + i + 1)

        for i in range(3):
            st, marks = timed_chunk(scopes.EXCHANGE_LOOP, exchange, st, i)
            exch_samples.append(marks.wall_s / n_ex)
            rec.chunk_span("jacobi.exchange", marks, n_ex, phase="exchange")
        curr = st[h.idx]
        # per-phase attribution: pair the cost model's prediction for the
        # realized plan with the measured exchange share — the autotuner's
        # calibration (fitted, when the plan DB carries one) prices it, so
        # the records judge the constants that actually ranked this plan
        from ..obs import attribution
        from ..plan.ir import PlanChoice, PlanConfig
        from .machine_info import fabric_fingerprint

        pm = dd.plan_meta()
        plan_choice = PlanChoice.from_json(pm["choice"])
        tuned = dd.autotune_result
        attribution.attribute_and_judge(
            rec, PlanConfig.from_json(pm["key"]), plan_choice,
            exch_samples, phase="jacobi.exchange",
            calibration=tuned.calibration if tuned is not None else None,
            kernel_variant=plan_choice.kernel_variant,
            fabric=fabric_fingerprint(devices=devices))
        # the run's plan identity: which exact PlanChoice produced these
        # numbers, under which calibration — the join key between a
        # metrics file, the plan DB, and a fitted calibration row
        rec.meta("plan.fingerprint",
                 fingerprint=plan_choice.fingerprint(),
                 choice=plan_choice.label(),
                 calibration=(tuned.calibration_provenance
                              if tuned is not None else "modeled(default)"))
        if metrics_dma:
            # static per-kernel HBM DMA truth from the compiled Mosaic
            # artifact (utils/mosaic_traffic) — only meaningful where the
            # Pallas fast path engages (a TPU-lowered kernel exists)
            from ..ops.jacobi import _want_pallas

            if _want_pallas(dd.halo_exchange, None):
                # rebuild EXACTLY the measured configuration (same temporal
                # depth pin as get_loop) — the DMA truth must describe the
                # kernel that actually ran
                telemetry.record_dma_traffic(
                    lambda: (
                        make_jacobi_loop(
                            dd.halo_exchange, chunk, overlap=overlap,
                            use_pallas=True,
                            temporal_k=tk,
                            multistep_rows=multistep_rows),
                        (curr, nxt, sel),
                    ),
                )
            else:
                rec.meta("dma.skipped",
                         reason="pallas fast path not engaged")
    dd.set_curr(h, curr)
    dd.set_next(h, nxt)
    end_steps()

    if paraview:
        dd.write_paraview(prefix + "jacobi3d_final")

    cells = size.flatten()
    if iter_time.count() == 0:
        # resumed at/past the target step: nothing left to time (the inf
        # placeholder keeps downstream ratios at 0, and gauges that would
        # serialize as non-strict JSON are skipped below)
        log.info(f"resume found step {start} >= iters {iters}; no timed work")
        iter_time.insert(float("inf"))
    trimean = iter_time.trimean()
    result = {
        "app": "jacobi3d",
        "method": method.value,
        "processes": jax.process_count(),
        "devices": n,
        "x": size.x,
        "y": size.y,
        "z": size.z,
        "exchange_bytes": dd.exchange_bytes_for_method(method),
        "iter_min_s": iter_time.min(),
        "iter_trimean_s": trimean,
        "mcells_per_s": cells / trimean / 1e6,
        "mcells_per_s_per_dev": cells / trimean / 1e6 / n,
        "overlap": overlap,
        "domain": dd,
        "handle": h,
    }
    if rec.enabled:
        rec.gauge("jacobi.loop_wall_s", loop_wall_s, phase="step", unit="s")
        rec.gauge("jacobi.mcells_per_s", result["mcells_per_s"], phase="step")
        rec.gauge("jacobi.mcells_per_s_per_dev",
                  result["mcells_per_s_per_dev"], phase="step")
        if np.isfinite(trimean):  # inf would serialize as non-strict JSON
            rec.gauge("jacobi.iter_trimean_s", trimean, phase="step",
                      unit="s")
        rec.counter("jacobi.exchange_bytes", bytes=result["exchange_bytes"],
                    phase="exchange", method=method.value)
    return result


def csv_row(r: dict) -> str:
    return (
        f"jacobi3d,{r['method']},{r['processes']},{r['devices']},"
        f"{r['x']},{r['y']},{r['z']},{r['exchange_bytes']},"
        f"{r['iter_min_s']:.6f},{r['iter_trimean_s']:.6f}"
    )


def main(argv: Optional[list] = None) -> int:
    from ..parallel.distributed import maybe_init_from_env
    maybe_init_from_env()
    from ..utils.jax_cache import configure_compile_cache
    configure_compile_cache()
    p = argparse.ArgumentParser(description="3D Jacobi heat diffusion (TPU)")
    p.add_argument("--x", type=int, default=512)
    p.add_argument("--y", type=int, default=512)
    p.add_argument("--z", type=int, default=512)
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--no-overlap", action="store_true", help="disable interior/exterior overlap")
    p.add_argument("--direct26", action="store_true", help="use 26 per-direction permutes")
    p.add_argument("--method", choices=[m.value for m in Method], default=None,
                   help="exchange strategy (auto-spmd lets the SPMD "
                        "partitioner synthesize the halo collectives; "
                        "overrides --direct26)")
    p.add_argument("--no-weak", action="store_true", help="fixed total domain (strong)")
    p.add_argument("--paraview", action="store_true")
    p.add_argument("--paraview-every", type=int, default=-1,
                   help="with --paraview, also dump every N iterations")
    p.add_argument("--checkpoint-period", type=int, default=None,
                   help="DEPRECATED alias of --paraview-every (it was always "
                        "a ParaView dump cadence; real checkpointing is "
                        "--ckpt-dir/--ckpt-every)")
    p.add_argument("--ckpt-dir", type=str, default="",
                   help="write elastic checkpoint snapshots here (ckpt/ "
                        "subsystem: sharded npz + manifest, crash-safe)")
    p.add_argument("--ckpt-every", type=int, default=0,
                   help="checkpoint every N iterations (0 = only the final "
                        "state; needs --ckpt-dir)")
    p.add_argument("--ckpt-keep", type=int, default=3,
                   help="retention: keep the newest N snapshots")
    p.add_argument("--resume", action="store_true",
                   help="resume from the newest valid snapshot under "
                        "--ckpt-dir when one exists (fresh start otherwise)")
    p.add_argument("--health-every", type=int, default=0,
                   help="numerical health guard (fault/): one fused "
                        "isfinite reduction over the state every N steps; "
                        "a fault rolls back to the newest valid snapshot "
                        "(0 = off; the step-loop HLO is unchanged)")
    p.add_argument("--max-abs", type=float, default=0.0,
                   help="with --health-every, also fault when any "
                        "quantity's max|u| exceeds this divergence "
                        "ceiling (0 = no ceiling)")
    p.add_argument("--max-rollbacks", type=int, default=3,
                   help="rollbacks allowed per faulting step before the "
                        "run aborts with rc 43 + a fault-evidence.json "
                        "bundle")
    p.add_argument("--rollback-backoff", type=float, default=0.25,
                   help="first-retry backoff seconds (doubles per repeated "
                        "fault at the same step)")
    p.add_argument("--inject", type=str, default="",
                   help="deterministic fault injection spec, e.g. "
                        "'nan@3,crash@5:rc=7' (see fault/inject.py; "
                        "default: the STENCIL_FAULT_INJECT env var)")
    p.add_argument("--autotune", action="store_true",
                   help="choose the exchange plan (partition x method x "
                        "quantity batching) via the plan/ autotuner: plan-DB "
                        "hit replays with zero probes, miss static-ranks + "
                        "probes and persists the winner to --plan-db")
    p.add_argument("--plan-db", type=str, default="",
                   help="on-disk plan DB (JSON) for --autotune; also "
                        "inspectable via apps/plan_tool.py")
    p.add_argument("--replan", action="store_true",
                   help="mid-run plan hot-swap (needs --live-sentinel): "
                        "on replan.requested the autotuner re-tunes "
                        "between chunks and the winning compiled plan is "
                        "installed in place (replan.applied/rejected in "
                        "the metrics; state is bit-identical across the "
                        "swap)")
    p.add_argument("--replan-probe", action="store_true",
                   help="with --replan, refine the re-tune with measured "
                        "probes (default: static ranking only, so the "
                        "swap stays cheap)")
    p.add_argument("--wire-dtype", type=str, default="",
                   help="on-the-wire halo compression (bfloat16 or the fp8 "
                        "tier float8_e4m3fn): wire-crossing "
                        "exchange carriers narrow to this dtype (LOSSY — "
                        "halos round to the wire precision; "
                        "bench_exchange --wire-ab measures the error)")
    p.add_argument("--prefix", type=str, default="")
    p.add_argument("--cpu", type=int, default=0, help="force N virtual CPU devices")
    p.add_argument("--deep-halo", type=int, default=None,
                   help="override the halo depth K the application picks: "
                        "radius-K halos let the fused loop advance K steps "
                        "per exchange on multi-block meshes "
                        "(communication-avoiding temporal blocking). "
                        "Default: on TPUs with a tight-x multi-block mesh "
                        "and overlap, the deepest K <= the dispatch's steps "
                        "whose staging fits VMEM, a divisor of them where "
                        "one fits (ops/pallas_stencil.pick_temporal_depth); "
                        "1 everywhere else")
    p.add_argument("--multistep-rows", type=int, default=None,
                   help="force the temporal multistep's row-strip height "
                        "(default: automatic — full planes while they reach "
                        "the depth cap, row-tiled staging beyond; the "
                        "probing knob for the 768^3 depth regime)")
    from ._bench_common import (add_live_flags, add_metrics_flags,
                                canonicalize_live_config, finish_live,
                                make_live, start_metrics)
    add_metrics_flags(p, dma=True)
    add_live_flags(p)
    args = p.parse_args(argv)
    try:
        canonicalize_live_config(args)
    except (OSError, ValueError) as e:
        p.error(f"bad --live-config: {e}")

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        # must happen before backend init to actually create N devices
        jax.config.update("jax_num_cpu_devices", args.cpu)
    rec = start_metrics(args, "jacobi3d")
    sentinel, status = make_live(args, rec, "jacobi3d")

    paraview_every = args.paraview_every
    if args.checkpoint_period is not None:
        log.warn("--checkpoint-period is deprecated (it names a ParaView "
                 "dump cadence, not a checkpoint): use --paraview-every; "
                 "checkpoints are --ckpt-dir/--ckpt-every")
        if paraview_every < 0:
            paraview_every = args.checkpoint_period

    from ..fault import FAULT_RC, RecoveryExhausted

    try:
        r = run(
            args.x,
            args.y,
            args.z,
            iters=args.iters,
            overlap=not args.no_overlap,
            method=Method(args.method) if args.method
            else (Method.DIRECT26 if args.direct26 else Method.AXIS_COMPOSED),
            devices=jax.devices()[: args.cpu] if args.cpu else None,
            weak=not args.no_weak,
            paraview=args.paraview,
            paraview_every=paraview_every,
            prefix=args.prefix,
            deep_halo=args.deep_halo,
            multistep_rows=args.multistep_rows,
            metrics_dma=args.metrics_dma and rec.enabled,
            ckpt_dir=args.ckpt_dir or None,
            ckpt_every=args.ckpt_every,
            ckpt_keep=args.ckpt_keep,
            resume=args.resume,
            autotune=args.autotune,
            plan_db=args.plan_db or None,
            health_every=args.health_every,
            max_abs=args.max_abs or None,
            max_rollbacks=args.max_rollbacks,
            rollback_backoff=args.rollback_backoff,
            inject=args.inject or None,
            wire_dtype=args.wire_dtype or None,
            sentinel=sentinel,
            status=status,
            replan=args.replan,
            replan_probe=args.replan_probe,
        )
    except RecoveryExhausted as e:
        # the loud-degrade contract: evidence bundle on disk, the distinct
        # rc for the watchdog/bench ladder, metrics flushed for archiving
        log.error(f"jacobi3d: {e}")
        finish_live(rec, sentinel, status, outcome="fault")
        if rec.enabled:
            rec.record_timer_buckets()
            rec.close()
        return FAULT_RC
    finish_live(rec, sentinel, status, outcome="done")
    print(csv_row(r))
    log.info(f"mcells/s = {r['mcells_per_s']:.1f} ({r['mcells_per_s_per_dev']:.1f}/device)")
    log.info(timer.report())
    if rec.enabled:
        rec.record_timer_buckets()
        rec.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
