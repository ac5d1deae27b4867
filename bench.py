#!/usr/bin/env python
"""Headline benchmark: jacobi3d Mcells/s/chip at 512^3 (reference default
size, bin/jacobi3d.cu:100-102) plus halo-exchange GB/s and the astaroth
flagship details, printed as ONE JSON line — when, and only when, every
leg ran on an accelerator.

The PARENT process never initializes a JAX backend — it does not even
import the ``stencil_tpu`` package (whose ``__init__`` imports jax); the
revival watcher, ``stencil_tpu/obs/watchdog.py``, is pure stdlib and loaded
by FILE PATH. A chip belongs to one process at a time, so all measurement
runs in ONE child subprocess at a time, supervised on two layered deadlines
(total budget + telemetry heartbeat staleness — a wedged child is killed as
a STALL long before the budget). The ladder is: accelerator child, one
retry with backoff, then a NON-ZERO exit with the attempt report on stderr.
There is no CPU rung and no static payload: a run that found no
accelerator, or in which any leg raised, prints no result line and fails.

``--child cpu`` is reachable only when asked for explicitly (the CPU
tests and CI rehearse the legs with it, at small sizes): its payload says
``"platform": "cpu"`` and every number in it carries a ``cpu_`` prefix, so
a CPU number never appears under a device metric's name.

Children emit heartbeats through stencil_tpu.obs.telemetry (a background
beat thread plus per-leg beats); set STENCIL_BENCH_LOG_DIR to archive
per-attempt child logs, STENCIL_BENCH_HEARTBEAT_S to tune the stall
deadline, and STENCIL_BENCH_METRICS_OUT to also get the children's
metrics JSONL (same schema as the apps' --metrics-out).

vs_baseline for the headline compares against this repo's recorded ROUND-1
TPU number (the reference publishes no absolute numbers — BASELINE.md §1),
so the driver sees the cumulative speedup (~23x as of round 3). The
exchange ratio compares like-for-like against the ROUND-2 Pallas self-fill
number measured with this exact leg (round 1's 2.18 GB/s was the
pre-Pallas slab path; dividing by it conflated a kernel rewrite with a
methodology change — VERDICT r3 weak #6).
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

# Recorded TPU v5e single-chip numbers (BASELINE.md "Recorded numbers").
BASELINE_MCELLS_PER_S_PER_CHIP = 3394.8  # round 1, jacobi3d 512^3
BASELINE_EXCHANGE_GB_S = 15.75  # round 2, Pallas self-fill, same leg as below

# The one JSON line the driver reads is marked so the parent can find it in
# the child's stdout regardless of logging noise around it.
SENTINEL = "STENCIL_BENCH_JSON: "

# child exit codes the parent tells apart from a crash: the accel child
# found no accelerator (retrying cannot help), or legs raised (the
# remaining legs ran; the run still fails and prints no result line)
NO_ACCELERATOR_RC = 3
LEGS_FAILED_RC = 4


# ---------------------------------------------------------------- child side


def _child_main(mode: str, resume: bool = False) -> int:
    """Measure and print SENTINEL+JSON. ``mode``: 'accel' | 'cpu'.

    ``resume`` is what the parent's Revival ladder passes on the retry:
    with STENCIL_BENCH_CKPT_DIR set, the jacobi headline leg checkpoints
    per chunk and a revived child continues from its last durable step
    instead of step 0."""
    hang = float(os.environ.get("STENCIL_BENCH_SELFTEST_HANG_S", "0") or 0)
    if hang and mode == "accel":
        # self-test hook (tests/test_driver_hardening.py): simulate the
        # wedged backend init the parent must be able to time out
        time.sleep(hang)

    import jax

    if mode == "cpu":
        # the explicit CPU rehearsal: 8 virtual devices so the batched-
        # exchange leg runs on a real 2x2x2 CPU mesh; the other legs pin
        # devices[:1] and are unaffected
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 8)
    elif jax.devices()[0].platform == "cpu":
        print("[bench:accel] no accelerator: jax.devices()[0].platform is "
              "'cpu' (the CPU rehearsal is `bench.py --child cpu`)",
              file=sys.stderr, flush=True)
        return NO_ACCELERATOR_RC

    from stencil_tpu.utils.jax_cache import configure_compile_cache

    configure_compile_cache()

    # telemetry: heartbeats for the supervising watchdog (no-op unsupervised)
    # + optional metrics JSONL; configure BEFORE any backend init so a
    # wedged init is already covered by the beat thread
    from stencil_tpu.obs import telemetry

    rec = telemetry.configure(
        metrics_out=os.environ.get("STENCIL_BENCH_METRICS_OUT") or None,
        app="bench",
    )

    budget_s = float(os.environ.get("STENCIL_BENCH_LEG_BUDGET_S", "840"))
    t0 = time.time()
    errors: dict[str, str] = {}

    def failed(name: str, e: Exception) -> None:
        """A leg raised: keep the evidence, let the remaining legs run —
        the run exits LEGS_FAILED_RC at the end."""
        errors[name] = f"{type(e).__name__}: {e}"[:400]
        print(f"[bench:{mode}] leg {name} FAILED:\n"
              f"{traceback.format_exc()}", file=sys.stderr, flush=True)

    def leg(name: str) -> bool:
        left = budget_s - (time.time() - t0)
        rec.heartbeat()
        print(
            f"[bench:{mode}] {name}: {time.time()-t0:.0f}s elapsed, "
            f"{left:.0f}s budget left",
            file=sys.stderr,
            flush=True,
        )
        return left > 0

    # sizes follow the MODE that was asked for, never the platform found
    on_accel = mode == "accel"
    n = 512 if on_accel else 128
    # large fused chunks amortize the per-dispatch host cost (the
    # reference's >=30-iteration timing loops, bin/exchange_weak.cu:168-177,
    # served the same purpose for CUDA launch/MPI overhead)
    chunk = 360 if on_accel else 3

    from stencil_tpu.apps.jacobi3d import run
    from stencil_tpu.fault import FAULT_RC, RecoveryExhausted
    from stencil_tpu.utils.statistics import Statistics
    from stencil_tpu.utils.sync import hard_sync

    # headline jacobi: REQUIRED — if this dies the child fails. With a
    # checkpoint dir, the leg is durable per
    # chunk and a revived child (--resume) continues mid-campaign. The
    # health guard checks the field once per fused chunk: an in-band NaN
    # burst (a bad device, a corrupted payload) rolls back to the last
    # durable chunk instead of poisoning the headline number, and a run
    # that cannot recover exits the DISTINCT fault rc so the parent's
    # ladder reports "numerics broken", not a generic crash.
    ckpt_dir = os.environ.get("STENCIL_BENCH_CKPT_DIR") or None
    if ckpt_dir:
        # per-config subdir: a 128^3 CPU rehearsal must never repoint
        # LATEST or prune away the 512^3 accel campaign's snapshots
        ckpt_dir = os.path.join(ckpt_dir, f"jacobi{n}")
    leg("jacobi3d headline")
    try:
        r = run(n, n, n, iters=3 * chunk, weak=False,
                devices=jax.devices()[:1],
                warmup=1, chunk=chunk,
                ckpt_dir=ckpt_dir, ckpt_every=chunk if ckpt_dir else 0,
                resume=resume and ckpt_dir is not None,
                health_every=chunk)
        import math

        if ckpt_dir and not math.isfinite(r["iter_trimean_s"]):
            # the previous child finished this leg (snapshot at step==iters)
            # but died before delivering the sentinel, so its timings are
            # gone: a resume has nothing to time and would report a 0.0
            # headline — re-measure fresh instead
            print(f"[bench:{mode}] resume found the jacobi leg complete; "
                  "re-measuring", file=sys.stderr, flush=True)
            r = run(n, n, n, iters=3 * chunk, weak=False,
                    devices=jax.devices()[:1], warmup=1, chunk=chunk,
                    ckpt_dir=ckpt_dir, ckpt_every=chunk, resume=False,
                    health_every=chunk)
    except RecoveryExhausted as e:
        print(f"[bench:{mode}] headline leg faulted beyond recovery: {e}",
              file=sys.stderr, flush=True)
        return FAULT_RC
    mcells = r["mcells_per_s_per_dev"]

    # exchange benchmark: radius-3, 4 float quantities (exchange_weak config,
    # bin/exchange_weak.cu:49-51,143), fused loop of `chunk` exchanges.
    # Timed twice: the manual AXIS_COMPOSED transport and the AUTO_SPMD
    # strategy whose collectives XLA's partitioner synthesizes — the
    # tracked manual-vs-auto leg of the bench_mpi_pack ablation
    # (reference: bin/bench_mpi_pack.cu:18-80; BASELINE.md "auto-SPMD").
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.parallel import HaloExchange, Method, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks
    import numpy as np

    def _exchange_leg(method, nq: int = 4, ndev: int = 1, nb: int = None,
                      batched: bool = True, dim: Dim3 = None,
                      placement=None, hierarchy=None) -> float:
        nb = nb if nb is not None else n
        if dim is None:
            dim = Dim3(2, 2, 2) if ndev == 8 else Dim3(1, 1, 1)
        spec = GridSpec(Dim3(nb, nb, nb), dim, Radius.constant(3))
        devs = jax.devices()[:ndev]
        if placement is not None:
            # topology-aware block placement: mesh position i hosted by
            # devs[placement[i]] (the PlanChoice.placement convention)
            devs = [devs[placement[i]] for i in range(len(devs))]
        mesh = grid_mesh(spec.dim, devs, ordered=placement is not None)
        ex = HaloExchange(spec, mesh, method, batch_quantities=batched,
                          hierarchy=hierarchy)
        loop = ex.make_loop(chunk)
        state = {
            i: shard_blocks(np.zeros((nb, nb, nb), np.float32), spec, mesh)
            for i in range(nq)
        }
        state = loop(state)  # compile + warm
        hard_sync(state)
        st = Statistics()
        for _ in range(3):
            t1 = time.perf_counter()
            state = loop(state)
            hard_sync(state)
            st.insert((time.perf_counter() - t1) / chunk)
        return ex.bytes_logical([4] * nq) / st.trimean() / 1e9

    ex_gb_s = 0.0
    if leg("halo exchange"):
        try:
            ex_gb_s = _exchange_leg(Method.AXIS_COMPOSED)
        except Exception as e:
            failed("exchange", e)
    ex_auto_gb_s = 0.0
    if leg("halo exchange (auto-spmd)"):
        try:
            ex_auto_gb_s = _exchange_leg(Method.AUTO_SPMD)
        except Exception as e:
            failed("exchange_auto", e)

    # kernel-initiated remote-DMA exchange (ISSUE 10 / ROADMAP #2): the
    # fourth transport vs the composed baseline at the same config, on an
    # 8-device mesh so phases actually cross the wire. On TPU this times
    # the Pallas carrier kernels (pltpu.make_async_remote_copy — the
    # tx_colocated analogue, 0 ppermutes); on the CPU child it times the
    # host-orchestrated emulation, which is a CORRECTNESS vehicle — the
    # ratio is expected < 1 there and only the TPU number is the claim.
    ex_rd_gb_s = 0.0
    ex_rd_base_gb_s = 0.0
    if leg("halo exchange (remote-dma)"):
        try:
            rd = dict(nq=4, ndev=8 if len(jax.devices()) >= 8 else 1,
                      nb=min(n, 128))
            ex_rd_gb_s = _exchange_leg(Method.REMOTE_DMA, **rd)
            ex_rd_base_gb_s = _exchange_leg(Method.AXIS_COMPOSED, **rd)
        except Exception as e:
            failed("exchange_remote_dma", e)

    # fused compute+exchange jacobi (ROADMAP #5): the fused REMOTE_DMA
    # step — interior compute overlapping the kernel-initiated copies —
    # vs the serialized remote-dma step (exchange dispatch then sweep)
    # at 128^3 on the 8-device mesh. CPU-emulation caveat, exactly like
    # exchange_remote_dma_over_composed above: on the CPU child both
    # legs run the host-orchestrated schedule, so the ratio there prices
    # host orchestration, not ICI overlap — only the TPU mega-kernel
    # number carries the ROADMAP-5 claim. Ledger ingest auto-appends
    # every numeric key below via STENCIL_BENCH_LEDGER.
    jac_fused_mc = 0.0
    jac_rd_mc = 0.0
    if leg("jacobi fused-over-remote-dma (128^3, 8-dev)"):
        try:
            import jax.numpy as jnp

            from stencil_tpu.ops.jacobi import (INIT_TEMP, make_jacobi_loop,
                                                sphere_sel)

            nbf = min(n, 128)
            ndevf = 8 if len(jax.devices()) >= 8 else 1
            dimf = Dim3(2, 2, 2) if ndevf == 8 else Dim3(1, 1, 1)
            specf = GridSpec(Dim3(nbf, nbf, nbf), dimf, Radius.constant(1))
            meshf = grid_mesh(specf.dim, jax.devices()[:ndevf])
            self_ = shard_blocks(sphere_sel((nbf, nbf, nbf)), specf, meshf)
            field0 = shard_blocks(
                np.full((nbf,) * 3, INIT_TEMP, np.float32), specf, meshf)

            def jac_leg(fused: bool) -> float:
                ex = HaloExchange(specf, meshf, Method.REMOTE_DMA,
                                  fused=fused)
                sub_iters = 3
                loop = make_jacobi_loop(ex, sub_iters)
                c = field0
                nx_ = jax.device_put(jnp.zeros_like(c), ex.sharding())
                c, nx_ = loop(c, nx_, self_)  # compile + warm
                hard_sync((c, nx_))
                st = Statistics()
                for _ in range(2):
                    t1 = time.perf_counter()
                    c, nx_ = loop(c, nx_, self_)
                    hard_sync((c, nx_))
                    st.insert((time.perf_counter() - t1) / sub_iters)
                return nbf ** 3 / st.trimean() / 1e6

            jac_fused_mc = jac_leg(True)
            jac_rd_mc = jac_leg(False)
        except Exception as e:
            failed("jacobi_fused", e)

    # persistent whole-chunk jacobi (ROADMAP #7): the communication-
    # avoiding temporal-fusion variant — ONE deep (radius*k) exchange +
    # ONE k-substep chunk program per chunk, 2 dispatches per chunk
    # instead of 2k — vs the per-step fused kernel at 32^3 and 64^3 on
    # the 8-device mesh. Same CPU-emulation caveat as the fused leg: on
    # the CPU child both legs are host-orchestrated, the ratio prices
    # host dispatch amortization (which IS the lever the variant pulls),
    # and only the TPU mega-kernel number (scripts/probe_persistent.py,
    # item-1 session) carries the launch-count hardware claim. Ledger
    # ingest auto-appends both sizes via STENCIL_BENCH_LEDGER.
    jac_pers = {}
    if leg("jacobi persistent-over-fused (32^3/64^3, 8-dev)"):
        try:
            import jax.numpy as jnp

            from stencil_tpu.ops.jacobi import (INIT_TEMP, make_jacobi_loop,
                                                sphere_sel)

            ndevp_ = 8 if len(jax.devices()) >= 8 else 1
            dimp_ = Dim3(2, 2, 2) if ndevp_ == 8 else Dim3(1, 1, 1)
            kp = 2

            def pers_leg(nb: int, persistent: bool) -> float:
                spec_ = GridSpec(Dim3(nb, nb, nb), dimp_,
                                 Radius.constant(kp if persistent else 1))
                mesh_ = grid_mesh(spec_.dim, jax.devices()[:ndevp_])
                ex = HaloExchange(spec_, mesh_, Method.REMOTE_DMA,
                                  persistent=persistent,
                                  fused=not persistent)
                sub_iters = 4
                loop = make_jacobi_loop(
                    ex, sub_iters,
                    temporal_k=kp if persistent else None)
                sel_ = shard_blocks(sphere_sel((nb, nb, nb)), spec_, mesh_)
                c = shard_blocks(
                    np.full((nb,) * 3, INIT_TEMP, np.float32), spec_, mesh_)
                nx_ = jax.device_put(jnp.zeros_like(c), ex.sharding())
                c, nx_ = loop(c, nx_, sel_)  # compile + warm
                hard_sync((c, nx_))
                st = Statistics()
                for _ in range(2):
                    t1 = time.perf_counter()
                    c, nx_ = loop(c, nx_, sel_)
                    hard_sync((c, nx_))
                    st.insert((time.perf_counter() - t1) / sub_iters)
                return nb ** 3 / st.trimean() / 1e6

            for nb_ in (32, 64):
                jac_pers[f"jacobi_persistent_mcells_per_s_{nb_}"] = round(
                    pers_leg(nb_, True), 2)
                jac_pers[f"jacobi_fused_base_mcells_per_s_{nb_}"] = round(
                    pers_leg(nb_, False), 2)
                base_ = jac_pers[f"jacobi_fused_base_mcells_per_s_{nb_}"]
                jac_pers[f"jacobi_persistent_over_fused_{nb_}"] = (
                    round(jac_pers[f"jacobi_persistent_mcells_per_s_{nb_}"]
                          / base_, 3) if base_ else 0.0)
        except Exception as e:
            failed("jacobi_persistent", e)

    # quantity-batching A/B at Q=8 (the astaroth field count): one packed
    # ppermute carrier per axis phase vs one collective per quantity. On an
    # 8-device mesh (the CPU child forces 8 virtual devices) the partition
    # is 2x2x2 and the permute count drops 48 -> 6; a single accel chip
    # self-wraps and the leg measures the batched fill path instead.
    # nb is capped: Q=8 at 512^3 would not fit the leg budget.
    ex_bq_gb_s = 0.0
    ex_pq_gb_s = 0.0
    if leg("halo exchange (batched Q=8 A/B)"):
        try:
            ab = dict(nq=8, ndev=8 if len(jax.devices()) >= 8 else 1,
                      nb=min(n, 256))
            ex_bq_gb_s = _exchange_leg(Method.AXIS_COMPOSED, batched=True, **ab)
            ex_pq_gb_s = _exchange_leg(Method.AXIS_COMPOSED, batched=False, **ab)
        except Exception as e:
            failed("exchange_batched", e)

    # topology-aware placement leg (ISSUE 15 / ROADMAP #6): the same
    # composed exchange on an ANISOTROPIC 1x2x4 partition of the 8-dev
    # mesh, identity device order vs a rotated block->device assignment
    # (the PlanChoice.placement mechanism the QAP feeds). Results are
    # bit-identical by construction; the tracked ratio is a parity/no-
    # regression pin on the placed mesh path — on the single-process CPU
    # mesh every link costs the same, so ~1.0 is the honest expectation
    # and only a TPU slice (non-uniform ICI hops) can show a win.
    ex_placed_gb_s = 0.0
    ex_ident_gb_s = 0.0
    if leg("halo exchange (placed vs identity)"):
        try:
            ndevp8 = 8 if len(jax.devices()) >= 8 else 1
            pl = dict(nq=4, ndev=ndevp8, nb=min(n, 128),
                      dim=Dim3(1, 2, 4) if ndevp8 == 8 else Dim3(1, 1, 1))
            rot = tuple((i + 1) % ndevp8 for i in range(ndevp8))
            ex_placed_gb_s = _exchange_leg(
                Method.AXIS_COMPOSED, placement=rot if ndevp8 > 1 else None,
                **pl)
            ex_ident_gb_s = _exchange_leg(Method.AXIS_COMPOSED, **pl)
        except Exception as e:
            failed("exchange_placed", e)

    # hierarchical ICI+DCN leg (ISSUE 17 / ROADMAP #3): the composed
    # exchange at 128^3 on the 8-dev mesh split into 2 virtual hosts x 4
    # devices (STENCIL_VIRTUAL_HOSTS emulation), z-outer hierarchy vs
    # the flat single-level plan on the same 1x2x4 partition. Results
    # are bit-identical by construction; on the CPU child the "DCN"
    # copies are host-orchestrated device_puts between in-process
    # devices, so the tracked ratio prices that orchestration overhead
    # (expected <= 1), not a real two-tier fabric — only a multi-host
    # TPU run (scripts/probe_dcn.py seeds its calibration) carries the
    # cross-host overlap claim.
    ex_hier_gb_s = 0.0
    ex_hier_flat_gb_s = 0.0
    if leg("halo exchange (hierarchical vs flat, 2 virtual hosts)"):
        vh_prev = os.environ.get("STENCIL_VIRTUAL_HOSTS")
        try:
            ndevh = 8 if len(jax.devices()) >= 8 else 1
            hx = dict(nq=4, ndev=ndevh, nb=min(n, 128),
                      dim=Dim3(1, 2, 4) if ndevh == 8 else Dim3(1, 1, 1))
            if ndevh == 8:
                os.environ["STENCIL_VIRTUAL_HOSTS"] = "2"
                ex_hier_gb_s = _exchange_leg(
                    Method.AXIS_COMPOSED, hierarchy=("z", 2), **hx)
            ex_hier_flat_gb_s = _exchange_leg(Method.AXIS_COMPOSED, **hx)
        except Exception as e:
            failed("exchange_hierarchical", e)
        finally:
            if vh_prev is None:
                os.environ.pop("STENCIL_VIRTUAL_HOSTS", None)
            else:
                os.environ["STENCIL_VIRTUAL_HOSTS"] = vh_prev

    # exchange-plan autotuner leg (ROADMAP #3): tune (partition x method x
    # batching) for a radius-3 4-quantity config, then time the tuned plan
    # against the plan-less default (NodePartition + AXIS_COMPOSED +
    # batching) at the SAME size — the tracked plan_autotuned_over_default
    # ratio (> 1 means the autotuner beat the default). The tuner runs
    # in-memory here (no DB): the leg measures tuning quality, not cache
    # behavior (scripts/ci_plan_gate.py pins the zero-probe replay).
    plan_tuned_gb_s = 0.0
    plan_default_gb_s = 0.0
    plan_label = None
    plan_fingerprint = None
    plan_calibration = None
    if leg("exchange plan autotune"):
        try:
            from stencil_tpu.plan.autotune import autotune, default_choice

            nbp = min(n, 128) if on_accel else 64
            ndevp = 8 if len(jax.devices()) >= 8 else 1
            res = autotune(
                Dim3(nbp, nbp, nbp), Radius.constant(3), ["float32"] * 4,
                devices=jax.devices()[:ndevp], top_n=2, probe_iters=3,
            )
            ch = res.choice
            plan_label = ch.label()
            # the plan identity the observatory joins on: which exact
            # PlanChoice produced this leg, priced by which calibration
            plan_fingerprint = ch.fingerprint()
            plan_calibration = res.calibration_provenance
            from stencil_tpu.parallel import Method as _M

            plan_tuned_gb_s = _exchange_leg(
                _M(ch.method), nq=4, ndev=ndevp, nb=nbp,
                batched=ch.batch_quantities, dim=Dim3.of(ch.partition),
            )
            dflt = default_choice(res.config)
            plan_default_gb_s = _exchange_leg(
                _M(dflt.method), nq=4, ndev=ndevp, nb=nbp,
                batched=dflt.batch_quantities, dim=Dim3.of(dflt.partition),
            )
        except Exception as e:
            failed("plan_autotune", e)

    # multi-tenant campaign A/B (ROADMAP #4): B=64 independent 32^3
    # tenants served as ONE batched compiled program (batch axis sharded
    # over the mesh, zero collectives, per-tenant self-wrap halos) vs the
    # same 64 tenants run sequentially through the standard single-domain
    # machinery on the same devices — the tracked
    # campaign_batched_over_sequential ratio (> 1: batching wins) with
    # p50/p99 per-tenant step latency for the tail story.
    camp_b = camp_s = 0.0
    camp_p50 = camp_p99 = None
    if leg("multi-tenant campaign (B=64 32^3 A/B)"):
        try:
            import tempfile as _tf

            from stencil_tpu.campaign import (CampaignDriver, TenantJob,
                                              run_sequential)

            ndevc = 8 if len(jax.devices()) >= 8 else 1
            camp_B, camp_n, camp_steps = 64, 32, 6
            jobs = [TenantJob(f"t{i}", (camp_n, camp_n, camp_n), camp_steps,
                              "float32", seed=i) for i in range(camp_B)]
            camp_dir = os.environ.get("STENCIL_BENCH_CKPT_DIR") or None
            if camp_dir:
                # per-config subdir isolation (the headline-leg rule): a
                # CPU-rehearsal campaign must never repoint or prune an
                # accel campaign's per-tenant snapshots
                camp_dir = os.path.join(camp_dir, f"campaign{camp_B}x{camp_n}")
            else:
                camp_dir = _tf.mkdtemp(prefix="bench-campaign-")
            seq = run_sequential(jobs, devices=jax.devices()[:ndevc],
                                 chunk=3)
            bat = CampaignDriver(jobs, camp_B, camp_dir,
                                 devices=jax.devices()[:ndevc],
                                 chunk=3).run()
            import math as _math

            camp_b = bat["aggregate_mcells_per_s"]
            camp_s = seq["aggregate_mcells_per_s"]
            camp_p50, camp_p99 = bat["p50_step_s"], bat["p99_step_s"]
            if not _math.isfinite(camp_p50):
                camp_p50 = None  # a latency-less run must stay strict JSON
            if camp_p99 is not None and not _math.isfinite(camp_p99):
                camp_p99 = None
        except Exception as e:
            failed("campaign", e)

    # always-on serving leg (ISSUE 19): 16 pre-dropped jobs through the
    # serve scheduler's B=8 continuous-batching slot — tracked as
    # offered-load throughput (serve_tenants_per_hour) and the per-step
    # p99 the admission controller prices deadlines from (serve_p99_ms)
    serve_tph = 0.0
    serve_p99_ms = None
    if leg("always-on serve (16 jobs, continuous batching)"):
        try:
            import math as _math
            import tempfile as _tf

            from stencil_tpu.serve import ServeScheduler

            sdir = _tf.mkdtemp(prefix="bench-serve-")
            incoming = os.path.join(sdir, "jobs", "incoming")
            os.makedirs(incoming, exist_ok=True)
            serve_n, serve_jobs = 16, 16
            for i in range(serve_jobs):
                doc = {
                    "job": f"b-{i:04d}", "size": serve_n, "steps": 4,
                    "dtype": "float32", "workload": "jacobi", "seed": i,
                    "tenant": f"tenant-{i % 4}", "priority": "normal",
                }
                tmp = os.path.join(incoming, f".tmp-{i}")
                with open(tmp, "w") as f:
                    json.dump(doc, f)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, os.path.join(incoming, f"{doc['job']}.json"))
            ndevs = 8 if len(jax.devices()) >= 8 else 1
            summ = ServeScheduler(
                sdir, 8, devices=jax.devices()[:ndevs], chunk=2,
                poll_s=0.05, max_idle_s=0.5).serve()
            if summ["retired"] != serve_jobs:
                raise RuntimeError(
                    f"serve leg retired {summ['retired']}/{serve_jobs}")
            serve_tph = summ["tenants_per_hour"]
            p99 = summ.get("p99_step_s")
            if p99 is not None and _math.isfinite(p99):
                serve_p99_ms = p99 * 1e3
        except Exception as e:
            failed("serve", e)

    # serve capacity engine A/B (ISSUE 20): the SAME seeded mixed-tenant
    # queue — 16 SHALLOW buckets (2 normal tenants each at a distinct
    # size 20..35, 16 steps) plus a 4-job high bucket — through the
    # PR 19 fixed-slot daemon (B=8, head-of-queue buckets) and through
    # the capacity engine (elastic width 2..16, scored cross-bucket
    # packing, stride fairness). Shallow buckets are exactly where a
    # fixed slot bleeds: every chunk boundary device_gets and zeros the
    # FULL 8-lane batch for 2 live tenants, while the engine sizes each
    # slot to its queue depth. Each config gets a WARM pass on a shared
    # CompileCache first, so the measured pass prices scheduling and
    # host transfer, not compilation. Tracked: serve_mixed_over_fixed
    # (the >= 1.3x acceptance floor) and the high-priority p99 split
    # (the engine must not buy throughput with the high class's
    # latency).
    serve_mixed_tph = serve_mixed_fixed_tph = 0.0
    serve_mixed_ratio = 0.0
    serve_mixed_hi_p99 = serve_mixed_fixed_hi_p99 = None
    if leg("serve capacity engine (mixed tenants A/B)"):
        try:
            import math as _math
            import tempfile as _tf

            from stencil_tpu.campaign.compile_cache import CompileCache
            from stencil_tpu.serve import ServeScheduler

            def _mixed_drop(sdir):
                incoming = os.path.join(sdir, "jobs", "incoming")
                os.makedirs(incoming, exist_ok=True)
                docs = [{"job": f"n-{b:02d}-{j}", "size": 20 + b,
                         "steps": 16, "dtype": "float32",
                         "workload": "jacobi", "seed": b * 7 + j,
                         "tenant": f"tenant-{b % 4}",
                         "priority": "normal"}
                        for b in range(16) for j in range(2)]
                docs += [{"job": f"h-{i:04d}", "size": 10, "steps": 8,
                          "dtype": "float32", "workload": "jacobi",
                          "seed": 100 + i, "tenant": "tenant-hi",
                          "priority": "high"} for i in range(4)]
                for doc in docs:
                    tmp = os.path.join(incoming, f".tmp-{doc['job']}")
                    with open(tmp, "w") as f:
                        json.dump(doc, f)
                        f.flush()
                        os.fsync(f.fileno())
                    os.replace(
                        tmp, os.path.join(incoming, f"{doc['job']}.json"))
                return len(docs)

            def _mixed_serve(cache, **cfg):
                sdir = _tf.mkdtemp(prefix="bench-serve-mixed-")
                n_jobs = _mixed_drop(sdir)
                ndevs = 8 if len(jax.devices()) >= 8 else 1
                summ = ServeScheduler(
                    sdir, 8, devices=jax.devices()[:ndevs], chunk=2,
                    poll_s=0.02, max_idle_s=0.1, cache=cache,
                    **cfg).serve()
                if summ["retired"] != n_jobs:
                    raise RuntimeError(
                        f"mixed serve retired {summ['retired']}/{n_jobs}")
                return summ

            def _hi_p99(summ):
                v = (summ.get("p99_ms_by_priority") or {}).get("high")
                return v if v is not None and _math.isfinite(v) else None

            engine_cfg = dict(slot_min=2, slot_max=16, packing=True,
                              fairness=True)
            cache_fixed, cache_engine = CompileCache(), CompileCache()
            _mixed_serve(cache_fixed)                  # warm pass:
            _mixed_serve(cache_engine, **engine_cfg)   # compiles cached
            fixed = _mixed_serve(cache_fixed)
            eng = _mixed_serve(cache_engine, **engine_cfg)
            serve_mixed_fixed_tph = fixed["tenants_per_hour"]
            serve_mixed_tph = eng["tenants_per_hour"]
            if serve_mixed_fixed_tph > 0:
                serve_mixed_ratio = serve_mixed_tph / serve_mixed_fixed_tph
            serve_mixed_hi_p99 = _hi_p99(eng)
            serve_mixed_fixed_hi_p99 = _hi_p99(fixed)
        except Exception as e:
            failed("serve_mixed", e)

    # astaroth flagship details (BASELINE configs 4/4b): 8 fp32 fields,
    # fused Pallas RK3 substeps; skipped off-accelerator, via
    # STENCIL_BENCH_FAST=1, or when over budget (the three sliding-window
    # substep kernels compile in ~50 s each; the 512^3 set in ~150 s)
    asta_ms = None
    asta512_ms = None
    if on_accel and not os.environ.get("STENCIL_BENCH_FAST"):
        from stencil_tpu.apps.astaroth import run as asta_run

        if leg("astaroth 256^3"):
            try:
                # chunk 30 amortizes the ~87 ms dispatch cost to <3 ms/iter
                a = asta_run(iters=60, devices=jax.devices()[:1],
                             dtype="float32", nx=256, chunk=30)
                asta_ms = round(a["iter_trimean_s"] * 1e3, 2)
            except Exception as e:
                failed("astaroth_256", e)
        # the open flagship target (512^3 <= 180 ms/iter) is driver-tracked
        # from round 4 on (VERDICT r3 item 8); needs ~180 s compile+run
        if leg("astaroth 512^3") and budget_s - (time.time() - t0) > 200:
            try:
                a = asta_run(iters=12, devices=jax.devices()[:1],
                             dtype="float32", nx=512, chunk=6)
                asta512_ms = round(a["iter_trimean_s"] * 1e3, 2)
            except Exception as e:
                failed("astaroth_512", e)

    # flagship-size jacobi (config-5 per-chip regime): 768^3 is where the
    # full-plane multistep self-capped the temporal depth at k=4
    # (55.3 Gcells/s, VERDICT r5 weak #2); the row-tiled staging restores
    # k=12 there. Optional LAST leg (after the driver-tracked astaroth
    # rows) — skipped off-accelerator, under STENCIL_BENCH_FAST, or when
    # the remaining budget cannot cover its ~2 min compile+run.
    jac768 = None
    if on_accel and not os.environ.get("STENCIL_BENCH_FAST"):
        if leg("jacobi3d 768^3") and budget_s - (time.time() - t0) > 150:
            try:
                r768 = run(768, 768, 768, iters=60, weak=False,
                           devices=jax.devices()[:1], warmup=1, chunk=30)
                jac768 = round(r768["mcells_per_s_per_dev"], 1)
            except Exception as e:
                failed("jacobi_768", e)
    leg("done")

    value = round(mcells, 1)
    # the recorded baseline is a 512^3 TPU number; the CPU rehearsal gets
    # its own metric name and no baseline ratio so the two never conflate
    vs = value / BASELINE_MCELLS_PER_S_PER_CHIP if on_accel else 0.0
    metric = (
        "jacobi3d_512_mcells_per_s_per_chip"
        if on_accel
        else f"cpu_jacobi3d_{n}_mcells_per_s"
    )
    detail = {
        "iter_trimean_s": round(r["iter_trimean_s"], 6),
        "exchange_gb_per_s_r3_4q": round(ex_gb_s, 2),
        # like-for-like: same Pallas self-fill leg as the round-2 baseline
        "exchange_vs_baseline": (
            round(ex_gb_s / BASELINE_EXCHANGE_GB_S, 3) if on_accel else 0.0
        ),
        # the bench_mpi_pack ablation leg: manual transport over the
        # XLA-synthesized AUTO_SPMD path, same size/radius/quantities
        # (> 1 means the hand-built exchange wins)
        "exchange_auto_gb_per_s": round(ex_auto_gb_s, 2),
        "exchange_manual_over_auto": (
            round(ex_gb_s / ex_auto_gb_s, 3) if ex_auto_gb_s else 0.0
        ),
        # kernel-initiated remote-DMA transport over the composed ppermute
        # baseline at the same 8-dev config (> 1 means bypassing the XLA
        # collective path won; expected < 1 on the CPU emulation — only
        # the TPU carrier-kernel number carries the §5.8 claim)
        "exchange_remote_dma_gb_per_s": round(ex_rd_gb_s, 2),
        "exchange_remote_dma_base_gb_per_s": round(ex_rd_base_gb_s, 2),
        "exchange_remote_dma_over_composed": (
            round(ex_rd_gb_s / ex_rd_base_gb_s, 3)
            if ex_rd_base_gb_s else 0.0
        ),
        # fused compute+exchange step over the serialized remote-dma
        # step, 128^3 / 8-dev (> 1 means hiding the wire behind interior
        # compute won; on the CPU child both legs are the
        # host-orchestrated emulation — the ratio there prices host
        # orchestration, and only the TPU mega-kernel number carries the
        # ROADMAP-5 overlap claim)
        "jacobi_fused_mcells_per_s": round(jac_fused_mc, 2),
        "jacobi_remote_dma_mcells_per_s": round(jac_rd_mc, 2),
        "jacobi_fused_over_remote_dma": (
            round(jac_fused_mc / jac_rd_mc, 3) if jac_rd_mc else 0.0
        ),
        # persistent whole-chunk variant over the per-step fused kernel
        # at 32^3 and 64^3 (> 1 means paying 2 dispatches per k-step
        # chunk beat 2 per step; the tracked jacobi_persistent_over_
        # fused_{32,64} legs — CPU A/B here, TPU in the item-1 session)
        **jac_pers,
        # quantity-batching leg (Q=8, the astaroth field count): batched
        # packed-carrier exchange over the per-quantity program
        # (> 1 means one-collective-per-phase wins)
        "exchange_batchedq_gb_per_s": round(ex_bq_gb_s, 2),
        "exchange_perq_gb_per_s": round(ex_pq_gb_s, 2),
        "exchange_batchedq_over_perq": (
            round(ex_bq_gb_s / ex_pq_gb_s, 3) if ex_pq_gb_s else 0.0
        ),
        # topology-aware placement leg: placed (rotated assignment) over
        # identity on the anisotropic 1x2x4 8-dev partition — a parity/
        # no-regression pin on CPU (uniform links -> ~1.0); the QAP win
        # claim needs non-uniform ICI and lives in the TPU session
        "exchange_placed_gb_per_s": round(ex_placed_gb_s, 2),
        "exchange_identity_gb_per_s": round(ex_ident_gb_s, 2),
        "exchange_placed_over_identity": (
            round(ex_placed_gb_s / ex_ident_gb_s, 3)
            if ex_ident_gb_s else 0.0
        ),
        # hierarchical ICI+DCN leg: two-level (2 virtual hosts x 4 dev)
        # exchange over the flat plan at the same 1x2x4 config — a
        # parity/no-regression pin on CPU (the emulated DCN copies are
        # in-process device_puts, so <= 1 is the honest expectation);
        # the cross-host overlap claim needs a real multi-host fabric
        "exchange_hierarchical_gb_per_s": round(ex_hier_gb_s, 2),
        "exchange_hier_flat_gb_per_s": round(ex_hier_flat_gb_s, 2),
        "exchange_hierarchical_over_flat": (
            round(ex_hier_gb_s / ex_hier_flat_gb_s, 3)
            if ex_hier_flat_gb_s else 0.0
        ),
        # exchange-plan autotuner leg: tuned plan's bandwidth over the
        # plan-less default at the same config (> 1: the tuner won)
        "plan_autotuned_gb_per_s": round(plan_tuned_gb_s, 2),
        "plan_default_gb_per_s": round(plan_default_gb_s, 2),
        "plan_autotuned_over_default": (
            round(plan_tuned_gb_s / plan_default_gb_s, 3)
            if plan_default_gb_s else 0.0
        ),
        "plan_choice": plan_label,
        "plan_fingerprint": plan_fingerprint,
        "plan_calibration": plan_calibration,
        # multi-tenant campaign leg: one batched program serving B=64
        # 32^3 tenants over the sequential baseline (> 1: batching wins),
        # with the per-tenant step-latency tail (utils/statistics
        # percentiles) the serving story is judged on
        "campaign_batched_mcells_per_s": round(camp_b, 2),
        "campaign_sequential_mcells_per_s": round(camp_s, 2),
        "campaign_batched_over_sequential": (
            round(camp_b / camp_s, 3) if camp_s else 0.0
        ),
        "campaign_p50_step_s": (
            round(camp_p50, 6) if camp_p50 is not None else None
        ),
        "campaign_p99_step_s": (
            round(camp_p99, 6) if camp_p99 is not None else None
        ),
        # serving leg: offered-load throughput through the daemon's
        # continuous-batching scheduler and the per-step p99 (ms) its
        # admission controller prices deadlines from
        "serve_tenants_per_hour": round(serve_tph, 1),
        "serve_p99_ms": (
            round(serve_p99_ms, 3) if serve_p99_ms is not None else None
        ),
        # capacity-engine A/B: engine vs fixed-slot tenants/hour on the
        # seeded mixed queue (>= 1.3 is the ISSUE 20 acceptance floor)
        # and the high class's p99 under each scheduler
        "serve_mixed_tenants_per_hour": round(serve_mixed_tph, 1),
        "serve_mixed_fixed_tenants_per_hour": round(serve_mixed_fixed_tph, 1),
        "serve_mixed_over_fixed": round(serve_mixed_ratio, 3),
        "serve_mixed_high_p99_ms": (
            round(serve_mixed_hi_p99, 3)
            if serve_mixed_hi_p99 is not None else None
        ),
        "serve_mixed_fixed_high_p99_ms": (
            round(serve_mixed_fixed_hi_p99, 3)
            if serve_mixed_fixed_hi_p99 is not None else None
        ),
        "astaroth_256_iter_ms": asta_ms,
        "astaroth_512_iter_ms": asta512_ms,
        "jacobi3d_768_mcells_per_s": jac768,
        "platform": jax.devices()[0].platform,
        "size": n,
    }
    if errors:
        # the remaining legs ran, the evidence is on stderr, and the run
        # fails: no result line, so no partial payload reaches a ledger
        print(f"[bench:{mode}] {len(errors)} leg(s) failed: "
              f"{json.dumps(errors)}", file=sys.stderr, flush=True)
        return LEGS_FAILED_RC
    if not on_accel:
        # a CPU number is never printed under a device metric's name
        detail = {
            (f"cpu_{k}" if isinstance(v, (int, float))
             and not isinstance(v, bool) else k): v
            for k, v in detail.items()
        }
    print(
        SENTINEL
        + json.dumps(
            {
                "metric": metric,
                "value": value,
                "unit": "Mcells/s",
                "vs_baseline": round(vs, 3),
                "detail": detail,
            }
        ),
        flush=True,
    )
    return 0


# --------------------------------------------------------------- parent side


def _load_obs(stem: str, modname: str):
    """Load a stencil_tpu/obs/ module by FILE PATH.

    The parent must never import the ``stencil_tpu`` package: its
    ``__init__`` imports jax, and the wedge being supervised lives in JAX
    backend/plugin machinery. watchdog.py and ledger.py are pure stdlib
    by contract."""
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "stencil_tpu", "obs", f"{stem}.py",
    )
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    # register BEFORE exec: dataclasses resolves string annotations through
    # sys.modules[cls.__module__]
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def _load_watchdog():
    return _load_obs("watchdog", "stencil_watchdog")


def _append_ledger(payload: dict) -> None:
    """Append the round's payload to the performance ledger named by
    STENCIL_BENCH_LEDGER (no-op otherwise): the driver's one JSON line
    becomes durable, diffable history that ``perf_tool trend``/``gate``
    read across rounds. STENCIL_BENCH_LABEL names the round (default: a
    timestamp label). Best-effort by design — a ledger problem must never
    cost the driver its payload line."""
    path = os.environ.get("STENCIL_BENCH_LEDGER")
    if not path:
        return
    try:
        ledger = _load_obs("ledger", "stencil_ledger")
        label = (os.environ.get(ledger.ENV_LABEL)
                 or time.strftime("bench-%Y%m%dT%H%M%S"))
        entries = ledger.entries_from_bench_payload(
            payload, label=label,
            rev=ledger.git_rev(os.path.dirname(os.path.abspath(__file__))),
            source="bench")
        n = ledger.append_entries(path, entries)
        print(f"[bench] ledger: +{n} entries ({label}) -> {path}",
              file=sys.stderr, flush=True)
    except Exception as e:  # noqa: BLE001 — evidence, never the measurement
        print(f"[bench] ledger append failed: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)


def _parse_sentinel(stdout: str) -> dict | None:
    payload = None
    for line in stdout.splitlines():
        if line.startswith(SENTINEL):
            try:
                payload = json.loads(line[len(SENTINEL):])
            except json.JSONDecodeError:
                payload = None
    return payload


def main() -> int:
    watchdog = _load_watchdog()
    budget_s = float(os.environ.get("STENCIL_BENCH_BUDGET_S", "900"))
    # stall deadline: generous — a leg can sit in a single XLA compile for
    # minutes, and a compile that holds the interpreter also pauses the
    # child's beat thread (that pause must not read as a wedge)
    heartbeat_s = float(os.environ.get("STENCIL_BENCH_HEARTBEAT_S", "300"))
    rev = watchdog.Revival(
        budget_s=budget_s,
        parse=_parse_sentinel,
        archive_dir=os.environ.get("STENCIL_BENCH_LOG_DIR") or None,
    )

    def child(timeout_s: float, resume: bool = False):
        env = dict(os.environ)
        env["STENCIL_BENCH_LEG_BUDGET_S"] = str(max(60.0, timeout_s - 60.0))
        # resume-on-revival: the retry tells the child to continue from
        # its last durable checkpoint (no-op without STENCIL_BENCH_CKPT_DIR)
        cmd = [sys.executable, os.path.abspath(__file__), "--child", "accel"]
        if resume:
            cmd.append("--resume")
        return rev.attempt(
            "bench-accel",
            cmd,
            timeout_s=timeout_s,
            heartbeat_timeout_s=heartbeat_s,
            env=env,
        )

    # schedule: accel try 1 gets the lion's share (the astaroth 512^3
    # leg's gate needs ~260s left in the child after the earlier legs'
    # ~280s, so a 900s default budget must translate to a >=540s
    # first-try leg budget), backoff, accel try 2 — then failure.
    avail = max(0.0, budget_s - 10.0)
    for i, timeout_s in enumerate((avail * 0.85, avail * 0.15)):
        if i > 0:
            rev.backoff(20.0)
        timeout_s = min(timeout_s, rev.remaining())
        if timeout_s < 10.0:
            continue  # not enough time to even import jax
        payload = child(timeout_s, resume=i > 0)
        if payload is not None:
            print(json.dumps(payload), flush=True)
            _append_ledger(payload)
            return 0
        if rev.attempts and rev.attempts[-1].rc in (NO_ACCELERATOR_RC,
                                                    LEGS_FAILED_RC):
            break  # deterministic: a retry would fail the same way
    print(f"[bench] no result; attempts: {json.dumps(rev.report())}",
          file=sys.stderr, flush=True)
    return 1


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        raise SystemExit(_child_main(sys.argv[2],
                                     resume="--resume" in sys.argv[3:]))
    raise SystemExit(main())
