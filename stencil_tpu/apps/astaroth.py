"""astaroth — the MHD mini-app driver, weak-scaled.

TPU-native port of the reference driver (reference: astaroth/astaroth.cu):
8 double-precision fields, radius-3 halos, per iteration 3 RK3 substeps of
{interior integrate / halo exchange / exterior integrate}, buffers swapped
per iteration, dt = 1e-8. Init: hash-random everything, constant 0.5
lnrho, radial-explosion velocity (astaroth.cu:493-520). Output row matches
the reference (astaroth.cu:672-679):

  <processes>,<nx>,<ny>,<nz>,<iter trimean s>,<exch trimean s>

(nx/ny/nz are the per-config base extents; the global domain is that times
decompose_zyx(#devices), astaroth.cu:263-276,370-377.)

Usage: python -m stencil_tpu.apps.astaroth 10 [--conf path] [--cpu 8]
"""

from __future__ import annotations

import argparse
import os
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..api import DistributedDomain
from ..astaroth.config import load_config
from ..astaroth.init import const_init, hash_init, radial_explosion_init
from ..astaroth.integrate import FIELDS, make_astaroth_step, uses_pallas
from ..astaroth.reductions import Reductions
from ..geometry import Dim3, Radius, prime_factors
from ..obs import scopes, telemetry
from ..parallel import Method
from ..apps._bench_common import placement_from_flags
from ..utils import timer
from ..utils.statistics import Statistics
from ..utils.sync import hard_sync, timed_chunk
from ..utils import logging as log

DEFAULT_CONF = os.path.join(os.path.dirname(__file__), "..", "astaroth", "astaroth.conf")


def decompose_zyx(p: int) -> Dim3:
    """Split device count over axes, z first (reference: astaroth.cu:263-276)."""
    x = y = z = 1
    for pf in prime_factors(p):
        if z <= y and z <= x:
            z *= pf
        elif y <= x:
            y *= pf
        else:
            x *= pf
    return Dim3(x, y, z)


def _on_tpu(devices) -> bool:
    """Every device is a TPU: what the layout decisions below ask (tests
    turn it on to walk them on the CPU mesh)."""
    return all(d.platform == "tpu" for d in devices)


def run(
    iters: int = 10,
    conf: str = DEFAULT_CONF,
    devices=None,
    overlap: Optional[bool] = None,
    method: Method = Method.AXIS_COMPOSED,
    trivial: bool = False,
    random_: bool = False,
    no_compute: bool = False,
    dtype: str = "float64",
    nx: Optional[int] = None,
    paraview_init: bool = False,
    paraview_final: bool = False,
    swap_per_substep: bool = False,
    reductions: bool = False,
    dt: float = 1e-8,
    use_pallas=None,
    chunk: int = 1,
    kernel_variant: Optional[str] = None,
    metrics_dma: bool = False,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 0,
    ckpt_keep: int = 3,
    resume: bool = False,
    batch_quantities: bool = True,
    autotune: bool = False,
    plan_db: Optional[str] = None,
    health_every: int = 0,
    max_abs: Optional[float] = None,
    max_rollbacks: int = 3,
    rollback_backoff: float = 0.25,
    inject: Optional[str] = None,
) -> dict:
    """Run ``iters`` iterations (plus one untimed warmup chunk) and return
    timing stats + the domain.

    Iterations execute in fused chunks of ``chunk`` compiled together; when
    ``chunk`` does not divide ``iters``, the count is rounded UP to the next
    chunk multiple (a tail program would double the compile cost for a
    benchmark driver) — the returned ``iters_run`` records the actual
    number of timed iterations the state advanced.

    ``overlap=None`` leaves the schedule to :func:`make_astaroth_step`
    (exchange-first on the fused Pallas path, hoisted overlap on the XLA
    path); ``True`` / ``False`` ask for the shells or for none."""
    devices = list(devices) if devices is not None else jax.devices()
    if (overlap is not False and np.dtype(dtype) == np.float64
            and _on_tpu(devices)
            and os.environ.get("STENCIL_F64_OVERLAP") != "1"):
        # fp64 on TPU: the serialized step compiles in ~2 min. The round-3
        # per-substep overlap structure (7 integrate regions x 3 substeps
        # x f64 emulation expansion) blew a 25-minute compile budget; the
        # round-4 hoisted-exchange overlap iteration is 9 bodies and is
        # expected to compile — set STENCIL_F64_OVERLAP=1 to take it
        # (default stays serialized until the chip record lands,
        # BASELINE.md round 4, scripts/probe_f64*.py)
        log.info("fp64 on TPU: forcing overlap=False (set "
                 "STENCIL_F64_OVERLAP=1 for the hoisted overlap structure)")
        overlap = False
    # run() is covered by spans without a hole: realize, init, warmup
    # (build, compile or cache load, first call), steps
    rec = telemetry.get()
    end_realize = rec.open_span("astaroth.realize", phase="init")
    info, ok = load_config(conf)
    if not ok:
        log.warn(f"config has uninitialized values: {info.uninitialized()[:5]} ...")
    if nx is not None:
        info.int_params["AC_nx"] = nx
        info.int_params["AC_ny"] = nx
        info.int_params["AC_nz"] = nx
        info.update_builtin_params()

    # weak scaling: base extent x device decomposition. On TPU the split
    # stays in z/y (geometry.decompose_zy): every chip keeps the tight-x
    # layout, no minor-dim slab slicing, 2D ICI mesh — the reference's
    # 3-axis decompose_zyx (astaroth.cu:263-276) remains for CPU.
    if len(devices) > 1 and _on_tpu(devices):
        from ..geometry import decompose_zy

        d3 = decompose_zy(len(devices))
    else:
        d3 = decompose_zyx(len(devices))
    size = Dim3(
        info.int_params["AC_nx"] * d3.x,
        info.int_params["AC_ny"] * d3.y,
        info.int_params["AC_nz"] * d3.z,
    )

    dd = DistributedDomain(size.x, size.y, size.z)
    radius = Radius.constant(3)
    if d3.x == 1 and use_pallas is not False:
        # tight-x layout on a single-block x axis (any y/z mesh): no x halo
        # columns (kernel forms the periodic x pencils with lane rolls) —
        # sheds the px/nx DMA lane padding AND the x self-fill's lane-tile
        # RMW entirely; multi-block y/z halos ride the exchange and their
        # overlap shells take the x-wrapped slab integrate. Engage only
        # when the fused kernel supports the resulting layout.
        from ..domain.grid import GridSpec
        from ..ops.pallas_astaroth import substep_supported

        tight = radius.without_x()
        tight_spec = GridSpec(size, d3, tight)
        if (np.dtype(dtype) == np.float32 and _on_tpu(devices)
                and substep_supported(tight_spec, jnp.float32)):
            radius = tight
            # the layout holds on the partition the domain was sized for:
            # left to itself, realize() splits the axis with the smallest
            # halo interface first, and with no x halo that is x (PR 33:
            # four chips stopped in make_astaroth_step on a split x axis)
            dd.set_partition(d3)
    dd.set_radius(radius)
    dd.set_methods(method)
    # the 8-field state is where quantity batching pays: one packed
    # ppermute carrier per axis phase instead of 8 (default on; the A/B
    # knob keeps the per-quantity collectives measurable)
    dd.set_quantity_batching(batch_quantities)
    dd.set_devices(devices)
    dd.set_placement(placement_from_flags(trivial, random_))
    if autotune:
        # plan/ subsystem: the 8-field exchange is where plan choice pays
        # (batched vs per-quantity, partition shape); DB hits replay with
        # zero probes, misses probe the statically-ranked top candidates
        dd.enable_autotune(db_path=plan_db)
    handles = {name: dd.add_data(name, dtype) for name in FIELDS}
    dd.realize()
    end_realize()
    if autotune:
        method = dd._method  # the tuned method labels the CSV row

    # init (reference: astaroth.cu:493-520): hash-random everything,
    # constant 0.5 lnrho, radial-explosion velocity
    np_dtype = np.dtype(dtype)
    with rec.span("astaroth.init", phase="init"):
        ds = (
            info.real_params["AC_dsx"],
            info.real_params["AC_dsy"],
            info.real_params["AC_dsz"],
        )
        h = hash_init(size, dtype=np_dtype)  # coordinate-determined, same per field
        for name in ("entropy", "ax", "ay", "az"):
            dd.set_curr_global(handles[name], h)
        dd.set_curr_global(handles["lnrho"], const_init(size, 0.5, dtype=np_dtype))
        uux, uuy, uuz = radial_explosion_init(size, ds=ds, dtype=np_dtype)
        dd.set_curr_global(handles["uux"], uux)
        dd.set_curr_global(handles["uuy"], uuy)
        dd.set_curr_global(handles["uuz"], uuz)

    if paraview_init:
        dd.write_paraview("init")

    # checkpoint/restart (ckpt/): the 8 fields' per-block interiors are the
    # durable campaign state; resume elastically replaces the fresh init
    start = 0
    if ckpt_dir and no_compute:
        log.warn("--ckpt-dir ignored with --no-compute (pure-exchange "
                 "benchmark has no campaign state worth resuming)")
        ckpt_dir = None
    if ckpt_dir and resume:
        from ._bench_common import resume_from_checkpoint

        start = resume_from_checkpoint(dd, ckpt_dir, iters)

    def save_ckpt(step: int, state) -> None:
        for name in FIELDS:
            dd.set_curr(handles[name], state[name])
        dd.save_checkpoint(ckpt_dir, step, keep=ckpt_keep)

    curr = {name: dd.get_curr(handles[name]) for name in FIELDS}
    nxt = {name: dd.get_next(handles[name]) for name in FIELDS}

    iter_time = Statistics()
    exch_time = Statistics()

    def timed_exchange(loop, st, n):
        """One exchange-only chunk of ``n`` exchanges: its span's seconds
        are the whole chunk's."""
        st, marks = timed_chunk(scopes.EXCHANGE_LOOP, loop, st)
        exch_time.insert(marks.wall_s)
        rec.chunk_span("astaroth.exchange", marks, n, per=marks.wall_s,
                       phase="exchange")
        return st, marks

    if no_compute:
        # measure pure exchange per substep (reference --no-compute flag)
        with rec.span("astaroth.warmup", phase="compile"):
            loop = dd.halo_exchange.make_loop(3, like=curr)
            curr = loop(curr)
            hard_sync(curr)
        end_steps = rec.open_span("astaroth.steps", phase="step")
        for _ in range(iters):
            curr, marks = timed_exchange(loop, curr, 3)
            iter_time.insert(marks.wall_s)
    else:
        chunk = max(1, min(chunk, iters))
        with rec.span("astaroth.warmup", phase="compile", iters=chunk):
            step = make_astaroth_step(
                dd.halo_exchange,
                info,
                dt=dt,
                overlap=overlap,
                swap_per_substep=swap_per_substep,
                use_pallas=use_pallas,
                dtype=dtype,
                iters=chunk,
                kernel_variant=kernel_variant,
            )
            if ckpt_dir:
                # step-exact contract for checkpointed runs: warm the
                # compile caches on throwaway copies (the step donates its
                # inputs), never advancing the real state
                step(jax.tree.map(lambda a: a + 0, curr),
                     jax.tree.map(lambda a: a + 0, nxt))
                hard_sync(curr)
            else:
                curr, nxt = step(curr, nxt)  # compile + warm (one chunk)
                hard_sync(curr)
        # The exchange share can't be timed inside the fused step, so it is
        # measured as a standalone loop on the same state each iteration
        # (halo exchange is idempotent on exchanged data, so this does not
        # perturb the fields) — the analogue of the reference's exchElapsed
        # within the iteration (astaroth.cu:586-590). The loop length
        # mirrors the step's exchanges per iteration: 3 (one per substep)
        # on the XLA path, 1 on the fused Pallas path (non-swap mode).
        pallas_on = uses_pallas(dd.halo_exchange, use_pallas, dtype)
        n_ex = 1 if (pallas_on and not swap_per_substep) else 3
        with rec.span("astaroth.warmup", phase="compile", iters=n_ex):
            exch_loop = dd.halo_exchange.make_loop(n_ex, like=curr)
            curr = exch_loop(curr)
            hard_sync(curr)
        end_steps = rec.open_span("astaroth.steps", phase="step")

        # Self-healing (fault/): when a health guard or injection schedule
        # is configured, the 8-field loop runs under the same guarded
        # engine as jacobi3d (step -> inject -> check -> checkpoint, with
        # rollback-with-backoff on a NumericalFault); otherwise the
        # historical fixed-chunk loop runs untouched — identical compiled
        # programs either way.
        from ..fault import (FaultPlan, HealthGuard, RecoveryPolicy,
                             chunk_plan, run_guarded)

        guard = (HealthGuard(every=health_every, max_abs=max_abs)
                 if health_every > 0 else None)
        injector = FaultPlan.from_spec(inject)
        done = start
        if guard is not None or injector is not None:
            steps_cache = {chunk: step}

            def get_step(k: int):
                # fault-mode chunk plans may carry tail sizes the fixed
                # benchmark chunking never needed; compile them on demand
                if k not in steps_cache:
                    steps_cache[k] = make_astaroth_step(
                        dd.halo_exchange, info, dt=dt, overlap=overlap,
                        swap_per_substep=swap_per_substep,
                        use_pallas=use_pallas, dtype=dtype, iters=k,
                        kernel_variant=kernel_variant,
                    )
                return steps_cache[k]

            def plan_fn(s: int):
                return chunk_plan(
                    s, iters, chunk,
                    every=(ckpt_every if (ckpt_dir and ckpt_every > 0) else 0,
                           health_every if guard is not None else 0),
                    at=injector.steps() if injector is not None else (),
                )

            marks = None

            def step_fn(st, k):
                nonlocal nxt, marks
                (c, nxt), marks = timed_chunk(scopes.ASTAROTH_ITER,
                                              get_step(k), st, nxt)
                return c

            def on_chunk(st, k, per, done_now):
                for _ in range(k):
                    iter_time.insert(per)
                rec.chunk_span("astaroth.iter", marks, k, per=per)
                return timed_exchange(exch_loop, st, n_ex)[0]

            save_fn = restore_fn = quarantine_fn = flush_fn = None
            if ckpt_dir:
                if ckpt_every > 0:
                    save_fn = save_ckpt
                flush_fn = dd.flush_checkpoints

                def restore_fn():
                    s = dd.restore_checkpoint(ckpt_dir)
                    if s is None:
                        return None
                    return s, {name: dd.get_curr(handles[name])
                               for name in FIELDS}

                def quarantine_fn(s):
                    from ..ckpt import quarantine_snapshot, snapshot_name

                    quarantine_snapshot(
                        ckpt_dir, snapshot_name(s),
                        reason="restored state failed health check")

            curr, done = run_guarded(
                curr, start=start, iters=iters, plan_fn=plan_fn,
                step_fn=step_fn, guard=guard, injector=injector,
                policy=RecoveryPolicy(max_rollbacks=max_rollbacks,
                                      backoff_s=rollback_backoff),
                save_fn=save_fn, ckpt_every=ckpt_every,
                restore_fn=restore_fn, quarantine_fn=quarantine_fn,
                flush_fn=flush_fn, on_chunk=on_chunk, spec=dd.spec,
                ckpt_dir=ckpt_dir, app="astaroth",
            )
        else:
            next_ckpt = (start // ckpt_every + 1) * ckpt_every if (
                ckpt_dir and ckpt_every > 0) else None
            while done < iters:
                (curr, nxt), marks = timed_chunk(scopes.ASTAROTH_ITER, step,
                                                 curr, nxt)
                for _ in range(chunk):
                    iter_time.insert(marks.wall_s / chunk)
                rec.chunk_span("astaroth.iter", marks, chunk)
                done += chunk
                if next_ckpt is not None and done >= next_ckpt and done < iters:
                    save_ckpt(done, curr)
                    next_ckpt = (done // ckpt_every + 1) * ckpt_every
                curr, _ = timed_exchange(exch_loop, curr, n_ex)
        if ckpt_dir:
            if done > start or start == 0:
                save_ckpt(done, curr)  # the final state is always durable
            # a resume that found nothing left to run never re-labels the
            # existing (possibly further-along) snapshot
            dd.finish_checkpoints()

    timed_iters = iter_time.count()
    if iter_time.count() == 0:
        # resumed at/past the target iteration count: nothing left to time
        # (inf placeholder; non-finite gauges are skipped — they would
        # serialize as non-strict JSON)
        log.info(f"resume found step {start} >= iters {iters}; no timed work")
        iter_time.insert(float("inf"))
    if exch_time.count() == 0:
        exch_time.insert(float("inf"))

    if rec.enabled:
        # compile-time truth of this method's exchange (on-wire volume)
        telemetry.record_exchange_truth(
            dd.halo_exchange, dict(curr), [np_dtype.itemsize] * len(FIELDS))
        if metrics_dma and not no_compute:
            if uses_pallas(dd.halo_exchange, use_pallas, dtype):
                telemetry.record_dma_traffic(
                    lambda: (
                        make_astaroth_step(
                            dd.halo_exchange, info, dt=dt, overlap=overlap,
                            swap_per_substep=swap_per_substep,
                            use_pallas=use_pallas, dtype=dtype, iters=chunk,
                            kernel_variant=kernel_variant,
                        ),
                        (curr, nxt),
                    ),
                )
            else:
                rec.meta("dma.skipped",
                         reason="pallas fused substep not engaged")
        if np.isfinite(iter_time.trimean()):
            rec.gauge("astaroth.iter_trimean_s", iter_time.trimean(),
                      phase="step", unit="s")
        if np.isfinite(exch_time.trimean()):
            rec.gauge("astaroth.exch_trimean_s", exch_time.trimean(),
                      phase="exchange", unit="s")

    for name in FIELDS:
        dd.set_curr(handles[name], curr[name])
        if not no_compute:
            dd.set_next(handles[name], nxt[name])
    end_steps()

    if paraview_final:
        dd.write_paraview("final")

    result = {
        "processes": jax.process_count(),
        "devices": len(devices),
        "nx": info.int_params["AC_nx"],
        "ny": info.int_params["AC_ny"],
        "nz": info.int_params["AC_nz"],
        "global": size,
        "iter_trimean_s": iter_time.trimean(),
        "exch_trimean_s": exch_time.trimean(),
        "iters_run": timed_iters,
        "domain": dd,
        "handles": handles,
        "info": info,
    }
    if reductions:
        red = Reductions(dd.halo_exchange)
        result["reductions"] = {
            "lnrho": red.scal(dd.get_curr(handles["lnrho"])),
            "uu": red.vec(
                dd.get_curr(handles["uux"]),
                dd.get_curr(handles["uuy"]),
                dd.get_curr(handles["uuz"]),
            ),
        }
    return result


def csv_row(r: dict) -> str:
    return (
        f"{r['devices']},{r['nx']},{r['ny']},{r['nz']},"
        f"{r['iter_trimean_s']:e},{r['exch_trimean_s']:e}"
    )


def main(argv: Optional[list] = None) -> int:
    from ..parallel.distributed import maybe_init_from_env
    maybe_init_from_env()
    from ..utils.jax_cache import configure_compile_cache
    configure_compile_cache()
    p = argparse.ArgumentParser(description="Astaroth MHD mini-app (TPU)")
    p.add_argument("iters", type=int, nargs="?", default=10)
    p.add_argument("--conf", default=DEFAULT_CONF)
    p.add_argument("--nx", type=int, default=None, help="override AC_n{x,y,z}")
    p.add_argument("--trivial", action="store_true", help="trivial placement")
    p.add_argument("--random", action="store_true", help="random placement")
    p.add_argument("--no-compute", action="store_true")
    p.add_argument("--no-overlap", action="store_true")
    p.add_argument("--paraview-init", action="store_true")
    p.add_argument("--paraview-final", action="store_true")
    p.add_argument("--f32", action="store_true", help="float32 fields (TPU-native)")
    p.add_argument("--f64", action="store_true",
                   help="float64 fields on TPU (software-emulated: works on "
                        "the serialized XLA path, ~45 ms/iter at 64^3 with "
                        "a ~2 min compile; the reference's native dtype)")
    p.add_argument("--reductions", action="store_true", help="print field reductions")
    p.add_argument("--no-pallas", action="store_true",
                   help="force the unfused XLA substep path")
    p.add_argument("--per-quantity-exchange", action="store_true",
                   help="disable quantity batching: one collective per "
                        "field per phase instead of one packed carrier for "
                        "all 8 fields (the A/B baseline)")
    p.add_argument("--kernel-variant", choices=("shift", "ring"), default=None,
                   help="fused-substep sliding-window discipline: 'shift' "
                        "(plane-copy window shifts, the recorded kernel) or "
                        "'ring' (shift-free modular-slot rotation); default "
                        "reads STENCIL_ASTAROTH_VARIANT, else 'shift'")
    p.add_argument("--chunk", type=int, default=1,
                   help="iterations fused per dispatch (benchmarking; a "
                        "final partial chunk still runs a full chunk)")
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
                        "isfinite reduction over all 8 fields every N "
                        "steps; a fault rolls back to the newest valid "
                        "snapshot (0 = off)")
    p.add_argument("--max-abs", type=float, default=0.0,
                   help="with --health-every, divergence ceiling on any "
                        "field's max|u| (0 = no ceiling)")
    p.add_argument("--max-rollbacks", type=int, default=3,
                   help="rollbacks allowed per faulting step before the "
                        "run aborts with rc 43 + an evidence bundle")
    p.add_argument("--rollback-backoff", type=float, default=0.25,
                   help="first-retry backoff seconds (doubles per repeat)")
    p.add_argument("--inject", type=str, default="",
                   help="deterministic fault injection spec (see "
                        "fault/inject.py; default: STENCIL_FAULT_INJECT)")
    p.add_argument("--autotune", action="store_true",
                   help="choose the exchange plan (partition x method x "
                        "quantity batching) via the plan/ autotuner; a plan-"
                        "DB hit replays with zero probes")
    p.add_argument("--plan-db", type=str, default="",
                   help="on-disk plan DB (JSON) for --autotune")
    p.add_argument("--cpu", type=int, default=0)
    from ._bench_common import add_metrics_flags, start_metrics
    add_metrics_flags(p, dma=True)
    args = p.parse_args(argv)
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    rec = start_metrics(args, "astaroth")
    # dtype default: the reference's double on CPU, float32 on TPU (f64 is
    # software-emulated on TPU; it works through the serialized XLA path —
    # run() forces overlap off there — but is ~20x slower than fp32)
    use_f64 = args.f64 or (
        not args.f32 and jax.devices()[0].platform != "tpu"
    )
    if use_f64:
        jax.config.update("jax_enable_x64", True)
    elif not args.f32 and not args.f64:
        log.info("TPU platform: defaulting to float32 fields (use --f64 to force)")
    from ..fault import FAULT_RC, RecoveryExhausted

    try:
        r = run(
            iters=args.iters,
            conf=args.conf,
            trivial=args.trivial,
            random_=args.random,
            no_compute=args.no_compute,
            overlap=False if args.no_overlap else None,
            dtype="float64" if use_f64 else "float32",
            nx=args.nx,
            paraview_init=args.paraview_init,
            paraview_final=args.paraview_final,
            reductions=args.reductions,
            use_pallas=False if args.no_pallas else None,
            chunk=args.chunk,
            kernel_variant=args.kernel_variant,
            metrics_dma=args.metrics_dma and rec.enabled,
            ckpt_dir=args.ckpt_dir or None,
            ckpt_every=args.ckpt_every,
            ckpt_keep=args.ckpt_keep,
            resume=args.resume,
            batch_quantities=not args.per_quantity_exchange,
            autotune=args.autotune,
            plan_db=args.plan_db or None,
            health_every=args.health_every,
            max_abs=args.max_abs or None,
            max_rollbacks=args.max_rollbacks,
            rollback_backoff=args.rollback_backoff,
            inject=args.inject or None,
        )
    except RecoveryExhausted as e:
        log.error(f"astaroth: {e}")
        if rec.enabled:
            rec.record_timer_buckets()
            rec.close()
        return FAULT_RC
    print(csv_row(r))
    log.info(timer.report())
    if rec.enabled:
        rec.record_timer_buckets()
        rec.close()
    if "reductions" in r:
        for k, v in r["reductions"].items():
            log.info(f"{k}: {v}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
