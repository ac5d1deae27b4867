"""serve — the always-on campaign serving daemon.

The CLI over ``stencil_tpu/serve/``: point it at a ``--serve-dir`` and
it serves forever — producers drop job JSONs into
``<serve-dir>/jobs/incoming/`` (atomically: write a tmp file, rename;
``scripts/serve_loadgen.py`` is the reference producer), the daemon
admits them against per-tenant ``--quota`` and ledger-priced deadlines,
packs batch slots via the CAPACITY ENGINE (on by default: stride-
weighted fairness with aging, scored cross-bucket packing, elastic slot
width over ``--slot-min``/``--slot-max``, priced chunk-boundary
preemption — each individually defeatable via ``--no-fairness`` /
``--no-packing`` / ``--no-preempt`` and fixed width by default),
backfills retired lanes from the live queue MID-SLOT (continuous
batching — no slot-wide barrier), and streams each result into
``<serve-dir>/results/<job>.json`` the moment the tenant retires.

Lifecycle:

- **SIGTERM** drains gracefully: intake stops, live lanes park as
  revivable snapshots at the next segment boundary, the queue persists
  to ``serve-state.json``, the daemon exits 0.
- **SIGKILL / crash** loses nothing: restart the same command (the PR 3
  watchdog ladder does this automatically) and the daemon revives every
  admitted-but-unserved job from ``serve-state.json`` — running jobs
  resume from their newest snapshot (bit-identical by the ckpt
  contract), retired jobs are NEVER re-run, replayed job files are
  quarantined as duplicates.
- ``--max-idle-s`` / ``--max-wall-s`` bound a session (CI gates, bench
  legs); 0 means serve until drained.

Watch it: ``report --status <status-file> --follow`` renders the live
queue line (depth/admitted/rejected/backfills) next to the lane table.

Usage: python -m stencil_tpu.apps.serve --serve-dir /srv/stencil \
           --cpu 8 --slot 4 --quota 2 --max-idle-s 30 \
           --metrics-out serve.jsonl --status-file status.json
"""

from __future__ import annotations

import argparse
import json
import os
import signal
from typing import Optional

import jax

from ..obs import telemetry
from ..utils import logging as log

# injected-kill hook (CI serve gate / tests): after the Nth tenant
# retires — serve-state.json durable, the result streamed — die hard
# with rc 17 (the ckpt kill hook's rc: "killed on purpose, revive me"),
# so the gate can prove a revived daemon finishes the queue without
# re-running the retired work
KILL_ENV = "STENCIL_SERVE_KILL_AFTER_RETIRE"


def build_scheduler(args, sentinel=None, status=None):
    from ..serve import ServeScheduler

    devices = jax.devices()[: args.cpu] if args.cpu else jax.devices()
    weights = {}
    for part in (args.fair_weights or "").split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise SystemExit(f"bad --fair-weights entry {part!r} "
                             "(want CLASS=WEIGHT)")
        k, v = part.split("=", 1)
        weights[k.strip()] = float(v)
    sched = ServeScheduler(
        args.serve_dir, args.slot,
        quota=args.quota, admission_ledger=args.admission_ledger or None,
        poll_s=args.poll_s, max_idle_s=args.max_idle_s,
        max_wall_s=args.max_wall_s,
        slot_min=args.slot_min or None, slot_max=args.slot_max or None,
        packing=not args.no_packing, preempt=not args.no_preempt,
        fairness=not args.no_fairness, fair_weights=weights or None,
        aging_s=args.aging_s,
        preempt_cost_chunks=args.preempt_cost_chunks,
        devices=devices, chunk=args.chunk,
        ckpt_every=args.ckpt_every, ckpt_keep=args.ckpt_keep,
        health_every=args.health_every, max_abs=args.max_abs or None,
        max_rollbacks=args.max_rollbacks,
        rollback_backoff=args.rollback_backoff,
        sentinel=sentinel, status=status,
    )
    if args.replan:
        # the campaign's between-slot hot-swap, with serving's extra
        # trigger: SLO pressure (deadline-at-risk vs the online p99)
        # latches the controller exactly like a sentinel anomaly; the
        # re-tune targets the LAST slot's bucket and persists into
        # --plan-db (force=True, static-only — slots must not stall)
        from ..campaign.driver import WORKLOADS
        from ..geometry import Dim3, Radius
        from ..plan.replan import ReplanController

        def retune_fn():
            from ..plan.autotune import autotune as _plan_autotune

            bucket = sched._last_bucket
            if bucket is None:
                raise ValueError("no slot has run yet; nothing to retune")
            (size, dtype, workload) = bucket
            wl = WORKLOADS[workload]
            nq = len(wl.quantity_names(dtype))
            res = _plan_autotune(
                Dim3(size[0], size[1], size[2]),
                Radius.constant(wl.default_radius),
                [dtype] * nq, devices=devices,
                db_path=args.plan_db or None, probe=False, force=True,
            )
            return res.choice

        controller = ReplanController(
            retune_fn, lambda choice, st: None, sentinel=sentinel)
        if sentinel is not None:
            sentinel.on_replan = controller.request
        sched.replan = controller
    return sched


def install_kill_hook(sched) -> None:
    """Arm the CI kill hook when the env var names a retirement count."""
    kill_after = int(os.environ.get(KILL_ENV, "0") or 0)
    if kill_after <= 0:
        return
    orig = sched._on_result

    def killing(r):
        orig(r)
        if sched._retired_run >= kill_after:
            log.warn(f"{KILL_ENV}: dying after {sched._retired_run} "
                     "retirement(s)")
            os._exit(17)

    sched._on_result = killing


def main(argv: Optional[list] = None) -> int:
    from ..parallel.distributed import maybe_init_from_env
    maybe_init_from_env()
    from ..utils.jax_cache import configure_compile_cache
    configure_compile_cache()
    p = argparse.ArgumentParser(
        description="always-on campaign serving daemon")
    p.add_argument("--serve-dir", required=True,
                   help="service root: jobs/{incoming,claimed,bad}, "
                        "campaign/ (slots + tenant snapshots), results/, "
                        "serve-state.json")
    p.add_argument("--slot", type=int, default=4,
                   help="batch-slot size B (lanes per compiled program); "
                        "with --slot-min/--slot-max this is only the "
                        "elastic ladder's default")
    p.add_argument("--slot-min", type=int, default=0,
                   help="elastic width floor: each slot is sized to its "
                        "bucket's queue depth on a power-of-two ladder "
                        "from --slot-min to --slot-max (0 = --slot, "
                        "i.e. fixed width)")
    p.add_argument("--slot-max", type=int, default=0,
                   help="elastic width ceiling; a mid-slot surge grows "
                        "the running slot at a chunk boundary "
                        "(park-repartition-revive, bit-identical) "
                        "(0 = --slot)")
    p.add_argument("--fair-weights", default="",
                   help="served-share weights as CLASS=WEIGHT commas, "
                        "e.g. 'high=8,normal=4,low=1' (the default); "
                        "shares are stride-scheduled, so doubling a "
                        "weight can only raise that class's share")
    p.add_argument("--aging-s", type=float, default=30.0,
                   help="seconds of queue wait that promote a job one "
                        "priority class; a job waiting past "
                        "aging_s*(rank+1) leads the next slot outright "
                        "— the hard no-starvation bound (0 = no aging)")
    p.add_argument("--no-fairness", action="store_true",
                   help="strict priority order (PR 19): no weighted "
                        "shares, no aging — sustained high load may "
                        "starve low")
    p.add_argument("--no-packing", action="store_true",
                   help="head-of-queue bucket selection instead of the "
                        "scored cross-bucket packing pass")
    p.add_argument("--no-preempt", action="store_true",
                   help="never park a running slot for an infeasible "
                        "high arrival")
    p.add_argument("--preempt-cost-chunks", type=float, default=1.0,
                   help="priced resume cost per victim, in fused chunks "
                        "of its bucket's p99 — preemption (and mid-slot "
                        "growth) fires only when the priced gain "
                        "exceeds this")
    p.add_argument("--chunk", type=int, default=2,
                   help="fused steps per dispatch")
    p.add_argument("--quota", type=int, default=0,
                   help="per-tenant cap on live (queued+running) jobs; an "
                        "over-quota job is DEFERRED and promoted when one "
                        "of the tenant's jobs retires (0 = unlimited)")
    p.add_argument("--admission-ledger", default="",
                   help="performance ledger (obs/ledger.py) seeding "
                        "per-bucket p99 deadline pricing; the daemon "
                        "appends its own serve.step_p99_ms entries back "
                        "at exit, so pricing survives restarts")
    p.add_argument("--poll-s", type=float, default=0.2,
                   help="idle intake poll interval")
    p.add_argument("--max-idle-s", type=float, default=0.0,
                   help="exit after this long with an empty queue "
                        "(0 = serve until drained)")
    p.add_argument("--max-wall-s", type=float, default=0.0,
                   help="total wall budget; reaching it drains gracefully "
                        "(0 = unbounded)")
    p.add_argument("--ckpt-every", type=int, default=2,
                   help="checkpoint every active lane every N slot steps — "
                        "the revival substrate (0 = only final/park "
                        "snapshots; a SIGKILLed daemon then replays whole "
                        "tenants instead of resuming mid-flight)")
    p.add_argument("--ckpt-keep", type=int, default=3)
    p.add_argument("--health-every", type=int, default=0,
                   help="per-lane health-check cadence in slot steps "
                        "(default: every fused chunk)")
    p.add_argument("--max-abs", type=float, default=0.0,
                   help="divergence ceiling on max|u| (0 = none)")
    p.add_argument("--max-rollbacks", type=int, default=2)
    p.add_argument("--rollback-backoff", type=float, default=0.05)
    p.add_argument("--replan", action="store_true",
                   help="between-slot plan hot-swap: SLO pressure "
                        "(deadline-at-risk vs the bucket's online p99) or "
                        "a sentinel anomaly latches a re-tune of the last "
                        "slot's bucket, persisted into --plan-db")
    p.add_argument("--plan-db", default="",
                   help="plan DB the --replan re-tune persists into")
    p.add_argument("--cpu", type=int, default=0,
                   help="force N virtual CPU devices")
    from ._bench_common import (add_live_flags, add_metrics_flags,
                                canonicalize_live_config, finish_live,
                                finish_metrics, make_live, start_metrics)
    add_metrics_flags(p)
    add_live_flags(p)
    args = p.parse_args(argv)
    if args.replan and not args.plan_db:
        # same contract as the campaign: the swap's APPLY is the DB
        # install — without a DB it would install nothing
        p.error("--replan persists the re-tuned plan into --plan-db; "
                "pass one (the swap would otherwise install nothing)")
    try:
        canonicalize_live_config(args)
    except (OSError, ValueError) as e:
        p.error(f"bad --live-config: {e}")

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    # jobs choose their dtype at drop time; a float64 job must not be
    # silently downcast by a daemon started before it existed
    jax.config.update("jax_enable_x64", True)
    rec = start_metrics(args, "serve")
    sentinel, status = make_live(args, rec, "serve")

    sched = build_scheduler(args, sentinel=sentinel, status=status)
    install_kill_hook(sched)
    # SIGTERM = drain: stop claiming, park lanes at the next segment
    # boundary, persist the queue, exit 0 (the systemd/k8s stop contract)
    signal.signal(signal.SIGTERM,
                  lambda signum, frame: sched.request_drain("sigterm"))

    summary = sched.serve()
    out = {
        "app": "serve",
        "serve_dir": args.serve_dir,
        "slot": args.slot,
        "quota": args.quota,
        "devices": len(sched.devices),
    }
    out.update({k: v for k, v in summary.items() if k != "results"})
    if isinstance(out.get("tenants_per_hour"), float):
        out["tenants_per_hour"] = round(out["tenants_per_hour"], 3)
    print(json.dumps(out, default=str))
    finish_live(rec, sentinel, status, outcome=summary["outcome"])
    finish_metrics(rec)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
