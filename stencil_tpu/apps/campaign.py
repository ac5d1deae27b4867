"""campaign — multi-tenant batched serving of many small domains.

The CLI over ``stencil_tpu/campaign/``: queue N tenant jobs (independent
periodic jacobi boxes, seeded per-tenant initial fields), serve them in
fixed-size batch slots under one compiled program per shape bucket
(``--mode batched``), one at a time through the standard single-domain
machinery (``--mode sequential``), or both back-to-back with the
tracked ratio and an optional bit-parity check (``--mode ab`` — the
``campaign_batched_over_sequential`` bench leg and the CI campaign
gate's harness).

Prints ONE JSON summary line (aggregate Mcells/s, p50/p99 per-tenant
step latency, evictions, compile-cache hits) and records the same as
gauges when ``--metrics-out`` is set:

- ``campaign.batched_mcells_per_s`` / ``campaign.sequential_mcells_per_s``
- ``campaign.batched_p50_step_s`` / ``..._p99_step_s`` (+ sequential)
- ``campaign.batched_over_sequential`` (ab mode; > 1 = batching wins)

Fault handling rides the driver: ``--inject nan@3:tenant=t2:repeat=always``
drives one tenant to the rc-43 ``fault`` outcome — it is evicted (its
lane backfilled from the queue) while its siblings keep stepping, and
its evidence bundle + last-healthy snapshot land under
``<campaign-dir>/tenants/t2/``.

Usage: python -m stencil_tpu.apps.campaign --cpu 8 --tenants 8 --slot 4 \
           --size 16 --steps 6 --mode ab --check-parity
"""

from __future__ import annotations

import argparse
import json
import math
import os
import tempfile
from typing import Optional

import numpy as np
import jax

from ..obs import telemetry
from ..utils import logging as log


def _finite_gauge(rec, name: str, value: float, **tags) -> None:
    if value is not None and math.isfinite(value):
        rec.gauge(name, value, **tags)


def _round6(value: float):
    """None for a non-finite sample (a latency-less run — e.g. 0 steps
    or everything revived-complete) so the one-line summary stays strict
    JSON: ``json.dumps`` would happily emit a bare ``NaN`` token."""
    return round(value, 6) if math.isfinite(value) else None


def parse_deadlines(spec: str) -> dict:
    """``--deadline-ms`` grammar: a bare number applies to every tenant
    (``"50"``), comma-separated ``tid=ms`` pairs pin individual tenants
    (``"t1=0.5,t3=100"``); ``*=ms`` mixes a default with overrides.
    Raises ValueError on anything else — a mistyped SLO must never run
    the campaign silently un-judged (the fault-spec discipline)."""
    out: dict = {}
    if not spec:
        return out
    for item in spec.split(","):
        item = item.strip()
        if not item:
            continue
        if "=" in item:
            tid, ms = item.split("=", 1)
            out[tid.strip()] = float(ms)
        else:
            out["*"] = float(item)
    for tid, ms in out.items():
        if not math.isfinite(ms) or ms <= 0:
            # float('nan') parses fine but p99 > nan is always False —
            # the tenant would run with its SLO silently un-judged
            raise ValueError(f"deadline for {tid!r} must be a positive "
                             f"finite number of ms, got {ms!r}")
    return out


def build_jobs(args) -> list:
    from ..campaign import TenantJob

    # main() stashes the validated dict; a programmatic caller without
    # it falls back to parsing the raw flag
    deadlines = getattr(args, "_deadlines", None)
    if deadlines is None:
        deadlines = parse_deadlines(args.deadline_ms)
    return [
        TenantJob(f"t{i}", (args.size, args.size, args.size), args.steps,
                  args.dtype, seed=args.init_seed + i,
                  workload=args.workload,
                  deadline_ms=deadlines.get(f"t{i}", deadlines.get("*")))
        for i in range(args.tenants)
    ]


def run_modes(args, campaign_dir: str, sentinel=None, status=None) -> dict:
    from ..campaign import CampaignDriver, CompileCache, run_sequential

    devices = jax.devices()[: args.cpu] if args.cpu else jax.devices()
    jobs = build_jobs(args)
    rec = telemetry.get()
    out: dict = {
        "app": "campaign",
        "mode": args.mode,
        "tenants": args.tenants,
        "slot": args.slot,
        "size": args.size,
        "steps": args.steps,
        "dtype": args.dtype,
        "devices": len(devices),
        "campaign_dir": campaign_dir,
    }

    seq = None
    if args.mode in ("sequential", "ab"):
        seq = run_sequential(jobs, devices=devices, chunk=args.chunk)
        out["sequential_mcells_per_s"] = round(
            seq["aggregate_mcells_per_s"], 3)
        out["sequential_p50_step_s"] = _round6(seq["p50_step_s"])
        out["sequential_p99_step_s"] = _round6(seq["p99_step_s"])
        _finite_gauge(rec, "campaign.sequential_mcells_per_s",
                      seq["aggregate_mcells_per_s"], phase="step")
        _finite_gauge(rec, "campaign.sequential_p50_step_s",
                      seq["p50_step_s"], phase="step", unit="s")
        _finite_gauge(rec, "campaign.sequential_p99_step_s",
                      seq["p99_step_s"], phase="step", unit="s")

    bat = None
    if args.mode in ("batched", "ab"):
        cache = CompileCache()
        controller = None
        if getattr(args, "replan", False) and sentinel is not None:
            # the campaign's between-slot swap: a latched
            # replan.requested re-tunes the bucket's exchange-plan
            # config (force=True, static-only — slots must not stall on
            # probes) and persists the verdict into --plan-db, where
            # every later plan consumer replays it. The slot programs
            # themselves are bucket-keyed (batch-axis, zero-collective):
            # the apply is the DB install, not a mid-slot reshard.
            from ..campaign.driver import WORKLOADS
            from ..geometry import Dim3, Radius
            from ..plan.replan import ReplanController

            wl = WORKLOADS[args.workload]
            nq = len(wl.quantity_names(args.dtype))
            radius = Radius.constant(wl.default_radius)

            def retune_fn():
                from ..plan.autotune import autotune as _plan_autotune

                res = _plan_autotune(
                    Dim3(args.size, args.size, args.size), radius,
                    [args.dtype] * nq, devices=devices,
                    db_path=args.plan_db or None, probe=False, force=True,
                )
                return res.choice

            controller = ReplanController(
                retune_fn, lambda choice, st: None, sentinel=sentinel)
            sentinel.on_replan = controller.request
        elif getattr(args, "replan", False):
            log.warn("campaign: --replan needs --live-sentinel; ignoring")
        drv = CampaignDriver(
            jobs, args.slot, campaign_dir,
            devices=devices, chunk=args.chunk,
            ckpt_every=args.ckpt_every, ckpt_keep=args.ckpt_keep,
            health_every=args.health_every, max_abs=args.max_abs or None,
            max_rollbacks=args.max_rollbacks,
            rollback_backoff=args.rollback_backoff,
            inject=args.inject or None, inject_seed=args.inject_seed,
            resume=args.resume, cache=cache, use_pallas=args.use_pallas,
            sentinel=sentinel, status=status, replan=controller,
        )
        bat = drv.run()
        if controller is not None:
            out["replans_applied"] = controller.swaps
            out["replans_rejected"] = controller.rejected
        out["batched_mcells_per_s"] = round(
            bat["aggregate_mcells_per_s"], 3)
        out["batched_p50_step_s"] = _round6(bat["p50_step_s"])
        out["batched_p99_step_s"] = _round6(bat["p99_step_s"])
        out["slots"] = bat["slots"]
        out["evicted"] = bat["evicted"]
        out["slo_violations"] = bat["slo_violations"]
        out["anomalies"] = bat["anomalies"]
        out["cache"] = bat["cache"]
        _finite_gauge(rec, "campaign.batched_mcells_per_s",
                      bat["aggregate_mcells_per_s"], phase="step")
        _finite_gauge(rec, "campaign.batched_p50_step_s",
                      bat["p50_step_s"], phase="step", unit="s")
        _finite_gauge(rec, "campaign.batched_p99_step_s",
                      bat["p99_step_s"], phase="step", unit="s")

    if args.mode == "ab":
        ratio = (bat["aggregate_mcells_per_s"]
                 / seq["aggregate_mcells_per_s"]
                 if seq["aggregate_mcells_per_s"] > 0 else 0.0)
        out["batched_over_sequential"] = round(ratio, 3)
        _finite_gauge(rec, "campaign.batched_over_sequential", ratio,
                      phase="step")
        if args.check_parity:
            mismatches = []
            for tid, br in bat["results"].items():
                if br.outcome != "done":
                    continue  # evicted tenants diverge by construction
                sr = seq["results"].get(tid)
                if sr is None or sr.final.tobytes() != br.final.tobytes():
                    mismatches.append(tid)
            out["parity"] = "ok" if not mismatches else "MISMATCH"
            out["parity_mismatches"] = mismatches
            if mismatches:
                log.error(f"campaign: batched results differ from "
                          f"sequential for {mismatches}")
    return out


def main(argv: Optional[list] = None) -> int:
    from ..parallel.distributed import maybe_init_from_env
    maybe_init_from_env()
    from ..utils.jax_cache import configure_compile_cache
    configure_compile_cache()
    p = argparse.ArgumentParser(
        description="multi-tenant batched campaign driver")
    p.add_argument("--tenants", type=int, default=8,
                   help="number of queued tenant jobs")
    p.add_argument("--slot", type=int, default=4,
                   help="batch-slot size B: tenants stepped per compiled "
                        "program (padded with dead tenants when the queue "
                        "drains)")
    p.add_argument("--size", type=int, default=16,
                   help="per-tenant cubic domain edge")
    p.add_argument("--steps", type=int, default=6,
                   help="steps per tenant")
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "float64"])
    p.add_argument("--workload", choices=["jacobi", "astaroth"],
                   default="jacobi",
                   help="tenant physics: jacobi (single-quantity heat) or "
                        "astaroth (8-field MHD via the batched RK3 step); "
                        "astaroth serves --mode batched only (its "
                        "sequential baseline is a B=1 slot)")
    p.add_argument("--chunk", type=int, default=2,
                   help="fused steps per dispatch")
    p.add_argument("--mode", choices=["batched", "sequential", "ab"],
                   default="batched",
                   help="ab = sequential baseline then batched, with the "
                        "campaign_batched_over_sequential ratio")
    p.add_argument("--check-parity", action="store_true",
                   help="(ab) exit 1 unless every completed tenant's final "
                        "field is bit-identical between modes")
    p.add_argument("--campaign-dir", default="",
                   help="per-tenant durable state root (default: a fresh "
                        "temp dir)")
    p.add_argument("--ckpt-every", type=int, default=0,
                   help="checkpoint every active lane every N slot steps "
                        "(0 = only final/eviction snapshots)")
    p.add_argument("--ckpt-keep", type=int, default=3)
    p.add_argument("--resume", action="store_true",
                   help="pack tenants from their newest valid snapshot "
                        "(revives evicted tenants)")
    p.add_argument("--health-every", type=int, default=0,
                   help="per-lane health-check cadence in slot steps "
                        "(default: every fused chunk)")
    p.add_argument("--max-abs", type=float, default=0.0,
                   help="divergence ceiling on max|u| (0 = none)")
    p.add_argument("--max-rollbacks", type=int, default=2,
                   help="rollbacks per faulting step before the tenant is "
                        "EVICTED with the rc-43 evidence bundle")
    p.add_argument("--rollback-backoff", type=float, default=0.05)
    p.add_argument("--inject", default="",
                   help="per-tenant fault spec, e.g. "
                        "'nan@3:tenant=t2:repeat=always' (campaign/inject)")
    p.add_argument("--inject-seed", type=int, default=None)
    p.add_argument("--init-seed", type=int, default=0,
                   help="tenant i's initial field is seeded init-seed + i")
    p.add_argument("--replan", action="store_true",
                   help="between-slot plan hot-swap (needs "
                        "--live-sentinel, batched/ab mode): a latched "
                        "replan.requested re-tunes the bucket's exchange "
                        "plan at the next slot boundary and persists it "
                        "to --plan-db (replan.applied/rejected records)")
    p.add_argument("--plan-db", default="",
                   help="plan DB the --replan re-tune persists into")
    p.add_argument("--use-pallas", action="store_true",
                   help="batched Pallas fast path (TPU; aligned layout)")
    p.add_argument("--deadline-ms", default="",
                   help="per-step latency SLO: a bare number applies to "
                        "all tenants, 'tid=ms' pairs pin individuals "
                        "('t1=0.5,t3=100'); a tenant whose ONLINE p99 "
                        "exceeds its deadline emits one slo.violation "
                        "record and shows as violated in the status lanes")
    p.add_argument("--cpu", type=int, default=0,
                   help="force N virtual CPU devices")
    from ._bench_common import (add_live_flags, add_metrics_flags,
                                finish_live, finish_metrics, make_live,
                                start_metrics)
    add_metrics_flags(p)
    add_live_flags(p)
    args = p.parse_args(argv)
    try:
        deadlines = parse_deadlines(args.deadline_ms)
    except ValueError as e:
        p.error(f"bad --deadline-ms: {e}")
    args._deadlines = deadlines  # parsed once; build_jobs reuses it
    known = {f"t{i}" for i in range(args.tenants)} | {"*"}
    unknown = sorted(set(deadlines) - known)
    if unknown:
        # a mistyped tenant id must not run the campaign un-judged
        p.error(f"--deadline-ms names unknown tenant(s) {unknown} "
                f"(tenants are t0..t{args.tenants - 1})")
    if args.mode == "sequential":
        # the live layer rides the guarded batched driver; accepting the
        # flags here would silently observe nothing
        if args.live_sentinel:
            p.error("--live-sentinel rides the batched driver; --mode "
                    "sequential runs outside it (use batched or ab)")
        if args.replan:
            # same slot-boundary machinery: sequential serving has no
            # slots to swap between
            p.error("--replan swaps plans at slot boundaries of the "
                    "batched driver; --mode sequential has none "
                    "(use batched or ab)")
        if args.status_file:
            # may come from the globally-exported STENCIL_STATUS_FILE
            # env var rather than the command line — warn + ignore
            # instead of breaking every sequential invocation in an
            # environment that sets it for the other apps
            log.warn("campaign: --status-file/STENCIL_STATUS_FILE is "
                     "ignored in --mode sequential (status snapshots "
                     "ride the guarded batched driver)")
            args.status_file = ""
    if args.replan and not args.plan_db:
        # the campaign swap's APPLY is the DB install — without a DB the
        # re-tune would persist nowhere, no slot program would ever
        # consult it, and replan.applied would claim a swap that did
        # nothing (the sibling misuses error loudly; so does this one)
        p.error("--replan persists the re-tuned plan into --plan-db; "
                "pass one (the swap would otherwise install nothing)")
    from ._bench_common import canonicalize_live_config
    try:
        canonicalize_live_config(args)
    except (OSError, ValueError) as e:
        p.error(f"bad --live-config: {e}")

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    if args.dtype == "float64":
        jax.config.update("jax_enable_x64", True)
    if args.workload == "astaroth" and args.mode != "batched":
        p.error("--workload astaroth serves --mode batched only (the "
                "sequential baseline is a B=1 slot through the driver)")
    if args.workload == "astaroth" and args.use_pallas:
        p.error("--workload astaroth runs the XLA batched step; the "
                "batched Pallas astaroth substep is a hardware-session "
                "follow-up (drop --use-pallas)")
    rec = start_metrics(args, "campaign")
    sentinel, status = make_live(args, rec, "campaign")

    campaign_dir = args.campaign_dir or tempfile.mkdtemp(prefix="campaign-")
    out = run_modes(args, campaign_dir, sentinel=sentinel, status=status)
    print(json.dumps(out, default=str))
    # gauge=False: the driver's run() already recorded live.anomaly_count
    finish_live(rec, sentinel, status, outcome="done", gauge=False)
    finish_metrics(rec)
    if out.get("parity") == "MISMATCH":
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
