#!/usr/bin/env python
"""CI live-observability gate: mid-run detection, status snapshots, SLO.

The executable acceptance proof of ISSUE 12 (obs/live.py + obs/status.py
wired through the guarded loop and the campaign driver) on the
8-virtual-device CPU mesh — no TPU needed:

1. **mid-run anomaly**: jacobi3d 24^3 with two injected ``slow@N``
   faults and the live sentinel ON must emit ``anomaly.detected``
   *during* the run — the gate polls the atomic status snapshot while
   the child runs and must observe the ACTIVE anomaly (not just the
   post-mortem), detection must land within 3 chunks of the injection
   step, the anomaly must CLEAR once latencies normalize (final
   snapshot: 1 detected, 1 cleared, none active), ``replan.requested``
   must accompany the detection, and the exported trace must render the
   anomaly instant markers;
2. **clean-run silence**: the same config without the injection emits
   ZERO anomaly/replan records and a zero-anomaly final snapshot;
3. **SLO tracking**: a campaign with one deadline-doomed tenant
   (``--deadline-ms t1=0.0001``) must emit ``slo.violation`` for t1
   ONLY, finish every tenant (a breach is evidence, not an eviction),
   show t1 as violated in the status lane table, and render the
   ``slo.violation`` instant marker in its trace;
4. **schema + ledger**: every record passes ``report --validate``; both
   jacobi runs ingest into a fresh ledger where ``live.anomaly_count``
   trends 1 -> 0 and ``perf_tool trend --json`` archives the
   machine-readable trajectory.

Exit 0 only if every stage holds. Run from the repo root:

  python scripts/ci_live_gate.py [--size 24] [--out-dir DIR]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PY = sys.executable

# injections land AFTER the sentinel's min_history warmup (chunks end at
# 2,4,6,8 with --health-every 2, default min_history 4) so detection is
# judged at the first slow chunk; the second slow keeps the anomaly
# ACTIVE long enough for the status poll to observe it mid-run
ITERS = 14
HEALTH_EVERY = 2
SLOW_STEPS = (9, 10)
SLOW_SECONDS = (12.0, 8.0)
# "within 3 chunks of injection": chunks here are <= HEALTH_EVERY steps
DETECT_WINDOW_STEPS = 3 * HEALTH_EVERY


def run(cmd, expect_rc=0, name="", **kw):
    print(f"[live-gate] {name}: {' '.join(cmd)}", flush=True)
    p = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True, **kw)
    if p.returncode != expect_rc:
        print(p.stdout)
        print(p.stderr, file=sys.stderr)
        raise SystemExit(
            f"[live-gate] {name}: rc={p.returncode}, expected {expect_rc}")
    return p


def load_records(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def by_name(records, name):
    return [r for r in records if r["name"] == name]


def instant_markers(trace_path):
    with open(trace_path) as f:
        tr = json.load(f)
    return {e["name"] for e in tr["traceEvents"] if e.get("ph") == "i"}


def jacobi_cmd(args, metrics, status, inject=""):
    cmd = [
        PY, "-m", "stencil_tpu.apps.jacobi3d", "--cpu", "8",
        "--x", str(args.size), "--y", str(args.size), "--z", str(args.size),
        "--iters", str(ITERS), "--health-every", str(HEALTH_EVERY),
        "--metrics-out", metrics, "--status-file", status,
        "--live-sentinel",
    ]
    if inject:
        cmd += ["--inject", inject]
    return cmd


def poll_status_while(proc, status_path, observed):
    """Collect status snapshots while ``proc`` runs (the LIVE half of the
    proof: the anomaly must be visible before the run ends)."""
    while proc.poll() is None:
        try:
            with open(status_path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            doc = None  # not written yet / mid-rename on exotic FS
        if doc:
            a = doc.get("anomalies") or {}
            observed.append({
                "step": doc.get("step"),
                "active": [ev.get("metric") for ev in a.get("active") or []],
                "detected": a.get("detected", 0),
                "cleared": a.get("cleared", 0),
            })
        time.sleep(0.1)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--size", type=int, default=24)
    p.add_argument("--out-dir", default="",
                   help="keep traces + trend artifact here for CI upload "
                        "(default: a temp dir, removed)")
    args = p.parse_args()

    work = tempfile.mkdtemp(prefix="live-gate-")
    out_dir = os.path.abspath(args.out_dir) if args.out_dir else work
    os.makedirs(out_dir, exist_ok=True)
    try:
        # ---- 1. mid-run anomaly detection ------------------------------------
        m_live = os.path.join(work, "m_live.jsonl")
        st_live = os.path.join(out_dir, "status-live.json")
        inject = ",".join(f"slow@{s}:seconds={sec:g}"
                          for s, sec in zip(SLOW_STEPS, SLOW_SECONDS))
        cmd = jacobi_cmd(args, m_live, st_live, inject=inject)
        print(f"[live-gate] anomaly-run (polled): {' '.join(cmd)}",
              flush=True)
        # child output goes to FILES, not pipes: the poll loop never
        # drains a pipe, so a chatty child (debug logging, jax warnings)
        # would fill the OS buffer, block on write, and deadlock the
        # gate — the lesson watchdog.supervise encodes
        out_path = os.path.join(work, "anomaly-run.log")
        with open(out_path, "w") as log_f:
            proc = subprocess.Popen(cmd, cwd=REPO, stdout=log_f,
                                    stderr=subprocess.STDOUT, text=True)
            observed = []
            poll_status_while(proc, st_live, observed)
            proc.wait()
        if proc.returncode != 0:
            with open(out_path) as f:
                print(f.read()[-8000:], file=sys.stderr)
            raise SystemExit(f"[live-gate] anomaly-run rc={proc.returncode}")
        live_polls = [o for o in observed if o["active"]]
        if not live_polls:
            raise SystemExit(
                "[live-gate] the status snapshot NEVER showed an active "
                f"anomaly while the run executed (polled {len(observed)} "
                "snapshots) — detection was not live")
        if not any("step.latency_s" in m for o in live_polls
                   for m in o["active"]):
            raise SystemExit(f"[live-gate] active anomalies never named "
                             f"step.latency_s: {live_polls[:3]}")
        print(f"[live-gate] observed the ACTIVE anomaly in "
              f"{len(live_polls)}/{len(observed)} mid-run polls")

        with open(st_live) as f:
            final = json.load(f)
        a = final.get("anomalies") or {}
        if (a.get("detected") != 1 or a.get("cleared") != 1
                or a.get("active")):
            raise SystemExit(f"[live-gate] final snapshot must show the "
                             f"detect AND the clear: {a}")
        if final.get("outcome") != "done":
            raise SystemExit(f"[live-gate] final outcome: {final.get('outcome')}")

        recs = load_records(m_live)
        det = by_name(recs, "anomaly.detected")
        clr = by_name(recs, "anomaly.cleared")
        rep = by_name(recs, "replan.requested")
        inj = [r for r in by_name(recs, "fault.injected")
               if r.get("fault_kind") == "slow"]
        if len(det) != 1 or len(clr) != 1 or not rep:
            raise SystemExit(f"[live-gate] want 1 detect / 1 clear / >=1 "
                             f"replan, got {len(det)}/{len(clr)}/{len(rep)}")
        first_inject = min(r["step"] for r in inj)
        delta = det[0]["step"] - first_inject
        if not 0 <= delta <= DETECT_WINDOW_STEPS:
            raise SystemExit(
                f"[live-gate] detection at step {det[0]['step']} is not "
                f"within {DETECT_WINDOW_STEPS} steps (3 chunks) of the "
                f"injection at {first_inject}")
        if clr[0]["step"] <= det[0]["step"]:
            raise SystemExit("[live-gate] clear must follow the detect")
        print(f"[live-gate] detected at step {det[0]['step']} "
              f"(injection {first_inject}, +{delta} steps), cleared at "
              f"{clr[0]['step']}")

        run([PY, "-m", "stencil_tpu.apps.report", m_live, "--validate"],
            name="validate-live")
        trace_live = os.path.join(out_dir, "trace-live.json")
        run([PY, "-m", "stencil_tpu.apps.report", m_live,
             "--trace-out", trace_live], name="trace-live")
        need = {"anomaly.detected", "anomaly.cleared", "replan.requested",
                "fault.injected"}
        inst = instant_markers(trace_live)
        if not need <= inst:
            raise SystemExit(f"[live-gate] trace lacks instant markers "
                             f"{sorted(need - inst)} (has {sorted(inst)})")

        # ---- 2. clean-run silence --------------------------------------------
        m_clean = os.path.join(work, "m_clean.jsonl")
        st_clean = os.path.join(work, "status-clean.json")
        run(jacobi_cmd(args, m_clean, st_clean), name="clean-run")
        recs = load_records(m_clean)
        noisy = [r["name"] for r in recs
                 if r["name"].startswith(("anomaly.", "replan.", "slo."))]
        if noisy:
            raise SystemExit(f"[live-gate] the clean run emitted anomaly "
                             f"records: {noisy}")
        with open(st_clean) as f:
            a = json.load(f).get("anomalies") or {}
        if a.get("detected") != 0 or a.get("active"):
            raise SystemExit(f"[live-gate] clean snapshot not clean: {a}")
        run([PY, "-m", "stencil_tpu.apps.report", m_clean, "--validate"],
            name="validate-clean")
        print("[live-gate] clean run: zero anomaly records, clean snapshot")

        # ---- 3. campaign SLO -------------------------------------------------
        m_camp = os.path.join(work, "m_camp.jsonl")
        st_camp = os.path.join(out_dir, "status-campaign.json")
        g = run([PY, "-m", "stencil_tpu.apps.campaign", "--cpu", "8",
                 "--tenants", "4", "--slot", "4", "--size", "16",
                 "--steps", "8", "--chunk", "2", "--mode", "batched",
                 "--metrics-out", m_camp, "--status-file", st_camp,
                 "--live-sentinel", "--deadline-ms", "t1=0.0001"],
                name="campaign-slo")
        summary = json.loads(g.stdout.strip().splitlines()[-1])
        if summary.get("slo_violations") != ["t1"]:
            raise SystemExit(f"[live-gate] want slo_violations == ['t1'], "
                             f"got {summary.get('slo_violations')}")
        if summary.get("evicted"):
            raise SystemExit("[live-gate] an SLO breach must not evict: "
                             f"{summary['evicted']}")
        recs = load_records(m_camp)
        viol = by_name(recs, "slo.violation")
        if not viol or {r["tenant"] for r in viol} != {"t1"}:
            raise SystemExit(f"[live-gate] slo.violation must name t1 and "
                             f"ONLY t1: {[r.get('tenant') for r in viol]}")
        with open(st_camp) as f:
            camp = json.load(f)
        lanes = {ln.get("tenant"): ln for ln in camp.get("lanes") or []}
        if lanes.get("t1", {}).get("slo") != "violated":
            raise SystemExit(f"[live-gate] status lanes must show t1 "
                             f"violated: {camp.get('lanes')}")
        clean_lanes = [t for t, ln in lanes.items()
                       if t not in (None, "t1") and ln.get("slo") == "violated"]
        if clean_lanes:
            raise SystemExit(f"[live-gate] survivors must stay clean, but "
                             f"{clean_lanes} read violated")
        run([PY, "-m", "stencil_tpu.apps.report", m_camp, "--validate"],
            name="validate-campaign")
        trace_camp = os.path.join(out_dir, "trace-campaign.json")
        run([PY, "-m", "stencil_tpu.apps.report", m_camp,
             "--trace-out", trace_camp], name="trace-campaign")
        if "slo.violation" not in instant_markers(trace_camp):
            raise SystemExit("[live-gate] campaign trace lacks the "
                             "slo.violation instant marker")
        print("[live-gate] campaign: t1 violated, survivors clean, "
              "marker rendered")

        # ---- 4. ledger + trend --json ---------------------------------------
        ledger = os.path.join(work, "ledger.jsonl")
        for metrics, label in ((m_live, "live1"), (m_clean, "clean1")):
            run([PY, "-m", "stencil_tpu.apps.perf_tool", "ingest",
                 "--ledger", ledger, "--label", label, "--platform", "cpu",
                 metrics], name=f"ingest-{label}")
        trend = os.path.join(out_dir, "trend.json")
        g = run([PY, "-m", "stencil_tpu.apps.perf_tool", "trend",
                 "--ledger", ledger, "--json", "--out", trend,
                 "--metric", "live.anomaly_count"], name="trend-json")
        doc = json.loads(g.stdout)
        legs = [leg for leg in doc["legs"]
                if leg["metric"] == "live.anomaly_count"]
        if len(legs) != 1:
            raise SystemExit(f"[live-gate] live.anomaly_count must trend as "
                             f"ONE leg (both runs share a config "
                             f"fingerprint): {[(leg['metric'], leg['config']) for leg in doc['legs']]}")
        traj = {pt["label"]: pt["value"] for pt in legs[0]["points"]}
        if traj != {"live1": 1.0, "clean1": 0.0}:
            raise SystemExit(f"[live-gate] anomaly count must trend "
                             f"1 -> 0 across the runs: {traj}")
        print("[live-gate] ledger trends live.anomaly_count 1 -> 0; "
              "trend --json archived")

        print(f"[live-gate] PASS (artifacts: {out_dir})")
        return 0
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
