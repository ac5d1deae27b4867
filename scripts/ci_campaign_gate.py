#!/usr/bin/env python
"""CI multi-tenant campaign gate: batched == sequential, faults evict,
the compile cache serves, the ledger judges.

The executable acceptance proof of stencil_tpu/campaign/ on the
8-virtual-device CPU mesh (no TPU needed), B=4 tenants of 16^3:

1. parity + win: ``campaign --mode ab --check-parity`` must exit 0 with
   every tenant's batched final field bit-identical to its sequential
   run AND ``campaign_batched_over_sequential`` > 1.0 — the batched
   program earns its complexity on the smallest CI mesh, not just at
   B=64;
2. fault eviction: a clean campaign and one with
   ``nan@3:tenant=t1:repeat=always`` + ``--max-rollbacks 1``; the
   injected tenant must be EVICTED with the rc-43 evidence bundle under
   ``tenants/t1/`` while every surviving tenant's final snapshot is
   bit-identical to the clean campaign's (``ckpt_tool diff --data``
   per tenant dir) — eviction never stalls or corrupts the slot;
3. compile cache: two same-shape campaigns through ONE CompileCache —
   the second must run with ZERO new ``compile.build`` spans and every
   ``compile.cache_hit`` gauge pinned at 1 (the one-compiled-program-
   serves-every-slot claim, measured not asserted);
4. schema: every produced metrics file passes ``report --validate``
   (the campaign.*/compile.* vocabulary is NAME_FIELDS-gated) and the
   span table renders with the new ``--p99`` column;
5. ledger: two ab runs ingest under run1/run2 labels into a fresh
   ledger and ``perf_tool gate`` judges run2's
   ``campaign.batched_mcells_per_s`` (throughput leg: trips LOW) inside
   run1's band — the bench leg's cross-run regression sentinel, proven
   live.

Exit code 0 only if every stage holds. Run from the repo root:

  python scripts/ci_campaign_gate.py [--size 16] [--steps 6]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PY = sys.executable


def run(cmd, env=None, expect_rc=0, name=""):
    print(f"[campaign-gate] {name}: {' '.join(cmd)}", flush=True)
    e = dict(os.environ)
    if env:
        e.update(env)
    p = subprocess.run(cmd, env=e, cwd=REPO, capture_output=True, text=True)
    if p.returncode != expect_rc:
        print(p.stdout)
        print(p.stderr, file=sys.stderr)
        raise SystemExit(
            f"[campaign-gate] {name}: rc={p.returncode}, expected {expect_rc}")
    return p


def campaign(args, extra, name="", tenants=4):
    cmd = [
        PY, "-m", "stencil_tpu.apps.campaign", "--cpu", "8",
        "--tenants", str(tenants), "--slot", "4", "--size",
        str(args.size), "--steps", str(args.steps), "--chunk", "2",
    ] + extra
    p = run(cmd, name=name)
    return json.loads(p.stdout.strip().splitlines()[-1])


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--size", type=int, default=16)
    p.add_argument("--steps", type=int, default=6)
    args = p.parse_args()

    work = tempfile.mkdtemp(prefix="campaign-gate-")
    metrics = []
    try:
        # 1. parity + the batched win at B=4
        m1 = os.path.join(work, "m1.jsonl")
        metrics.append(m1)
        out = campaign(args, ["--mode", "ab", "--check-parity",
                              "--campaign-dir", os.path.join(work, "ab"),
                              "--metrics-out", m1], name="ab-parity")
        if out.get("parity") != "ok":
            raise SystemExit(f"[campaign-gate] parity: {out}")
        ratio = out["batched_over_sequential"]
        if not ratio > 1.0:
            raise SystemExit(
                f"[campaign-gate] batched did not beat sequential: "
                f"ratio={ratio} (batched {out['batched_mcells_per_s']} vs "
                f"sequential {out['sequential_mcells_per_s']} Mcells/s)")
        print(f"[campaign-gate] batched_over_sequential = {ratio}")

        # 2. fault eviction: evidence + survivors bit-identical; a 5th
        # tenant waits in the queue so the evicted lane is BACKFILLED
        clean_dir = os.path.join(work, "clean")
        inj_dir = os.path.join(work, "inj")
        campaign(args, ["--mode", "batched", "--campaign-dir", clean_dir,
                        "--ckpt-every", "2", "--max-rollbacks", "1"],
                 name="clean", tenants=5)
        m2 = os.path.join(work, "m2.jsonl")
        metrics.append(m2)
        out = campaign(args, ["--mode", "batched", "--campaign-dir",
                              inj_dir, "--ckpt-every", "2",
                              "--max-rollbacks", "1",
                              "--rollback-backoff", "0.01",
                              "--inject", "nan@3:tenant=t1:repeat=always",
                              "--metrics-out", m2], name="evict",
                       tenants=5)
        if out.get("evicted") != ["t1"]:
            raise SystemExit(f"[campaign-gate] expected t1 evicted: {out}")
        evidence = os.path.join(inj_dir, "tenants", "t1",
                                "fault-evidence.json")
        with open(evidence) as f:
            ev = json.load(f)
        if ev["rc"] != 43 or "max rollbacks" not in ev["reason"]:
            raise SystemExit(f"[campaign-gate] bad evidence bundle: {ev}")
        recs = [json.loads(l) for l in open(m2) if l.strip()]
        need = {"fault.injected", "health.fault", "recover.rollback",
                "campaign.evict", "campaign.backfill"}
        have = {r["name"] for r in recs}
        if not need <= have:
            raise SystemExit(
                f"[campaign-gate] metrics lack {sorted(need - have)}")
        for tid in ("t0", "t2", "t3", "t4"):
            run([PY, "-m", "stencil_tpu.apps.ckpt_tool", "diff",
                 os.path.join(clean_dir, "tenants", tid),
                 os.path.join(inj_dir, "tenants", tid), "--data"],
                name=f"diff-{tid}")

        # 3. compile cache: the second same-shape campaign is a pure hit
        m3 = os.path.join(work, "m3.jsonl")
        metrics.append(m3)
        code = f"""
import json
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
from stencil_tpu.obs import telemetry
from stencil_tpu.campaign import CampaignDriver, CompileCache, TenantJob
telemetry.configure(metrics_out={m3!r}, app="campaign-gate")
cache = CompileCache()
def jobs(s0):
    return [TenantJob(f"w{{s0}}-{{i}}", ({args.size},) * 3, {args.steps},
                      seed=s0 + i) for i in range(4)]
CampaignDriver(jobs(0), 4, {os.path.join(work, 'wave1')!r}, chunk=2,
               cache=cache).run()
first = dict(cache.stats())
CampaignDriver(jobs(50), 4, {os.path.join(work, 'wave2')!r}, chunk=2,
               cache=cache).run()
print(json.dumps({{"first": first, "second": cache.stats()}}))
"""
        p3 = run([PY, "-c", code], name="cache-waves")
        st = json.loads(p3.stdout.strip().splitlines()[-1])
        if st["second"]["misses"] != st["first"]["misses"]:
            raise SystemExit(
                f"[campaign-gate] second same-shape campaign recompiled: "
                f"{st}")
        recs = [json.loads(l) for l in open(m3) if l.strip()]
        builds = [r for r in recs if r["name"] == "compile.build"]
        hits = [r for r in recs if r["name"] == "compile.cache_hit"]
        if len(builds) != st["first"]["misses"]:
            raise SystemExit(f"[campaign-gate] {len(builds)} compile.build "
                             f"spans, expected {st['first']['misses']}")
        tail = [r["value"] for r in hits[st["first"]["misses"]
                                         + st["first"]["hits"]:]]
        if not tail or any(v != 1 for v in tail):
            raise SystemExit(
                f"[campaign-gate] second wave's compile.cache_hit gauges "
                f"not pinned at 1: {tail}")

        # 4. schema gate + the p99 span column renders
        run([PY, "-m", "stencil_tpu.apps.report"] + metrics + ["--validate"],
            name="report-validate")
        p99 = run([PY, "-m", "stencil_tpu.apps.report", m1, "--p99"],
                  name="report-p99")
        if "p99_s" not in p99.stdout:
            raise SystemExit("[campaign-gate] report --p99 lacks the "
                             "p99_s span column")

        # 5. the bench leg's sentinel, live: ingest two runs, judge run2
        m4 = os.path.join(work, "m4.jsonl")
        campaign(args, ["--mode", "ab", "--check-parity", "--campaign-dir",
                        os.path.join(work, "ab2"), "--metrics-out", m4],
                 name="ab-run2")
        ledger = os.path.join(work, "ledger.jsonl")
        for label, mfile in (("run1", m1), ("run2", m4)):
            run([PY, "-m", "stencil_tpu.apps.perf_tool", "ingest",
                 "--ledger", ledger, "--label", label, "--platform", "cpu",
                 mfile], name=f"ingest-{label}")
        g = run([PY, "-m", "stencil_tpu.apps.perf_tool", "gate",
                 "--ledger", ledger, "--label", "run2",
                 "--metric", "campaign.batched_mcells_per_s",
                 "--min-history", "1", "--rel-tol", "2.0"],
                name="perf-gate")
        if "PASS" not in g.stdout:
            raise SystemExit(f"[campaign-gate] sentinel did not PASS:\n"
                             f"{g.stdout}")

        print("[campaign-gate] PASS")
        return 0
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
