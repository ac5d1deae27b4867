#!/usr/bin/env python
"""CI serving gate: continuous batching, drain, revival, SLO replan.

The executable acceptance proof of ISSUE 19 (stencil_tpu/serve/ — the
always-on campaign serving daemon) on the 8-virtual-device CPU mesh,
no TPU needed:

1. **continuous batching**: 8 pre-dropped jobs overflow a ``--slot 4``
   daemon; the gate polls the atomic status snapshot, and the moment a
   slot is observed RUNNING it drops a 9th job into the live intake —
   the final summary must show exactly ONE slot, every job retired,
   and >= 5 backfills (jobs entered mid-slot, no slot-wide barrier);
   the metric stream must show the late job's ``serve.admitted``
   AFTER ``campaign.slot``, and a mid-run status poll must see the
   queue's ``admitted`` count reach 9 while the slot is still going;
2. **SIGTERM drain**: a daemon mid-slot on 3 long jobs receives
   SIGTERM and must exit 0 with outcome ``drained``, every trajectory
   parked mid-flight (``serve.parked`` with 0 < step < steps, zero
   retirements), and a restarted daemon revives all 3 from
   ``serve-state.json`` and finishes them — each job retires exactly
   once across both runs;
3. **kill -> revive bit-identical**: the daemon runs under the PR 3
   watchdog (``obs/watchdog.supervise``) with the injected kill hook
   (``STENCIL_SERVE_KILL_AFTER_RETIRE=2`` -> ``os._exit(17)``); the
   watchdog classifies the death as a CRASH, the revival attempt
   finishes the queue, no retired job is ever re-run, and EVERY
   tenant's final snapshot is bit-identical to an uninterrupted
   reference serve of the same seeded load (``ckpt_tool diff --data``);
4. **SLO-pressure replan**: deadline-doomed jobs (no admission ledger,
   so they are admitted and the pressure builds online) must emit
   ``replan.requested`` with reason ``slo-pressure`` and hot-swap a
   plan between slots (``replan.applied``, trigger ``slo-pressure``)
   persisted into ``--plan-db``;
5. **priced preemption, bit-identical** (ISSUE 20): a high-priority
   deadline job dropped mid-slot against a seeded pricing ledger must
   preempt the running slot at a chunk boundary (``serve.preempted``
   with ``gain_ms > resume_cost_ms``, both victims ``serve.parked``
   with reason ``preempt`` mid-flight), and every tenant's final
   snapshot — victims included — must be bit-identical to an
   undisturbed ``--no-preempt`` reference serve of the same seeded
   load (``ckpt_tool diff --data``);
6. **elastic slot width**: a ``--slot-min 2 --slot-max 8`` daemon
   grows a running width-2 slot when 6 same-bucket jobs land mid-slot
   (``serve.resized`` reason ``grow``, lanes parked with reason
   ``resize``), and a later wave revisiting the grown width compiles
   NOTHING new — every ``compile.build`` key (which carries the slot
   width as ``batch``) is built exactly once across the daemon's life;
7. every metrics file passes ``report --validate``.

Exit 0 only if every stage holds. Run from the repo root:

  python scripts/ci_serve_gate.py [--out-dir DIR]
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PY = sys.executable
KILL_ENV = "STENCIL_SERVE_KILL_AFTER_RETIRE"

# one compiled bucket for stage 1: the late drop must be backfillable
# into the already-running slot's program
SIZE = 14


def run(cmd, expect_rc=0, name="", env=None):
    print(f"[serve-gate] {name}: {' '.join(cmd)}", flush=True)
    p = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True,
                       env=env)
    if p.returncode != expect_rc:
        print(p.stdout)
        print(p.stderr, file=sys.stderr)
        raise SystemExit(
            f"[serve-gate] {name}: rc={p.returncode}, expected {expect_rc}")
    return p


def load_records(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def by_name(records, name):
    return [r for r in records if r["name"] == name]


def summary_of(stdout_text, name):
    """The daemon's one-line JSON summary (the last JSON line printed)."""
    for line in reversed(stdout_text.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise SystemExit(f"[serve-gate] {name}: no JSON summary in stdout")


def loadgen(serve_dir, *, jobs, steps, seed, tenants=2, size=SIZE,
            rate=0.0, prefix="j", deadline_ms=0.0):
    cmd = [PY, os.path.join(REPO, "scripts", "serve_loadgen.py"),
           "--serve-dir", serve_dir, "--jobs", str(jobs),
           "--steps", str(steps), "--seed", str(seed),
           "--tenants", str(tenants), "--size", str(size),
           "--rate", str(rate), "--prefix", prefix]
    if deadline_ms > 0:
        cmd += ["--deadline-ms", str(deadline_ms)]
    return run(cmd, name=f"loadgen-{prefix}{seed}")


def serve_cmd(serve_dir, metrics, status, *, slot=4, max_idle_s=2.0,
              extra=()):
    return [PY, "-m", "stencil_tpu.apps.serve", "--serve-dir", serve_dir,
            "--cpu", "8", "--slot", str(slot), "--chunk", "2",
            "--poll-s", "0.05", "--max-idle-s", str(max_idle_s),
            "--metrics-out", metrics, "--status-file", status,
            *extra]


def read_status(path):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None  # not written yet / mid-rename on exotic FS


def newest_snapshot(serve_dir, tid):
    d = os.path.join(serve_dir, "campaign", "tenants", tid)
    steps = [s for s in os.listdir(d) if s.startswith("step-")]
    if not steps:
        raise SystemExit(f"[serve-gate] no snapshots under {d}")
    return os.path.join(
        d, max(steps, key=lambda s: int(s.split("-", 1)[1])))


def retired_jobs(*metric_paths):
    out = []
    for path in metric_paths:
        out.extend(r["job"] for r in by_name(load_records(path),
                                             "serve.retired"))
    return out


def drop_doc(serve_dir, doc):
    """Atomically drop one job document (the loadgen write contract;
    used directly when a stage needs a field loadgen has no flag for,
    e.g. an explicit priority)."""
    incoming = os.path.join(serve_dir, "jobs", "incoming")
    os.makedirs(incoming, exist_ok=True)
    tmp = os.path.join(incoming, f".tmp-{doc['job']}-{os.getpid()}")
    with open(tmp, "w") as f:
        json.dump(doc, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, os.path.join(incoming, f"{doc['job']}.json"))


def seed_pricing_ledger(path, prices):
    """Seed ``serve.step_p99_ms`` bucket priors WITHOUT importing
    stencil_tpu (the gate process never pays the jax import): plain v1
    rows in the obs/ledger.py schema, keyed by ``detail.bucket`` —
    exactly what BucketPricer loads."""
    with open(path, "w") as f:
        for i, (bucket, ms) in enumerate(sorted(prices.items())):
            f.write(json.dumps({
                "v": 1, "kind": "perf-ledger",
                "metric": "serve.step_p99_ms", "value": float(ms),
                "unit": "ms", "platform": "cpu",
                "config": f"seed-{bucket}", "rev": None, "label": "seed",
                "source": "serve", "t": float(i + 1), "run": None,
                "detail": {"bucket": bucket, "samples": 8},
            }, sort_keys=True) + "\n")


def poll_daemon(cmd, status_path, out_path, err_path, on_status):
    """Run a daemon to completion, feeding every status snapshot to
    ``on_status`` (output to FILES, not pipes — the stage-1 deadlock
    rule). Returns the daemon's JSON summary."""
    print(f"[serve-gate] daemon (polled): {' '.join(cmd)}", flush=True)
    with open(out_path, "w") as out_f, open(err_path, "w") as err_f:
        proc = subprocess.Popen(cmd, cwd=REPO, stdout=out_f, stderr=err_f,
                                text=True)
        while proc.poll() is None:
            doc = read_status(status_path)
            if doc:
                on_status(doc)
            time.sleep(0.05)
        proc.wait()
    if proc.returncode != 0:
        with open(err_path) as f:
            print(f.read()[-8000:], file=sys.stderr)
        raise SystemExit(f"[serve-gate] polled daemon rc={proc.returncode}")
    with open(out_path) as f:
        return summary_of(f.read(), os.path.basename(out_path))


def stage1_continuous_batching(work):
    sdir = os.path.join(work, "s1")
    m1 = os.path.join(work, "m1.jsonl")
    st1 = os.path.join(work, "status1.json")
    loadgen(sdir, jobs=8, steps=12, seed=7, tenants=3)
    cmd = serve_cmd(sdir, m1, st1)
    print(f"[serve-gate] daemon (polled): {' '.join(cmd)}", flush=True)
    # child output goes to FILES, not pipes: the poll loop never drains
    # a pipe, so a chatty child would fill the OS buffer and deadlock
    # the gate (the lesson watchdog.supervise encodes)
    out_path = os.path.join(work, "daemon1.out")
    err_path = os.path.join(work, "daemon1.err")
    polls, dropped_late, seen_nine = [], False, False
    with open(out_path, "w") as out_f, open(err_path, "w") as err_f:
        proc = subprocess.Popen(cmd, cwd=REPO, stdout=out_f, stderr=err_f,
                                text=True)
        while proc.poll() is None:
            doc = read_status(st1)
            if doc and doc.get("queue"):
                q = doc["queue"]
                polls.append({"step": doc.get("step"),
                              "admitted": q.get("admitted"),
                              "depth": q.get("depth")})
                mid_run = not doc.get("outcome")
                if (not dropped_late and mid_run
                        and (doc.get("step") or 0) >= 2):
                    # the slot is observably RUNNING: drop job 9 into
                    # the live intake — it must be admitted and
                    # backfilled into THIS slot, not a second one
                    loadgen(sdir, jobs=1, steps=4, seed=1, tenants=1,
                            prefix="late")
                    dropped_late = True
                if dropped_late and mid_run and q.get("admitted") == 9:
                    seen_nine = True
            time.sleep(0.05)
        proc.wait()
    if proc.returncode != 0:
        with open(err_path) as f:
            print(f.read()[-8000:], file=sys.stderr)
        raise SystemExit(f"[serve-gate] daemon1 rc={proc.returncode}")
    if not dropped_late:
        raise SystemExit(
            f"[serve-gate] the status snapshot never showed a running "
            f"slot, so the late job was never dropped ({len(polls)} polls)")
    if not seen_nine:
        raise SystemExit(
            "[serve-gate] no mid-run status poll observed the late job "
            f"admitted (queue.admitted == 9): {polls[-6:]}")
    with open(out_path) as f:
        summary = summary_of(f.read(), "daemon1")
    if summary.get("slots") != 1:
        raise SystemExit(f"[serve-gate] 9 jobs through a B=4 slot must "
                         f"run as ONE slot (continuous batching), got "
                         f"slots={summary.get('slots')}")
    if summary.get("retired") != 9 or summary.get("rejected"):
        raise SystemExit(f"[serve-gate] want 9 retired / 0 rejected: "
                         f"{summary}")
    if summary.get("backfills", 0) < 5:
        raise SystemExit(f"[serve-gate] 9 jobs minus 4 lanes means >= 5 "
                         f"backfills, got {summary.get('backfills')}")
    results = os.listdir(os.path.join(sdir, "results"))
    if len(results) != 9:
        raise SystemExit(f"[serve-gate] want 9 streamed results, got "
                         f"{sorted(results)}")
    recs = load_records(m1)
    slot_idx = min(i for i, r in enumerate(recs)
                   if r["name"] == "campaign.slot")
    late_idx = [i for i, r in enumerate(recs)
                if r["name"] == "serve.admitted"
                and r["job"].startswith("late-")]
    if not late_idx or late_idx[0] <= slot_idx:
        raise SystemExit(
            f"[serve-gate] the late job's serve.admitted must land AFTER "
            f"campaign.slot (admitted mid-slot): slot at {slot_idx}, "
            f"late at {late_idx}")
    run([PY, "-m", "stencil_tpu.apps.report", m1, "--validate"],
        name="validate-1")
    print(f"[serve-gate] stage 1: 1 slot, {summary['backfills']} "
          f"backfills, late job admitted mid-slot (status poll saw "
          f"admitted=9 live; {len(polls)} polls)")


def stage2_sigterm_drain(work):
    sdir = os.path.join(work, "s2")
    m2a = os.path.join(work, "m2a.jsonl")
    m2b = os.path.join(work, "m2b.jsonl")
    st2 = os.path.join(work, "status2.json")
    steps = 16
    loadgen(sdir, jobs=3, steps=steps, seed=5, tenants=3, size=12)
    cmd = serve_cmd(sdir, m2a, st2, slot=4)
    print(f"[serve-gate] daemon (SIGTERM pending): {' '.join(cmd)}",
          flush=True)
    out_path = os.path.join(work, "daemon2.out")
    err_path = os.path.join(work, "daemon2.err")
    with open(out_path, "w") as out_f, open(err_path, "w") as err_f:
        proc = subprocess.Popen(cmd, cwd=REPO, stdout=out_f, stderr=err_f,
                                text=True)
        while proc.poll() is None:
            doc = read_status(st2)
            if doc and (doc.get("step") or 0) >= 2 and not doc.get("outcome"):
                proc.send_signal(signal.SIGTERM)
                break
            time.sleep(0.05)
        rc = proc.wait(timeout=120)
    if rc != 0:
        with open(err_path) as f:
            print(f.read()[-8000:], file=sys.stderr)
        raise SystemExit(f"[serve-gate] SIGTERM must drain to exit 0, "
                         f"got rc={rc}")
    with open(out_path) as f:
        summary = summary_of(f.read(), "daemon2")
    if summary.get("outcome") != "drained" or summary.get("retired") != 0:
        raise SystemExit(f"[serve-gate] want outcome=drained with 0 "
                         f"retired (parked mid-flight): {summary}")
    if summary.get("queued_remaining") != 3:
        raise SystemExit(f"[serve-gate] all 3 jobs must survive the drain "
                         f"in the queue: {summary}")
    recs = load_records(m2a)
    parked = by_name(recs, "serve.parked")
    if len(parked) != 3 or not all(0 < r["step"] < steps for r in parked):
        raise SystemExit(f"[serve-gate] want 3 mid-flight parks "
                         f"(0 < step < {steps}): "
                         f"{[(r.get('job'), r.get('step')) for r in parked]}")
    drains = by_name(recs, "serve.drain")
    if not drains or drains[0].get("reason") != "sigterm":
        raise SystemExit(f"[serve-gate] serve.drain must name sigterm: "
                         f"{drains}")
    if not os.path.exists(os.path.join(sdir, "serve-state.json")):
        raise SystemExit("[serve-gate] drain left no serve-state.json")

    g = run(serve_cmd(sdir, m2b, st2, slot=4), name="drain-revival")
    summary = summary_of(g.stdout, "drain-revival")
    if summary.get("revived") != 3 or summary.get("retired") != 3:
        raise SystemExit(f"[serve-gate] the restart must revive and "
                         f"finish all 3: {summary}")
    jobs = retired_jobs(m2a, m2b)
    if sorted(jobs) != sorted(set(jobs)) or len(set(jobs)) != 3:
        raise SystemExit(f"[serve-gate] each job must retire exactly "
                         f"once across drain+revival: {sorted(jobs)}")
    for path, name in ((m2a, "validate-2a"), (m2b, "validate-2b")):
        run([PY, "-m", "stencil_tpu.apps.report", path, "--validate"],
            name=name)
    print("[serve-gate] stage 2: SIGTERM drained (3 mid-flight parks), "
          "restart revived and finished all 3, nobody re-ran")


def stage3_kill_revive_bit_identical(work):
    spec = importlib.util.spec_from_file_location(
        "stencil_watchdog",
        os.path.join(REPO, "stencil_tpu", "obs", "watchdog.py"))
    watchdog = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = watchdog  # dataclass resolves __module__
    spec.loader.exec_module(watchdog)

    ref = os.path.join(work, "s3-ref")
    killed = os.path.join(work, "s3-killed")
    for d in (ref, killed):
        loadgen(d, jobs=5, steps=6, seed=11, tenants=2, size=12)
    m_ref = os.path.join(work, "m3ref.jsonl")
    g = run(serve_cmd(ref, m_ref, os.path.join(work, "status3r.json")),
            name="reference-serve")
    if summary_of(g.stdout, "reference-serve").get("retired") != 5:
        raise SystemExit("[serve-gate] reference serve must retire all 5")

    m3a = os.path.join(work, "m3a.jsonl")
    m3b = os.path.join(work, "m3b.jsonl")
    st3 = os.path.join(work, "status3.json")
    env = dict(os.environ)
    env[KILL_ENV] = "2"
    att = watchdog.supervise(
        serve_cmd(killed, m3a, st3), timeout_s=300, env=env, cwd=REPO,
        name="serve-killed")
    if att.outcome != watchdog.CRASH or att.rc != 17:
        raise SystemExit(f"[serve-gate] the kill hook must die as a "
                         f"watchdog CRASH with rc 17: outcome="
                         f"{att.outcome} rc={att.rc}")
    att = watchdog.supervise(
        serve_cmd(killed, m3b, st3), timeout_s=300, cwd=REPO,
        name="serve-revived")
    if att.outcome != watchdog.OK:
        raise SystemExit(f"[serve-gate] revival attempt: outcome="
                         f"{att.outcome} rc={att.rc}\n{att.stderr_tail}")
    summary = summary_of(att.stdout, "serve-revived")
    if summary.get("retired") != 3 or not summary.get("revived"):
        raise SystemExit(f"[serve-gate] revival must pick up the 3 "
                         f"unserved jobs (2 retired pre-kill): {summary}")
    jobs = retired_jobs(m3a, m3b)
    if sorted(jobs) != sorted(set(jobs)) or len(set(jobs)) != 5:
        raise SystemExit(f"[serve-gate] kill+revival must retire each of "
                         f"the 5 jobs exactly once: {sorted(jobs)}")
    for tid in sorted(set(jobs)):
        a = newest_snapshot(killed, tid)
        b = newest_snapshot(ref, tid)
        run([PY, "-m", "stencil_tpu.apps.ckpt_tool", "diff", a, b,
             "--data"], name=f"diff-{tid}")
    for path, name in ((m3a, "validate-3a"), (m3b, "validate-3b")):
        run([PY, "-m", "stencil_tpu.apps.report", path, "--validate"],
            name=name)
    print("[serve-gate] stage 3: watchdog CRASH rc=17 at 2 retirements, "
          "revival finished 3, all 5 finals bit-identical to the "
          "uninterrupted reference")


def stage4_slo_pressure_replan(work):
    sdir = os.path.join(work, "s4")
    m4 = os.path.join(work, "m4.jsonl")
    plan_db = os.path.join(work, "plans4.json")
    # no admission ledger: the doomed deadline cannot be priced at
    # admission, so the jobs run and the ONLINE p99 builds the pressure
    loadgen(sdir, jobs=4, steps=8, seed=3, tenants=2, size=12,
            deadline_ms=0.001)
    g = run(serve_cmd(sdir, m4, os.path.join(work, "status4.json"),
                      extra=("--replan", "--plan-db", plan_db)),
            name="slo-pressure-serve")
    summary = summary_of(g.stdout, "slo-pressure-serve")
    if summary.get("retired") != 4:
        raise SystemExit(f"[serve-gate] a deadline breach is evidence, "
                         f"not an eviction — all 4 must finish: {summary}")
    recs = load_records(m4)
    req = [r for r in by_name(recs, "replan.requested")
           if r.get("reason") == "slo-pressure"]
    if not req:
        raise SystemExit("[serve-gate] no slo-pressure replan.requested")
    app = [r for r in by_name(recs, "replan.applied")
           if r.get("trigger") == "slo-pressure"]
    if not app:
        raise SystemExit(f"[serve-gate] the latched pressure must "
                         f"hot-swap between slots (replan.applied): "
                         f"{by_name(recs, 'replan.rejected')}")
    if not os.path.exists(plan_db) or not os.path.getsize(plan_db):
        raise SystemExit("[serve-gate] the re-tuned plan must persist "
                         "into --plan-db")
    run([PY, "-m", "stencil_tpu.apps.report", m4, "--validate"],
        name="validate-4")
    print(f"[serve-gate] stage 4: slo-pressure requested at step "
          f"{req[0].get('step')}, plan {app[0].get('old')} -> "
          f"{app[0].get('new')} persisted")


def stage5_preemption_bit_identical(work):
    """A rush high-deadline arrival preempts the running slot — priced
    against the victims' resume cost off a SEEDED ledger — and the
    parked victims resume to finals bit-identical to an undisturbed
    ``--no-preempt`` reference of the same seeded load."""
    lpath = os.path.join(work, "prices5.jsonl")
    # victims' bucket priced slow, the rush bucket fast: waiting in
    # queue provably breaks the rush budget, and the priced gain dwarfs
    # two victims' resume cost
    seed_pricing_ledger(lpath, {
        f"{SIZE}x{SIZE}x{SIZE}/float32/jacobi": 100.0,
        "10x10x10/float32/jacobi": 1.0,
    })
    rush = {"job": "rush", "size": 10, "steps": 2, "dtype": "float32",
            "workload": "jacobi", "seed": 77, "tenant": "tenant-hi",
            "priority": "high", "deadline_ms": 2.0}
    steps = 12
    extra = ("--admission-ledger", lpath, "--preempt-cost-chunks", "0.05")

    ref = os.path.join(work, "s5-ref")
    loadgen(ref, jobs=2, steps=steps, seed=21, tenants=2, prefix="vic")
    drop_doc(ref, rush)
    m_ref = os.path.join(work, "m5ref.jsonl")
    g = run(serve_cmd(ref, m_ref, os.path.join(work, "status5r.json"),
                      extra=("--no-preempt",) + extra),
            name="preempt-reference")
    if summary_of(g.stdout, "preempt-reference").get("retired") != 3:
        raise SystemExit("[serve-gate] preempt reference must retire all 3")

    live = os.path.join(work, "s5")
    loadgen(live, jobs=2, steps=steps, seed=21, tenants=2, prefix="vic")
    m5 = os.path.join(work, "m5.jsonl")
    st5 = os.path.join(work, "status5.json")
    state = {"dropped": False}

    def on_status(doc):
        if (not state["dropped"] and not doc.get("outcome")
                and (doc.get("step") or 0) >= 2):
            # the victim slot is observably RUNNING: now the rush job
            # arrives — preemption must fire at a chunk boundary
            drop_doc(live, rush)
            state["dropped"] = True

    summary = poll_daemon(
        serve_cmd(live, m5, st5, extra=extra), st5,
        os.path.join(work, "daemon5.out"), os.path.join(work, "daemon5.err"),
        on_status)
    if not state["dropped"]:
        raise SystemExit("[serve-gate] stage 5 never saw a running slot "
                         "to drop the rush job into")
    if summary.get("retired") != 3 or summary.get("preemptions") != 1:
        raise SystemExit(f"[serve-gate] want 3 retired / 1 preemption: "
                         f"{summary}")
    recs = load_records(m5)
    pre = by_name(recs, "serve.preempted")
    if len(pre) != 1 or pre[0].get("job") != "rush":
        raise SystemExit(f"[serve-gate] want ONE serve.preempted for the "
                         f"rush job: {pre}")
    if not pre[0]["gain_ms"] > pre[0]["resume_cost_ms"]:
        raise SystemExit(f"[serve-gate] preemption must only fire when "
                         f"the priced gain exceeds the victims' resume "
                         f"cost: {pre[0]}")
    if sorted(pre[0].get("victims", [])) != ["vic-21-0000", "vic-21-0001"]:
        raise SystemExit(f"[serve-gate] both victims must be named: "
                         f"{pre[0]}")
    parked = [r for r in by_name(recs, "serve.parked")
              if r.get("reason") == "preempt"]
    if len(parked) != 2 or not all(0 < r["step"] < steps for r in parked):
        raise SystemExit(f"[serve-gate] want both victims parked "
                         f"mid-flight (0 < step < {steps}): "
                         f"{[(r.get('job'), r.get('step')) for r in parked]}")
    for tid in ("vic-21-0000", "vic-21-0001", "rush"):
        run([PY, "-m", "stencil_tpu.apps.ckpt_tool", "diff",
             newest_snapshot(live, tid), newest_snapshot(ref, tid),
             "--data"], name=f"diff5-{tid}")
    run([PY, "-m", "stencil_tpu.apps.report", m5, "--validate"],
        name="validate-5")
    run([PY, "-m", "stencil_tpu.apps.report", m_ref, "--validate"],
        name="validate-5ref")
    print(f"[serve-gate] stage 5: rush preempted the slot (gain "
          f"{pre[0]['gain_ms']:.4g} ms > resume cost "
          f"{pre[0]['resume_cost_ms']:.4g} ms), both victims parked and "
          f"resumed, all 3 finals bit-identical to the no-preempt "
          f"reference")


def stage6_elastic_resize(work):
    """A width-2 slot grows to the queue's width mid-flight, and a
    second wave revisiting the grown width recompiles NOTHING — one
    ``compile.build`` per (bucket, width) for the daemon's whole life."""
    lpath = os.path.join(work, "prices6.jsonl")
    seed_pricing_ledger(lpath, {"12x12x12/float32/jacobi": 50.0})
    sdir = os.path.join(work, "s6")
    steps1 = 16
    loadgen(sdir, jobs=2, steps=steps1, seed=31, tenants=2, size=12,
            prefix="w1")
    m6 = os.path.join(work, "m6.jsonl")
    st6 = os.path.join(work, "status6.json")
    state = {"wave2": False, "wave3": False, "wave4": False}

    def on_status(doc):
        q = doc.get("queue") or {}
        mid_run = not doc.get("outcome")
        if (not state["wave2"] and mid_run
                and (doc.get("step") or 0) >= 2):
            # the width-2 slot is RUNNING: 6 more same-bucket jobs make
            # the queue wider than the slot — it must grow, not crawl.
            # Dropped in-process (not via the loadgen subprocess): the
            # whole wave must land while THIS slot is still mid-flight
            for i in range(6):
                drop_doc(sdir, {"job": f"w2-32-{i:04d}", "size": 12,
                                "steps": 8, "dtype": "float32",
                                "workload": "jacobi", "seed": 320 + i,
                                "tenant": f"tenant-{i % 2}",
                                "priority": "normal"})
            state["wave2"] = True
        if (state["wave2"] and not state["wave3"] and mid_run
                and q.get("retired") == 8):
            # everything retired, daemon idling: a second wave at the
            # SAME depth revisits the grown width — a compile-cache hit
            # by construction
            loadgen(sdir, jobs=8, steps=8, seed=33, tenants=2, size=12,
                    prefix="w3")
            state["wave3"] = True
        if (state["wave3"] and not state["wave4"] and mid_run
                and q.get("retired") == 16):
            # the surge is over: a 2-deep trickle must SHRINK the next
            # slot back down the ladder (and hit the width-2 program)
            loadgen(sdir, jobs=2, steps=8, seed=34, tenants=2, size=12,
                    prefix="w4")
            state["wave4"] = True

    summary = poll_daemon(
        serve_cmd(sdir, m6, st6, slot=2,
                  extra=("--slot-min", "2", "--slot-max", "8",
                         "--no-preempt", "--preempt-cost-chunks", "0.25",
                         "--admission-ledger", lpath)),
        st6, os.path.join(work, "daemon6.out"),
        os.path.join(work, "daemon6.err"), on_status)
    if not state["wave4"]:
        raise SystemExit(f"[serve-gate] stage 6 never reached the later "
                         f"waves: {state}")
    if summary.get("retired") != 18 or not summary.get("resizes"):
        raise SystemExit(f"[serve-gate] want 18 retired with >= 1 resize: "
                         f"{summary}")
    recs = load_records(m6)
    grew = [r for r in by_name(recs, "serve.resized")
            if r.get("reason") == "grow" and r.get("from_width") == 2]
    if not grew:
        raise SystemExit(f"[serve-gate] want a grow from width 2: "
                         f"{by_name(recs, 'serve.resized')}")
    shrank = [r for r in by_name(recs, "serve.resized")
              if r.get("reason") == "shrink"]
    if not shrank:
        raise SystemExit(f"[serve-gate] the post-surge trickle must "
                         f"shrink the slot back down the ladder: "
                         f"{by_name(recs, 'serve.resized')}")
    parked = [r for r in by_name(recs, "serve.parked")
              if r.get("reason") == "resize"]
    if not parked or not all(0 < r["step"] < steps1 for r in parked):
        raise SystemExit(f"[serve-gate] the grow must park the running "
                         f"lanes mid-flight: "
                         f"{[(r.get('job'), r.get('step')) for r in parked]}")
    builds = [r["key"] for r in by_name(recs, "compile.build")]
    if len(builds) != len(set(builds)):
        raise SystemExit(f"[serve-gate] a width revisit must be a cache "
                         f"HIT — some program compiled twice: {builds}")
    widths = {json.loads(k).get("batch") for k in builds} - {None}
    slot_widths = {r.get("width") for r in by_name(recs, "campaign.slot")}
    if len(widths) < 2 or 2 not in slot_widths or not (slot_widths - {2}):
        raise SystemExit(f"[serve-gate] want slots at width 2 AND a grown "
                         f"width, one program each: builds={sorted(widths)} "
                         f"slots={sorted(slot_widths)}")
    run([PY, "-m", "stencil_tpu.apps.report", m6, "--validate"],
        name="validate-6")
    print(f"[serve-gate] stage 6: grew 2 -> {grew[0].get('to_width')} "
          f"mid-slot ({len(parked)} resize parks), second wave at the "
          f"grown width recompiled nothing ({len(builds)} builds for "
          f"widths {sorted(widths)}), post-surge trickle shrank back to "
          f"{shrank[0].get('to_width')}")


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--out-dir", default="",
                   help="keep status/metrics artifacts here for CI upload "
                        "(default: a temp dir, removed)")
    args = p.parse_args()
    work = tempfile.mkdtemp(prefix="serve-gate-")
    try:
        stage1_continuous_batching(work)
        stage2_sigterm_drain(work)
        stage3_kill_revive_bit_identical(work)
        stage4_slo_pressure_replan(work)
        stage5_preemption_bit_identical(work)
        stage6_elastic_resize(work)
        if args.out_dir:
            out = os.path.abspath(args.out_dir)
            os.makedirs(out, exist_ok=True)
            for name in os.listdir(work):
                if name.endswith((".jsonl", ".json", ".out", ".err")):
                    shutil.copy2(os.path.join(work, name),
                                 os.path.join(out, name))
            print(f"[serve-gate] artifacts: {out}")
        print("[serve-gate] PASS")
        return 0
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
