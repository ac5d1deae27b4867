#!/usr/bin/env python
"""CI plan gate: tune -> persist -> replay with ZERO probes -> bit parity.

The executable acceptance proof of the plan/ subsystem on the 8-virtual-
device CPU mesh (no TPU needed):

1. tune: ``plan_tool autotune`` at 24^3 for Q in {1, 4} (uniform radius
   2, 8 CPU devices) — each first run must MISS the DB (``cache_hit: 0``
   gauge) and execute measured probes, persisting its winner;
2. replay: the same two invocations again — each must be a pure DB hit:
   ``plan.cache_hit`` gauge 1, ``plan.probes_run`` counter 0, and NOT A
   SINGLE ``plan.probe`` span in the metrics JSONL;
3. app wiring: ``jacobi3d --autotune --plan-db`` tunes its own config on
   the first run and replays it probe-free on the second (same gauges,
   via the DistributedDomain knob);
4. bit parity: one exchange under the tuned Q=4 plan must equal the
   ``Method.AXIS_COMPOSED`` default program field-for-field on
   coordinate data (the plan changes the program, never the physics);
5. schema: every produced metrics file passes the telemetry validate
   gate, and ``plan_tool show`` lists exactly the tuned entries.

Exit code 0 only if every stage holds. Run from the repo root:

  python scripts/ci_plan_gate.py [--size 24] [--quantities 1 4]
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

PARITY_CHILD = r"""
import sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
import numpy as np
from stencil_tpu.apps._bench_common import coord_state, time_exchange
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.parallel import Method
from stencil_tpu.plan import db as plandb
from stencil_tpu.plan.ir import PlanChoice, PlanConfig

db_path, size, q = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
db = plandb.load_db(db_path)
cfg = PlanConfig.make(Dim3(size, size, size), Radius.constant(2),
                      ["float32"] * q, 8, "cpu")
entry = plandb.lookup(db, cfg)
assert entry is not None, f"no DB entry for {cfg.key()}"
choice = PlanChoice.from_json(entry["choice"])
# both legs run on the TUNED partition so the stacked layouts (and thus
# every halo cell) are directly comparable; the default leg is the
# AXIS_COMPOSED + batched program realize() would build plan-less
outs = {}
for label, method, batched in (
    ("tuned", Method(choice.method), choice.batch_quantities),
    ("default", Method.AXIS_COMPOSED, True),
):
    r = time_exchange(Dim3(size, size, size), Radius.constant(2), 2,
                      method=method, quantities=q, batch_quantities=batched,
                      partition=choice.partition)
    dd = r["domain"]
    out = dd.halo_exchange(coord_state(dd, q))
    outs[label] = np.stack(
        [np.asarray(jax.device_get(out[i])) for i in sorted(out)]
    )
assert np.array_equal(outs["tuned"], outs["default"]), \
    "tuned plan's exchange disagrees with the AXIS_COMPOSED default"
print("PARITY_OK")
"""


def run(cmd, env=None, expect_rc=0, name=""):
    print(f"[plan-gate] {name}: {' '.join(cmd)}", flush=True)
    e = dict(os.environ)
    if env:
        e.update(env)
    p = subprocess.run(cmd, env=e, cwd=REPO, capture_output=True, text=True)
    if p.returncode != expect_rc:
        print(p.stdout)
        print(p.stderr, file=sys.stderr)
        raise SystemExit(
            f"[plan-gate] {name}: rc={p.returncode}, expected {expect_rc}"
        )
    return p


def metrics_records(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def check_metrics(path, expect_hit: bool, name: str) -> None:
    """The telemetry proof: cache_hit gauge, probes_run counter, and (on
    a hit) the absence of any probe span."""
    recs = metrics_records(path)
    hits = [r["value"] for r in recs
            if r["kind"] == "gauge" and r["name"] == "plan.cache_hit"]
    probes = [r["value"] for r in recs
              if r["kind"] == "counter" and r["name"] == "plan.probes_run"]
    probe_spans = [r for r in recs
                   if r["kind"] == "span" and r["name"] == "plan.probe"]
    if not hits or not probes:
        raise SystemExit(f"[plan-gate] {name}: metrics lack plan.cache_hit/"
                         "plan.probes_run")
    if expect_hit:
        if hits[-1] != 1 or probes[-1] != 0 or probe_spans:
            raise SystemExit(
                f"[plan-gate] {name}: expected a pure DB hit, got "
                f"cache_hit={hits[-1]} probes_run={probes[-1]} "
                f"probe_spans={len(probe_spans)}"
            )
    else:
        if hits[-1] != 0 or probes[-1] < 1:
            raise SystemExit(
                f"[plan-gate] {name}: expected a tuning run with probes, "
                f"got cache_hit={hits[-1]} probes_run={probes[-1]}"
            )


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--size", type=int, default=24)
    p.add_argument("--quantities", type=int, nargs="+", default=[1, 4])
    args = p.parse_args()

    work = tempfile.mkdtemp(prefix="plan-gate-")
    db = os.path.join(work, "plans.json")
    try:
        def tool(q, metrics, name):
            return run(
                [PY, "-m", "stencil_tpu.apps.plan_tool", "autotune",
                 "--cpu", "8", "--db", db,
                 "--x", str(args.size), "--y", str(args.size),
                 "--z", str(args.size), "--radius", "2",
                 "--quantities", str(q), "--probe-iters", "2",
                 "--top-n", "2", "--metrics-out", metrics],
                name=name,
            )

        # 1. tune (DB miss, probes run) / 2. replay (pure hit, zero probes)
        for q in args.quantities:
            m1 = os.path.join(work, f"tune_q{q}.jsonl")
            tool(q, m1, f"tune-q{q}")
            check_metrics(m1, expect_hit=False, name=f"tune-q{q}")
            m2 = os.path.join(work, f"replay_q{q}.jsonl")
            r = tool(q, m2, f"replay-q{q}")
            check_metrics(m2, expect_hit=True, name=f"replay-q{q}")
            if "cache_hit: True" not in r.stdout or "probes_run: 0" not in r.stdout:
                raise SystemExit(f"[plan-gate] replay-q{q} stdout does not "
                                 "report the DB hit")
            run([PY, "-m", "stencil_tpu.apps.report", m1, m2, "--validate"],
                name=f"schema-q{q}")

        # 3. app wiring: jacobi3d --autotune tunes, then replays probe-free
        jm1 = os.path.join(work, "jacobi_tune.jsonl")
        jm2 = os.path.join(work, "jacobi_replay.jsonl")
        jcmd = [PY, "-m", "stencil_tpu.apps.jacobi3d", "--cpu", "8",
                "--x", str(args.size), "--y", str(args.size),
                "--z", str(args.size), "--iters", "2", "--no-weak",
                "--autotune", "--plan-db", db]
        run(jcmd + ["--metrics-out", jm1], name="jacobi-tune")
        check_metrics(jm1, expect_hit=False, name="jacobi-tune")
        run(jcmd + ["--metrics-out", jm2], name="jacobi-replay")
        check_metrics(jm2, expect_hit=True, name="jacobi-replay")
        run([PY, "-m", "stencil_tpu.apps.report", jm1, jm2, "--validate"],
            name="schema-jacobi")

        # 4. bit parity: tuned plan vs the AXIS_COMPOSED default program
        q = max(args.quantities)
        r = run([PY, "-c", PARITY_CHILD, db, str(args.size), str(q)],
                name="parity")
        if "PARITY_OK" not in r.stdout:
            raise SystemExit("[plan-gate] parity child produced no verdict")

        # 5. the DB lists exactly the tuned entries
        r = run([PY, "-m", "stencil_tpu.apps.plan_tool", "show", "--db", db],
                name="show")
        want = len(args.quantities) + 1  # + jacobi's own config
        if f"# {want} entries" not in r.stdout:
            print(r.stdout)
            raise SystemExit(f"[plan-gate] expected {want} DB entries")
        print("[plan-gate] PASS")
        return 0
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
