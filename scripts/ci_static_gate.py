#!/usr/bin/env python
"""CI static-analysis gate: the executable acceptance proof of
stencil_tpu/analysis/ (no TPU needed — 8 virtual CPU devices).

1. clean tree: ``lint_tool lint`` exits 0 against the committed tree
   and its baseline;
2. every shipped rule FIRES: each rule's deliberately-bad fixture must
   produce exactly that rule's finding with exit 1 (a gate that cannot
   detect anything proves nothing) — and the inline
   ``# lint: disable=<rule>`` suppression silences it again;
3. plan conformance: ``lint_tool verify-plan`` agrees for all four
   exchange methods on the CPU mesh (exit 0), and TRIPS (exit 1) when
   an IR prediction is perturbed via ``--perturb-collectives``;
   an infeasible sweep (27-block partition on 8 devices) degrades
   loudly with exit 2 and no traceback;
4. jit audit: the clean jacobi chunk loop PASSES; the injected-
   recompile and injected-host-sync fixtures both FAIL with exit 1;
5. schema: every metrics file the auditors produced passes
   ``report --validate`` (the ``analysis.*`` vocabulary is gated like
   every other subsystem's).

Artifacts (``--out-dir``): the lint/sweep/audit JSON documents + the
metrics JSONL.

Run from the repo root:  python scripts/ci_static_gate.py
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

# -- per-rule bad fixtures (each must fire EXACTLY its rule) ------------------

FIXTURES = {
    # nested + aliased import in a file-path-loaded module
    "pure-stdlib": ("obs/watchdog.py", """\
import os

def beat():
    import numpy as np  # nested: still forbidden at any depth
    return np.zeros(3)
"""),
    "telemetry-vocab": ("lib/metrics_site.py", """\
def emit(rec):
    rec.gauge("recover.rollbck", 1.0)  # typo'd vocabulary name
"""),
    "atomic-write": ("lib/writer.py", """\
import json

def save(path, doc):
    with open(path, "w") as f:
        json.dump(doc, f)
"""),
    "no-bare-assert": ("lib/api_mod.py", """\
def realize(n):
    assert n >= 1, "need at least one device"
    return n
"""),
    "fstring-placeholder": ("lib/errors.py", """\
def fail(name):
    raise ValueError("unknown method {name}")
"""),
    "host-sync-in-hot-loop": ("lib/hot.py", """\
import time
import jax

def make_step():
    def body(x):
        t = time.time()  # trace-time constant burial
        return x + t
    return jax.jit(body)
"""),
}

SUPPRESSED_SUFFIX = {
    # the same bad line with an inline disable pragma: must be clean
    "no-bare-assert": ("lib/api_ok.py", """\
def realize(n):
    assert n >= 1  # lint: disable=no-bare-assert
    return n
"""),
}


def run(args, **kw):
    print(f"+ {' '.join(args)}", flush=True)
    return subprocess.run(args, cwd=REPO, capture_output=True, text=True,
                          **kw)


def must(cond, what, proc=None):
    if cond:
        print(f"  ok: {what}")
        return
    print(f"FAILED: {what}", file=sys.stderr)
    if proc is not None:
        print(proc.stdout[-4000:], file=sys.stderr)
        print(proc.stderr[-4000:], file=sys.stderr)
    sys.exit(1)


def save_artifact(out_dir, name, text):
    if not out_dir:
        return
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, name), "w") as f:
        f.write(text)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default="",
                    help="write the JSON documents + metrics here "
                         "(CI artifact dir)")
    args = ap.parse_args()
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    # 1. the committed tree lints clean
    p = run([PY, "-m", "stencil_tpu.apps.lint_tool", "lint", "--json"],
            env=env)
    save_artifact(args.out_dir, "lint.json", p.stdout)
    must(p.returncode == 0, "tree lints clean (rc 0)", p)
    doc = json.loads(p.stdout)
    must(doc["new"] == 0 and not doc["errors"],
         "zero new findings, zero engine errors", p)

    # 2. every rule fires on its bad fixture, and the pragma silences it
    tmp = tempfile.mkdtemp(prefix="static-gate-")
    try:
        for rule, (relpath, src) in FIXTURES.items():
            fpath = os.path.join(tmp, relpath)
            os.makedirs(os.path.dirname(fpath), exist_ok=True)
            with open(fpath, "w") as f:
                f.write(src)
            p = run([PY, "-m", "stencil_tpu.apps.lint_tool", "lint",
                     fpath, "--json", "--baseline",
                     os.path.join(tmp, "empty-baseline.json")], env=env)
            must(p.returncode == 1, f"rule {rule} fixture exits 1", p)
            got = json.loads(p.stdout)
            fired = {f["rule"] for f in got["findings"]}
            must(fired == {rule},
                 f"rule {rule} fires exactly (got {sorted(fired)})", p)
        for rule, (relpath, src) in SUPPRESSED_SUFFIX.items():
            fpath = os.path.join(tmp, relpath)
            with open(fpath, "w") as f:
                f.write(src)
            p = run([PY, "-m", "stencil_tpu.apps.lint_tool", "lint",
                     fpath, "--json"], env=env)
            must(p.returncode == 0,
                 f"inline disable silences {rule} (rc 0)", p)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # 3. plan conformance: agree, trip when perturbed, degrade loudly
    metrics = os.path.join(args.out_dir or tempfile.gettempdir(),
                           "static-gate-metrics.jsonl")
    if os.path.exists(metrics):
        os.remove(metrics)
    p = run([PY, "-m", "stencil_tpu.apps.lint_tool", "verify-plan",
             "--cpu", "8", "--json", "--metrics-out", metrics], env=env)
    save_artifact(args.out_dir, "plan-sweep.json", p.stdout)
    must(p.returncode == 0, "verify-plan agrees on the CPU mesh (rc 0)", p)
    doc = json.loads(p.stdout)
    methods = {v["method"] for v in doc["verdicts"] if not v["skipped"]}
    must(methods == {"axis-composed", "direct26", "auto-spmd"},
         f"all three methods checked (got {sorted(methods)})", p)
    must(doc["failed"] == 0 and doc["checked"] > 0,
         f"{doc['checked']} configs agree", p)

    p = run([PY, "-m", "stencil_tpu.apps.lint_tool", "verify-plan",
             "--cpu", "8", "--partitions", "2x2x2", "--quantities", "f32",
             "--methods", "axis-composed", "--perturb-collectives", "1"],
            env=env)
    must(p.returncode == 1, "perturbed IR prediction TRIPS (rc 1)", p)

    p = run([PY, "-m", "stencil_tpu.apps.lint_tool", "verify-plan",
             "--cpu", "8", "--partitions", "3x3x3", "--quantities", "f32"],
            env=env)
    must(p.returncode == 2, "infeasible sweep degrades to rc 2", p)
    must("Traceback" not in p.stderr, "…with a message, not a traceback", p)

    # 4. jit audit: clean pass, injected fixtures fail
    p = run([PY, "-m", "stencil_tpu.apps.lint_tool", "jit-audit",
             "--cpu", "8", "--json", "--metrics-out", metrics], env=env)
    save_artifact(args.out_dir, "jit-audit.json", p.stdout)
    must(p.returncode == 0, "clean jacobi chunk loop PASSES", p)
    doc = json.loads(p.stdout)
    must(doc["recompiles"] == 0 and not doc["transfer_trips"],
         "zero post-warmup recompiles, zero transfers", p)
    for inject in ("recompile", "host-sync"):
        p = run([PY, "-m", "stencil_tpu.apps.lint_tool", "jit-audit",
                 "--cpu", "8", "--inject", inject], env=env)
        must(p.returncode == 1, f"injected {inject} FAILS the audit", p)

    # 5. the analysis.* records pass the telemetry schema gate
    p = run([PY, "-m", "stencil_tpu.apps.report", metrics, "--validate"],
            env=env)
    must(p.returncode == 0, "analysis.* metrics pass report --validate", p)
    if args.out_dir and os.path.dirname(metrics) != args.out_dir:
        shutil.copy(metrics, os.path.join(args.out_dir,
                                          "static-gate-metrics.jsonl"))

    print("static gate: all stages passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
