#!/usr/bin/env python
"""CI replan gate: topology-aware placement + the mid-run plan hot-swap.

The executable acceptance proof of ISSUE 15 on the 8-virtual-device CPU
mesh — no TPU needed:

1. **placement conformance**: ``lint_tool verify-plan --placements 3``
   audits >= 3 non-identity block->device permutations on the 2x2x2
   mesh — the realized mesh's device order IS the permuted assignment,
   the compiled ``source_target_pairs`` match the plan's logical
   schedule (so each pair rides exactly the permuted physical link),
   and the exchanged field is bit-identical to identity;
2. **QAP never worse than identity**: on the DERIVED matrices (GridSpec
   wire volumes x live-device link costs — uniform on this mesh, so
   identity must be recognized as optimal) AND on a synthetic
   non-uniform fabric where the solved placement must be STRICTLY
   cheaper, with the static cost model ranking the placed candidate
   below its identity sibling;
3. **hot-swap e2e**: jacobi3d 24^3 starting on direct26 with an injected
   ``slow@N`` and the live sentinel + ``--replan`` ON must emit
   ``replan.requested`` then ``replan.applied`` within 2 chunks, finish
   rc 0, and the final checkpointed field must be BIT-IDENTICAL to an
   unswapped direct26 run (``ckpt_tool diff --data`` — elastic across
   the swap's partition change); a clean replan-armed run emits ZERO
   replan records;
4. **schema**: every record — the new ``replan.applied``/``rejected``
   and the ``qap.placement_cost``/``qap.improvement`` gauges of
   ``bench_qap --derived`` included — passes ``report --validate``.

Exit 0 only if every stage holds. Run from the repo root:

  python scripts/ci_replan_gate.py [--size 24] [--out-dir DIR]
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

ITERS = 12
CHUNK = 2
SLOW_STEP = 6
# "within 2 chunks" of the request, in steps
SWAP_WINDOW_STEPS = 2 * CHUNK
# the sentinel must be armed before the injected slow chunk: two healthy
# chunks of history, a tight band, immediate clear
LIVE_CONFIG = json.dumps(
    {"*": {"min_history": 2, "window": 8, "rel_tol": 0.5,
           "clear_after": 1}})

QAP_SNIPPET = r"""
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.parallel.topology import link_cost_matrix
from stencil_tpu.plan import cost as C
from stencil_tpu.plan.ir import PlanConfig

# derived matrices: the real inputs (uniform links on this mesh ->
# identity must be recognized as optimal, not "improved" by noise)
spec = GridSpec(Dim3(24, 24, 24), Dim3(2, 2, 2), Radius.constant(2))
w = C.placement_wire_matrix(spec, Dim3(2, 2, 2))
link = link_cost_matrix(jax.devices()[:8])
assert C.uniform_link_costs(link), "single-process CPU links must be uniform"
assert C.solve_placement(w, link) is None, \
    "uniform links must solve to identity"

# synthetic non-uniform fabric (scrambled ring: cheap links 3 apart):
# the QAP-placed cost must be <= identity, here STRICTLY cheaper
spec_r = GridSpec(Dim3(24, 24, 24), Dim3(1, 1, 8), Radius.constant(1))
w_r = C.placement_wire_matrix(spec_r, Dim3(1, 1, 8))
link_r = np.full((8, 8), 7.0)
for i in range(8):
    link_r[i, (i + 3) % 8] = link_r[(i + 3) % 8, i] = 1.0
np.fill_diagonal(link_r, 0.1)
f = C.solve_placement(w_r, link_r)
assert f is not None, "scrambled ring must admit a better-than-identity placement"
ident = C.placement_cost(w_r, link_r)
placed = C.placement_cost(w_r, link_r, f)
assert placed < ident, (placed, ident)

# the static model must rank the placed candidate below identity
cfg = PlanConfig.make((24, 24, 24), Radius.constant(1), ["float32"], 8, "cpu")
ranked = C.rank(cfg, C.enumerate_candidates(cfg, link_costs=link_r),
                link_costs=link_r)
comp = [(c, ch) for c, ch in ranked
        if ch.method == "axis-composed" and ch.partition == (1, 1, 8)]
ident_c = next(t for t in comp if not t[1].is_placed)
placed_c = next(t for t in comp if t[1].is_placed)
assert placed_c[0].total_s < ident_c[0].total_s, \
    (placed_c[0].total_s, ident_c[0].total_s)
print(f"qap-model: placed {placed:.0f} < identity {ident:.0f} "
      f"({ident / placed:.2f}x); model {placed_c[0].total_s:.3g} < "
      f"{ident_c[0].total_s:.3g}")
"""


def run(cmd, expect_rc=0, name="", **kw):
    print(f"[replan-gate] {name}: {' '.join(cmd)}", flush=True)
    p = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True, **kw)
    if p.returncode != expect_rc:
        print(p.stdout)
        print(p.stderr, file=sys.stderr)
        raise SystemExit(
            f"[replan-gate] {name}: rc={p.returncode}, expected {expect_rc}")
    return p


def load_records(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def by_name(records, name):
    return [r for r in records if r["name"] == name]


def jacobi_cmd(args, ckpt, metrics=None, swap=False, inject=""):
    cmd = [
        PY, "-m", "stencil_tpu.apps.jacobi3d", "--cpu", "8",
        "--x", str(args.size), "--y", str(args.size), "--z", str(args.size),
        "--iters", str(ITERS), "--method", "direct26",
        # health boundaries force CHUNK-step fused chunks, so the
        # sentinel sees per-chunk samples (two healthy warmup chunks
        # before the injected slow at SLOW_STEP)
        "--health-every", str(CHUNK),
        "--ckpt-dir", ckpt,
    ]
    if metrics:
        cmd += ["--metrics-out", metrics]
    if swap:
        cmd += ["--live-sentinel", "--live-config", LIVE_CONFIG, "--replan"]
    if inject:
        cmd += ["--inject", inject]
    return cmd


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--size", type=int, default=24)
    p.add_argument("--out-dir", default="",
                   help="keep metrics artifacts here for CI upload "
                        "(default: a temp dir, removed)")
    args = p.parse_args()

    work = tempfile.mkdtemp(prefix="replan-gate-")
    out_dir = os.path.abspath(args.out_dir) if args.out_dir else work
    os.makedirs(out_dir, exist_ok=True)
    try:
        # ---- 1. placement conformance (>= 3 non-identity permutations) ------
        run([PY, "-m", "stencil_tpu.apps.lint_tool", "verify-plan",
             "--cpu", "8", "--methods", "axis-composed",
             "--quantities", "f32", "--placements", "3"],
            name="placement-conformance")
        print("[replan-gate] 3 non-identity placements: mesh order, "
              "source_target_pairs, and bit parity all conform")

        # ---- 2. QAP cost vs identity (derived + synthetic + model) ----------
        g = run([PY, "-c", QAP_SNIPPET], name="qap-vs-identity")
        print("[replan-gate] " + g.stdout.strip().splitlines()[-1])

        # ---- 3. hot-swap e2e -------------------------------------------------
        ck_swap = os.path.join(work, "ck-swap")
        m_swap = os.path.join(out_dir, "m_swap.jsonl")
        run(jacobi_cmd(args, ck_swap, metrics=m_swap, swap=True,
                       inject=f"slow@{SLOW_STEP}:seconds=0.6"),
            name="swap-run")
        recs = load_records(m_swap)
        req = by_name(recs, "replan.requested")
        app = by_name(recs, "replan.applied")
        rej = by_name(recs, "replan.rejected")
        if not req:
            raise SystemExit("[replan-gate] the sentinel never requested "
                             "a replan (injection missed the band?)")
        if not app:
            raise SystemExit(f"[replan-gate] replan requested but never "
                             f"APPLIED (rejected: "
                             f"{[r.get('reason') for r in rej]})")
        delta = app[0]["step"] - req[0]["step"]
        if not 0 <= delta <= SWAP_WINDOW_STEPS:
            raise SystemExit(
                f"[replan-gate] swap at step {app[0]['step']} is not "
                f"within 2 chunks ({SWAP_WINDOW_STEPS} steps) of the "
                f"request at {req[0]['step']}")
        if app[0]["old"] == app[0]["new"]:
            raise SystemExit(f"[replan-gate] the swap must install a "
                             f"DIFFERENT plan: {app[0]}")
        print(f"[replan-gate] swap applied at step {app[0]['step']} "
              f"(+{delta} steps): {app[0]['old']} -> {app[0]['new']}")

        ck_ref = os.path.join(work, "ck-ref")
        run(jacobi_cmd(args, ck_ref), name="unswapped-reference")
        run([PY, "-m", "stencil_tpu.apps.ckpt_tool", "diff", ck_ref,
             ck_swap, "--data", "--elastic"],
            name="diff-swap-vs-unswapped")
        print("[replan-gate] swapped run bit-identical to the unswapped "
              "reference (elastic across the partition change)")

        # a clean replan-armed run must stay silent
        ck_clean = os.path.join(work, "ck-clean")
        m_clean = os.path.join(work, "m_clean.jsonl")
        run(jacobi_cmd(args, ck_clean, metrics=m_clean, swap=True),
            name="clean-armed-run")
        noisy = [r["name"] for r in load_records(m_clean)
                 if r["name"].startswith("replan.")]
        if noisy:
            raise SystemExit(f"[replan-gate] clean armed run emitted "
                             f"replan records: {noisy}")
        print("[replan-gate] clean armed run: zero replan records")

        # ---- 4. vocabulary schema (replan.* + qap.*) -------------------------
        m_qap = os.path.join(out_dir, "m_qap.jsonl")
        run([PY, "-m", "stencil_tpu.apps.bench_qap", "--derived",
             "--cpu", "8", "--x", "32", "--sizes", "4",
             "--catch-sizes", "16", "--metrics-out", m_qap],
            name="bench-qap-derived")
        qrecs = load_records(m_qap)
        for need in ("qap.placement_cost", "qap.improvement"):
            if not by_name(qrecs, need):
                raise SystemExit(f"[replan-gate] bench_qap --derived "
                                 f"recorded no {need} gauge")
        for metrics, name in ((m_swap, "swap"), (m_clean, "clean"),
                              (m_qap, "qap")):
            run([PY, "-m", "stencil_tpu.apps.report", metrics,
                 "--validate"], name=f"validate-{name}")
        print("[replan-gate] replan.*/qap.* vocabulary schema-valid")

        print(f"[replan-gate] PASS (artifacts: {out_dir})")
        return 0
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
