#!/usr/bin/env python
"""CI hierarchical ICI+DCN gate: the ISSUE-17 acceptance proof on the
CPU mesh (STENCIL_VIRTUAL_HOSTS virtual-host fabric).

Five stages, exit 0 only if every one holds:

1. **step-loop bit parity**: at 16^3 on the 2x2x2 8-virtual-device mesh
   split z x 2 hosts, the hierarchical exchange (cross-host DCN slabs
   started before the inner per-host programs, ``parallel/hierarchy.py``)
   lands the 5-iteration jacobi loop bit-identical to the flat plan
   through EVERY inner transport — axis-composed (overlap on and off),
   remote-dma, fused, persistent;
2. **DCN conformance**: ``lint_tool verify-plan --hierarchy 2`` audits
   predicted-vs-executed DCN transfers and wire bytes, unchanged inner
   census pins, zero stray collectives, and flat bit parity across
   partitions x inner methods x dtype sets — and ``--perturb-dcn 1``
   must TRIP it (rc 1: the auditor has teeth);
3. **two-level NodeAware**: on the anisotropic 16x16x64 grid with an
   interleaved 2-host device map (the scrambled fabric), the blocks->
   hosts + blocks->chips QAP composes a placement STRICTLY cheaper than
   identity (pinned cost values), while the uniform fabric solves to
   identity by design (``(None, None)`` — flat-equivalent);
4. **autotuner round-trip**: with the virtual-host fabric open, the
   ranked candidate space contains hierarchical plans, the winner
   persists, a second invocation replays it as a pure DB hit with zero
   probes, the DB validates, and a hierarchical choice realizes
   end-to-end through ``DistributedDomain`` (executed DCN transfers
   nonzero); all metrics pass ``report --validate``;
5. **lint**: the repo lint stays green over the new modules.

Run from the repo root:  python scripts/ci_dcn_gate.py
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PY = sys.executable

CHILD_PRELUDE = r"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["STENCIL_VIRTUAL_HOSTS"] = "2"
import jax
import numpy as np
"""

PARITY_CHILD = CHILD_PRELUDE + r"""
from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.ops.jacobi import make_jacobi_loop, sphere_masks
from stencil_tpu.parallel import HaloExchange, Method, grid_mesh
from stencil_tpu.parallel.exchange import shard_blocks

spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(2))
g = spec.global_size
rng = np.random.default_rng(0)
CURR = rng.standard_normal((g.z, g.y, g.x)).astype(np.float32)
hot, cold = sphere_masks(g)
SEL = np.zeros((g.z, g.y, g.x), np.float32)
SEL[hot] = 1
SEL[cold] = 2

def run(method, hierarchy, iters=5, overlap=True, **kw):
    mesh = grid_mesh(spec.dim)
    ex = HaloExchange(spec, mesh, method=method, hierarchy=hierarchy, **kw)
    c = shard_blocks(CURR, spec, mesh)
    n = shard_blocks(np.zeros_like(CURR), spec, mesh)
    s = shard_blocks(SEL, spec, mesh)
    loop = make_jacobi_loop(ex, iters, overlap=overlap)
    out, _ = loop(c, n, s)
    return np.asarray(jax.device_get(out))

def check(tag, a, b):
    assert np.array_equal(a, b), f"HIERARCHICAL differs from FLAT: {tag}"

flat = run(Method.AXIS_COMPOSED, None)
check("composed", flat, run(Method.AXIS_COMPOSED, ("z", 2)))
check("composed/overlap-off", flat,
      run(Method.AXIS_COMPOSED, ("z", 2), overlap=False))
check("remote-dma", run(Method.REMOTE_DMA, None),
      run(Method.REMOTE_DMA, ("z", 2)))
check("fused", run(Method.REMOTE_DMA, None, fused=True),
      run(Method.REMOTE_DMA, ("z", 2), fused=True))
check("persistent", run(Method.REMOTE_DMA, None, persistent=True),
      run(Method.REMOTE_DMA, ("z", 2), persistent=True))
check("remote==composed", flat, run(Method.REMOTE_DMA, None))
print("DCN_PARITY_OK")
"""

QAP_CHILD = r"""
import numpy as np
from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.plan.cost import (placement_cost, placement_wire_matrix,
                                   solve_two_level_placement)

# the anisotropic grid: a 2x2x2 partition of 16x16x64 wires far more
# bytes across z faces than x/y, so host grouping MATTERS (a cubic grid
# ties by symmetry and proves nothing)
spec = GridSpec(Dim3(16, 16, 64), Dim3(2, 2, 2), Radius.constant(2))
md = spec.dim
w = placement_wire_matrix(spec, md)

# scrambled 2-host fabric: devices interleaved across hosts, cross-host
# links 7x the intra-host cost (the PR-15 process-boundary ladder)
host_map = [0, 1, 0, 1, 0, 1, 0, 1]
same = np.equal.outer(host_map, host_map)
link = np.where(np.eye(8, dtype=bool), 0.0, np.where(same, 1.0, 7.0))
hp, perm = solve_two_level_placement(w, link, md, ("z", 2), host_map)
assert perm is not None, "scrambled fabric solved to identity"
placed = placement_cost(w, link, perm)
ident = placement_cost(w, link, None)
print(f"two-level QAP: placed {placed:.0f} identity {ident:.0f} "
      f"perm {list(perm)}")
assert placed < ident, f"two-level placement not cheaper: {placed} >= {ident}"
assert (placed, ident) == (52736.0, 108032.0), (placed, ident)

# uniform fabric: identity by design — flat-equivalent
uni = np.where(np.eye(8, dtype=bool), 0.0, 1.0)
hp2, perm2 = solve_two_level_placement(w, uni, md, ("z", 2), None)
assert hp2 is None and perm2 is None, (hp2, perm2)
print("DCN_QAP_OK")
"""

TUNE_CHILD = CHILD_PRELUDE + r"""
import sys
from stencil_tpu.api import DistributedDomain
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.plan import db as plandb
from stencil_tpu.plan.autotune import autotune

dbp = sys.argv[1]
res = autotune(Dim3(32, 32, 32), Radius.constant(2), ["float32"],
               devices=jax.devices(), db_path=dbp, probe=True, top_n=3,
               probe_iters=2)
nhier = sum(1 for _c, ch in res.ranked if ch.is_hierarchical)
assert nhier > 0, "no hierarchical candidates in the ranked space"
res2 = autotune(Dim3(32, 32, 32), Radius.constant(2), ["float32"],
                devices=jax.devices(), db_path=dbp, probe=True)
assert res2.cache_hit and res2.probes_run == 0, (res2.cache_hit,
                                                 res2.probes_run)
assert res2.choice == res.choice
errs = plandb.validate_db(plandb.load_db(dbp))
assert not errs, errs[:3]

# a hierarchical choice realizes end-to-end and actually moves DCN slabs
ch = next(ch for _c, ch in res.ranked
          if ch.is_hierarchical and ch.method == "axis-composed")
dd = DistributedDomain(32, 32, 32, plan=ch)
dd.set_radius(2)
h = dd.add_data("u", "float32")
dd.realize()
assert dd.halo_exchange.hierarchical
assert dd.plan_meta()["choice"]["hierarchy"] is not None
dd.set_curr_global(h, np.random.default_rng(1)
                   .standard_normal((32, 32, 32)).astype(np.float32))
dd.exchange()
n = dd.halo_exchange._compiled.last_transfer_count
assert n > 0, "hierarchical exchange executed zero DCN transfers"
print(f"tuned {res.choice.label()} hier_candidates {nhier} dcn_copies {n}")
print("DCN_TUNE_OK")
"""


def run(cmd, env=None, expect_rc=0, name=""):
    shown = " ".join(a if len(a) < 200 else "<inline child>" for a in cmd)
    print(f"[dcn-gate] {name}: {shown}", flush=True)
    e = dict(os.environ)
    if env:
        e.update(env)
    p = subprocess.run(cmd, env=e, cwd=REPO, capture_output=True, text=True)
    if p.returncode != expect_rc:
        print(p.stdout)
        print(p.stderr, file=sys.stderr)
        raise SystemExit(f"[dcn-gate] {name}: rc={p.returncode}, "
                         f"expected {expect_rc}")
    return p


def main() -> int:
    work = tempfile.mkdtemp(prefix="dcn-gate-")
    try:
        # 1. flat == hierarchical through every inner transport
        r = run([PY, "-c", PARITY_CHILD], name="parity")
        if "DCN_PARITY_OK" not in r.stdout:
            raise SystemExit("[dcn-gate] parity child gave no verdict")

        # 2. the DCN conformance sweep is green, and the perturb knob
        # proves the auditor trips on IR drift
        vm = os.path.join(work, "verify.jsonl")
        run([PY, "-m", "stencil_tpu.apps.lint_tool", "verify-plan",
             "--cpu", "8", "--hierarchy", "2", "--metrics-out", vm],
            name="verify-plan")
        run([PY, "-m", "stencil_tpu.apps.lint_tool", "verify-plan",
             "--cpu", "8", "--hierarchy", "2", "--perturb-dcn", "1"],
            expect_rc=1, name="verify-plan-perturbed")

        # 3. two-level NodeAware: strictly cheaper on the scrambled
        # fabric, identity (flat-equivalent) on the uniform one
        r = run([PY, "-c", QAP_CHILD], name="two-level-qap")
        if "DCN_QAP_OK" not in r.stdout:
            raise SystemExit("[dcn-gate] QAP child gave no verdict")
        print("[dcn-gate] " + r.stdout.splitlines()[0])

        # 4. tune -> persist -> zero-probe replay -> realize
        db = os.path.join(work, "plans.json")
        r = run([PY, "-c", TUNE_CHILD, db], name="tune-roundtrip")
        if "DCN_TUNE_OK" not in r.stdout:
            raise SystemExit("[dcn-gate] tune child gave no verdict")

        # every metrics record passes the schema gate
        run([PY, "-m", "stencil_tpu.apps.report", vm, "--validate"],
            name="schema")

        # 5. the repo lint stays green over the new modules
        run([PY, "-m", "stencil_tpu.apps.lint_tool", "lint"], name="lint")
        print("[dcn-gate] PASS")
        return 0
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
