#!/usr/bin/env python
"""CI persistent whole-chunk gate: the ISSUE-16 acceptance proof on the
CPU mesh.

Four stages, exit 0 only if every one holds:

1. **parity + launch census**: at 24^3 on the 2x2x2 8-virtual-device
   mesh, the PERSISTENT chunk loop (``HaloExchange(Method.REMOTE_DMA,
   persistent=True)`` — ONE deep radius*k exchange + ONE k-substep chunk
   program per chunk) lands bit-identical to the AXIS_COMPOSED baseline
   AND to the per-step plain REMOTE_DMA loop at k in {2, 4}, uniform AND
   uneven partitions, with the measured ``last_launches_per_chunk``
   pinned at 2 (O(chunks), not O(steps)) and recorded as the
   ``exchange.launches_per_chunk`` gauge (source=measured);
2. **conformance**: ``analysis/verify_plan`` audits the
   ``remote-dma+persistent`` label — zero-collective census, predicted
   DMA count, and measured-vs-predicted launches_per_chunk — and trips
   when the DMA prediction is perturbed;
3. **autotuner round-trip**: ``plan_tool autotune --methods remote-dma
   --variants persistent --ks 1,2`` tunes (probes run against the
   deep-halo emulation), persists a kernel_variant=persistent entry,
   and a second invocation replays it as a pure DB hit with zero
   probes; all metrics pass ``report --validate``;
4. **lint**: ``lint_tool lint`` stays green over the new modules
   (0 new findings against the committed baseline).

Run from the repo root:  python scripts/ci_persistent_gate.py [--size 24]
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
import jax.numpy as jnp
from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.obs import telemetry
from stencil_tpu.ops.jacobi import INIT_TEMP, make_jacobi_loop, sphere_sel
from stencil_tpu.parallel import HaloExchange, Method, grid_mesh
from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

size, metrics = int(sys.argv[1]), sys.argv[2]
rec = telemetry.configure(metrics_out=metrics, app="ci_persistent_gate")

def run_loop(sz, dim, k, iters, mode):
    spec = GridSpec(Dim3(*sz), Dim3(*dim), Radius.constant(k))
    mesh = grid_mesh(spec.dim, jax.devices()[: spec.dim.flatten()])
    if mode == "persistent":
        ex = HaloExchange(spec, mesh, Method.REMOTE_DMA, persistent=True)
        loop = make_jacobi_loop(ex, iters, temporal_k=k)
    elif mode == "plain":
        ex = HaloExchange(spec, mesh, Method.REMOTE_DMA)
        loop = make_jacobi_loop(ex, iters, temporal_k=k)
    else:
        ex = HaloExchange(spec, mesh, Method.AXIS_COMPOSED)
        loop = make_jacobi_loop(ex, iters)
    g = spec.global_size
    c = shard_blocks(np.full((g.z, g.y, g.x), INIT_TEMP, np.float32),
                     spec, mesh)
    n = jax.device_put(jnp.zeros_like(c), ex.sharding())
    sel = shard_blocks(sphere_sel((g.x, g.y, g.z)), spec, mesh)
    c, _ = loop(c, n, sel)
    if mode == "persistent":
        lpc = ex.last_launches_per_chunk
        assert lpc == 2, f"measured launches/chunk {lpc} != 2 (O(chunks))"
        telemetry.record_exchange_truth(
            ex, {0: c}, [4], variant="persistent")
    return unshard_blocks(c, spec)

# k in {2, 4} on the uniform 2x2x2 partition (tail chunk at k=4), plus
# an UNEVEN anisotropic split — all bit-identical to composed AND to the
# per-step plain remote-dma loop at the same deep-halo config
cases = [
    ((size, size, size), (2, 2, 2), 2, 8),
    ((size, size, size), (2, 2, 2), 4, 10),
    ((size - 6, size - 4, size - 2), (1, 2, 4), 2, 6),
]
for sz, dim, k, iters in cases:
    ref = run_loop(sz, dim, k, iters, "composed")
    plain = run_loop(sz, dim, k, iters, "plain")
    pers = run_loop(sz, dim, k, iters, "persistent")
    tag = f"{sz}/{dim}/k{k}"
    assert np.array_equal(ref, pers), f"PERSISTENT differs from COMPOSED {tag}"
    assert np.array_equal(plain, pers), f"PERSISTENT differs from PLAIN {tag}"

# conformance sweep: the remote-dma+persistent label audits clean and
# the perturbed sweep trips (the gate proves the auditor has teeth)
from stencil_tpu.analysis import verify_plan as vp

cfgs = vp.sweep_configs(size=16, radius=2, partitions=[(2, 2, 2)],
                        methods=[vp.PERSISTENT_METHOD_LABEL],
                        qsets=[("float32",)])
res = vp.run_sweep(cfgs)
assert res["checked"] == 1 and res["failed"] == 0, res
checks = {c["name"]: c for c in res["verdicts"][0].checks}
assert checks["launches_per_chunk"]["predicted"] == 2, checks
assert checks["launches_per_chunk"]["ok"], checks
res = vp.run_sweep(cfgs, perturb_dmas=1)
assert res["failed"] == 1, "perturbed persistent sweep did not trip"
rec.close()
print("PERSISTENT_PARITY_OK")
"""


def run(cmd, env=None, expect_rc=0, name=""):
    shown = " ".join(a if len(a) < 200 else "<inline child>" for a in cmd)
    print(f"[persistent-gate] {name}: {shown}", flush=True)
    e = dict(os.environ)
    if env:
        e.update(env)
    p = subprocess.run(cmd, env=e, cwd=REPO, capture_output=True, text=True)
    if p.returncode != expect_rc:
        print(p.stdout)
        print(p.stderr, file=sys.stderr)
        raise SystemExit(
            f"[persistent-gate] {name}: rc={p.returncode}, "
            f"expected {expect_rc}"
        )
    return p


def metrics_records(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--size", type=int, default=24)
    args = p.parse_args()

    work = tempfile.mkdtemp(prefix="persistent-gate-")
    db = os.path.join(work, "plans.json")
    try:
        # 1 + 2. parity at k in {2,4} / uneven split, measured launch
        # census == 2, conformance auditor green and trippable
        pm = os.path.join(work, "parity.jsonl")
        r = run([PY, "-c", PARITY_CHILD, str(args.size), pm], name="parity")
        if "PERSISTENT_PARITY_OK" not in r.stdout:
            raise SystemExit("[persistent-gate] parity child gave no verdict")
        recs = metrics_records(pm)
        gauges = [rec for rec in recs if rec["kind"] == "gauge"
                  and rec["name"] == "exchange.launches_per_chunk"]
        measured = [g for g in gauges if g.get("source") == "measured"]
        if not measured or any(g["value"] != 2 for g in measured):
            raise SystemExit(
                f"[persistent-gate] measured launches_per_chunk gauges "
                f"not pinned at 2: {[g.get('value') for g in gauges]}"
            )

        # 3. autotuner DB round-trip with a persistent-variant entry
        def tune(metrics, name):
            return run(
                [PY, "-m", "stencil_tpu.apps.plan_tool", "autotune",
                 "--cpu", "8", "--db", db, "--methods", "remote-dma",
                 "--variants", "persistent", "--ks", "1,2",
                 "--x", str(args.size), "--y", str(args.size),
                 "--z", str(args.size), "--radius", "1",
                 "--quantities", "1", "--probe-iters", "2", "--top-n", "1",
                 "--metrics-out", metrics],
                name=name,
            )

        t1 = os.path.join(work, "tune.jsonl")
        r = tune(t1, "tune-persistent")
        if "persistent" not in r.stdout:
            raise SystemExit(
                f"[persistent-gate] tuner did not pick the persistent "
                f"variant:\n{r.stdout}")
        t2 = os.path.join(work, "replay.jsonl")
        r = tune(t2, "replay-persistent")
        if "cache_hit: True" not in r.stdout or "probes_run: 0" not in r.stdout:
            raise SystemExit(
                f"[persistent-gate] replay was not a pure DB hit:\n"
                f"{r.stdout}")
        with open(db) as f:
            dbobj = json.load(f)
        variants = [e["choice"].get("kernel_variant")
                    for e in dbobj["entries"].values()]
        if variants != ["persistent"]:
            raise SystemExit(
                f"[persistent-gate] DB entries carry variants {variants}, "
                "expected exactly one 'persistent' entry")

        # every metrics file passes the schema gate
        run([PY, "-m", "stencil_tpu.apps.report", pm, t1, t2,
             "--validate"], name="schema")

        # 4. the repo lint stays green over the new modules
        run([PY, "-m", "stencil_tpu.apps.lint_tool", "lint"], name="lint")
        print("[persistent-gate] PASS")
        return 0
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
