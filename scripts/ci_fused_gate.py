#!/usr/bin/env python
"""CI fused compute+exchange gate: the ISSUE-14 acceptance proof on the
CPU mesh.

Five stages, exit 0 only if every one holds:

1. **parity + census**: at 24^3 on the 2x2x2 8-virtual-device mesh, the
   FUSED exchange (``HaloExchange(Method.REMOTE_DMA, fused=True)`` — the
   concurrent per-direction schedule) is bit-identical to AXIS_COMPOSED
   on coordinate fields (fp32 AND a mixed fp32/fp64 dict), its census
   over every compiled piece contains ZERO collective-permutes, the
   recorded ``exchange.permutes_per_quantity`` gauge reads 0, AND the
   full fused jacobi step loop (pack -> start -> interior -> wait ->
   boundary, 4 iterations) lands bit-identical to the composed step;
2. **overlap telemetry**: the parity run's metrics carry the
   ``fused.interior`` / ``fused.dma_wait`` / ``fused.boundary`` spans
   and a ``fused.overlap_fraction`` gauge in [0, 1], all schema-valid
   under ``report --validate``;
3. **fp8 wire A/B**: ``bench_exchange --wire-ab --wire-dtype
   float8_e4m3fn`` must gate >= 3.8x on-wire byte reduction vs fp32 at
   an unchanged permute/DMA count with max error inside the e4m3
   half-ulp bound (the app exits 1 itself otherwise);
4. **autotuner round-trip**: ``plan_tool autotune --methods remote-dma
   --variants fused`` tunes (probes run against the fused emulation),
   persists a kernel_variant=fused entry, and a second invocation
   replays it as a pure DB hit with zero probes;
5. **lint**: ``lint_tool lint`` stays green over the new modules
   (0 new findings against the committed baseline).

Run from the repo root:  python scripts/ci_fused_gate.py [--size 24]
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
jax.config.update("jax_enable_x64", True)
import numpy as np
import jax.numpy as jnp
from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.obs import telemetry
from stencil_tpu.parallel import HaloExchange, Method, grid_mesh
from stencil_tpu.parallel.exchange import shard_blocks

size, metrics = int(sys.argv[1]), sys.argv[2]
rec = telemetry.configure(metrics_out=metrics, app="ci_fused_gate")
spec = GridSpec(Dim3(size, size, size), Dim3(2, 2, 2), Radius.constant(2))
mesh = grid_mesh(spec.dim, jax.devices()[:8])
g = spec.global_size
coord = (np.arange(g.z)[:, None, None] * 1e6
         + np.arange(g.y)[None, :, None] * 1e3
         + np.arange(g.x)[None, None, :])

def state(dtypes):
    return {i: shard_blocks((coord + i).astype(dt), spec, mesh)
            for i, dt in enumerate(dtypes)}

# exchange-level parity + census, fp32 and mixed-dtype
for dtypes in ([np.float32] * 4, [np.float32, np.float64, np.float32]):
    outs = {}
    for method, fused in ((Method.AXIS_COMPOSED, False),
                          (Method.REMOTE_DMA, True)):
        ex = HaloExchange(spec, mesh, method, fused=fused)
        out = ex(state(dtypes))
        outs[fused] = [np.asarray(jax.device_get(out[i]))
                       for i in sorted(out)]
        if fused:
            census = ex.collective_census(state(dtypes))
            assert census.get("collective-permute", (0, 0))[0] == 0, census
            assert sum(c for c, _b in census.values()) == 0, census
            itemsizes = [np.dtype(dt).itemsize for dt in dtypes]
            telemetry.record_exchange_truth(ex, state(dtypes), itemsizes,
                                            variant="fused")
    for a, b in zip(outs[False], outs[True]):
        assert np.array_equal(a, b), "FUSED exchange differs from COMPOSED"

# full fused jacobi step-loop parity (the overlap schedule end to end)
from stencil_tpu.ops.jacobi import INIT_TEMP, make_jacobi_loop, sphere_sel

sel = shard_blocks(sphere_sel((size, size, size)), spec, mesh)
results = {}
for method, fused in ((Method.AXIS_COMPOSED, False),
                      (Method.REMOTE_DMA, True)):
    ex = HaloExchange(spec, mesh, method, fused=fused)
    loop = make_jacobi_loop(ex, 4)
    # per-leg field: the composed loop donates its input buffers
    c = shard_blocks(np.full((size,) * 3, INIT_TEMP, np.float32),
                     spec, mesh)
    n = jax.device_put(jnp.zeros_like(c), ex.sharding())
    c, _n = loop(c, n, sel)
    results[fused] = np.asarray(jax.device_get(c))
assert np.array_equal(results[False], results[True]), \
    "fused jacobi step loop differs from the composed step"
rec.close()
print("FUSED_PARITY_OK")
"""


def run(cmd, env=None, expect_rc=0, name=""):
    shown = " ".join(a if len(a) < 200 else "<inline child>" for a in cmd)
    print(f"[fused-gate] {name}: {shown}", flush=True)
    e = dict(os.environ)
    if env:
        e.update(env)
    p = subprocess.run(cmd, env=e, cwd=REPO, capture_output=True, text=True)
    if p.returncode != expect_rc:
        print(p.stdout)
        print(p.stderr, file=sys.stderr)
        raise SystemExit(
            f"[fused-gate] {name}: rc={p.returncode}, expected {expect_rc}"
        )
    return p


def metrics_records(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--size", type=int, default=24)
    args = p.parse_args()

    work = tempfile.mkdtemp(prefix="fused-gate-")
    db = os.path.join(work, "plans.json")
    try:
        # 1. parity + 0-ppermute census + step-loop parity
        pm = os.path.join(work, "parity.jsonl")
        r = run([PY, "-c", PARITY_CHILD, str(args.size), pm], name="parity")
        if "FUSED_PARITY_OK" not in r.stdout:
            raise SystemExit("[fused-gate] parity child gave no verdict")
        recs = metrics_records(pm)
        gauges = [rec for rec in recs if rec["kind"] == "gauge"
                  and rec["name"] == "exchange.permutes_per_quantity"]
        if not gauges or any(g["value"] != 0 for g in gauges):
            raise SystemExit(
                f"[fused-gate] permutes_per_quantity gauge not 0: "
                f"{[g.get('value') for g in gauges]}"
            )

        # 2. overlap telemetry: the fused spans + overlap_fraction gauge
        spans = {rec["name"] for rec in recs if rec["kind"] == "span"}
        for want in ("fused.interior", "fused.dma_wait", "fused.boundary"):
            if want not in spans:
                raise SystemExit(
                    f"[fused-gate] span {want!r} missing from the fused "
                    f"run's metrics (saw {sorted(spans)})"
                )
        overlaps = [rec["value"] for rec in recs if rec["kind"] == "gauge"
                    and rec["name"] == "fused.overlap_fraction"]
        if not overlaps or any(not (0.0 <= v <= 1.0) for v in overlaps):
            raise SystemExit(
                f"[fused-gate] fused.overlap_fraction missing or out of "
                f"[0, 1]: {overlaps}"
            )

        # 3. fp8 wire A/B (the app's own gate: >=3.8x bytes, e4m3 bound,
        # unchanged count)
        wm = os.path.join(work, "wire.jsonl")
        run([PY, "-m", "stencil_tpu.apps.bench_exchange", "--wire-ab",
             "--x", str(args.size), "--y", str(args.size),
             "--z", str(args.size), "--iters", "3", "--quantities", "4",
             "--partition", "2x2x2", "--wire-dtype", "float8_e4m3fn",
             "--metrics-out", wm],
            env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
            name="wire-ab-fp8")
        ratios = [rec["value"] for rec in metrics_records(wm)
                  if rec["kind"] == "gauge"
                  and rec["name"] == "wire_ab.bytes_ratio"]
        if not ratios or ratios[-1] < 3.8:
            raise SystemExit(
                f"[fused-gate] fp8 wire bytes ratio {ratios} < 3.8")

        # 4. autotuner DB round-trip with a fused-variant entry
        def tune(metrics, name):
            return run(
                [PY, "-m", "stencil_tpu.apps.plan_tool", "autotune",
                 "--cpu", "8", "--db", db, "--methods", "remote-dma",
                 "--variants", "fused",
                 "--x", str(args.size), "--y", str(args.size),
                 "--z", str(args.size), "--radius", "2",
                 "--quantities", "1", "--probe-iters", "2", "--top-n", "1",
                 "--metrics-out", metrics],
                name=name,
            )

        t1 = os.path.join(work, "tune.jsonl")
        r = tune(t1, "tune-fused")
        if "/fused" not in r.stdout:
            raise SystemExit(
                f"[fused-gate] tuner did not pick the fused variant:\n"
                f"{r.stdout}")
        t2 = os.path.join(work, "replay.jsonl")
        r = tune(t2, "replay-fused")
        if "cache_hit: True" not in r.stdout or "probes_run: 0" not in r.stdout:
            raise SystemExit(
                f"[fused-gate] replay was not a pure DB hit:\n{r.stdout}")
        with open(db) as f:
            dbobj = json.load(f)
        variants = [e["choice"].get("kernel_variant")
                    for e in dbobj["entries"].values()]
        if variants != ["fused"]:
            raise SystemExit(
                f"[fused-gate] DB entries carry variants {variants}, "
                "expected exactly one 'fused' entry")

        # every metrics file passes the schema gate
        run([PY, "-m", "stencil_tpu.apps.report", pm, wm, t1, t2,
             "--validate"], name="schema")

        # 5. the repo lint stays green over the new modules
        run([PY, "-m", "stencil_tpu.apps.lint_tool", "lint"], name="lint")
        print("[fused-gate] PASS")
        return 0
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
