#!/usr/bin/env python
"""CI remote-DMA gate: the ISSUE-10 acceptance proof on the CPU mesh.

Four stages, exit 0 only if every one holds:

1. **parity + census**: a 24^3 REMOTE_DMA exchange on the 2x2x2
   8-virtual-device mesh is bit-identical to AXIS_COMPOSED on coordinate
   fields (fp32 AND a mixed fp32/fp64 dict), its census over every
   compiled piece of the emulation contains ZERO collective-permutes,
   and the recorded ``exchange.permutes_per_quantity`` gauge reads 0;
2. **wire A/B**: ``bench_exchange --wire-ab`` at the same config must
   report >= 1.9x on-wire byte reduction for bfloat16 with the measured
   max error inside the bf16 rounding bound (the app exits 1 itself
   otherwise) and schema-valid metrics;
3. **autotuner round-trip**: ``plan_tool autotune --methods remote-dma``
   tunes (measured probes run against the emulation), persists a
   remote-dma-keyed entry, and a second invocation replays it as a pure
   DB hit with zero probes;
4. **schema**: every metrics file passes ``report --validate``.

Run from the repo root:  python scripts/ci_remote_dma_gate.py [--size 24]
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
from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.obs import telemetry
from stencil_tpu.parallel import HaloExchange, Method, grid_mesh
from stencil_tpu.parallel.exchange import shard_blocks

size, metrics = int(sys.argv[1]), sys.argv[2]
rec = telemetry.configure(metrics_out=metrics, app="ci_remote_dma_gate")
spec = GridSpec(Dim3(size, size, size), Dim3(2, 2, 2), Radius.constant(2))
mesh = grid_mesh(spec.dim, jax.devices()[:8])
g = spec.global_size
coord = (np.arange(g.z)[:, None, None] * 1e6
         + np.arange(g.y)[None, :, None] * 1e3
         + np.arange(g.x)[None, None, :])

def state(dtypes):
    return {i: shard_blocks((coord + i).astype(dt), spec, mesh)
            for i, dt in enumerate(dtypes)}

for dtypes in ([np.float32] * 4, [np.float32, np.float64, np.float32]):
    outs = {}
    for method in (Method.AXIS_COMPOSED, Method.REMOTE_DMA):
        ex = HaloExchange(spec, mesh, method)
        out = ex(state(dtypes))
        outs[method] = [np.asarray(jax.device_get(out[i]))
                        for i in sorted(out)]
        if method == Method.REMOTE_DMA:
            census = ex.collective_census(state(dtypes))
            assert census.get("collective-permute", (0, 0))[0] == 0, census
            assert sum(c for c, _b in census.values()) == 0, census
            itemsizes = [np.dtype(dt).itemsize for dt in dtypes]
            telemetry.record_exchange_truth(ex, state(dtypes), itemsizes)
    for a, b in zip(outs[Method.AXIS_COMPOSED], outs[Method.REMOTE_DMA]):
        assert np.array_equal(a, b), "REMOTE_DMA differs from AXIS_COMPOSED"
rec.close()
print("REMOTE_DMA_PARITY_OK")
"""


def run(cmd, env=None, expect_rc=0, name=""):
    print(f"[remote-dma-gate] {name}: {' '.join(cmd)}", flush=True)
    e = dict(os.environ)
    if env:
        e.update(env)
    p = subprocess.run(cmd, env=e, cwd=REPO, capture_output=True, text=True)
    if p.returncode != expect_rc:
        print(p.stdout)
        print(p.stderr, file=sys.stderr)
        raise SystemExit(
            f"[remote-dma-gate] {name}: rc={p.returncode}, "
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

    work = tempfile.mkdtemp(prefix="remote-dma-gate-")
    db = os.path.join(work, "plans.json")
    try:
        # 1. parity + 0-ppermute census + gauge
        pm = os.path.join(work, "parity.jsonl")
        r = run([PY, "-c", PARITY_CHILD, str(args.size), pm], name="parity")
        if "REMOTE_DMA_PARITY_OK" not in r.stdout:
            raise SystemExit("[remote-dma-gate] parity child gave no verdict")
        gauges = [rec for rec in metrics_records(pm)
                  if rec["kind"] == "gauge"
                  and rec["name"] == "exchange.permutes_per_quantity"]
        if not gauges or any(g["value"] != 0 for g in gauges):
            raise SystemExit(
                f"[remote-dma-gate] permutes_per_quantity gauge not 0: "
                f"{[g.get('value') for g in gauges]}"
            )

        # 2. bf16 wire A/B (the app's own gate: >=1.9x bytes + error bound)
        wm = os.path.join(work, "wire.jsonl")
        run([PY, "-m", "stencil_tpu.apps.bench_exchange", "--wire-ab",
             "--x", str(args.size), "--y", str(args.size),
             "--z", str(args.size), "--iters", "3", "--quantities", "4",
             "--partition", "2x2x2", "--metrics-out", wm],
            env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
            name="wire-ab")
        ratios = [rec["value"] for rec in metrics_records(wm)
                  if rec["kind"] == "gauge"
                  and rec["name"] == "wire_ab.bytes_ratio"]
        if not ratios or ratios[-1] < 1.9:
            raise SystemExit(
                f"[remote-dma-gate] wire bytes ratio {ratios} < 1.9")

        # 3. autotuner DB round-trip with a remote-dma-keyed entry
        def tune(metrics, name):
            return run(
                [PY, "-m", "stencil_tpu.apps.plan_tool", "autotune",
                 "--cpu", "8", "--db", db, "--methods", "remote-dma",
                 "--x", str(args.size), "--y", str(args.size),
                 "--z", str(args.size), "--radius", "2",
                 "--quantities", "1", "--probe-iters", "2", "--top-n", "1",
                 "--metrics-out", metrics],
                name=name,
            )

        t1 = os.path.join(work, "tune.jsonl")
        r = tune(t1, "tune-remote")
        if "remote-dma" not in r.stdout:
            raise SystemExit("[remote-dma-gate] tuner did not pick "
                             f"remote-dma:\n{r.stdout}")
        t2 = os.path.join(work, "replay.jsonl")
        r = tune(t2, "replay-remote")
        if "cache_hit: True" not in r.stdout or "probes_run: 0" not in r.stdout:
            raise SystemExit("[remote-dma-gate] replay was not a pure DB "
                             f"hit:\n{r.stdout}")
        with open(db) as f:
            dbobj = json.load(f)
        methods = [e["choice"]["method"] for e in dbobj["entries"].values()]
        if methods != ["remote-dma"]:
            raise SystemExit(
                f"[remote-dma-gate] DB entries carry {methods}, expected "
                "exactly one remote-dma entry")

        # 4. every metrics file passes the schema gate
        run([PY, "-m", "stencil_tpu.apps.report", pm, wm, t1, t2,
             "--validate"], name="schema")
        print("[remote-dma-gate] PASS")
        return 0
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
