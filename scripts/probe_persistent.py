"""On-chip probe: the persistent whole-chunk mega-kernel A/B — per-step
remote-dma / fused / PERSISTENT at k in {2, 4} — the launch-economics
measurement.

The ISSUE-16 hardware half (ROADMAP #7 -> #1): the persistent variant
(ops/persistent_stencil.py — one deep radius*k exchange + one k-substep
chunk program, 2 dispatches per chunk instead of 2k) is parity-pinned on
the CPU emulation, but the claim it was built for — per-LAUNCH overhead
dominates small-block stencil chunks, and temporal fusion amortizes it —
needs real silicon. This probe is the decisive A/B, staged for ONE
multi-chip TPU session:

1. per-step remote-dma / fused / persistent@k2 / persistent@k4
   back-to-back at the probe config (fp32 jacobi, one block per chip),
   trimean ms/ITERATION + Mcells/s/chip, with the measured
   ``launches_per_chunk`` census printed per leg (the plan predicts 2
   for persistent vs 2k per-step; the TPU mega-kernel path should
   measure 1 — that number is what flips ir.launches_per_chunk's
   conservative 2 and prices DEFAULT_CALIBRATION["persistent"]
   provenance modeled -> measured);
2. numbers feed ``plan/cost.py DEFAULT_CALIBRATION["persistent"]``
   (launch_overhead_s) and the plan DB via ``plan_tool autotune --ks``
   (item-1 recalibration session).

Needs >= 2 TPU chips (a single chip self-wraps every direction and the
deep exchange issues no remote DMA). Exits early with one line when no
TPU is present; ``--cpu-smoke`` runs the full A/B against the
host-orchestrated emulation at a tiny size instead (the CI-covered
path; ratios there price host dispatch, not ICI).

Usage: python scripts/probe_persistent.py [n] [iters]
       python scripts/probe_persistent.py --cpu-smoke
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

cpu_smoke = "--cpu-smoke" in sys.argv
args = [a for a in sys.argv[1:] if a != "--cpu-smoke"]

if cpu_smoke:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

if cpu_smoke:
    jax.config.update("jax_platforms", "cpu")

if not cpu_smoke and jax.devices()[0].platform != "tpu":
    print("probe_persistent: no TPU on this host — run on the bench host "
          "(or --cpu-smoke for the emulation path)")
    raise SystemExit(0)

import jax.numpy as jnp
import numpy as np

from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, NodePartition, Radius
from stencil_tpu.ops.jacobi import INIT_TEMP, make_jacobi_loop, sphere_sel
from stencil_tpu.parallel import HaloExchange, Method, grid_mesh
from stencil_tpu.parallel.exchange import shard_blocks
from stencil_tpu.utils.statistics import Statistics
from stencil_tpu.utils.sync import hard_sync

n = int(args[0]) if args else (24 if cpu_smoke else 256)
iters = int(args[1]) if len(args) > 1 else (4 if cpu_smoke else 40)
ndev = min(8, len(jax.devices()))
if ndev < 2:
    print(f"probe_persistent: {ndev} device(s) — the deep exchange needs a "
          "multi-chip ring (single chip self-wraps every direction)")
    raise SystemExit(0)

part = NodePartition(Dim3(n, n, n), Radius.constant(4), 1, ndev).dim()


def leg(tag, radius, k=None, fused=False, persistent=False):
    spec = GridSpec(Dim3(n, n, n), part, Radius.constant(radius))
    mesh = grid_mesh(part, jax.devices()[:ndev])
    ex = HaloExchange(spec, mesh, Method.REMOTE_DMA, fused=fused,
                      persistent=persistent)
    loop = make_jacobi_loop(ex, iters, temporal_k=k)
    sel = shard_blocks(sphere_sel((n, n, n)), spec, mesh)
    c = shard_blocks(np.full((n,) * 3, INIT_TEMP, np.float32), spec, mesh)
    nx = jax.device_put(jnp.zeros_like(c), ex.sharding())
    t0 = time.time()
    c, nx = loop(c, nx, sel)  # compile + warm
    hard_sync((c, nx))
    build_s = time.time() - t0
    st = Statistics()
    for _ in range(3):
        t0 = time.perf_counter()
        c, nx = loop(c, nx, sel)
        hard_sync((c, nx))
        st.insert((time.perf_counter() - t0) / iters)
    lpc = getattr(ex, "last_launches_per_chunk", 0)
    mc = n ** 3 / st.trimean() / 1e6 / ndev
    print(f"{tag:28s} {st.trimean()*1e3:9.3f} ms/iter  {mc:9.2f} "
          f"Mcells/s/chip  launches/chunk={lpc}  (compile {build_s:.0f}s)",
          flush=True)
    return st.trimean(), lpc


print(f"persistent probe: {n}^3, partition {part}, {ndev} devices, "
      f"fp32 jacobi, {iters} iters/call", flush=True)
t_rd, _ = leg("remote-dma per-step", radius=1)
t_fu, _ = leg("remote-dma fused", radius=1, fused=True)
t_p2, lpc2 = leg("persistent k=2", radius=2, k=2, persistent=True)
t_p4, lpc4 = leg("persistent k=4", radius=4, k=4, persistent=True)
# the host-orchestrated schedule pays exactly 2 dispatches per chunk
# (deep exchange + chunk program); the TPU mega-kernel path measures 1
assert lpc2 in (1, 2), f"persistent k=2 census {lpc2} not O(chunks)"
assert lpc4 in (1, 2), f"persistent k=4 census {lpc4} not O(chunks)"
kind = ("TPU mega-kernel" if not cpu_smoke
        else "CPU emulation — dispatch amortization, not ICI")
print(f"persistent_k2_over_fused:  {t_fu / t_p2:.3f}x ({kind})", flush=True)
print(f"persistent_k4_over_fused:  {t_fu / t_p4:.3f}x ({kind})", flush=True)
print(f"persistent_k4_over_perstep: {t_rd / t_p4:.3f}x ({kind})", flush=True)
