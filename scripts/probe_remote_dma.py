"""On-chip probe: the 5-way exchange A/B — composed / auto-spmd /
direct26 / remote-dma / FUSED — plus the wire-compression tiers.

The ISSUE-10 hardware half grown by ISSUE 14 (ROADMAP #5 -> #1): the
kernel-initiated exchange (ops/remote_dma.py) and its FUSED
compute+exchange variant (ops/fused_stencil.py — every per-direction
copy started boundary-first so interior compute hides the wire) are
parity-pinned on the CPU emulation, but the claims they were built
for — per-collective DISPATCH overhead dominates (rounds 7/10), and
wire time can hide behind interior FLOPs — need real ICI. This probe is
the decisive A/B, staged for ONE multi-chip TPU session:

1. composed / direct26 / auto-spmd / remote-dma / fused back-to-back at
   the probe config (radius 2, 4 fp32 quantities, one block per chip),
   trimean ms/exchange + GB/s logical, with the 0-ppermute census
   verified on both kernel-initiated programs;
2. wire-compression rows: remote-dma and fused under
   ``wire_dtype=bfloat16`` (2x bytes) and the fp8 tier
   ``float8_e4m3fn`` (4x bytes) — on TPU the carriers really ship the
   narrow dtype, so this measures what the byte reduction buys on real
   links at each overlap level;
3. numbers feed ``plan/cost.py DEFAULT_CALIBRATION`` ("remote_dma" and
   "fused" provenance flip modeled -> measured) and the plan DB via
   ``plan_tool autotune`` (item-1 recalibration session).

Needs >= 2 TPU chips (a single chip self-wraps every phase and issues no
remote DMA). Exits early with one line when no TPU is present;
``--cpu-smoke`` runs the full 5-way + wire rows against the emulation at
a tiny size instead (the CI-covered path; ratios there are correctness
vehicles, not claims).

Usage: python scripts/probe_remote_dma.py [n] [chunk]
       python scripts/probe_remote_dma.py --cpu-smoke
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
    print("probe_remote_dma: no TPU on this host — run on the bench host "
          "(or --cpu-smoke for the emulation path)")
    raise SystemExit(0)

import numpy as np

from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.parallel import HaloExchange, Method, grid_mesh
from stencil_tpu.parallel.exchange import shard_blocks
from stencil_tpu.utils.statistics import Statistics
from stencil_tpu.utils.sync import hard_sync

n = int(args[0]) if args else (16 if cpu_smoke else 256)
chunk = int(args[1]) if len(args) > 1 else (2 if cpu_smoke else 60)
ndev = min(8, len(jax.devices()))
if ndev < 2:
    print(f"probe_remote_dma: {ndev} device(s) — remote DMA needs a "
          "multi-chip ring (single chip self-wraps every phase)")
    raise SystemExit(0)

# the largest 3-factor split of ndev, z-major (grid_mesh handles ICI layout)
from stencil_tpu.geometry import NodePartition

part = NodePartition(Dim3(n, n, n), Radius.constant(2), 1, ndev).dim()
spec = GridSpec(Dim3(n, n, n), part, Radius.constant(2))
mesh = grid_mesh(part, jax.devices()[:ndev])
NQ = 4


def leg(method, wire_dtype=None, fused=False):
    ex = HaloExchange(spec, mesh, method, wire_dtype=wire_dtype,
                      fused=fused)
    loop = ex.make_loop(chunk)
    state = {
        i: shard_blocks(np.zeros((n, n, n), np.float32), spec, mesh)
        for i in range(NQ)
    }
    t0 = time.time()
    state = loop(state)
    hard_sync(state)
    build_s = time.time() - t0
    st = Statistics()
    for _ in range(3):
        t0 = time.perf_counter()
        state = loop(state)
        hard_sync(state)
        st.insert((time.perf_counter() - t0) / chunk)
    census = ex.collective_census(
        {i: shard_blocks(np.zeros((n, n, n), np.float32), spec, mesh)
         for i in range(NQ)})
    cp = census.get("collective-permute", (0, 0))
    gb = ex.bytes_logical([4] * NQ) / st.trimean() / 1e9
    tag = (method.value + ("+fused" if fused else "")
           + (f"+wire={wire_dtype}" if wire_dtype else ""))
    print(f"{tag:40s} {st.trimean()*1e3:9.3f} ms/exchange  {gb:7.2f} GB/s  "
          f"permutes={cp[0]:3d} cp_bytes={cp[1]}  (compile {build_s:.0f}s)",
          flush=True)
    return st.trimean(), cp


print(f"remote-dma/fused probe: {n}^3, partition {part}, {ndev} devices, "
      f"r2, {NQ} fp32 quantities, chunk {chunk}", flush=True)
# the 5-way A/B: every transport at the same config
t_comp, _ = leg(Method.AXIS_COMPOSED)
leg(Method.DIRECT26)
leg(Method.AUTO_SPMD)
t_rd, cp_rd = leg(Method.REMOTE_DMA)
assert cp_rd[0] == 0, f"REMOTE_DMA census shows {cp_rd[0]} ppermutes"
t_fu, cp_fu = leg(Method.REMOTE_DMA, fused=True)
assert cp_fu[0] == 0, f"FUSED census shows {cp_fu[0]} ppermutes"
# wire tiers on both kernel-initiated transports: bf16 (2x) + fp8 (4x)
for wd in ("bfloat16", "float8_e4m3fn"):
    leg(Method.REMOTE_DMA, wire_dtype=wd)
    leg(Method.REMOTE_DMA, wire_dtype=wd, fused=True)
kind = ("TPU carrier kernels" if not cpu_smoke
        else "CPU emulation — correctness vehicle, ratios not claims")
print(f"remote_dma_over_composed: {t_comp / t_rd:.3f}x ({kind})", flush=True)
print(f"fused_over_remote_dma:    {t_rd / t_fu:.3f}x ({kind})", flush=True)
