"""On-host probe: the hierarchical ICI+DCN exchange A/B — flat vs
two-level at the same config — plus the raw DCN link measurement that
recalibrates ``plan/cost.DEFAULT_CALIBRATION["dcn"]``.

The ISSUE-17 hardware half (ROADMAP #3): the hierarchical plan
dimension (outer DCN-axis split across hosts, inner per-host ICI mesh,
cross-host boundary slabs overlapped behind intra-host work —
parallel/hierarchy.py) is parity-pinned on the STENCIL_VIRTUAL_HOSTS
emulation, but the claim it was built for — DCN latency/bandwidth are
orders of magnitude worse than ICI, and boundary-first overlap hides
them — needs a real multi-host fabric. This probe is the decisive
measurement, staged for ONE multi-host TPU session
(``scripts/launch_multiprocess.sh`` on >= 2 workers):

1. raw DCN link: time ``jax.device_put`` round-trips of exchange-sized
   slabs between a local and a remote-process device, at three sizes —
   the intercept is ``transfer_latency_s``, the slope
   ``wire_bytes_per_s`` (the two modeled constants of the "dcn"
   calibration row; printing them here flips its provenance
   modeled -> measured);
2. hierarchical vs flat composed exchange at the probe config (one
   block per chip, hosts = jax.process_count()): trimean ms/exchange +
   GB/s, with the executed DCN copy census
   (``ex._compiled.last_transfer_count``) printed per leg — the same
   counters analysis/verify_plan.py audits;
3. numbers feed ``DEFAULT_CALIBRATION["dcn"]`` and the plan DB via
   ``plan_tool autotune`` on the multi-host fabric (item-1
   recalibration session).

Needs >= 2 hosts (a single process has no DCN; the hierarchy would be
flat-equivalent). Exits early with one line when run single-host
without ``--cpu-smoke``; ``--cpu-smoke`` runs the full A/B against the
STENCIL_VIRTUAL_HOSTS=2 emulation at a tiny size instead (the
CI-covered path; "DCN" copies there are in-process device_puts, so the
measured constants price host orchestration, not a real network — the
printed calibration is labeled accordingly and must NOT be persisted).

Usage: python scripts/probe_dcn.py [n] [iters]
       python scripts/probe_dcn.py --cpu-smoke
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
    os.environ["STENCIL_VIRTUAL_HOSTS"] = "2"

import jax

if cpu_smoke:
    jax.config.update("jax_platforms", "cpu")

from stencil_tpu.parallel.device_topo import host_assignment, virtual_hosts

nhosts = (2 if cpu_smoke and virtual_hosts() else jax.process_count())
if nhosts < 2:
    print("probe_dcn: single host — the DCN level needs >= 2 processes "
          "(scripts/launch_multiprocess.sh), or --cpu-smoke for the "
          "virtual-host emulation path")
    raise SystemExit(0)

import jax.numpy as jnp
import numpy as np

from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, NodePartition, Radius
from stencil_tpu.parallel import HaloExchange, Method, grid_mesh
from stencil_tpu.parallel.exchange import shard_blocks
from stencil_tpu.utils.statistics import Statistics
from stencil_tpu.utils.sync import hard_sync

n = int(args[0]) if args else (32 if cpu_smoke else 256)
iters = int(args[1]) if len(args) > 1 else (4 if cpu_smoke else 20)
ndev = min(8, len(jax.devices()))
if ndev < nhosts:
    print(f"probe_dcn: {ndev} device(s) over {nhosts} hosts — need at "
          "least one device per host")
    raise SystemExit(0)

devs = jax.devices()[:ndev]
assign = host_assignment(devs)

# -- 1. raw DCN link: latency + bandwidth of cross-host device_put ------------
remote = next((d for d, h in zip(devs, assign) if h != assign[0]), None)
print(f"dcn probe: {nhosts} hosts, {ndev} devices, "
      f"{'virtual-host emulation' if cpu_smoke else 'real fabric'}",
      flush=True)
points = []
for mb in (1, 4, 16):
    buf = jnp.zeros((mb * 1024 * 1024 // 4,), jnp.float32)
    buf = jax.device_put(buf, devs[0])
    jax.block_until_ready(buf)
    st = Statistics()
    for _ in range(8):
        t0 = time.perf_counter()
        out = jax.device_put(buf, remote)
        jax.block_until_ready(out)
        st.insert(time.perf_counter() - t0)
    points.append((mb * 1024 * 1024, st.trimean()))
    print(f"  device_put {mb:3d} MiB cross-host: {st.trimean()*1e3:8.3f} ms"
          f"  ({mb * 1024 * 1024 / st.trimean() / 1e9:6.2f} GB/s)",
          flush=True)
# two-point fit: latency intercept + bandwidth slope (the two constants
# of DEFAULT_CALIBRATION["dcn"])
(b0, t0_), (b1, t1_) = points[0], points[-1]
bw = (b1 - b0) / max(t1_ - t0_, 1e-9)
lat = max(t0_ - b0 / bw, 0.0)
tag = ("CPU-emulation figure — do NOT persist; prices host "
       "orchestration, not a network" if cpu_smoke
       else "measured — flips DEFAULT_CALIBRATION['dcn'] provenance")
print(f"  transfer_latency_s ~= {lat:.2e}  wire_bytes_per_s ~= {bw:.3e}"
      f"  ({tag})", flush=True)

# -- 2. hierarchical vs flat composed exchange --------------------------------
part = NodePartition(Dim3(n, n, n), Radius.constant(3), 1, ndev).dim()
axis = "z" if part.z % nhosts == 0 else \
       "y" if part.y % nhosts == 0 else \
       "x" if part.x % nhosts == 0 else None
if axis is None:
    print(f"probe_dcn: no axis of partition {part} divides into "
          f"{nhosts} hosts — pick n/ndev so one does")
    raise SystemExit(0)


def leg(tag, hierarchy):
    spec = GridSpec(Dim3(n, n, n), part, Radius.constant(3))
    mesh = grid_mesh(part, devs)
    ex = HaloExchange(spec, mesh, Method.AXIS_COMPOSED,
                      hierarchy=hierarchy)
    loop = ex.make_loop(iters)
    state = {i: shard_blocks(np.zeros((n,) * 3, np.float32), spec, mesh)
             for i in range(4)}
    state = loop(state)  # compile + warm
    hard_sync(state)
    st = Statistics()
    for _ in range(3):
        t1 = time.perf_counter()
        state = loop(state)
        hard_sync(state)
        st.insert((time.perf_counter() - t1) / iters)
    dcn = (ex._compiled.last_transfer_count if hierarchy else 0)
    gb = ex.bytes_logical([4] * 4) / st.trimean() / 1e9
    print(f"{tag:28s} {st.trimean()*1e3:9.3f} ms/exchange  {gb:8.2f} GB/s"
          f"  dcn_copies={dcn}", flush=True)
    return st.trimean()


print(f"exchange A/B: {n}^3, partition {part}, hierarchy {axis} x "
      f"{nhosts} hosts, fp32 Q=4, {iters} iters/call", flush=True)
t_flat = leg("flat (single-level)", None)
t_hier = leg(f"hierarchical ({axis}{nhosts})", (axis, nhosts))
kind = ("real DCN — the ROADMAP-3 overlap claim" if not cpu_smoke
        else "CPU emulation — host orchestration, not a network")
print(f"hierarchical_over_flat: {t_flat / t_hier:.3f}x ({kind})",
      flush=True)
