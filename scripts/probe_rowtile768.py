"""Measure the row-tiled multistep at the flagship 768^3 size on the chip.

The full-plane multistep self-capped temporal depth at k=4 at 768^3 (VMEM
staging holds full (py, px) planes — 55.3 Gcells/s vs 79-83 at 512^3,
VERDICT r5 weak #2; log deleted in PR 21, older unverified figure).
Row-tiled staging (ops/pallas_stencil.py, plan_multistep_staging) unchains
depth from plane size; this probe A/Bs:

- default plan (row-tiled, k up to the 12 cap) — the new production path;
- temporal_k=4 pin (what the old full-plane kernel could reach).

Done-bar from VERDICT r5 Next #2: >= 70 Gcells/s at 768^3.

  python scripts/probe_rowtile768.py [n] [iters]
  python scripts/probe_rowtile768.py --cpu-smoke   # tiny CPU run
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

cpu_smoke = "--cpu-smoke" in sys.argv
args = [a for a in sys.argv[1:] if a != "--cpu-smoke"]

import jax  # noqa: E402

from stencil_tpu.apps.jacobi3d import run  # noqa: E402
from stencil_tpu.domain.grid import GridSpec  # noqa: E402
from stencil_tpu.geometry import Dim3, Radius  # noqa: E402
from stencil_tpu.ops.pallas_stencil import plan_multistep_staging  # noqa: E402

n = int(args[0]) if len(args) > 0 else 768
iters = int(args[1]) if len(args) > 1 else 60

spec = GridSpec(Dim3(n, n, n), Dim3(1, 1, 1), Radius.constant(1).without_x())
k, rows = plan_multistep_staging(spec, 12, 46 * 1024 * 1024)
print(f"{n}^3 staging plan: k={k} rows={rows} "
      f"({'row-tiled' if rows else 'full-plane'})", flush=True)

if jax.devices()[0].platform != "tpu":
    if not cpu_smoke:
        # fail fast and actionably: the probe settles a chip wall-clock
        # question (ROADMAP #2); a CPU run at 768^3 would just churn
        sys.exit("probe_rowtile768: no TPU visible (platform="
                 f"{jax.devices()[0].platform}) — run on the TPU bench host,"
                 " or pass --cpu-smoke for a tiny CPU sanity run")
    print("WARNING: --cpu-smoke — running a tiny CPU smoke instead", flush=True)
    n, iters = 128, 4

for label, cap in (
    ("default plan (row-tiled depth)", None),
    ("k=4 cap (what full-plane staging reached)", "4"),
):
    if cap is None:
        os.environ.pop("STENCIL_TEMPORAL_K_CAP", None)
    else:
        os.environ["STENCIL_TEMPORAL_K_CAP"] = cap
    r = run(n, n, n, iters=iters, weak=False, devices=jax.devices()[:1],
            warmup=1, chunk=min(iters, 30))
    print(f"{label}: {r['iter_trimean_s']*1e3:.3f} ms/iter, "
          f"{r['mcells_per_s_per_dev']:.0f} Mcells/s", flush=True)
os.environ.pop("STENCIL_TEMPORAL_K_CAP", None)
