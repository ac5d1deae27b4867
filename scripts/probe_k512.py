"""Probe: jacobi 512^3 temporal depth beyond the k=10 cap.

The cap was measured before the tight-x kernels (k=2 5.69 / k=6 3.88 /
k=10 3.20 ms/step, BASELINE round 2); the current multistep runs 1.77
ms/step at k=10, so the wavefront floor moved and the diminishing-returns
point needs re-measuring. The VMEM staging budget allows k~13 at 512^3.
Fused chunks, an untimed warmup chunk, trimean over chunk means.

Usage: python scripts/probe_k512.py [n] [k ...]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax

n = int(sys.argv[1]) if len(sys.argv) > 1 else 512
ks = [int(a) for a in sys.argv[2:]] or [10, 12, 13]
on_accel = jax.devices()[0].platform != "cpu"
chunk = 360 if on_accel else 3

from stencil_tpu.apps.jacobi3d import run  # noqa: E402

for k in ks:
    os.environ["STENCIL_TEMPORAL_K_CAP"] = str(k)
    r = run(n, n, n, iters=3 * chunk, weak=False, devices=jax.devices()[:1],
            warmup=1, chunk=chunk)
    print(
        f"k_cap={k}: {r['iter_trimean_s']*1e3:.3f} ms/iter "
        f"({r['mcells_per_s_per_dev']:.0f} Mcells/s/dev)",
        flush=True,
    )
