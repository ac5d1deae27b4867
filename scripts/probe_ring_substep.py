"""A/B the astaroth sliding-window variants at 512^3 on the chip.

Settles the round-5 floor contradiction (VERDICT r5 weak #1): the closure
summed a 12.7 ms *standalone* window-shift leg into the 70.5 ms substep
floor, but the round-3 in-situ probe measured only 0.4 ms for removing the
shifts inside the kernel — both cannot be additive truths. The ring
variant (ops/pallas_astaroth.py, ``variant="ring"``) removes the shift ops
entirely with CORRECT results, so this probe is the decisive in-situ
measurement:

- delta ~ 12 ms/substep  -> the shifts really serialized at 512^3; the
  ring window recovers more than the 10.5 ms gap to the 60 ms/substep
  target (the 180 ms/iter flagship target reopens and likely falls);
- delta <~ 1 ms/substep -> the shifts hide under DMA/VPU contention; the
  12.7 ms standalone leg was never a floor term and BASELINE.md's closure
  must carry this delta instead.

Bench discipline: fused chunks, untimed
warmup chunk, trimean over chunk means, hard_sync. Run on the TPU host:

  python scripts/probe_ring_substep.py [n] [iters] [chunk]
  python scripts/probe_ring_substep.py --cpu-smoke   # tiny interpret run
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

cpu_smoke = "--cpu-smoke" in sys.argv
args = [a for a in sys.argv[1:] if a != "--cpu-smoke"]

import jax  # noqa: E402

from stencil_tpu.apps.astaroth import run  # noqa: E402

n = int(args[0]) if len(args) > 0 else 512
iters = int(args[1]) if len(args) > 1 else 12
chunk = int(args[2]) if len(args) > 2 else 6

if jax.devices()[0].platform != "tpu":
    if not cpu_smoke:
        # fail fast and actionably: an interpret-mode "measurement" at this
        # size would grind for hours and answer nothing (the probe exists
        # to settle a chip-timing question, ROADMAP #1)
        sys.exit("probe_ring_substep: no TPU visible (platform="
                 f"{jax.devices()[0].platform}) — run on the TPU bench host,"
                 " or pass --cpu-smoke for a tiny interpret-mode sanity run")
    print("WARNING: --cpu-smoke — numbers below are CPU-interpret smoke only",
          flush=True)
    n, iters, chunk = 32, 4, 2

results = {}
for variant in ("shift", "ring"):
    r = run(iters=iters, devices=jax.devices()[:1], dtype="float32",
            nx=n, chunk=chunk, kernel_variant=variant)
    ms = r["iter_trimean_s"] * 1e3
    results[variant] = ms
    print(f"{variant}: {ms:.2f} ms/iter = {ms/3:.2f} ms/substep "
          f"({n}^3, {r['iters_run']} iters)", flush=True)

delta = (results["shift"] - results["ring"]) / 3
print(f"ring saves {delta:.2f} ms/substep "
      f"({'the shifts serialized — floor leg stands' if delta > 6 else 'the shifts hid under DMA/VPU — retire the 12.7 ms leg'})",
      flush=True)
