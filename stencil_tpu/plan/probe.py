"""Measured refinement: time the top static candidates, briefly.

The static model (plan/cost.py) orders the search space; this module
buys the truth for the few candidates that matter. Each probe reuses
``apps/_bench_common.time_exchange`` — the SAME harness every exchange
bench runs, so a probe emits the same telemetry-JSONL evidence
(census counters, ``exchange.trimean_s`` gauges) as a full bench leg,
plus ``plan.probe`` spans and a ``plan.probe_trimean_s`` gauge tagged
with the candidate label.

Probes measure the exchange program of a candidate: its partition shape,
method, quantity batching, and the DEEPENED radius of its temporal k
(the k-step multistep exchanges radius*k halos once per k steps, so the
probed per-step exchange cost is trimean/k).
"""

from __future__ import annotations

import time
from typing import List, Optional, Sequence, Tuple

from ..geometry import Dim3
from .cost import scale_radius
from .ir import PlanChoice, PlanConfig


def probe_choice(config: PlanConfig, choice: PlanChoice,
                 iters: int = 4, devices=None,
                 chunk: Optional[int] = None) -> dict:
    """Time one candidate's exchange; returns a probe record
    (label/trimean_s/per_step_s/gb_per_s + the census the run recorded).
    Raises on an unrealizable candidate — callers filter with
    cost.feasible first."""
    import jax

    from ..apps._bench_common import time_exchange
    from ..obs import telemetry
    from ..parallel import Method

    devices = list(devices) if devices is not None else \
        jax.devices()[: config.ndev]
    # probe the dominant dtype at the full quantity count: mixed-dtype
    # configs group per dtype at lowering time either way, and the
    # collective economics under test are count-driven
    dtype = max(config.quantities, key=lambda t: (t[1], t[0]))[0]
    radius = scale_radius(config.radius_obj(), choice.multistep_k)
    rec = telemetry.get()
    label = choice.label()
    t0 = time.perf_counter()
    with rec.span("plan.probe", phase="plan", plan=label):
        r = time_exchange(
            Dim3.of(config.grid), radius, iters,
            method=Method(choice.method), devices=devices,
            quantities=config.num_quantities, dtype=dtype,
            chunk=chunk if chunk is not None else min(iters, 5),
            batch_quantities=choice.batch_quantities,
            partition=choice.partition,
            # a placed candidate probes on its placed mesh — the tuned
            # assignment must be what the measurement measured
            placement=choice.placement,
        )
    trimean = r["trimean_s"]
    rec.gauge("plan.probe_trimean_s", trimean, phase="plan", unit="s",
              plan=label)
    return {
        "label": label,
        "choice": choice.to_json(),
        "trimean_s": trimean,
        "per_step_s": trimean / choice.multistep_k,
        "gb_per_s": r["gb_per_s"],
        "iters": iters,
        "wall_s": time.perf_counter() - t0,
    }


def refine(config: PlanConfig,
           ranked: Sequence[Tuple[object, PlanChoice]],
           top_n: int = 3, iters: int = 4,
           devices=None) -> Tuple[Optional[PlanChoice], List[dict]]:
    """Probe the ``top_n`` cheapest static candidates and return
    (measured winner by per-step seconds, probe records). A probe that
    raises is recorded as failed and skipped — a candidate the backend
    cannot realize must not kill the tuning run."""
    from ..utils import logging as log

    probes: List[dict] = []
    best: Optional[PlanChoice] = None
    best_s = float("inf")
    for _cost, choice in list(ranked)[:top_n]:
        try:
            p = probe_choice(config, choice, iters=iters, devices=devices)
        except Exception as e:  # noqa: BLE001 — evidence, then next candidate
            log.warn(f"plan probe {choice.label()} failed: "
                     f"{type(e).__name__}: {e}")
            probes.append({
                "label": choice.label(), "choice": choice.to_json(),
                "error": f"{type(e).__name__}: {e}"[:400],
            })
            continue
        probes.append(p)
        if p["per_step_s"] < best_s:
            best_s = p["per_step_s"]
            best = choice
    return best, probes
