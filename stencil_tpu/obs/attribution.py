"""Map measured exchange-phase time back onto the plan IR's prediction.

The predict→measure→refit loop's MEASURE third. The autotuner ranks
plans with ``plan/cost.score`` — a prediction in seconds — and
``verify_plan`` audits the structural half of that prediction
(collectives, bytes) against the realized IR; what nobody checks
is the seconds themselves. This module closes that gap per run: each
timed exchange phase (the ``trace_range`` names of the host spans —
"stencil.exchange_loop", …) becomes one ``plan.attrib.phase`` meta
record pairing the installed calibration's prediction with the measured
wall time for the SAME (method, collectives, wire_bytes) point:

    plan.attrib.phase  phase= method= kernel_variant=
                       predicted_s= measured_s= residual=
                       collectives= wire_bytes=

Those records are the raw material of ``plan/calibrate.fit`` (fitted
calibration rows) and the evidence ``perf_tool drift`` /
``verify_plan --time`` judge. ``judge_drift`` here is the single band
authority for both: the same trimean ± max(k·MAD, rtol·|center|, atol)
formula ``perf_tool.evaluate_gate`` applies to ledger history, applied
to a phase's measured samples with the prediction as the judged value —
a stale calibration is a prediction that fell out of the band of what
the fabric actually does.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

from ..plan import cost as plan_cost
from ..plan.ir import PlanChoice, PlanConfig
from .ledger import mad, trimean

ATTRIB_NAME = "plan.attrib.phase"
DRIFT_NAME = "calibration.drift"

# evaluate_gate's defaults (apps/perf_tool.py) — the shared band authority
DEFAULT_MAD_K = 3.0
DEFAULT_REL_TOL = 0.05
DEFAULT_ABS_TOL = 0.0


@dataclass(frozen=True)
class PhasePrediction:
    """The cost model's view of one exchange phase under a calibration."""

    method: str
    predicted_s: float
    collectives: int
    wire_bytes: int
    provenance: str = "modeled(default)"


def predict_exchange(config: PlanConfig, choice: PlanChoice,
                     calibration: Optional[dict] = None,
                     ) -> Optional[PhasePrediction]:
    """Price one step's exchange for ``choice`` under ``calibration``
    (None = DEFAULT_CALIBRATION) — None when the choice is infeasible
    for the config."""
    c = plan_cost.score(config, choice, calibration)
    if c is None:
        return None
    prov = "modeled(default)"
    if calibration:
        prov = str(calibration.get("provenance", "override"))
    return PhasePrediction(method=choice.method,
                           predicted_s=float(c.exchange_s),
                           collectives=int(c.collectives),
                           wire_bytes=int(c.wire_bytes),
                           provenance=prov)


def emit_phase(rec, pred: PhasePrediction, measured_s: float, *,
               phase: str, kernel_variant: Optional[str] = None,
               fabric: Optional[Dict[str, object]] = None) -> Optional[dict]:
    """Emit one attribution record (one measured sample of one phase).

    ``fabric`` is machine_info's fabric fingerprint (procs/hosts/
    platform); its scalars ride along as extra fields so a fitted row
    can be traced to the fabric it was measured on. No-op (None) when
    the recorder is disabled — attribution must never tax an
    uninstrumented run.
    """
    if rec is None or not getattr(rec, "enabled", False):
        return None
    extra: Dict[str, object] = {}
    for k, v in (fabric or {}).items():
        if isinstance(v, (str, int, float, bool)):
            extra[f"fabric_{k}"] = v
    return rec.meta(
        ATTRIB_NAME,
        phase=phase,
        method=pred.method,
        kernel_variant=kernel_variant,
        predicted_s=float(pred.predicted_s),
        measured_s=float(measured_s),
        residual=float(measured_s - pred.predicted_s),
        collectives=int(pred.collectives),
        wire_bytes=int(pred.wire_bytes),
        provenance=pred.provenance,
        **extra)


@dataclass(frozen=True)
class DriftVerdict:
    """judge_drift's answer: did the prediction fall out of the band?"""

    ok: bool
    phase: str
    predicted_s: float
    center: float        # trimean of the measured samples
    lo: float
    hi: float
    n: int

    def describe(self) -> str:
        state = "within" if self.ok else "OUTSIDE"
        return (f"{self.phase}: predicted {self.predicted_s:.3e}s {state} "
                f"measured band [{self.lo:.3e}, {self.hi:.3e}] "
                f"(center {self.center:.3e}s, n={self.n})")


def judge_drift(phase: str, predicted_s: float,
                samples: Sequence[float], *,
                mad_k: float = DEFAULT_MAD_K,
                rel_tol: float = DEFAULT_REL_TOL,
                abs_tol: float = DEFAULT_ABS_TOL) -> DriftVerdict:
    """The drift band authority — shared by ``perf_tool drift``,
    ``verify_plan --time``, and the in-run sentinel.

    Same formula as ``perf_tool.evaluate_gate``: center = trimean of
    the measured samples, tolerance = max(mad_k·MAD, rel_tol·|center|,
    abs_tol), direction both. The judged value is the calibration's
    PREDICTION: drift means the installed constants no longer describe
    the fabric, whichever side they miss on. Keep rel_tol < 1 — at 1
    the low band edge hits zero and an under-prediction (the fabric
    slower than the model says) can never trip.
    """
    vals = [float(v) for v in samples]
    if not vals:
        raise ValueError(f"no measured samples for phase {phase!r}")
    center = trimean(vals)
    tol = max(mad_k * mad(vals), rel_tol * abs(center), abs_tol)
    lo, hi = center - tol, center + tol
    return DriftVerdict(ok=lo <= predicted_s <= hi, phase=phase,
                        predicted_s=float(predicted_s), center=center,
                        lo=lo, hi=hi, n=len(vals))


def emit_drift(rec, verdict: DriftVerdict) -> Optional[dict]:
    """Record a tripped in-run verdict (``calibration.drift`` meta —
    the Perfetto instant marker). Emits nothing for a healthy phase:
    the marker is an alarm, not a pulse."""
    if rec is None or not getattr(rec, "enabled", False) or verdict.ok:
        return None
    return rec.meta(DRIFT_NAME,
                    phase=verdict.phase,
                    predicted_s=float(verdict.predicted_s),
                    measured_s=float(verdict.center),
                    band_lo=float(verdict.lo),
                    band_hi=float(verdict.hi),
                    n=verdict.n)


def attribute_and_judge(rec, config: PlanConfig, choice: PlanChoice,
                        samples_s: Sequence[float], *, phase: str,
                        calibration: Optional[dict] = None,
                        kernel_variant: Optional[str] = None,
                        fabric: Optional[Dict[str, object]] = None,
                        rel_tol: float = 0.75) -> Optional[DriftVerdict]:
    """The one-call in-run path (jacobi epilogue, _bench_common): emit
    one attribution record per measured sample, then apply the drift
    band leniently (wide rel_tol — an in-run check on a handful of
    noisy samples flags multiple-x staleness, not 5% drift; the strict
    judgement belongs to ``perf_tool drift`` over a full metrics file).
    rel_tol must stay BELOW 1: at 1 the band's low edge reaches zero
    and a prediction far below the measured center — the canonical
    "fabric got slower than the model" staleness — can never trip.
    Returns the verdict, or None when the choice is infeasible /
    recorder disabled / no samples."""
    if rec is None or not getattr(rec, "enabled", False) or not samples_s:
        return None
    pred = predict_exchange(config, choice, calibration)
    if pred is None:
        return None
    for s in samples_s:
        emit_phase(rec, pred, s, phase=phase,
                   kernel_variant=kernel_variant, fabric=fabric)
    verdict = judge_drift(phase, pred.predicted_s, samples_s,
                          rel_tol=rel_tol)
    emit_drift(rec, verdict)
    return verdict


def phases_from_records(records: Sequence[dict]
                        ) -> Dict[str, Dict[str, object]]:
    """Group a metrics file's attribution records for the drift
    sentinel: key -> {"predicted_s": latest prediction, "samples":
    [measured...], "method": str, "provenance": str}. Grouping is by
    (phase, method) — an autotune run's probe records put several
    methods under one phase name, and their samples must never be
    judged against one prediction. The key is the plain phase name
    when a single method owns it, ``phase[method]`` otherwise. The
    prediction is taken from the LAST record of each group (all of one
    run's records for a group share it; across concatenated runs the
    newest calibration wins — that is the one being judged)."""
    groups: Dict[tuple, Dict[str, object]] = {}
    for r in records:
        if r.get("kind") != "meta" or r.get("name") != ATTRIB_NAME:
            continue
        g = groups.setdefault((str(r["phase"]), str(r["method"])),
                              {"samples": [], "predicted_s": 0.0,
                               "method": "", "provenance": ""})
        g["samples"].append(float(r["measured_s"]))
        g["predicted_s"] = float(r["predicted_s"])
        g["method"] = str(r["method"])
        g["provenance"] = str(r.get("provenance", ""))
    per_phase: Dict[str, int] = {}
    for phase, _ in groups:
        per_phase[phase] = per_phase.get(phase, 0) + 1
    return {
        (phase if per_phase[phase] == 1 else f"{phase}[{method}]"): g
        for (phase, method), g in groups.items()
    }


def ledger_detail(pred: PhasePrediction, *, phase: str,
                  samples: int) -> Dict[str, object]:
    """The ``detail`` dict a ledger entry derived from attribution
    carries — exactly the fields ``plan/calibrate.samples_from_ledger``
    needs to reconstruct a Sample."""
    return {"phase": phase, "method": pred.method,
            "collectives": int(pred.collectives),
            "wire_bytes": int(pred.wire_bytes),
            "predicted_s": float(pred.predicted_s),
            "provenance": pred.provenance, "samples": int(samples)}
