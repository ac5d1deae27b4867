"""The plan observatory (obs/attribution + plan/calibrate): attribution
records validate under the v1 schema vocabulary, the least-squares fit
recovers known constants from synthetic residuals (and refuses the
degenerate cases loudly), the drift band is numerically THE SAME band
``perf_tool.evaluate_gate`` applies to ledger history, fitted rows
round-trip through the plan DB, and the trace export renders attribution
as paired counters with the drift marker."""

import json

import pytest

from stencil_tpu.obs import attribution, telemetry
from stencil_tpu.obs.attribution import (DriftVerdict, PhasePrediction,
                                         emit_drift, emit_phase, judge_drift,
                                         phases_from_records,
                                         predict_exchange)
from stencil_tpu.obs.ledger import mad, trimean
from stencil_tpu.plan import calibrate
from stencil_tpu.plan import db as plandb
from stencil_tpu.plan.calibrate import CalibrationError, Sample, fit
from stencil_tpu.plan.cost import DEFAULT_CALIBRATION
from stencil_tpu.plan.ir import (AXIS_COMPOSED, DIRECT26, PlanChoice,
                                 PlanConfig)
from stencil_tpu.geometry import Dim3, Radius


def _config():
    return PlanConfig.make(Dim3(24, 24, 24), Radius.constant(2),
                           ["float32"] * 4, 8, "cpu")


def _choice():
    return PlanChoice(partition=(2, 2, 2), method=AXIS_COMPOSED,
                      batch_quantities=True)


# -- schema vocabulary --------------------------------------------------------


def test_attrib_vocabulary_in_name_fields():
    for name in ("plan.attrib.phase", "plan.fingerprint",
                 "calibration.fitted", "calibration.drift"):
        assert name in telemetry.NAME_FIELDS, name
        assert name in telemetry.KNOWN_NAMES, name


def test_attrib_record_roundtrip_via_schema(tmp_path):
    path = str(tmp_path / "m.jsonl")
    rec = telemetry.Recorder(path, app="t", run_id="r1")
    pred = predict_exchange(_config(), _choice())
    assert pred is not None
    emit_phase(rec, pred, 0.002, phase="stencil.exchange",
               kernel_variant=None,
               fabric={"processes": 1, "platform": "cpu"})
    rec.meta("plan.fingerprint", fingerprint=_choice().fingerprint(),
             choice=_choice().label(), calibration="modeled(default)")
    v = judge_drift("stencil.exchange", pred.predicted_s,
                    [100.0, 101.0, 99.0], rel_tol=0.75)
    assert not v.ok  # prediction is millis, samples are 100 s
    emit_drift(rec, v)
    rec.close()
    with open(path) as f:
        lines = f.readlines()
    n_ok, errs = telemetry.validate_jsonl(lines)
    assert errs == []
    names = {json.loads(ln)["name"] for ln in lines}
    assert {"plan.attrib.phase", "plan.fingerprint",
            "calibration.drift"} <= names
    # fabric scalars ride along as extra fields
    attrib = [json.loads(ln) for ln in lines
              if json.loads(ln)["name"] == "plan.attrib.phase"][0]
    assert attrib["fabric_platform"] == "cpu"
    assert attrib["residual"] == pytest.approx(0.002 - pred.predicted_s)


def test_emit_drift_is_silent_when_healthy(tmp_path):
    path = str(tmp_path / "m.jsonl")
    rec = telemetry.Recorder(path, app="t", run_id="r1")
    v = judge_drift("p", 1.0, [1.0, 1.01, 0.99], rel_tol=0.75)
    assert v.ok
    assert emit_drift(rec, v) is None
    rec.close()
    assert "calibration.drift" not in open(path).read()


# -- the fit ------------------------------------------------------------------


def test_fit_recovers_known_constants():
    # synthetic truth: measured = overhead[m] * collectives + bytes / bw
    truth = {"axis-composed": 5e-4, "direct26": 2e-3}
    bw = 5e8
    samples = []
    for m, oh in truth.items():
        for c, b in ((2, 100_000), (4, 400_000), (6, 1_200_000),
                     (26, 2_400_000)):
            samples.append(Sample(method=m, collectives=c, wire_bytes=b,
                                  measured_s=oh * c + b / bw))
    row = fit(samples, platform="cpu")
    cal = row["calibration"]
    assert row["bandwidth_fit"] is True
    assert cal["permute_overhead_s"]["axis-composed"] == pytest.approx(
        5e-4, rel=1e-6)
    assert cal["permute_overhead_s"]["direct26"] == pytest.approx(
        2e-3, rel=1e-6)
    assert cal["wire_bytes_per_s"] == pytest.approx(bw, rel=1e-6)
    assert row["r2"] == pytest.approx(1.0, abs=1e-9)
    assert row["provenance"].startswith("fitted(n=8")


def test_fit_refuses_degenerate_single_sample():
    with pytest.raises(CalibrationError):
        fit([Sample(method=AXIS_COMPOSED, collectives=2, wire_bytes=1000,
                    measured_s=1e-3)])


def test_fit_pins_bandwidth_on_single_point_population():
    # every sample at ONE (collectives, bytes) point: the bandwidth
    # column is unidentifiable, so the fit pins it at the modeled
    # default and recovers only the per-collective overhead
    base_bw = DEFAULT_CALIBRATION["wire_bytes_per_s"]
    oh = 6.6e-4
    samples = [Sample(method=AXIS_COMPOSED, collectives=2,
                      wire_bytes=200_000,
                      measured_s=oh * 2 + 200_000 / base_bw)
               for _ in range(3)]
    row = fit(samples, platform="cpu")
    assert row["bandwidth_fit"] is False
    # pinned bandwidth stays ABSENT from the override (absent-field
    # discipline: score() falls back to the modeled default, which is
    # exactly the pin), and the overhead is recovered from the residual
    assert "wire_bytes_per_s" not in row["calibration"]
    assert row["calibration"]["permute_overhead_s"][AXIS_COMPOSED] == (
        pytest.approx(oh, rel=1e-6))


def test_samples_from_records_matches_emitted_shape(tmp_path):
    path = str(tmp_path / "m.jsonl")
    rec = telemetry.Recorder(path, app="t", run_id="r1")
    pred = predict_exchange(_config(), _choice())
    for s in (0.002, 0.0021, 0.0019):
        emit_phase(rec, pred, s, phase="exchange.iter")
    rec.close()
    records = [json.loads(ln) for ln in open(path)]
    samples = calibrate.samples_from_records(records)
    assert len(samples) == 3
    assert all(s.method == AXIS_COMPOSED for s in samples)
    assert all(s.collectives == pred.collectives for s in samples)
    assert all(s.phase == "exchange.iter" for s in samples)


def test_records_of_the_retired_transport_are_refused_by_name(tmp_path):
    """A metrics file the parent wrote under the kernel-initiated transport
    carries its attribution records: a fit over them would price a method
    no plan can name, so they are refused, by name, not dropped."""
    records = [{"kind": "meta", "name": calibrate.ATTRIB_NAME,
                "phase": "exchange.iter", "method": m, "collectives": 6,
                "wire_bytes": 1000, "measured_s": 2e-3}
               for m in (AXIS_COMPOSED, "remote-dma")]
    assert len(calibrate.samples_from_records(records[:1])) == 1
    with pytest.raises(CalibrationError, match="unknown method 'remote-dma'"):
        calibrate.samples_from_records(records)


def test_a_row_the_parent_fitted_for_the_retired_transport_still_prices():
    """``plan_tool calibrate`` of the parent could install a per-copy
    constant beside the permute overheads: the row stays valid on disk and
    the composed plans price as the permute overheads say."""
    row = {"calibration": {
               "permute_overhead_s": {AXIS_COMPOSED: 7e-4},
               "remote_dma": {"cpu_emulation_overhead_s": 4e-3,
                              "provenance": "fitted(n=4, r2=0.990)"},
               "provenance": "fitted(n=4, r2=0.990)"},
           "provenance": "fitted(n=4, r2=0.990)", "n": 4, "r2": 0.99,
           "platform": "cpu", "bandwidth_fit": False, "written_t": 0.0}
    assert plandb.validate_calibration_row("cpu", row) == []
    with_row = predict_exchange(_config(), _choice(), row["calibration"])
    plain = predict_exchange(
        _config(), _choice(),
        {"permute_overhead_s": {AXIS_COMPOSED: 7e-4}})
    assert with_row.predicted_s == plain.predicted_s
    assert with_row.collectives == plain.collectives == 6
    assert calibrate.diff_rows(row) == [
        (f"permute_overhead_s[{AXIS_COMPOSED}]", 7e-4,
         DEFAULT_CALIBRATION["permute_overhead_s"][AXIS_COMPOSED])]


# -- the drift band == the perf_tool band -------------------------------------


def test_drift_band_is_the_evaluate_gate_band():
    """judge_drift and perf_tool.evaluate_gate must compute the SAME
    band from the same history — one authority, two entry points."""
    from stencil_tpu.apps import perf_tool
    from stencil_tpu.obs import ledger as L

    hist = [1.0e-3, 1.3e-3, 0.9e-3, 1.1e-3, 1.2e-3]
    predicted = 2.9e-3
    mad_k, rtol = 3.0, 0.75
    v = judge_drift("p", predicted, hist, mad_k=mad_k, rel_tol=rtol)

    entries = [L.make_entry("m_s", h, label=f"h{i}", unit="s",
                            platform="cpu", config={"c": 1})
               for i, h in enumerate(hist)]
    entries.append(L.make_entry("m_s", predicted, label="new", unit="s",
                                platform="cpu", config={"c": 1}))
    [g] = perf_tool.evaluate_gate(
        entries, label="new", mad_k=mad_k, rel_tol=rtol, min_history=2,
        leg_config={"*": {"direction": "both"}})
    assert g["lo"] == pytest.approx(v.lo)
    assert g["hi"] == pytest.approx(v.hi)
    assert g["center"] == pytest.approx(v.center)
    assert (g["status"] == "pass") == v.ok


def test_drift_trips_on_stale_low_prediction():
    """The bug class this sentinel exists for: measured time inflated
    well past a stale (low) prediction MUST trip even at a wide
    rel_tol — the band's low edge stays positive for rel_tol < 1."""
    samples = [0.015, 0.016, 0.017]
    healthy = judge_drift("p", 0.0112, samples, rel_tol=0.75)
    assert healthy.ok
    stale = judge_drift("p", 0.0112, [s * 10 for s in samples],
                        rel_tol=0.75)
    assert not stale.ok
    assert stale.lo > 0.0112  # tripped on the LOW side
    assert "OUTSIDE" in stale.describe()


def test_phases_from_records_splits_methods(tmp_path):
    path = str(tmp_path / "m.jsonl")
    rec = telemetry.Recorder(path, app="t", run_id="r1")
    pa = PhasePrediction(method=AXIS_COMPOSED, predicted_s=1e-3,
                         collectives=2, wire_bytes=1000)
    pd = PhasePrediction(method=DIRECT26, predicted_s=5e-3,
                         collectives=26, wire_bytes=1000)
    emit_phase(rec, pa, 1.1e-3, phase="exchange.iter")
    emit_phase(rec, pd, 5.2e-3, phase="exchange.iter")
    emit_phase(rec, pa, 0.9e-3, phase="jacobi.exchange")
    rec.close()
    records = [json.loads(ln) for ln in open(path)]
    groups = phases_from_records(records)
    # mixed-method phase splits; single-method phase keeps its name
    assert set(groups) == {"exchange.iter[axis-composed]",
                           "exchange.iter[direct26]", "jacobi.exchange"}
    assert groups["exchange.iter[direct26]"]["predicted_s"] == (
        pytest.approx(5e-3))


# -- plan DB round-trip -------------------------------------------------------


def test_calibration_row_roundtrips_through_db(tmp_path):
    samples = [Sample(method=AXIS_COMPOSED, collectives=c, wire_bytes=b,
                      measured_s=7e-4 * c + b / 4e8)
               for c, b in ((2, 100_000), (4, 500_000), (6, 900_000))]
    row = fit(samples, platform="cpu")
    db_path = str(tmp_path / "plan.json")
    db = plandb.load_db(db_path)
    plandb.record_calibration(db, "cpu", row)
    plandb.save_db(db_path, db)
    back = plandb.lookup_calibration(plandb.load_db(db_path), "cpu")
    assert back is not None
    assert back["provenance"] == row["provenance"]
    assert back["provenance"].startswith("fitted(n=3")
    assert back["calibration"]["permute_overhead_s"][AXIS_COMPOSED] == (
        pytest.approx(7e-4, rel=1e-6))
    # a pre-observatory DB (no calibrations section) stays valid and
    # lookups answer None, not KeyError
    assert plandb.validate_db(plandb.empty_db()) == []
    assert plandb.lookup_calibration(plandb.empty_db(), "cpu") is None


def test_db_rejects_malformed_calibration_row():
    errs = plandb.validate_calibration_row(
        "cpu", {"calibration": {}, "provenance": "fitted(n=1, r2=0.0)",
                "n": 1, "r2": 0.0})
    assert errs  # n < 2 is the degenerate fit the CLI refuses too


# -- fingerprint + trace rendering -------------------------------------------


def test_fingerprint_is_stable_and_discriminating():
    a, b = _choice(), _choice()
    assert a.fingerprint() == b.fingerprint()
    assert len(a.fingerprint()) == 12
    assert int(a.fingerprint(), 16) >= 0  # hex
    c = PlanChoice(partition=(1, 2, 4), method=AXIS_COMPOSED,
                   batch_quantities=True)
    assert c.fingerprint() != a.fingerprint()


def test_trace_renders_paired_counters_and_drift_marker(tmp_path):
    from stencil_tpu.obs import trace_export

    path = str(tmp_path / "m.jsonl")
    rec = telemetry.Recorder(path, app="t", run_id="r1")
    pred = PhasePrediction(method=AXIS_COMPOSED, predicted_s=1e-3,
                           collectives=2, wire_bytes=1000)
    for s in (1.1e-3, 0.9e-3):
        emit_phase(rec, pred, s, phase="stencil.exchange")
    emit_drift(rec, DriftVerdict(ok=False, phase="stencil.exchange",
                                 predicted_s=1e-3, center=5e-3,
                                 lo=2e-3, hi=8e-3, n=2))
    rec.close()
    records = [json.loads(ln) for ln in open(path)]
    trace = trace_export.to_trace(records)
    assert trace_export.validate_trace(trace) == []
    counters = {e["name"] for e in trace["traceEvents"]
                if e["ph"] == "C"}
    assert "plan.attrib.stencil.exchange.predicted_s" in counters
    assert "plan.attrib.stencil.exchange.measured_s" in counters
    markers = [e for e in trace["traceEvents"]
               if e["ph"] == "i" and e["name"] == "calibration.drift"]
    assert markers and markers[0]["args"]["band_lo"] == pytest.approx(2e-3)


# -- ledger fold --------------------------------------------------------------


def test_ledger_folds_attribution_to_one_entry_per_phase_method(tmp_path):
    from stencil_tpu.obs import ledger as L

    path = str(tmp_path / "m.jsonl")
    rec = telemetry.Recorder(path, app="t", run_id="r1")
    pred = PhasePrediction(method=AXIS_COMPOSED, predicted_s=1e-3,
                           collectives=2, wire_bytes=64_000,
                           provenance="modeled(default)")
    for s in (1.0e-3, 1.2e-3, 1.1e-3):
        emit_phase(rec, pred, s, phase="jacobi.exchange")
    rec.close()
    records = [json.loads(ln) for ln in open(path)]
    entries = [e for e in L.entries_from_metrics_records(records, label="x")
               if e["metric"].startswith("plan.attrib.")]
    assert len(entries) == 1
    e = entries[0]
    assert e["metric"] == "plan.attrib.jacobi.exchange"
    assert e["value"] == pytest.approx(trimean([1.0e-3, 1.2e-3, 1.1e-3]))
    d = e["detail"]
    assert d["method"] == AXIS_COMPOSED and d["collectives"] == 2
    # ...and calibrate can reconstruct fit samples from that entry
    samples = calibrate.samples_from_ledger(entries)
    assert len(samples) == 1 and samples[0].wire_bytes == 64_000
