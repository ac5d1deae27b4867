"""The in-run sentinel (stencil_tpu/obs/live.py): streaming trimean ±
MAD windows, the anomaly state machine, the telemetry vocabulary, and
the run_guarded wiring.

The ISSUE-12 online-window edge cases are pinned here: warmup below
``min_history`` never fires, non-finite samples are dropped at
insertion (the metrics-ingest rule), window eviction keeps the band
anchored on recent history, and an anomaly re-arms after
``anomaly.cleared``.
"""

import io
import json
import time

import jax.numpy as jnp
import pytest

from stencil_tpu.fault import chunk_plan, run_guarded
from stencil_tpu.obs import ledger, telemetry
from stencil_tpu.obs.live import (
    LiveSentinel,
    OnlineWindow,
    base_metric,
    default_direction,
)


def _records(sink: io.StringIO):
    return [json.loads(line) for line in sink.getvalue().splitlines()]


def _rec(sink):
    return telemetry.Recorder(sink=sink)


# -- direction authority (perf_tool re-imports these) -------------------------


def test_direction_authority_is_shared_with_perf_tool():
    from stencil_tpu.apps import perf_tool

    # one authority, two importers: the cross-run and in-run sentinels
    # must never diverge on what "worse" means
    assert perf_tool.default_direction is default_direction
    assert perf_tool.base_metric is base_metric
    assert default_direction("step.latency_s", "s") == "lower"
    assert default_direction("step.latency_s[16x16x16,float32]",
                             "s") == "lower"
    assert default_direction("jacobi.mcells_per_s", None) == "higher"


# -- OnlineWindow edge cases --------------------------------------------------


def test_warmup_below_min_history_never_fires():
    w = OnlineWindow("step.latency_s", min_history=5, rel_tol=0.01,
                     mad_k=0.0, unit="s")
    # wildly varying samples — but below min_history NOTHING is judged
    for i, v in enumerate([0.1, 100.0, 0.001, 50.0]):
        assert w.observe(v, i) is None
    assert w.active is None and w.detected == 0


def test_nonfinite_samples_dropped_at_insertion():
    w = OnlineWindow("step.latency_s", min_history=3, rel_tol=1.0, unit="s")
    for i, v in enumerate([0.1, float("nan"), 0.1, float("inf"), 0.1]):
        assert w.observe(v, i) is None
    # only the three finite samples entered the window
    assert len(w.samples) == 3
    # and a NaN after warmup is dropped too, never judged as anomalous
    assert w.observe(float("nan"), 9) is None
    assert w.detected == 0


def test_band_uses_the_perf_tool_formula():
    w = OnlineWindow("step.latency_s", min_history=4, mad_k=3.0,
                     rel_tol=0.5, abs_tol=0.0, unit="s")
    vals = [1.0, 1.1, 0.9, 1.0]
    for i, v in enumerate(vals):
        w.observe(v, i)
    center, lo, hi = w.band()
    assert center == pytest.approx(ledger.trimean(vals))
    spread = 3.0 * ledger.mad(vals)
    # high edge: the perf_tool formula verbatim
    assert hi == pytest.approx(center + max(spread, 0.5 * abs(center)))
    # low edge: the rel component is ratio-symmetric (lo >= center/1.5
    # at rel_tol 0.5) so a wide band keeps a positive floor
    assert lo == pytest.approx(
        center - max(spread, abs(center) * 0.5 / 1.5))


def test_direction_aware_a_fast_sample_never_trips_a_seconds_key():
    w = OnlineWindow("step.latency_s", min_history=3, rel_tol=0.1, unit="s")
    for i in range(4):
        w.observe(1.0, i)
    # dramatically FASTER is an improvement on a "lower" key, not an anomaly
    assert w.observe(0.001, 5) is None
    assert w.detected == 0
    # on a throughput key the same drop DOES trip (direction "higher")
    t = OnlineWindow("agg.mcells_per_s", min_history=3, rel_tol=0.1)
    for i in range(4):
        t.observe(100.0, i)
    ev = t.observe(1.0, 5)
    assert ev and ev["event"] == "detected"


def test_window_eviction_keeps_band_anchored_on_recent_history():
    # a slow in-band drift walks the window forward: after eviction the
    # band centers on RECENT samples, so a value far from the original
    # regime but near the current one is healthy
    w = OnlineWindow("step.latency_s", window=8, min_history=4,
                     mad_k=3.0, rel_tol=0.3, unit="s")
    v, step = 1.0, 0
    while v < 4.0:
        assert w.observe(v, step) is None, f"in-band drift fired at {v}"
        v *= 1.05  # each step within 30% of the rolling center
        step += 1
    center, _lo, hi = w.band()
    # the original regime (1.0) is long evicted: the band no longer
    # admits it, and 4.0-era values are the new normal
    assert center > 2.5
    assert w.observe(center, step) is None
    # ...while the band still catches a real excursion from the NEW center
    ev = w.observe(center * 10, step + 1)
    assert ev and ev["event"] == "detected"


def test_anomalous_samples_do_not_normalize_the_band():
    w = OnlineWindow("step.latency_s", window=16, min_history=4,
                     rel_tol=0.5, clear_after=2, unit="s")
    for i in range(5):
        w.observe(1.0, i)
    n_before = len(w.samples)
    assert w.observe(50.0, 10)["event"] == "detected"
    for i in range(11, 30):
        assert w.observe(50.0, i) is None  # still anomalous, no re-emit
    # the excursion never entered the window: the band stayed anchored
    assert len(w.samples) == n_before
    assert w.active is not None and w.detected == 1


def test_clear_requires_consecutive_in_band_and_rearms():
    w = OnlineWindow("step.latency_s", min_history=3, rel_tol=0.5,
                     clear_after=2, unit="s")
    for i in range(4):
        w.observe(1.0, i)
    assert w.observe(10.0, 4)["event"] == "detected"
    assert w.observe(1.0, 5) is None          # streak 1: not yet cleared
    assert w.observe(10.0, 6) is None         # excursion resets the streak
    assert w.active is not None
    assert w.observe(1.0, 7) is None
    ev = w.observe(1.0, 8)
    assert ev and ev["event"] == "cleared" and ev["since_step"] == 4
    # re-armed: the next excursion fires a fresh detection
    ev2 = w.observe(10.0, 9)
    assert ev2 and ev2["event"] == "detected"
    assert w.detected == 2 and w.cleared == 1


def test_window_must_hold_min_history():
    # a ValueError, not an assert: -O must not turn this into a window
    # that silently can never fire
    with pytest.raises(ValueError):
        OnlineWindow("k", window=2, min_history=5)


def test_higher_direction_trips_under_the_wide_default_band():
    # the low edge's relative component is ratio-symmetric: with the
    # default rel_tol 3.0 a positive throughput keeps a POSITIVE floor
    # (center/4), so a collapse still trips — the additive form would
    # put lo below zero and the "higher" direction could never fire
    w = OnlineWindow("agg.mcells_per_s", min_history=4)  # default knobs
    for i in range(5):
        w.observe(100.0, i)
    center, lo, hi = w.band()
    assert lo > 0
    assert lo == pytest.approx(center / 4)
    assert w.observe(lo * 0.5, 6)["event"] == "detected"
    # the high edge keeps the perf_tool formula verbatim
    assert hi == pytest.approx(center * 4)


def test_validate_config_catches_bad_knobs():
    from stencil_tpu.obs.live import validate_config

    assert validate_config({}) == []
    assert validate_config({"*": {"rel_tol": 1.0, "window": 8,
                                  "min_history": 4}}) == []
    assert validate_config("x")
    assert validate_config({"k": 3})
    assert validate_config({"k": {"rel_tolerance": 1.0}})  # unknown knob
    assert validate_config({"k": {"min_history": 0}})
    assert validate_config({"k": {"rel_tol": float("nan")}})
    assert validate_config({"k": {"direction": "sideways"}})
    assert validate_config({"k": {"window": 2, "min_history": 8}})
    # the relation check sees the MERGED knobs: "*" defaults cascade
    assert validate_config({"*": {"min_history": 8},
                            "k": {"window": 2}})
    assert validate_config({"*": {"min_history": 8, "window": 16},
                            "k": {"window": 16}}) == []


# -- LiveSentinel: vocabulary, config resolution, replan hook -----------------


def test_sentinel_emits_schema_valid_vocabulary():
    sink = io.StringIO()
    s = LiveSentinel({"*": {"min_history": 3, "rel_tol": 0.5,
                            "clear_after": 1}}, rec=_rec(sink))
    for i in range(4):
        s.observe("step.latency_s", 1.0, step=i, unit="s")
    s.observe("step.latency_s", 10.0, step=4, unit="s")
    s.observe("step.latency_s", 1.0, step=5, unit="s")
    recs = _records(sink)
    names = [r["name"] for r in recs]
    assert names == ["anomaly.detected", "replan.requested",
                     "anomaly.cleared"]
    for r in recs:
        assert telemetry.validate_record(r) == [], r
    det = recs[0]
    assert det["metric"] == "step.latency_s" and det["step"] == 4
    assert det["lo"] < det["hi"] and det["direction"] == "lower"
    assert recs[1]["reason"] == "anomaly:step.latency_s"
    assert recs[2]["since_step"] == 4


def test_sentinel_replan_hook_fires_and_never_raises():
    sink = io.StringIO()
    seen = []

    def hook(ev):
        seen.append(ev)
        raise RuntimeError("a broken hook must not kill the run")

    s = LiveSentinel({"*": {"min_history": 2, "rel_tol": 0.5}},
                     rec=_rec(sink), on_replan=hook)
    for i in range(3):
        s.observe("k_s", 1.0, step=i, unit="s")
    s.observe("k_s", 10.0, step=3, unit="s")  # must not raise
    assert len(seen) == 1 and seen[0]["metric"] == "k_s"


def test_sentinel_replan_disabled():
    sink = io.StringIO()
    s = LiveSentinel({"*": {"min_history": 2, "rel_tol": 0.5}},
                     rec=_rec(sink), replan=False)
    for i in range(3):
        s.observe("k_s", 1.0, step=i, unit="s")
    s.observe("k_s", 10.0, step=3, unit="s")
    names = [r["name"] for r in _records(sink)]
    assert "replan.requested" not in names


def test_sentinel_config_resolution_tagged_key_inherits_base():
    s = LiveSentinel({"*": {"min_history": 9},
                      "step.latency_s": {"min_history": 2, "rel_tol": 0.25}})
    w = s._window("step.latency_s[16x16x16,float32,jacobi]", "s")
    # the tagged campaign key inherits the base metric's overrides,
    # exactly like perf_tool leg config
    assert w.min_history == 2 and w.rel_tol == 0.25
    # a fully-tagged override wins over the base
    s2 = LiveSentinel({"step.latency_s": {"rel_tol": 0.25},
                       "step.latency_s[a]": {"rel_tol": 0.75}})
    assert s2._window("step.latency_s[a]", "s").rel_tol == 0.75


# -- run_guarded wiring -------------------------------------------------------


def test_run_guarded_feeds_sentinel_and_detects_midrun(tmp_path):
    """The tentpole pin: a slow chunk cycle is detected DURING the run
    (the sentinel sees the whole step+inject+health+save cycle, so an
    injected slowdown is visible even though the step span is clean)."""
    sink = io.StringIO()
    rec = telemetry.Recorder(sink=sink)
    old = telemetry._recorder
    telemetry._recorder = rec
    try:
        sent = LiveSentinel({"*": {"min_history": 3, "rel_tol": 1.0,
                                   "clear_after": 2}}, rec=rec)

        def step_fn(st, k):
            # steps 1..5 fast; step 6's chunk sleeps (a stand-in for the
            # slow@N injection, whose sleep also lands inside the cycle).
            # 20 ms a step, not 2: under six loaded test workers a step's
            # own overhead jitters by milliseconds, which a 2 ms band read
            # as a second anomaly (the run then never cleared)
            time.sleep(0.4 if int(st["q"][0]) + k == 6 else 0.02)
            return {"q": st["q"] + k}

        state, done = run_guarded(
            {"q": jnp.zeros((2,))}, start=0, iters=10,
            plan_fn=lambda s: chunk_plan(s, 10, 1),
            step_fn=step_fn, sentinel=sent)
        assert done == 10
        recs = _records(sink)
        det = [r for r in recs if r["name"] == "anomaly.detected"]
        clr = [r for r in recs if r["name"] == "anomaly.cleared"]
        rep = [r for r in recs if r["name"] == "replan.requested"]
        assert len(det) == 1 and det[0]["step"] == 6
        assert len(rep) == 1
        assert len(clr) == 1 and clr[0]["step"] == 8  # clear_after=2
        assert sent.summary() == {"active": [], "detected": 1, "cleared": 1}
    finally:
        telemetry._recorder = old


def test_status_health_accumulates_across_guarded_segments(tmp_path):
    """A campaign calls run_guarded once per slot segment on one shared
    status writer — the health counters must accumulate, never regress
    mid-campaign."""
    from stencil_tpu.fault import HealthGuard
    from stencil_tpu.obs.status import StatusWriter, read_status

    path = str(tmp_path / "status.json")
    status = StatusWriter(path, app="t", run="r")
    guard = HealthGuard(every=1)

    def step_fn(st, k):
        return {"q": st["q"] + k}

    for seg in range(2):
        run_guarded({"q": jnp.zeros((2,))}, start=0, iters=3,
                    plan_fn=lambda s: chunk_plan(s, 3, 1),
                    step_fn=step_fn, guard=guard, status=status)
    doc = read_status(path)
    # 3 checks per segment; the second segment adds to the first
    assert doc["health"]["checks"] == 6


def test_anomaly_count_gauge_ingests_into_the_ledger(tmp_path):
    """The cross-run hook: live.anomaly_count rides the standard
    metrics-JSONL gauge ingest, so in-run instability shows in trends."""
    sink = io.StringIO()
    rec = telemetry.Recorder(sink=sink)
    rec.meta("config", config={"app": "t"})
    rec.gauge("live.anomaly_count", 2.0, phase="live")
    entries = ledger.entries_from_metrics_records(
        _records(sink), label="runX", platform="cpu")
    by_metric = {e["metric"]: e for e in entries}
    assert by_metric["live.anomaly_count"]["value"] == 2.0
    path = str(tmp_path / "ledger.jsonl")
    assert ledger.append_entries(path, entries) == len(entries)
