"""Performance-ledger tests: schema round-trip, atomic append + dedup,
corruption rejection, the three ingest shapes (bench payload, committed
legacy BENCH/MULTICHIP docs, metrics-JSONL gauge trimeans)."""

import io
import json
import math
import os

import pytest

from stencil_tpu.obs import ledger, telemetry
from stencil_tpu.utils.statistics import Statistics


def _entry(metric="leg", value=1.0, label="r01", **kw):
    kw.setdefault("platform", "cpu")
    kw.setdefault("config", {"size": 24})
    return ledger.make_entry(metric, value, label=label, **kw)


# -- schema + file round-trip -------------------------------------------------


def test_round_trip_and_dedup(tmp_path):
    path = str(tmp_path / "L.jsonl")
    assert ledger.load_ledger(path) == []  # missing file is an empty ledger
    e1 = _entry(value=10.0, label="r01")
    e2 = _entry(value=12.0, label="r02")
    assert ledger.append_entries(path, [e1, e2]) == 2
    back = ledger.load_ledger(path)
    assert [b["value"] for b in back] == [10.0, 12.0]
    assert all(ledger.validate_entry(b) == [] for b in back)
    # idempotent: same keys (metric/platform/config/rev/label) are skipped
    assert ledger.append_entries(path, [_entry(value=99.0, label="r01")]) == 0
    assert [b["value"] for b in ledger.load_ledger(path)] == [10.0, 12.0]
    # a NEW label appends without rewriting history lines
    assert ledger.append_entries(path, [_entry(value=14.0, label="r03")]) == 1
    assert len(ledger.load_ledger(path)) == 3


def test_validate_entry_catches_violations():
    ok = _entry()
    assert ledger.validate_entry(ok) == []
    assert ledger.validate_entry("not a dict")
    assert ledger.validate_entry({})
    assert ledger.validate_entry(dict(ok, value="fast"))
    assert ledger.validate_entry(dict(ok, value=float("nan")))
    assert ledger.validate_entry(dict(ok, metric=""))
    assert ledger.validate_entry(dict(ok, source="wishful"))
    assert ledger.validate_entry(dict(ok, kind="plan-db"))
    # future schema refused outright (a downgrade must not reinterpret)
    errs = ledger.validate_entry(dict(ok, v=ledger.SCHEMA_VERSION + 1))
    assert errs and "newer" in errs[0]


def test_corruption_rejected_not_clobbered(tmp_path):
    path = str(tmp_path / "L.jsonl")
    ledger.append_entries(path, [_entry()])
    with open(path, "a") as f:
        f.write("{torn line\n")
    with pytest.raises(ledger.LedgerError, match="unparseable"):
        ledger.load_ledger(path)
    # appending to a corrupt ledger must raise, and the file must be
    # byte-identical afterwards (never silently rewritten/shrunk)
    before = open(path).read()
    with pytest.raises(ledger.LedgerError):
        ledger.append_entries(path, [_entry(label="r09")])
    assert open(path).read() == before


def test_invalid_entry_refused_on_append(tmp_path):
    path = str(tmp_path / "L.jsonl")
    bad = _entry()
    bad["value"] = float("inf")
    with pytest.raises(ledger.LedgerError, match="refusing"):
        ledger.append_entries(path, [bad])
    assert not os.path.exists(path)


def test_config_fingerprint_ignores_volatile_keys():
    a = ledger.config_fingerprint({"x": 24, "metrics_out": "/tmp/a.jsonl",
                                   "inject": "slow@3", "run_id": "r1"})
    b = ledger.config_fingerprint({"x": 24, "metrics_out": "/tmp/b.jsonl",
                                   "run_id": "r2"})
    c = ledger.config_fingerprint({"x": 32})
    assert a == b != c
    # key order and None values do not matter
    assert ledger.config_fingerprint({"a": 1, "b": None}) == \
        ledger.config_fingerprint({"b": None, "a": 1}) == \
        ledger.config_fingerprint({"a": 1})


def test_trimean_and_mad_match_statistics():
    vals = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.3]
    assert ledger.trimean(vals) == pytest.approx(Statistics(vals).trimean())
    assert ledger.mad([1.0, 1.0, 1.0]) == 0.0
    assert ledger.mad([1.0, 2.0, 9.0]) == 1.0
    with pytest.raises(ValueError):
        ledger.trimean([])


# -- ingest shapes ------------------------------------------------------------


def test_entries_from_bench_payload():
    payload = {
        "metric": "jacobi3d_512_mcells_per_s_per_chip",
        "value": 83059.7, "unit": "Mcells/s", "vs_baseline": 24.467,
        "detail": {
            "iter_trimean_s": 0.001616, "exchange_gb_per_s_r3_4q": 15.92,
            "astaroth_256_iter_ms": None,  # absent leg: no entry, not 0
            "plan_choice": "2x2x2",        # string: not a measurement
            "leg_errors": {"x": "boom"},   # diagnostics: skipped
            "platform": "tpu", "size": 512,
        },
    }
    es = ledger.entries_from_bench_payload(payload, label="r05", rev="abc123")
    by = {e["metric"]: e for e in es}
    assert by["jacobi3d_512_mcells_per_s_per_chip"]["value"] == 83059.7
    assert by["jacobi3d_512_mcells_per_s_per_chip"]["unit"] == "Mcells/s"
    assert by["jacobi3d_512_mcells_per_s_per_chip.vs_baseline"]["value"] == \
        pytest.approx(24.467)
    assert by["exchange_gb_per_s_r3_4q"]["value"] == pytest.approx(15.92)
    assert "astaroth_256_iter_ms" not in by
    assert "plan_choice" not in by and "leg_errors" not in by
    assert all(e["platform"] == "tpu" and e["label"] == "r05"
               and e["rev"] == "abc123" for e in es)
    # same payload -> same config fingerprint across entries
    assert len({e["config"] for e in es}) == 1


def test_entries_from_legacy_bench_failed_round():
    # BENCH_r03-shaped: rc=1, no parsed payload — the outage still lands
    # as a bench.rc entry so the trend shows the round
    doc = {"n": 3, "cmd": "python bench.py", "rc": 1, "tail": "Traceback..."}
    es = ledger.entries_from_legacy_bench(doc)
    assert len(es) == 1
    assert es[0]["metric"] == "bench.rc" and es[0]["value"] == 1.0
    assert es[0]["label"] == "r03" and es[0]["source"] == "legacy-bench"


def test_entries_from_legacy_multichip():
    doc = {"n_devices": 8, "rc": 0, "ok": True, "skipped": False, "tail": ""}
    es = ledger.entries_from_legacy_multichip(doc, label="r04")
    assert es[0]["metric"] == "multichip_dryrun_ok" and es[0]["value"] == 1.0
    assert es[0]["detail"]["rc"] == 0


def test_entries_from_metrics_records_gauge_trimeans():
    buf = io.StringIO()
    rec = telemetry.Recorder(sink=buf, app="t", run_id="RUN1")
    rec.meta("config", config={"x": 24, "metrics_out": "/tmp/m.jsonl"})
    for v in (1.0, 2.0, 9.0):
        rec.gauge("leg.speed", v, unit="GB/s")
    rec.gauge("leg.speed", 5.0, method="direct26")  # tag splits the key
    rec.gauge("bad.inf", float("inf"))              # non-finite: skipped
    with rec.span("work", phase="step"):
        pass
    records = [json.loads(l) for l in buf.getvalue().splitlines()]
    es = ledger.entries_from_metrics_records(records, label="run1",
                                             platform="cpu")
    by = {e["metric"]: e for e in es}
    assert by["leg.speed"]["value"] == pytest.approx(
        Statistics([1.0, 2.0, 9.0]).trimean())
    assert by["leg.speed"]["unit"] == "GB/s"
    assert by["leg.speed"]["detail"]["samples"] == 3
    assert by["leg.speed[direct26]"]["value"] == 5.0
    assert "bad.inf" not in by
    assert "work.trimean_s" not in by  # spans only with spans=True
    assert all(e["run"] == "RUN1" and e["label"] == "run1" for e in es)
    # the volatile metrics_out key must not split the config fingerprint
    es2 = ledger.entries_from_metrics_records(
        [dict(r, **({"config": {"x": 24, "metrics_out": "/ELSEWHERE"}}
                    if r.get("name") == "config" else {}))
         for r in records], label="run2", platform="cpu")
    assert es2[0]["config"] == es[0]["config"]
    # spans=True ingests per-span trimeans under <name>.trimean_s
    es3 = ledger.entries_from_metrics_records(records, label="run1",
                                              spans=True)
    assert any(e["metric"] == "work.trimean_s" for e in es3)


def test_git_rev_best_effort(tmp_path):
    # inside this repo: a short rev (or None if git is unavailable);
    # outside: None — never an exception
    assert ledger.git_rev(str(tmp_path)) is None
    rev = ledger.git_rev(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    assert rev is None or (isinstance(rev, str) and len(rev) >= 7)


def test_concurrent_appends_serialize_under_the_lock(tmp_path):
    """Two processes appending disjoint entries must both land: the
    flock around the read-modify-write forbids the lost-update rewrite
    of 'append-only' history."""
    import subprocess
    import sys

    path = str(tmp_path / "L.jsonl")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prog = (
        "import sys; sys.path.insert(0, {repo!r})\n"
        "from stencil_tpu.obs import ledger\n"
        "es = [ledger.make_entry(f'leg{{i}}', float(i), label=sys.argv[2],\n"
        "                        platform='cpu', config={{'c': 1}})\n"
        "      for i in range(20)]\n"
        "ledger.append_entries(sys.argv[1], es)\n"
    ).format(repo=repo)
    procs = [subprocess.Popen([sys.executable, "-c", prog, path, lbl])
             for lbl in ("a", "b", "c")]
    assert all(p.wait() == 0 for p in procs)
    es = ledger.load_ledger(path)
    assert len(es) == 60  # 3 labels x 20 legs, nothing lost
    assert {e["label"] for e in es} == {"a", "b", "c"}


def test_metrics_ingest_drops_nonfinite_samples():
    """One NaN gauge sample must not poison the trimean of the good
    samples (NaN breaks sorted(), yielding a silently WRONG finite
    value, not NaN) — non-finite samples are dropped at collection like
    the bench-payload path does."""
    base = {"v": 1, "run": "R", "proc": 0, "t": 0.0}
    recs = [dict(base, kind="gauge", name="g", value=v)
            for v in (float("nan"), 1.0, 2.0, 3.0, 4.0, 5.0)]
    recs.append(dict(base, kind="span", name="s", seconds=float("inf")))
    recs.append(dict(base, kind="span", name="s", seconds=2.0))
    es = ledger.entries_from_metrics_records(recs, label="L", spans=True)
    by = {e["metric"]: e for e in es}
    assert by["g"]["value"] == 3.0  # true trimean of 1..5, NaN dropped
    assert by["g"]["detail"]["samples"] == 5
    assert by["s.trimean_s"]["value"] == 2.0
    # a gauge with ONLY non-finite samples produces no entry at all
    only_bad = [dict(base, kind="gauge", name="bad", value=float("nan"))]
    assert ledger.entries_from_metrics_records(only_bad, label="L") == []
