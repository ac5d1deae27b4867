"""The bounds self-check on recorded numbers: a bound inside the
contract's limits passes, PR 23's refusal (2.5 % against a widest spread of
0.24 %) is caught before hand-in, and a too-tight bound is caught too."""

import json
import os

import pytest

from _bench_util import DATA, ROOT, bench  # noqa: F401
from benchmark import bounds_check


def _runs(widths):
    """Two sets of six values around 100 for each metric, each with the
    given quartile spread."""
    out = {}
    for metric, w in widths.items():
        # six values whose quantiles(n=4) span is about w x median
        vals = [100 * (1 + w * d) for d in (-0.9, -0.45, -0.1, 0.1, 0.45, 0.9)]
        out[metric] = vals
    return {"cell": {1: dict(out), 2: dict(out)}}


def _bench(bounds):
    return {"end_to_end": [{"name": n, "bound": b} for n, b in bounds.items()]}


def test_spread_is_the_quartile_distance_over_the_median():
    vals = [98.0, 99.0, 100.0, 100.0, 101.0, 102.0]
    # statistics.quantiles(n=4): q1 = 98.75, q3 = 101.25
    assert bounds_check.spread(vals) == pytest.approx(0.025)


def test_bounds_inside_the_limits_pass():
    runs = _runs({"rate": 0.002, "setup_s": 0.02})
    assert bounds_check.check(_bench({"rate": 0.01, "setup_s": 0.25}), runs,
                              say=lambda _: None)


def test_pr23s_refusal_is_caught():
    """iter_ms_p95 bound 2.5 %, widest spread 0.24 %: at most 1.9 %."""
    runs = _runs({"iter_ms_p95": 0.0018})
    widest = max(bounds_check.spread(v) for v in runs["cell"][1].values())
    assert 0.002 < widest < 0.0026
    assert not bounds_check.check(_bench({"iter_ms_p95": 0.025}), runs,
                                  say=lambda _: None)
    assert bounds_check.check(_bench({"iter_ms_p95": 0.015}), runs,
                              say=lambda _: None)


def test_a_bound_under_twice_the_trimmed_spread_is_too_tight():
    runs = _runs({"rate": 0.012})
    trimmed = bounds_check.trimmed_spread(runs["cell"][1]["rate"])
    assert 0.005 < trimmed < bounds_check.spread(runs["cell"][1]["rate"])
    assert not bounds_check.check(_bench({"rate": 0.01}), runs,
                                  say=lambda _: None)
    assert bounds_check.check(_bench({"rate": 0.03}), runs,
                              say=lambda _: None)


def test_one_far_off_run_in_a_set_does_not_move_the_tightness_reading():
    vals = [100.0, 100.1, 99.9, 100.05, 99.95, 88.0]   # one stalled run
    assert bounds_check.spread(vals) > 0.02
    assert bounds_check.trimmed_spread(vals) < 0.002


def test_a_second_set_whose_median_moved_by_more_than_the_bound_fails():
    runs = _runs({"rate": 0.002})
    runs["cell"][2]["rate"] = [v * 1.03 for v in runs["cell"][2]["rate"]]
    assert not bounds_check.check(_bench({"rate": 0.01}), runs,
                                  say=lambda _: None)


def test_the_committed_bounds_hold_on_the_recorded_sets():
    """The medians and spreads of PR 24's own sets of runs (``data/sets.json``,
    written from the chip runs' result lines) against BENCHMARK.json."""
    with open(os.path.join(DATA, "sets.json")) as f:
        runs = {cell: {int(k): v for k, v in sets.items()}
                for cell, sets in json.load(f).items()}
    assert set(runs) == {w["name"] for w in bench()["workloads"]}
    lines = []
    assert bounds_check.check(bench(), runs, say=lines.append), "\n".join(lines)
