#!/usr/bin/env python3
"""The bounds, held against the spreads measured and the contract's limits.

    python3 benchmark/bounds_check.py <dir of run outputs> [...]

Reads the result lines of the builder's sets of runs (files named
``<cell>.set<k>.<seed>.out``, each ending in the contract's JSON object),
and for every end-to-end metric prints its bound, the widest spread seen in
any cell (interquartile range over the median, ``statistics.quantiles(n=4)``,
the wider of a cell's two sets) and what the contract allows, judged as the
driver judges: too LOOSE if the bound is over eight times that widest spread
(or over 1 %, if that is more); too TIGHT if in some cell the mean of the two
sets' spreads, each taken without the set's run farthest from its median, is
over half the bound. ``setup_s`` is judged by its median only and is fixed at
0.25. Exits non-zero if a bound is outside its limits, or a set's median moved
by more than the bound between the two sets.
"""

from __future__ import annotations

import glob
import json
import os
import re
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^(?P<cell>.+)\.set(?P<set>\d+)\.(?P<seed>\d+)\.out$")


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed_spread(values) -> float:
    """The spread without the run farthest from the median."""
    med = statistics.median(values)
    far = max(values, key=lambda v: abs(v - med))
    rest = list(values)
    rest.remove(far)
    return spread(rest)


def read_runs(dirs) -> dict:
    """{cell: {set: {metric: [values]}}} from the run outputs."""
    runs = {}
    for d in dirs:
        for path in sorted(glob.glob(os.path.join(d, "*.out"))):
            m = NAME.match(os.path.basename(path))
            if not m:
                continue
            with open(path) as f:
                lines = f.read().strip().splitlines()
            line = json.loads(lines[-1])
            if not line.get("correct"):
                raise SystemExit(f"{path}: correct is not true")
            by_metric = runs.setdefault(m["cell"], {}).setdefault(
                int(m["set"]), {})
            for name, val in line["metrics"].items():
                by_metric.setdefault(name, []).append(val["value"])
    return runs


def check(bench: dict, runs: dict, say=print) -> bool:
    ok = True
    for metric in bench["end_to_end"]:
        name, bound = metric["name"], metric["bound"]
        widest, where, drift, tightest, tight_in = 0.0, None, 0.0, 0.0, None
        for cell, sets in sorted(runs.items()):
            medians, trimmed = [], []
            for k, by_metric in sorted(sets.items()):
                if name not in by_metric:
                    continue
                vals = by_metric[name]
                if name == "setup_s":
                    vals = vals[1:]        # a set's first run may compile
                s = spread(vals)
                trimmed.append(trimmed_spread(vals))
                medians.append(statistics.median(vals))
                say(f"  {name} {cell} set {k}: n={len(vals)} median "
                    f"{medians[-1]:.6g} spread {100 * s:.3f} % (without the "
                    f"farthest run {100 * trimmed[-1]:.3f} %)")
                if s > widest:
                    widest, where = s, cell
            if trimmed and statistics.mean(trimmed) > tightest:
                tightest, tight_in = statistics.mean(trimmed), cell
            if len(medians) == 2:
                drift = max(drift, abs(medians[1] - medians[0]) / medians[0])
        if where is None:
            say(f"{name}: no runs read")
            ok = False
            continue
        if name == "setup_s":
            good = bound <= 0.25 and drift <= bound
            say(f"{name}: bound {100 * bound:.1f} % (fixed by the contract; "
                f"judged by its median only); widest spread "
                f"{100 * widest:.2f} % in {where}; medians of the two sets "
                f"differ by at most {100 * drift:.2f} % "
                f"{'ok' if good else 'OUTSIDE'}")
        else:
            most = max(8 * widest, 0.01)
            good = 2 * tightest <= bound <= most and drift <= bound
            say(f"{name}: bound {100 * bound:.2f} %; widest spread "
                f"{100 * widest:.3f} % in {where}, so at most "
                f"{100 * most:.2f} % (eight times it, or 1 %); mean trimmed "
                f"spread {100 * tightest:.3f} % in {tight_in}, so at least "
                f"{200 * tightest:.3f} % (twice it); medians of the two sets "
                f"differ by at most {100 * drift:.3f} % "
                f"{'ok' if good else 'OUTSIDE'}")
        ok = ok and good
    return ok


def main(argv=None) -> int:
    dirs = (argv if argv is not None else sys.argv[1:])
    if not dirs:
        print(__doc__)
        return 2
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    runs = read_runs(dirs)
    ok = check(bench, runs)
    print("bounds: within the contract's limits" if ok
          else "bounds: OUTSIDE the contract's limits")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
