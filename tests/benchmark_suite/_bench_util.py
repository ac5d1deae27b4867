"""Shared by the benchmark's tests: where things are, and how to drive the
harness in-process at the rehearsal size (the test session's JAX is the
8-device CPU mesh of ``tests/conftest.py``)."""

from __future__ import annotations

import argparse
import copy
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


CELLS = [w["name"] for w in bench()["workloads"]]


def one_more(b: dict) -> dict:
    """A copy of ``b`` as the next PR leaves it: one configuration, one cell
    (it joins every list ``mg512.steady`` is in) and one per-layer metric
    APPENDED, nothing that was there moved. The tests that hold an entry to
    its place take this and still pass."""
    b = copy.deepcopy(b)
    b["configs"].append({"name": "next-config", "source": "none",
                         "file": "benchmark/configs/next-config.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "next.cell", "config": "next-config",
                           "traffic": "steady", "chips": 1, "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "mg512.steady" in m.get("workloads", ()):
            m["workloads"].append("next.cell")
    b["per_layer"].append({"name": "next_metric", "unit": "ms",
                           "better": "lower", "source": "program_span",
                           "layer": "Stencil kernels",
                           "moves": "mcells_per_s_per_chip",
                           "workloads": ["next.cell"]})
    return b


def app_of(cell: str) -> str:
    """The adapter (``benchmark/apps/<app>.py``) a cell's configuration names."""
    from benchmark import harness

    _, entry = harness.find_cell(bench(), cell)
    return harness.load_json(ROOT, entry["file"])["app"]


def args(workload: str, seed: int = 4_000_000_007, seconds: float = 0.3,
         trace: int = 0):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace, rehearsal=True)


def rehearse(workload: str, wrap_session=None, **kw):
    """(result dict, rc) of one in-process rehearsal run."""
    from benchmark import harness

    return harness.run_cell(args(workload, **kw), time.perf_counter(),
                            wrap_session)


def open_session(workload: str):
    from benchmark import harness

    opened, rc = harness.open_session(args(workload), time.perf_counter())
    assert rc == 0
    return opened.session


def run_py(argv, cwd=ROOT, env=None, timeout=600):
    """``benchmark/run.py`` in a new process, as the driver starts it."""
    e = dict(os.environ)
    e.pop("XLA_FLAGS", None)
    e.update(env or {})
    return subprocess.run([sys.executable, os.path.join(cwd, "benchmark", "run.py"),
                           *argv], cwd=cwd, env=e, capture_output=True,
                          text=True, timeout=timeout)
