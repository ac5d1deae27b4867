"""Shared by the benchmark's tests: where things are, and how to drive the
harness in-process at the rehearsal size (the test session's JAX is the
8-device CPU mesh of ``tests/conftest.py``)."""

from __future__ import annotations

import argparse
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
