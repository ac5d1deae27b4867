"""Driver-facing entry points never hide the device.

``bench.py`` prints a result only when its legs ran on an accelerator: no
accelerator, a broken backend or a wedged child end in a non-zero exit with
NO result line — there is no forced-CPU rung and no static payload, and a
CPU number never appears under a device metric's name. The parent never
initializes a JAX backend (a chip belongs to one process at a time), and
neither does ``dryrun_multichip``'s parent.

The backend is broken deliberately here: a bogus JAX_PLATFORMS makes any
backend init in the subprocess raise.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _env(platforms, **extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = platforms
    env.update(extra)
    return env


def _run_bench(env, timeout, *args):
    return subprocess.run(
        [sys.executable, BENCH, *args], env=env, capture_output=True,
        text=True, timeout=timeout, cwd=REPO,
    )


def _assert_no_result(proc):
    assert proc.returncode != 0, proc.stderr[-2000:]
    # no payload at all: nothing under a device metric's name, no
    # cpu_fallback metric, no static line
    assert proc.stdout.strip() == "", proc.stdout[-2000:]
    assert "cpu_fallback" not in proc.stderr


def test_bench_refuses_without_accelerator():
    """JAX finds only the CPU: the accel child says so in one line and the
    run fails at once — no retry, no CPU rung."""
    proc = _run_bench(_env("cpu", STENCIL_BENCH_BUDGET_S="120"), 100)
    _assert_no_result(proc)
    assert "no accelerator" in proc.stderr
    attempts = json.loads(proc.stderr.rsplit("attempts: ", 1)[1])
    assert [a["rc"] for a in attempts] == [3]


@pytest.mark.slow
def test_bench_broken_backend_exits_nonzero():
    """Accel children fail fast (unknown backend); bench.py must exit
    non-zero and print no payload."""
    proc = _run_bench(
        _env("bogus_backend", STENCIL_BENCH_BUDGET_S="40",
             STENCIL_BENCH_FAST="1"), 200)
    _assert_no_result(proc)
    assert "produced no result" in proc.stderr


@pytest.mark.slow
def test_bench_times_out_wedged_child_and_exits_nonzero():
    """A child that hangs before even importing JAX (a wedged backend
    init) must be killed by the parent's timeout; the run then fails with
    no payload."""
    proc = _run_bench(
        _env("bogus_backend", STENCIL_BENCH_BUDGET_S="40",
             STENCIL_BENCH_FAST="1", STENCIL_BENCH_SELFTEST_HANG_S="600"),
        200)
    _assert_no_result(proc)
    assert "timed out" in proc.stderr


@pytest.mark.slow
def test_bench_cpu_child_only_on_request_and_never_a_device_metric():
    """``--child cpu`` is the explicit CPU rehearsal of the legs: its
    payload says platform cpu and every number carries a cpu_ prefix."""
    proc = _run_bench(
        _env("cpu", STENCIL_BENCH_FAST="1",
             STENCIL_BENCH_LEG_BUDGET_S="400"), 600, "--child", "cpu")
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [l for l in proc.stdout.splitlines()
             if l.startswith("STENCIL_BENCH_JSON: ")]
    payload = json.loads(lines[-1].split(": ", 1)[1])
    assert payload["metric"].startswith("cpu_")
    assert payload["vs_baseline"] == 0.0
    detail = payload["detail"]
    assert detail["platform"] == "cpu"
    for k, v in detail.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            assert k.startswith("cpu_"), (k, v)


@pytest.mark.slow
def test_dryrun_parent_never_initializes_backend():
    """dryrun_multichip must reach its CPU subprocess without initializing
    any backend in the parent: with a bogus JAX_PLATFORMS, a parent-side
    ``jax.devices()`` would raise — the run must still succeed."""
    code = (
        f"import sys; sys.path.insert(0, {REPO!r}); "
        "import __graft_entry__ as g; "
        "g.dryrun_multichip(2); "
        "print('hardened-dryrun: ok')"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env=_env("bogus_backend"),
        capture_output=True,
        text=True,
        timeout=540,
        cwd=REPO,
    )
    assert proc.returncode == 0, f"{proc.stdout[-1000:]}\n{proc.stderr[-2000:]}"
    assert "hardened-dryrun: ok" in proc.stdout
