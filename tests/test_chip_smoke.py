"""chip_smoke.py off the chip, and where the compile cache lives.

The smoke's pass line comes only from a TPU run. Here: without a TPU it
fails at once and runs no phase; its phase functions walk through at tiny
sizes with interpret kernels only behind ``--rehearsal``, which says so and
never prints the pass line; and ``configure_compile_cache`` honours
``JAX_COMPILATION_CACHE_DIR`` or else resolves to the fixed in-checkout
path.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

# every phase but astaroth, whose interpret-mode substeps alone take half
# a minute: the full rehearsal is the slow-tier test below
FAST_PHASES = ("four_chip_jacobi,four_chip_iso3dfd,mg_class_b_x4,lbm_x4,"
               "four_chip_exchange,jacobi,mg_class_a,hpcg_256,exchange,serve")


def _smoke(args, tmp_path, cwd=REPO, script=SMOKE, timeout=300):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    return subprocess.run(
        [sys.executable, script, *args], env=env, capture_output=True,
        text=True, timeout=timeout, cwd=cwd,
    )


def _no_pass_line(stdout: str) -> None:
    assert not any(l.lstrip().startswith('{"ok"') for l in stdout.splitlines())


def test_no_tpu_fails_at_once_and_runs_no_phase(tmp_path):
    proc = _smoke([], tmp_path, timeout=60)
    assert proc.returncode == 2
    assert proc.stdout == ""  # no phase started, no result
    reason = proc.stderr.strip().splitlines()
    assert len(reason) == 1 and "no TPU" in reason[0]


def test_alone_in_a_directory_it_fails(tmp_path):
    """The script proves the program, so without the program it fails."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    for args in ([], ["--rehearsal"]):
        proc = _smoke(args, tmp_path, cwd=str(tmp_path),
                      script=str(tmp_path / "chip_smoke.py"), timeout=60)
        assert proc.returncode != 0
        _no_pass_line(proc.stdout)


def _check_rehearsal(proc, tmp_path, phases):
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    _no_pass_line(proc.stdout)
    lines = proc.stdout.splitlines()
    assert lines[0].startswith("[smoke] rehearsal platform=cpu")
    assert lines[-1] == "[smoke] rehearsal complete: not a chip result"
    summary = json.loads(
        [l for l in lines if l.startswith("[smoke] summary: ")][-1]
        .split("summary: ", 1)[1])
    ran = {k for k, v in summary["phases"].items() if v == "passed"}
    assert ran == set(phases), summary["phases"]
    assert all(v == "passed" or v == "not run: not selected"
               for v in summary["phases"].values())
    # the cache went where the environment said
    assert summary["compile_cache"]["dir"] == str(tmp_path / "cache")


def test_rehearsal_walks_the_phases_and_never_passes(tmp_path):
    proc = _smoke(["--rehearsal", "--phases", FAST_PHASES], tmp_path)
    _check_rehearsal(proc, tmp_path, FAST_PHASES.split(","))


@pytest.mark.slow
def test_rehearsal_all_phases(tmp_path):
    proc = _smoke(["--rehearsal"], tmp_path, timeout=600)
    _check_rehearsal(proc, tmp_path, FAST_PHASES.split(",") + [
        "four_chip_exchange_x", "four_chip_astaroth", "astaroth"])


def test_compile_cache_placement(monkeypatch):
    from stencil_tpu.utils import jax_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    # placed from outside: JAX reads the variable itself, nothing is set
    # in code
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert jax_cache.configure_compile_cache() == "/some/dir"
    assert updates == []
    # otherwise: the fixed in-checkout path, the same on every call
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_cache")
    assert jax_cache.configure_compile_cache() == want
    assert jax_cache.configure_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)] * 2
