"""Driver-facing entry points never hide the device.

A parent that starts a JAX child never initializes a JAX backend itself
(a chip belongs to one process at a time): ``dryrun_multichip``'s parent
is held to that here.

The backend is broken deliberately: a bogus JAX_PLATFORMS makes any
backend init in the subprocess raise.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(platforms, **extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = platforms
    env.update(extra)
    return env


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
