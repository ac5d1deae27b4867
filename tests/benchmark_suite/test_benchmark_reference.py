"""Each plain reference against the repo's own: ``jacobi_reference``, the
Astaroth XLA path in float64, and the exchange's delivered halos."""

import numpy as np
import pytest

from _bench_util import ROOT, open_session  # noqa: F401
from benchmark import fields
from benchmark.reference import astaroth as ref_astaroth
from benchmark.reference import exchange as ref_exchange
from benchmark.reference import jacobi3d as ref_jacobi


def test_seeded_values_agree_between_numpy_and_the_device():
    import jax.numpy as jnp

    z, y, x = np.meshgrid(np.arange(5), np.arange(7), np.arange(9),
                          indexing="ij")
    for seed in (3, 2 ** 31 + 11, 5_000_000_011):
        host = fields.uniform(np, seed, 2, z, y, x)
        dev = fields._uniform_traced(jnp.asarray(fields.seed_words(seed)),
                                     jnp.uint32(2), jnp.asarray(z),
                                     jnp.asarray(y), jnp.asarray(x), 0)
        assert host.dtype == np.float32
        assert np.array_equal(host, np.asarray(dev))
        assert 0 <= host.min() and host.max() < 1 and host.std() > 0.2
    assert not np.array_equal(fields.uniform(np, 3, 0, z, y, x),
                              fields.uniform(np, 4, 0, z, y, x))


@pytest.mark.parametrize("steps", [1, 10])
def test_jacobi_reference_matches_the_repos_numpy_reference(steps):
    from stencil_tpu.geometry import Dim3
    from stencil_tpu.ops.jacobi import jacobi_reference, sphere_masks, sphere_sel

    g = (24, 20, 40)                               # z, y, x
    size = Dim3(g[2], g[1], g[0])
    z, y, x = np.meshgrid(*(np.arange(n) for n in g), indexing="ij")
    assert np.array_equal(ref_jacobi.sphere_codes(z, y, x, g), sphere_sel(size))
    start = fields.uniform(np, 77, 0, z, y, x)
    want = jacobi_reference(start, sphere_masks(size), steps)
    # a box whose core is the whole periodic grid
    got = ref_jacobi.box_after(77, (0, 0, 0), g, steps, g)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-14)
    assert (sphere_sel(size) > 0).any()


def test_astaroth_reference_matches_the_xla_path_in_float64():
    """The program's XLA path in float64 (the test session enables x64)
    against the numpy reference: agreement to rounding of float64, which no
    transcription error in either set of equations would survive."""
    import json
    import os

    from benchmark.harness import load_json, load_module

    config = load_json(ROOT, "benchmark/configs/astaroth-256-f32.json")
    # not as a rehearsal: on the CPU run() then takes its own XLA path
    config = dict(config, args={"nx": 16, "dtype": "float64", "iters": 2},
                  expects=dict(config["expects"], dtype="float64"))
    mix = load_json(ROOT, "benchmark/traffic/steady.json")
    import jax

    session = load_module("apps", "astaroth").open(
        config, mix, jax.devices()[:1], False, lambda _: None)
    assert session.builds == [], "expected the XLA path, got Pallas kernels"
    session.seed(123_456_789_012)
    before = {n: np.asarray(a) for n, a in session.curr.items()}
    jax.block_until_ready(session.dispatch())
    sample = session.sample()
    checks = session.compare(sample)
    assert len(checks) == 8
    # float64 rounding is 1e-16 and the slowest field moves by 5e-7
    for name, err, _ in checks:
        assert err < 1e-8, (name, err)
    moved = max(float(np.max(np.abs(np.asarray(session.curr[n]) - before[n])))
                for n in session.names)
    assert moved > 1e-6, "the step moved nothing: the comparison is empty"


def test_exchange_reference_is_what_the_exchange_delivers():
    session = open_session("exchange512.r3q4")
    seed = 3_000_000_019
    session.seed(seed)
    import jax

    jax.block_until_ready(session.dispatch())
    f = session.facts
    (rz, _), (ry, _), (rx, _) = f["radius_zyx"]
    spec = session.domain.spec
    off = spec.compute_offset()
    nz, ny, nx = f["block_zyx"]
    z = np.arange(-rz, nz + rz)[:, None, None]
    y = np.arange(-ry, ny + ry)[None, :, None]
    x = np.arange(-rx, nx + rx)[None, None, :]
    for q, arr in session.state.items():
        block = np.asarray(arr)[0, 0, 0]
        held = block[off.z - rz:off.z + nz + rz, off.y - ry:off.y + ny + ry,
                     off.x - rx:off.x + nx + rx]
        want = ref_exchange.expected(seed, q, z, y, x, f["global_zyx"])
        assert np.array_equal(held, want)
    assert session.sample() == 0
