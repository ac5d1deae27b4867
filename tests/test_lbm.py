"""D3Q19 lattice-Boltzmann through the normal path (``ops/lbm``,
``ops/pallas_lbm``, ``apps/lbm``) against the benchmark's plain float64
reference: every cell of every population, on one block and several, x
split and unsplit, the XLA form and the kernel interpreted; what the exact
update keeps (mass, momentum, the uniform state at rest) and what a step
built on an exchange without its edge gates gets wrong."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import lbm as ref
from stencil_tpu.api import DistributedDomain
from stencil_tpu.geometry import Dim3
from stencil_tpu.obs import telemetry
from stencil_tpu.ops import lbm
from stencil_tpu.parallel.exchange import unshard_blocks

OMEGA = lbm.omega_of(1.0)


def test_the_lattice_is_the_references_and_the_opposites_are_paired():
    assert lbm.VELOCITIES == ref.VELOCITIES and lbm.Q == ref.Q == 19
    assert lbm.WEIGHTS == ref.WEIGHTS
    assert OMEGA == ref.omega_of(1.0) == 2 / 7
    for i in range(1, 19, 2):       # what collide() pairs
        assert lbm.VELOCITIES[i + 1] == tuple(-c for c in lbm.VELOCITIES[i])
        assert lbm.WEIGHTS[i + 1] == lbm.WEIGHTS[i]


def test_collide_is_the_references_arithmetic():
    rng = np.random.RandomState(2)
    g = [rng.uniform(0.02, 0.4, (5, 6, 7)) for _ in range(19)]
    for a, b in zip(lbm.collide(g, OMEGA), ref.collide(g, OMEGA)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-15)


def test_a_populations_radius_is_its_faces_and_its_one_edge():
    for i, c in enumerate(lbm.VELOCITIES):
        r = lbm.population_radius(i)
        on = {d for d, v in r._r.items() if v}
        want = set()
        axes = [a for a in range(3) if c[a]]
        for a in axes:
            d = [0, 0, 0]
            d[a] = -c[a]
            want.add(tuple(d))
        if len(axes) == 2:
            want.add(tuple(-v for v in c))
        assert on == want, (c, on)
        tight = lbm.population_radius(i, tight_x=True)
        assert {d for d, v in tight._r.items() if v} == {
            d for d in want if d[0] == 0}
    whole = lbm.domain_radius(False)
    assert whole.x(1) == whole.y(-1) == whole.dir(1, -1, 0) == 1
    assert whole.dir(1, 1, 1) == 0 and lbm.domain_radius(True).x(1) == 0


def _state(shape_zyx, dtype, seed=1):
    rng = np.random.RandomState(seed)
    rho = rng.uniform(0.9, 1.1, shape_zyx)
    u = [rng.uniform(-0.05, 0.05, shape_zyx) for _ in range(3)]
    f = [ref.equilibrium(i, rho, *u)
         * (1 + rng.uniform(-0.01, 0.01, shape_zyx)) for i in range(19)]
    return [a.astype(dtype).astype(np.float64) for a in f]


def _run(n_xyz, part, dtype, steps, f0=None, edges=True, **how):
    """``steps`` steps in one program from ``f0``; the populations as
    global float64 arrays and the domain."""
    nx, ny, nz = n_xyz
    tight = part[0] == 1
    dd = DistributedDomain(nx, ny, nz)
    dd.set_radius(lbm.domain_radius(tight))
    dd.set_partition(part)
    dd.set_devices(jax.devices()[:Dim3.of(part).flatten()])
    hs = [dd.add_data(f"f{i}", dtype,
                      radius=lbm.population_radius(i, tight, edges=edges))
          for i in range(19)]
    dd.realize()
    f0 = _state((nz, ny, nx), dtype) if f0 is None else f0
    for h, a in zip(hs, f0):
        dd.set_curr_global(h, a)
    step = lbm.make_lbm_step(dd.halo_exchange, OMEGA, dtype, iters=steps,
                             **how)
    curr, _ = step([dd.get_curr(h) for h in hs],
                   [dd.get_next(h) for h in hs])
    return f0, [unshard_blocks(a, dd.spec).astype(np.float64)
                for a in curr], dd


def _worst(got, want):
    return max(float(np.abs(a - b).max()) for a, b in zip(got, want))


@pytest.mark.parametrize("steps", [1, 20])
@pytest.mark.parametrize("n, part", [
    ((16, 16, 16), (1, 1, 1)), ((16, 16, 16), (1, 2, 2)),
    ((16, 16, 16), (2, 2, 2)), ((16, 12, 8), (2, 1, 1)),
    # uneven blocks (x 9+8, y 10+9; z 4+4+4+4 on the last): dead pad rows
    # and columns beside the halos a population is read from
    ((17, 19, 16), (1, 2, 2)), ((17, 19, 16), (2, 2, 2)),
    ((17, 19, 16), (1, 2, 4)),
], ids=["one-block", "1x2x2", "2x2x2", "x-split", "uneven-1x2x2",
        "uneven-2x2x2", "uneven-1x2x4"])
def test_every_cell_of_every_population_is_the_references(n, part, steps):
    f0, got, dd = _run(n, part, "float64", steps)
    assert _worst(got, ref.run(f0, OMEGA, steps)) < 1e-15
    plan = telemetry.get().records(kind="counter", name="lbm.step_plan")[-1]
    assert plan["kernel"] == "xla" and plan["chunk"] == steps
    assert plan["layout"] == ("tight_x" if part[0] == 1 else "inline")
    assert all(len(v) == 5 for v in plan["carried"].values())
    assert sorted(plan["carried"]) == sorted(
        a + s for a in ("xyz" if part[0] > 1 else "yz") for s in "-+")
    assert 38 * plan["halo_bytes_sent"] == 10 * plan["halo_bytes_if_all"]


@pytest.mark.parametrize("n, part, steps", [
    ((128, 16, 8), (1, 1, 1), 1), ((128, 16, 8), (1, 1, 1), 3),
    ((128, 16, 8), (1, 2, 1), 2),
], ids=["one-step", "three-steps", "two-blocks"])
def test_the_kernel_interpreted_is_the_references(n, part, steps):
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        f0, got, dd = _run(n, part, "float32", steps, use_pallas=True,
                           interpret=True)
    finally:
        jax.config.update("jax_enable_x64", x64)
    plan = telemetry.get().records(kind="counter", name="lbm.step_plan")[-1]
    assert plan["kernel"] == "pallas" and plan["layout"] == "tight_x"
    assert _worst(got, ref.run(f0, OMEGA, steps)) < 1.5e-7
    # the XLA form on the same blocks agrees with it to float32 rounding
    _, xla, _ = _run(n, part, "float32", steps, f0=f0, use_pallas=False)
    assert _worst(got, xla) < 1.5e-7


def test_what_the_kernel_does_not_take_is_said_not_silent():
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.ops.pallas_lbm import (make_pallas_lbm_step,
                                            step_supported)

    def spec(n, part, tight=True, **kw):
        return GridSpec(Dim3(*n), Dim3(*part), lbm.domain_radius(tight), **kw)

    ok = spec((384, 384, 384), (1, 1, 1))
    assert step_supported(ok, jnp.float32)
    assert not step_supported(ok, jnp.float64)
    assert not step_supported(spec((96, 16, 16), (1, 1, 1)), jnp.float32)
    assert not step_supported(spec((256, 16, 16), (2, 1, 1), tight=False),
                              jnp.float32)              # x split
    assert not step_supported(spec((128, 12, 16), (1, 1, 1)), jnp.float32)
    with pytest.raises(ValueError, match="unsupported"):
        make_pallas_lbm_step(spec((96, 16, 16), (1, 1, 1)), OMEGA)
    # a forced kernel on a block it does not take is the XLA form, recorded
    _run((16, 16, 16), (1, 1, 1), "float32", 1, use_pallas=True)
    plan = telemetry.get().records(kind="counter", name="lbm.step_plan")[-1]
    assert plan["kernel"] == "xla"


def test_mass_and_momentum_in_float32_stay_within_the_stated_bound():
    """The exact update keeps all four; a float32 step rounds each of the
    19 populations of a cell at 6e-8 relative, and the errors of 16^3
    cells over 20 steps add up like a random walk: the stated bound is
    2e-6 of the mass for the mass and for each momentum (measured: 2e-7
    and less)."""
    f0, got, _ = _run((16, 16, 16), (1, 2, 2), "float32", 20)
    before, after = ref.invariants(f0), ref.invariants(got)
    for a, b in zip(before, after):
        assert abs(a - b) < 2e-6 * before[0], (before, after)


@pytest.mark.parametrize("how", [{}, {"use_pallas": True, "interpret": True}],
                         ids=["xla", "kernel"])
def test_a_uniform_equilibrium_at_rest_is_a_fixed_point_bit_for_bit(how):
    n = (128, 16, 8)
    f0 = [np.full(n[::-1], np.float64(np.float32(w))) for w in lbm.WEIGHTS]
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        _, got, _ = _run(n, (1, 1, 1), "float32", 3, f0=f0, **how)
    finally:
        jax.config.update("jax_enable_x64", x64)
    for a, b in zip(got, f0):
        assert np.array_equal(a, b)


def test_a_step_on_an_exchange_without_its_edge_gates_is_wrong_on_the_edges():
    f0, got, dd = _run((16, 16, 16), (1, 2, 2), "float64", 1, edges=False)
    want = ref.run(f0, OMEGA, 1)
    by = dd.spec.base.y
    for i in range(19):
        # a cell that pulls ONE population across a block's y-z edge has
        # its density off, so all 19 of it: the four lines of x cells of
        # each of the four blocks, and no other cell
        bad = np.argwhere(np.abs(got[i] - want[i]) > 1e-12)
        assert len(bad) == 4 * 4 * 16
        rows = {(z % 8, y % by) for z, y, _x in bad}
        assert rows == {(0, 0), (0, by - 1), (7, 0), (7, by - 1)}


def test_the_application_runs_the_normal_path_and_keeps_its_invariants():
    from stencil_tpu.apps import lbm as app

    r = app.run(x=16, y=16, z=32, steps=6, chunk=3, dtype="float64",
                devices=jax.devices()[:4])
    dd = r["domain"]
    assert dd.spec.dim == Dim3(1, 2, 2) and r["steps_run"] == 9
    assert dd.halo_exchange.quantity_radius is not None
    spans = {rec["name"] for rec in telemetry.get().records(kind="span")}
    assert {"lbm.realize", "lbm.init", "lbm.warmup", "lbm.steps",
            "lbm.step"} <= spans
    before, after = r["invariants_before"], r["invariants_after"]
    # the vortex is made in float32, whatever the lattice's dtype
    assert abs(before[0] - 16 * 16 * 32) < 1e-6 * 16 * 16 * 32
    for a, b in zip(before, after):
        assert abs(a - b) < 1e-9
    assert app.csv_row(r).startswith("lbm,1,4,16,16,32,6,")
    with pytest.raises(ValueError, match="one of the two"):
        app.run(steps=1)
    with pytest.raises(ValueError, match="do not split"):
        app.run(x=16, y=15, z=16, devices=jax.devices()[:4])
