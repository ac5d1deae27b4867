"""The layout the four-chip Astaroth cell runs (``astaroth256x4.weak``), at a
size tier-1 can afford: tight-x blocks on a (1,2,2) mesh, the fused substep
kernels (interpret mode) and ONE batched exchange an iteration, in both
schedules the fused path has. ``serial`` is exchange-first, what the cell
runs since PR 34 (``overlap=None``, the default, resolves to it on the
fused path: ``run()``'s own call and the plan at the cell's block say so
below). ``overlap`` is what ``overlap=True`` still builds and the cell ran
until then: substep 0's kernel on pre-exchange data and its overlap shells
re-integrated from the exchanged halos over x-wrapped slabs
(``_integrate_shell_wrap_x``). Every owned cell of the 8 fields is held
against the benchmark's plain float64 reference iterated on the periodic
global field in both, the overlap build against the serial one, and the
build-time counter ``astaroth.step_plan`` against the geometry counted the
slow way.

The tier-1 form of ``tests/test_astaroth.py::
test_tight_x_multiblock_yz_matches_reference`` (marked slow), whose
tolerance this file states again."""

import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import astaroth as reference  # noqa: E402
from stencil_tpu.apps.astaroth import DEFAULT_CONF  # noqa: E402
from stencil_tpu.astaroth import config as ac_config  # noqa: E402
from stencil_tpu.astaroth.integrate import FIELDS, make_astaroth_step  # noqa: E402
from stencil_tpu.domain.grid import GridSpec  # noqa: E402
from stencil_tpu.geometry import (Dim3, Radius, Rect3, exterior_regions,  # noqa: E402
                                  interior_region)
from stencil_tpu.obs import scopes, telemetry  # noqa: E402
from stencil_tpu.parallel import HaloExchange, grid_mesh  # noqa: E402
from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks  # noqa: E402

# x = 128 is the lane floor of the tight-x layout; y and z of 8 a block are
# the smallest that leave an interior between two radius-3 shells
NX, NY, NZ = 128, 16, 16
DT = 1e-3       # 1e5 x the driver's: an iteration moves the fields by 1e-2
ITERS = 2       # the second consumes exchanged RK3 output
TIGHT = Radius.constant(3).without_x()
PLAN_FIELDS = dict(telemetry.NAME_FIELDS["astaroth.step_plan"])


def _info(nx, ny, nz):
    info = ac_config.AcMeshInfo()
    with open(DEFAULT_CONF) as f:
        ac_config.parse_config(f.read(), info)
    info.int_params["AC_nx"] = nx
    info.int_params["AC_ny"] = ny
    info.int_params["AC_nz"] = nz
    info.update_builtin_params()
    return info


def _new_plans(build):
    """(what ``build()`` returned, the step plans it recorded)."""
    rec = telemetry.get()
    before = len(rec.records(kind="counter", name="astaroth.step_plan"))
    out = build()
    return out, rec.records(kind="counter", name="astaroth.step_plan")[before:]


def _reference(fields, iters):
    """``iters`` iterations of the plain reference on the periodic global
    field: wrap a margin of 3 on, iterate, and the margin is consumed."""
    state = {k: v.astype(np.float64) for k, v in fields.items()}
    for _ in range(iters):
        state = reference.iterate(
            {k: np.pad(v, reference.R, mode="wrap") for k, v in state.items()},
            dt=DT)
    return state


@pytest.fixture(scope="module")
def x4():
    """Both builds of the cell's layout driven ``ITERS`` iterations from the
    same seeded fields: ``{"overlap" | "serial": (fields, plans)}``, the
    seeded fields and the spec."""
    assert tuple(FIELDS) == reference.FIELDS
    info = _info(NX, NY, NZ)
    assert info.real_params["AC_dsx"] == reference.DS
    spec = GridSpec(Dim3(NX, NY, NZ), Dim3(1, 2, 2), TIGHT)
    assert spec.padded().x == NX and spec.compute_offset().x == 0
    mesh = grid_mesh(spec.dim, jax.devices()[:4])
    ex = HaloExchange(spec, mesh)
    rng = np.random.RandomState(33)
    seeded = {k: (rng.randn(NZ, NY, NX) * 0.05).astype(np.float32)
              for k in FIELDS}
    seeded["lnrho"] = seeded["lnrho"] + np.float32(0.5)
    runs = {}
    for mode, overlap in (("overlap", True), ("serial", False)):
        step, plans = _new_plans(lambda: make_astaroth_step(
            ex, info, dt=DT, dtype="float32", use_pallas=True,
            interpret=True, overlap=overlap))
        curr = {k: shard_blocks(seeded[k], spec, mesh) for k in FIELDS}
        nxt = {k: shard_blocks(np.zeros((NZ, NY, NX), np.float32), spec, mesh)
               for k in FIELDS}
        for _ in range(ITERS):
            curr, nxt = step(curr, nxt)
        runs[mode] = ({k: unshard_blocks(curr[k], spec) for k in FIELDS},
                      plans)
    return {"runs": runs, "seeded": seeded, "spec": spec,
            "want": _reference(seeded, ITERS)}


@pytest.mark.parametrize("schedule", ["serial", "overlap"])
@pytest.mark.parametrize("field", FIELDS)
def test_every_owned_cell_matches_the_plain_reference(x4, field, schedule):
    """The whole global field, so every shell, both block seams and every
    periodic wrap: the tolerance of the slow test it stands in for."""
    got = x4["runs"][schedule][0][field]
    want = x4["want"][field]
    assert got.shape == want.shape == (NZ, NY, NX)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-6,
                               err_msg=field)
    # the comparison sees the step: the fields moved 100 x the tolerance
    moved = np.max(np.abs(want - x4["seeded"][field]))
    assert moved > 1e-4, (field, moved)


@pytest.mark.parametrize("field", FIELDS)
def test_the_shells_do_not_change_the_answer(x4, field):
    """Overlap (kernel on pre-exchange data, then the shells in XLA) against
    serial (exchange, then the kernel alone). Not bit for bit: a shell cell
    of substep 0 is computed by XLA's arithmetic in one build and by the
    kernel's in the other, which round an operation differently. Stated:
    two float32 roundings of the field's largest value (readings here: 0.07
    to 1.4), 1e-3 of the tolerance against the reference."""
    a = x4["runs"]["overlap"][0][field]
    b = x4["runs"]["serial"][0][field]
    step = np.finfo(np.float32).eps * np.max(np.abs(b))
    assert np.max(np.abs(a - b)) <= 2 * step, (field, step)


def test_one_step_plan_a_build_with_the_declared_fields(x4):
    for mode in ("overlap", "serial"):
        (plan,) = x4["runs"][mode][1]
        assert not telemetry.validate_record(plan), \
            telemetry.validate_record(plan)
        for name, kind in PLAN_FIELDS.items():
            assert isinstance(plan[name], kind), (mode, name)
        assert plan["module"] == scopes.ASTAROTH_ITER and plan["value"] == 1
        assert (plan["pallas"], plan["tight_x"], plan["blocks"],
                plan["quantities"], plan["exchanges_per_iter"]) == (
            True, True, 4, 8, 1)
        assert plan["block_cells"] == NX * (NY // 2) * (NZ // 2)
        # y and z faces of the padded block, 3 deep each way, 8 fields
        p = x4["spec"].padded()
        assert plan["halo_bytes_sent"] == 2 * 3 * (p.z + p.y) * p.x * 8 * 4
    assert x4["runs"]["overlap"][1][0]["mode"] == "overlap"
    assert x4["runs"]["serial"][1][0]["mode"] == "serial"
    assert (x4["runs"]["serial"][1][0]["shells"],
            x4["runs"]["serial"][1][0]["shell_cells"]) == (0, 0)


def test_shell_cells_are_the_exteriors_counted_the_slow_way(x4):
    spec = x4["spec"]
    off = spec.compute_offset()
    compute = Rect3(off, off + spec.base)
    rects = exterior_regions(compute, interior_region(compute, spec.radius))
    held = np.zeros((spec.padded().z, spec.padded().y, spec.padded().x), int)
    for r in rects:
        held[r.lo.z:r.hi.z, r.lo.y:r.hi.y, r.lo.x:r.hi.x] += 1
    assert held.max() == 1          # the rects do not overlap
    (plan,) = x4["runs"]["overlap"][1]
    assert plan["shells"] == len(rects) == 4    # no x shell on tight-x
    assert plan["shell_cells"] == int(held.sum())
    # every owned cell within 3 of a y or z face of the block, once
    by, bz = NY // 2, NZ // 2
    assert plan["shell_cells"] == NX * (by * bz - (by - 6) * (bz - 6))


@pytest.mark.parametrize("dim, overlap, mode, shells", [
    ((1, 1, 1), None, "serial", 0),     # what astaroth256.steady builds
    ((1, 2, 2), None, "serial", 0),     # what astaroth256x4.weak builds
    ((1, 2, 2), True, "overlap", 4),    # ... built until PR 34; asked for
    ((1, 2, 2), False, "serial", 0),
    ((1, 1, 1), True, "serial", 0),     # one block has nothing to overlap
])
def test_the_plan_at_the_cells_own_block(dim, overlap, mode, shells):
    """The two Astaroth cells' builds at their real 256^3 block (nothing is
    traced or compiled by a build): the default is exchange-first on the
    fused path, the shells are there for whoever asks."""
    d = Dim3(*dim)
    spec = GridSpec(Dim3(256 * d.x, 256 * d.y, 256 * d.z), d, TIGHT)
    ex = HaloExchange(spec, grid_mesh(d, jax.devices()[:d.flatten()]))
    _, plans = _new_plans(lambda: make_astaroth_step(
        ex, _info(256, 256, 256), dtype="float32", use_pallas=True,
        interpret=True, overlap=overlap))
    (plan,) = plans
    assert (plan["mode"], plan["shells"], plan["blocks"],
            plan["exchanges_per_iter"]) == (mode, shells, d.flatten(), 1)
    assert plan["block_cells"] == 256 ** 3
    assert 256 * (256 ** 2 - 250 ** 2) == 777_216
    assert plan["shell_cells"] == (777_216 if shells else 0)
    # 26.2 MB a chip an exchange in either schedule; one block sends nothing
    assert plan["halo_bytes_sent"] == (26_247_168 if d.flatten() > 1 else 0)


def test_the_xla_path_records_its_plan_too():
    """Either path: the unfused step on inline halos, one exchange and six
    shells hoisted an iteration, three exchanges under swap_per_substep."""
    spec = GridSpec(Dim3(16, 16, 16), Dim3(1, 2, 2), Radius.constant(3))
    ex = HaloExchange(spec, grid_mesh(spec.dim, jax.devices()[:4]))
    info = _info(16, 16, 16)
    _, (plan,) = _new_plans(lambda: make_astaroth_step(
        ex, info, dtype="float32", use_pallas=False))
    assert (plan["mode"], plan["pallas"], plan["tight_x"],
            plan["exchanges_per_iter"], plan["shells"]) == (
        "overlap", False, False, 1, 6)
    assert plan["shell_cells"] == 8 * 8 * 16 - 2 * 2 * 10
    _, (plan,) = _new_plans(lambda: make_astaroth_step(
        ex, info, dtype="float32", use_pallas=False, swap_per_substep=True))
    assert (plan["mode"], plan["exchanges_per_iter"], plan["shells"]) == (
        "per_substep", 3, 18)
    _, (plan,) = _new_plans(lambda: make_astaroth_step(
        ex, info, dtype="float32", use_pallas=False, overlap=False))
    assert (plan["mode"], plan["exchanges_per_iter"], plan["shells"],
            plan["shell_cells"]) == ("serial", 3, 0, 0)


@pytest.mark.parametrize("asked, plan_is", [
    ({}, ("serial", True, 0)),          # run()'s defaults: the cell's call
    ({"overlap": True}, ("overlap", True, 4)),
], ids=["default", "overlap"])
def test_run_on_four_tpus_keeps_the_partition_it_sized_the_domain_for(
        tmp_path, monkeypatch, asked, plan_is):
    """``run()`` leaves the schedule to the builder, which takes
    exchange-first on the fused path (PR 34); ``overlap=True`` still gets
    the shells. What stopped the parent on the chip (PR 33): ``run()`` sized the
    domain for ``decompose_zy(4)`` = (1,2,2) and picked the tight-x radius,
    but left the partition to ``realize()``, whose min-interface split cuts
    the axis with no halo first: x, four ways, which the tight layout
    forbids. The application's own call, walked on the CPU mesh: the
    platform test is turned on and the builder told to interpret."""
    from stencil_tpu.apps import astaroth as app
    from stencil_tpu.geometry.partition import NodePartition

    size = Dim3(NX, NY, NZ)
    assert NodePartition(size, TIGHT, 1, 4).dim() == Dim3(4, 1, 1)
    conf = tmp_path / "astaroth.conf"
    with open(DEFAULT_CONF) as f:
        text = f.read()
    for axis, n in (("x", NX), ("y", NY // 2), ("z", NZ // 2)):
        text = text.replace(f"AC_n{axis} = 256", f"AC_n{axis} = {n}")
    conf.write_text(text)
    build = app.make_astaroth_step
    monkeypatch.setattr(app, "_on_tpu", lambda devices: True)
    monkeypatch.setattr(
        app, "make_astaroth_step", lambda *a, **kw: build(
            *a, **dict(kw, use_pallas=True, interpret=True)))
    (r, plans) = _new_plans(lambda: app.run(
        iters=1, conf=str(conf), dtype="float32", devices=jax.devices()[:4],
        **asked))
    spec = r["domain"].spec
    assert r["global"] == size and spec.dim == Dim3(1, 2, 2)
    assert spec.radius.x(-1) == spec.radius.x(1) == 0
    (plan,) = plans
    assert (plan["mode"], plan["tight_x"], plan["shells"]) == plan_is
    for k in FIELDS:
        f = r["domain"].get_curr_global(r["handles"][k])
        assert f.shape == (NZ, NY, NX) and np.isfinite(f).all(), k
