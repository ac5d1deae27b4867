"""A radius a quantity (``add_data(radius=)``): which halos the exchange
fills for each quantity, what the plan says of it, what its lowerings move,
what they refuse, and that a domain which passes none is the domain it was
before the option existed: the same plan and the same lowered program, for
the domains the five applications declare (the hashes were recorded on the
parent commit: ``python tests/test_quantity_radius.py`` prints them for
whatever ``stencil_tpu`` is on the path)."""

import dataclasses
import hashlib
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from stencil_tpu.api import DistributedDomain
from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.parallel import HaloExchange, Method, grid_mesh


def _d3q19(tight):
    from stencil_tpu.ops import lbm

    return lbm, [lbm.population_radius(i, tight) for i in range(lbm.Q)]


def _domain(n, part, radii, base, dtype="float32"):
    dd = DistributedDomain(*n)
    dd.set_radius(base)
    dd.set_partition(part)
    dd.set_devices(jax.devices()[:Dim3.of(part).flatten()])
    hs = [dd.add_data(f"q{i}", dtype, radius=r) for i, r in enumerate(radii)]
    dd.realize()
    return dd, hs


def _seeded(dd, hs, seed=0):
    g = dd.spec.global_size
    rng = np.random.RandomState(seed)
    fields = [rng.rand(g.z, g.y, g.x).astype(np.float32) for _ in hs]
    for h, a in zip(hs, fields):
        dd.set_curr_global(h, a)
    return fields


def _wrong_reads(dd, hs, fields, offsets):
    """Per quantity, how many of the cells it is READ at (every owned cell
    displaced by ``-offset``) hold something else than the periodic
    field."""
    spec = dd.spec
    off, b, g = spec.compute_offset(), spec.base, spec.global_size
    tight = not spec.radius.x(-1)
    wrong = []
    for h, a, c in zip(hs, fields, offsets):
        arr = np.asarray(dd.get_curr(h))
        bad = 0
        for iz in range(spec.dim.z):
            for iy in range(spec.dim.y):
                for ix in range(spec.dim.x):
                    o = spec.block_origin((ix, iy, iz))
                    want = a[np.ix_((np.arange(b.z) + o.z - c[2]) % g.z,
                                    (np.arange(b.y) + o.y - c[1]) % g.y,
                                    (np.arange(b.x) + o.x - c[0]) % g.x)]
                    xs = (slice(0, b.x) if tight else
                          slice(off.x - c[0], off.x - c[0] + b.x))
                    got = arr[iz, iy, ix, off.z - c[2]:off.z - c[2] + b.z,
                              off.y - c[1]:off.y - c[1] + b.y, xs]
                    if tight:
                        got = np.roll(got, c[0], axis=2)
                    bad += int((got != want).sum())
        wrong.append(bad)
    return wrong


@pytest.mark.parametrize("part, tight", [((1, 1, 1), True), ((1, 2, 2), True),
                                         ((2, 2, 2), False)], ids=str)
def test_a_d3q19_plan_carries_five_populations_a_direction_and_no_corner(
        part, tight):
    lbm, radii = _d3q19(tight)
    dd, hs = _domain((16, 16, 16), part, radii, lbm.domain_radius(tight))
    plan = dd.halo_exchange.plan
    assert plan.quantity_radius is not None
    active = [ph for ph in plan.axis_phases if ph.active]
    assert [ph.axis for ph in active] == (["y", "z"] if tight
                                          else ["x", "y", "z"])
    comp = {"x": 0, "y": 1, "z": 2}
    for ph in active:
        low, high = ph.sides
        assert len(low.keys) == len(high.keys) == 5
        assert not set(low.keys) & set(high.keys)
        # the low halo is wanted by what moves UP the axis
        assert all(lbm.VELOCITIES[k][comp[ph.axis]] == 1 for k in low.keys)
        assert all(lbm.VELOCITIES[k][comp[ph.axis]] == -1 for k in high.keys)
        assert not low.trim and not high.trim
        assert ph.collectives() == (2 if ph.ring > 1 else 0)
    assert all(not r.dir(d) for r in radii for d in [
        (a, b, c) for a in (-1, 1) for b in (-1, 1) for c in (-1, 1)])
    # 10 one-sided slabs an axis where one radius carries 38
    sizes = {h.idx: 4 for h in hs}
    one = HaloExchange(dd.spec, dd.mesh)
    moved = dd.halo_exchange.bytes_moved([4] * 19, keys=list(sizes))
    assert moved == dd.exchange_bytes_moved()
    assert moved * 38 == one.bytes_moved([4] * 19) * 10
    assert (plan.wire_bytes(sizes) * 38
            == one.plan.wire_bytes([4] * 19) * 10)
    # and every cell a population is read at is right, the rest's too
    fields = _seeded(dd, hs)
    dd.exchange()
    assert _wrong_reads(dd, hs, fields, lbm.VELOCITIES) == [0] * 19
    # logical bytes: each population its faces and its one edge
    want = 0
    for r in radii:
        for d in [(x, y, z) for x in (-1, 0, 1) for y in (-1, 0, 1)
                  for z in (-1, 0, 1) if (x, y, z) != (0, 0, 0)]:
            if r.dir(d):
                b = dd.spec.base
                want += 4 * Dim3.of(part).flatten() * int(np.prod(
                    [1 if c else n for c, n in zip(d, (b.x, b.y, b.z))]))
    assert dd.exchange_bytes_for_method(Method.AXIS_COMPOSED) == want


@pytest.mark.parametrize("part", [(1, 1, 1), (1, 2, 2)], ids=str)
def test_a_plan_with_every_edge_gate_off_leaves_the_edge_cells_wrong(part):
    """The fault the selection invites: a diagonal population whose radius
    names its two faces and not the edge between them. The z slabs then
    leave the y halo rows out, and exactly the cells read across an edge
    come out wrong (and they alone)."""
    lbm, _ = _d3q19(True)
    radii = [lbm.population_radius(i, True, edges=False) for i in range(19)]
    dd, hs = _domain((16, 16, 16), part, radii, lbm.domain_radius(True))
    for ph in dd.halo_exchange.plan.axis_phases:
        if ph.axis == "z":
            assert all(side.trim == ((4, 8, dd.spec.base.y),)
                       for side in ph.sides)
    fields = _seeded(dd, hs)
    dd.exchange()
    wrong = _wrong_reads(dd, hs, fields, lbm.VELOCITIES)
    blocks = Dim3.of(part).flatten()
    for c, bad in zip(lbm.VELOCITIES, wrong):
        # one line of x cells a block for a population that moves in y and z
        assert bad == (16 * blocks if c[1] and c[2] else 0), (c, bad)


def test_per_quantity_carriers_give_the_same_bits():
    lbm, radii = _d3q19(True)
    out = []
    for batched in (True, False):
        dd = DistributedDomain(16, 16, 16)
        dd.set_radius(lbm.domain_radius(True))
        dd.set_partition((1, 2, 2))
        dd.set_devices(jax.devices()[:4])
        dd.set_quantity_batching(batched)
        hs = [dd.add_data(f"q{i}", radius=r) for i, r in enumerate(radii)]
        dd.realize()
        _seeded(dd, hs)
        dd.exchange()
        out.append([np.asarray(dd.get_curr(h)) for h in hs])
    assert all(np.array_equal(a, b) for a, b in zip(*out))


def test_a_quantity_without_a_radius_takes_the_domains():
    """Some quantities with a radius of their own, some without: the
    latter are filled on every side."""
    base = Radius.constant(1)
    low_x = Radius()
    low_x.set_dir((-1, 0, 0), 1)
    dd, hs = _domain((8, 8, 8), (2, 2, 2), [low_x, None], base)
    plan = dd.halo_exchange.plan
    x = plan.axis_phases[0]
    assert x.sides[0].keys == (0, 1) and x.sides[1].keys == (1,)
    fields = _seeded(dd, hs)
    dd.exchange()
    assert _wrong_reads(dd, hs, fields, [(1, 0, 0), (-1, -1, -1)]) == [0, 0]
    assert _wrong_reads(dd, hs[:1], fields[:1], [(-1, 0, 0)]) != [0]


def test_what_a_radius_a_quantity_is_refused_for():
    spec = GridSpec(Dim3(8, 8, 8), Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(Dim3(2, 2, 2), jax.devices())
    face = Radius()
    face.set_dir((1, 0, 0), 1)
    for kw in ({"method": Method.DIRECT26}, {"method": Method.AUTO_SPMD},
               {"periodic": (True, False, True)}, {"wire_dtype": "bfloat16"}):
        with pytest.raises(ValueError, match="a radius a quantity is lowered"):
            HaloExchange(spec, mesh, quantity_radius={0: face}, **kw)
    with pytest.raises(ValueError, match="a radius a quantity is lowered"):
        HaloExchange(GridSpec(Dim3(8, 8, 8), Dim3(2, 2, 4),
                              Radius.constant(1)), mesh,
                     quantity_radius={0: face})            # residents
    wide = Radius()
    wide.set_dir((1, 0, 0), 2)
    with pytest.raises(ValueError, match="selects among the domain's halos"):
        HaloExchange(spec, mesh, quantity_radius={0: wide})
    ex = HaloExchange(spec, mesh, quantity_radius={0: face, 1: face})
    block = jnp.zeros(spec.stacked_shape_zyx(), jnp.float32)
    with pytest.raises(ValueError, match="are not the quantities"):
        ex({0: block})
    with pytest.raises(ValueError, match="pass keys="):
        ex.bytes_moved([4, 4])
    dd = DistributedDomain(8, 8, 8)
    with pytest.raises(ValueError, match="not exchanged"):
        dd.add_data("c", exchanged=False, radius=face)


@pytest.mark.parametrize("axis", ["y", "z"])
@pytest.mark.parametrize("sides", [(True, False), (False, True)], ids=str)
def test_a_self_fill_of_one_side_leaves_the_other_as_it_lies(axis, sides):
    """The fill kernels under a radius a quantity, interpreted: the wanted
    halo is the wrap, the other keeps every bit, and the build counts half
    the DMA bytes of a fill of both."""
    from stencil_tpu.obs import telemetry
    from stencil_tpu.ops.halo_fill import make_self_fill

    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        spec = GridSpec(Dim3(128, 16, 8), Dim3(1, 1, 1),
                        Radius.constant(1).without_x())
        p, off, b = spec.padded(), spec.compute_offset(), spec.base
        rng = np.random.RandomState(3)
        blocks = [rng.rand(p.z, p.y, p.x).astype(np.float32)
                  for _ in range(2)]
        fill = make_self_fill(spec, axis, interpret=True, nq=2, sides=sides)
        one = telemetry.get().records(
            kind="counter", name="halo.self_fill.bytes_dma")[-1]["bytes"]
        make_self_fill(spec, axis, interpret=True, nq=2)
        both = telemetry.get().records(
            kind="counter", name="halo.self_fill.bytes_dma")[-1]["bytes"]
        assert 2 * one == both
        out = [np.asarray(a) for a in fill(*[jnp.asarray(a) for a in blocks])]
    finally:
        jax.config.update("jax_enable_x64", x64)
    dim = {"y": 1, "z": 0}[axis]
    o, n = (off.y, b.y) if axis == "y" else (off.z, b.z)

    def plane(a, at):
        return np.take(a, at, axis=dim)

    for before, after in zip(blocks, out):
        low_ok = np.array_equal(plane(after, o - 1), plane(before, o + n - 1))
        high_ok = np.array_equal(plane(after, o + n), plane(before, o))
        low_kept = np.array_equal(plane(after, o - 1), plane(before, o - 1))
        high_kept = np.array_equal(plane(after, o + n), plane(before, o + n))
        assert (low_ok, high_ok) == sides
        assert (low_kept, high_kept) == tuple(not s for s in sides)
        owned = [slice(None)] * 3
        owned[dim] = slice(o, o + n)
        assert np.array_equal(after[tuple(owned)], before[tuple(owned)])


# ------------------------------------------------- the domains there were


def _jacobi():
    dd = DistributedDomain(16, 16, 16)
    dd.set_radius(Radius.constant(1))
    dd.set_partition((2, 2, 2))
    dd.add_data("temp", "float32")
    return dd, 8


def _astaroth():
    dd = DistributedDomain(16, 16, 16)
    dd.set_radius(Radius.constant(3))
    dd.set_partition((1, 2, 2))
    for name in ("lnrho", "uux", "uuy", "uuz", "ax", "ay", "az", "entropy"):
        dd.add_data(name, "float32")
    return dd, 4


def _iso3dfd():
    dd = DistributedDomain(32, 32, 32)
    dd.set_radius(Radius.face_edge_corner(8, 0, 0))
    dd.set_boundary(periodic=(False, False, False), faces_only=True)
    dd.set_partition((1, 2, 2))
    dd.add_data("prev", "float32", buffered=False)
    dd.add_data("next", "float32", buffered=False)
    dd.add_data("vel", "float32", exchanged=False, buffered=False)
    return dd, 4


def _mg():
    from stencil_tpu.ops.mg import level_radius

    dd = DistributedDomain(16, 16, 16)
    dd.set_radius(level_radius(16, Dim3(1, 2, 2)))
    dd.set_partition((1, 2, 2))
    for q in ("u", "r", "v"):
        dd.add_data(q, "float32", buffered=False)
    return dd, 4


def _exchange():
    dd = DistributedDomain(16, 16, 16)
    dd.set_radius(Radius.constant(3))
    dd.set_partition((2, 2, 1))
    for i in range(4):
        dd.add_data(f"d{i}", "float32")
    return dd, 4


DOMAINS = {"jacobi": _jacobi, "astaroth": _astaroth, "iso3dfd": _iso3dfd,
           "mg": _mg, "exchange": _exchange}

# sha256 of (the plan, the lowered exchange program) of each, recorded on
# commit d7a4a05 (PR 41), where ``add_data`` took no radius
PARENT = {
    "jacobi": ("607d7d02f1ddfbac", "40b5b6f7f98d1e19"),
    "astaroth": ("d1b806f3d338e3a6", "e8b3249fd44c235c"),
    "iso3dfd": ("75492da1f26c8d25", "b8479612fe1576e4"),
    "mg": ("7da3983e11e11690", "31bafc54a20e8578"),
    "exchange": ("45c6ea0d93591994", "88f3ba025d2bd745"),
}


def _fingerprints(name):
    dd, devices = DOMAINS[name]()
    dd.set_devices(jax.devices()[:devices])
    dd.realize()
    ex = dd.halo_exchange
    plan = dataclasses.asdict(ex.plan)
    # what this option added to the record says "not used" ...
    assert plan.pop("quantity_radius", None) is None
    for ph in plan["axis_phases"]:
        assert ph.pop("sides", None) is None
    # ... as the four fields of the kernel-initiated transport did in each
    # of these plans until PR 46 took them out of the record ...
    plan.update(remote_phases=(), fused_phases=(), fused=False,
                persistent=False)
    # ... and everything else is the parent's
    text = json.dumps(plan, sort_keys=True, default=str)
    hlo = ex._compiled.lower(dd._exchanged_state()).as_text()
    return tuple(hashlib.sha256(t.encode()).hexdigest()[:16]
                 for t in (text, hlo))


@pytest.mark.parametrize("name", sorted(DOMAINS))
def test_a_domain_that_passes_no_radius_is_the_domain_it_was(name):
    assert _fingerprints(name) == PARENT[name]


if __name__ == "__main__":
    import os

    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)
    for name in sorted(DOMAINS):
        print(f'    "{name}": {_fingerprints(name)!r},')
