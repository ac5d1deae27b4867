"""iso3dfd at small sizes on the CPU: the kernel (interpreted) and the
application's ``run()`` on one and on four blocks against the plain numpy
reference of the benchmark, the fixed ring, the exchange without the wrap,
per-quantity exchange, and the defaults left as they were."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import fields
from benchmark.reference import iso3dfd as reference
from stencil_tpu.api import DistributedDomain
from stencil_tpu.apps import iso3dfd as app
from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.ops import iso3dfd as ops
from stencil_tpu.ops.pallas_iso3dfd import pick_tiles, step_supported
from stencil_tpu.parallel import HaloExchange, grid_mesh
from stencil_tpu.parallel.exchange import unshard_blocks
from stencil_tpu.plan.ir import build_plan

R = 8
N = (48, 48, 48)            # the grid (z, y, x), ring included
TALL = (32, 88, 48)         # 16 planes of 72 rows: nine row groups a strip
STEPS = 3
SEED = 4_000_000_007
WAVES = ("prev", "next")


def _stacked(spec, mesh, grid_arr):
    """A grid array (ring included) as the domain holds it: owned cells,
    the halos between blocks and the ring in the halos at the edge."""
    shape = spec.stacked_shape_zyx()
    out = np.zeros(shape, np.float32)
    off, b = spec.compute_offset(), spec.base
    for iz in range(spec.dim.z):
        for iy in range(spec.dim.y):
            for ix in range(spec.dim.x):
                z0, y0, x0 = iz * b.z, iy * b.y, ix * b.x   # grid start - R
                out[iz, iy, ix, off.z - R:off.z + b.z + R,
                    off.y - R:off.y + b.y + R, off.x - R:off.x + b.x + R] = (
                        grid_arr[z0:z0 + b.z + 2 * R, y0:y0 + b.y + 2 * R,
                                 x0:x0 + b.x + 2 * R])
    return jax.device_put(out, jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("z", "y", "x")))


def _exchange(part, n=N):
    d = Dim3(*part)
    spec = GridSpec(Dim3(n[2] - 2 * R, n[1] - 2 * R, n[0] - 2 * R), d,
                    Radius.face_edge_corner(R, 0, 0))
    mesh = grid_mesh(d, jax.devices()[:d.flatten()])
    return HaloExchange(spec, mesh, periodic=(False,) * 3, faces_only=True)


def _initial(kind, n=N):
    if kind == "sample":
        return reference.sample_initial(n)
    z, y, x = (np.arange(m) for m in n)
    return [a.astype(np.float64) for a in reference.seeded(
        SEED, z[:, None, None], y[None, :, None], x[None, None, :], n)]


@functools.lru_cache(maxsize=None)
def _kernel_run(kind, tiles, n=N):
    """One block, the kernel interpreted: (got prev, got next, want prev,
    want next) over the interior after STEPS steps."""
    ex = _exchange((1, 1, 1), n)
    assert step_supported(ex.spec, jnp.float32)
    p0, n0, v0 = _initial(kind, n)
    step = ops.make_iso3dfd_step(ex, iters=1, use_pallas=True,
                                 interpret=True, tiles=tiles)
    prev, nxt, vel = (_stacked(ex.spec, ex.mesh, a.astype(np.float32))
                      for a in (p0, n0, v0))
    for _ in range(STEPS):
        prev, nxt = step(prev, nxt, vel)
    want_p, want_n = reference.advance(p0, n0, v0, STEPS)
    cut = (slice(R, -R),) * 3
    return (unshard_blocks(prev, ex.spec), unshard_blocks(nxt, ex.spec),
            want_p[cut], want_n[cut])


# where the body treats a row group differently: the first and the last of
# a strip read the window's halo rows as their neighbour group, and the
# last looks ahead into them for the x pencil of a group that is not there
TILE_CASES = {
    "pick": (None, N),              # one tile: a strip of four groups
    "4x8": ((4, 8), N),             # strips of ONE group, 4 strips of 8 tiles
    "1x16": ((1, 16), N),           # tz = 1; first and last group, a seam
    "2x16": ((2, 16), N),           # two groups a strip, two strips
    "16x32": ((16, 32), N),         # tz = 16: the ring's deepest tile
    "8x32": ((8, 32), N),           # four groups in one trip of the loop
    "2x72": ((2, 72), TALL),        # nine groups: three trips of three
    "4x24": ((4, 24), TALL),        # three strips of three groups
}


@pytest.mark.parametrize("field", WAVES)
@pytest.mark.parametrize("kind", ["sample", "seeded"])
@pytest.mark.parametrize("case", sorted(TILE_CASES))
def test_kernel_interpreted_matches_the_reference(kind, case, field):
    """One block; ``pick`` is the pick (one tile), 4x8 walks 4 strips of 8
    tiles through the ring of planes and every prefetch."""
    got_p, got_n, want_p, want_n = _kernel_run(kind, *TILE_CASES[case])
    got, want = (got_p, want_p) if field == "prev" else (got_n, want_n)
    scale = np.abs(want).max()
    assert scale > 0
    assert np.abs(got - want).max() <= 2e-6 * scale


def test_tile_pick_fits_the_budget_and_the_cells_block():
    from stencil_tpu.ops.pallas_iso3dfd import (_SCRATCH_BUDGET,
                                                _STRIP_GROUPS, scratch_bytes)

    spec = GridSpec(Dim3(1008, 1008, 2032), Dim3(1, 2, 2),
                    Radius.face_edge_corner(R, 0, 0))
    p = spec.padded()
    assert (p.x, p.y, p.z) == (1024, 520, 1032)
    tz, ty = pick_tiles(spec)
    assert 16 % tz == 0 and spec.base.z % tz == 0 and spec.base.y % ty == 0
    # the tiles the chip took best of those that divide the block (PR 43)
    assert (tz, ty) == (4, 168)
    assert _SCRATCH_BUDGET == 32 * 1024 * 1024
    assert scratch_bytes(spec, tz, ty) == 31_850_496 <= _SCRATCH_BUDGET
    assert ty // 8 <= _STRIP_GROUPS
    # a deeper tile or the whole block's rows in one strip are over it
    assert scratch_bytes(spec, 8, 168) > _SCRATCH_BUDGET
    assert spec.base.y // 8 > _STRIP_GROUPS
    assert step_supported(spec, jnp.float32)
    assert not step_supported(spec, jnp.float64)


def _eqns(jaxpr, out):
    for e in jaxpr.eqns:
        out.append(e)
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _eqns(sub, out)
    return out


@pytest.mark.parametrize("tiles", [None, (2, 16), (4, 8)])
def test_step_plan_counter_says_what_the_built_kernel_does(tiles):
    """The kernel's half of ``iso3dfd.step_plan`` against the traced body:
    every read of the window starts on the 8-row tile, a row group loads
    one group of its own plane and rolls whole rows 8 times."""
    from stencil_tpu.obs import telemetry
    from stencil_tpu.ops.pallas_iso3dfd import (kernel_plan,
                                                make_pallas_iso3dfd_step,
                                                scratch_bytes)

    ex = _exchange((1, 1, 1))
    spec = ex.spec
    ops.make_iso3dfd_step(ex, use_pallas=True, interpret=True, tiles=tiles)
    plan = telemetry.get().records(kind="counter",
                                   name="iso3dfd.step_plan")[-1]
    tz, ty = plan["tiles"]
    assert (tz, ty) == (tiles or pick_tiles(spec))
    want = kernel_plan(spec, tiles)
    assert {k: plan[k] for k in want} == want
    assert plan["grid_steps"] == (spec.base.z // tz) * (spec.base.y // ty)
    assert plan["scratch_bytes"] == scratch_bytes(spec, tz, ty)
    assert plan["prev_reread"] == (ty + 16) / ty
    assert plan["row_loads_off_tile"] == 0

    p = spec.padded()
    like = jax.ShapeDtypeStruct((p.z, p.y, p.x), jnp.float32)
    fn = make_pallas_iso3dfd_step(spec, ops.coefficients(), interpret=True,
                                  tiles=tiles)
    eqns = _eqns(jax.make_jaxpr(fn)(like, like, like).jaxpr, [])
    window = (2 * tz + 16, ty + 16, p.x)
    reads = [e for e in eqns if e.primitive.name == "get"
             and e.invars[0].aval.shape == window]
    groups = ty // 8
    # a plane's walk: its first two groups, then one more a group, and the
    # 16 z neighbours of each; the body is traced once a plane
    assert len(reads) == 2 + groups * (plan["row_loads"] + 16)
    for e in reads:
        _, rows, lanes = jax.tree_util.tree_unflatten(
            e.params["tree"], e.invars[1:])[0].indices
        assert rows.size == 8 and isinstance(rows.start, int)
        assert rows.start % 8 == 0, "a window read starts off the tile"
        assert (lanes.start, lanes.size) == (0, p.x)
    rolls = [e for e in eqns if e.primitive.name == "roll"]
    lane = [e for e in rolls if e.params["axis"] == 1]
    # a group: six of the centre rows and two of their weighted sums; the
    # 14 sublane rotations are the y pencil's, made in registers
    assert len(lane) == groups * plan["lane_rolls"] == groups * 8
    assert len(rolls) - len(lane) == groups * 14


PARTS = {"1": ((1, 1, 1), 1), "1x2x2": ((1, 2, 2), 4), "1x1x4": ((1, 1, 4), 4)}


@functools.lru_cache(maxsize=None)
def _app_run(name):
    part, ndev = PARTS[name]
    r = app.run(N[2], N[1], N[0], iters=STEPS - 1,
                devices=jax.devices()[:ndev], partition=part)
    dd, h = r["domain"], r["handles"]
    return {n: unshard_blocks(dd.get_curr(h[n]), dd.spec)
            for n in reference.QUANTITIES}, dd


@pytest.mark.parametrize("field", WAVES)
@pytest.mark.parametrize("name", sorted(PARTS))
def test_run_matches_the_reference_on_one_and_on_four_blocks(name, field):
    """``run()`` through DistributedDomain, realize(), the plan and
    HaloExchange from the sample's own data: one warm-up step and STEPS - 1
    more, every interior cell against the float64 reference."""
    got, dd = _app_run(name)
    assert tuple(dd.spec.dim) == PARTS[name][0]
    p0, n0, v0 = reference.sample_initial(N)
    want = dict(zip(WAVES, reference.advance(p0, n0, v0, STEPS)))
    w = want[field][R:-R, R:-R, R:-R]
    assert np.abs(got[field] - w).max() <= 2e-6 * np.abs(w).max()


@pytest.mark.parametrize("field", reference.QUANTITIES)
@pytest.mark.parametrize("name", ["1x2x2", "1x1x4"])
def test_four_blocks_equal_one_block_bit_for_bit(name, field):
    one, _ = _app_run("1")
    four, _ = _app_run(name)
    assert np.array_equal(one[field], four[field])


@pytest.mark.parametrize("n, part", [
    ((48, 48 + 2, 48), (1, 4, 1)),      # y: 34 interior rows on four blocks
    ((48, 48 + 1, 48), (1, 2, 2)),      # y: 17 + 16
    ((48, 48, 48 + 1), (1, 2, 2)),      # z: 17 + 16
    ((48, 48, 48 + 2), (1, 1, 4)),      # z: 9 + 9 + 8 + 8
], ids=["y-1x4x1", "y-1x2x2", "z-1x2x2", "z-1x1x4"])
@pytest.mark.parametrize("level", ["run", "step"])
def test_an_uneven_split_is_refused_with_a_message(n, part, level):
    """By the application before it realizes anything, and by the step
    builder for whoever brings an exchange of their own: the sweep covers
    the base block, and a shorter block's fixed ring lies inside it (3e-3
    of the field's scale wrong after three steps, were it built)."""
    with pytest.raises(ValueError, match="uneven split is not supported"):
        if level == "run":
            app.run(*n, iters=1, devices=jax.devices()[:4], partition=part)
        else:
            ops.make_iso3dfd_step(_exchange(part, n=n[::-1]))


@functools.lru_cache(maxsize=None)
def _ring_run(part):
    """STEPS steps from seeded data whose ring holds 7.0 (a value no
    neighbour holds): the stacked arrays before and after."""
    ex = _exchange(part)
    p0, n0, v0 = (a.astype(np.float32) for a in _initial("seeded"))
    ring = ~reference.inside(*np.meshgrid(*(np.arange(n) for n in N),
                                          indexing="ij"), N)
    p0[ring] = n0[ring] = 7.0
    before = [_stacked(ex.spec, ex.mesh, a) for a in (p0, n0, v0)]
    held = [np.asarray(a) for a in before]
    step = ops.make_iso3dfd_step(ex, iters=STEPS)
    prev, nxt = step(*before)
    return ex.spec, ring, held, [np.asarray(prev), np.asarray(nxt)]


@pytest.mark.parametrize("field", WAVES)
@pytest.mark.parametrize("part", [(1, 1, 1), (1, 2, 2), (1, 1, 4)])
def test_the_ring_is_bit_identical_after_k_steps(part, field):
    spec, ring, held, after = _ring_run(part)
    # an odd number of steps: the two wave fields have changed places
    was = held[1 if field == "prev" else 0]
    now = after[0 if field == "prev" else 1]
    off, b = spec.compute_offset(), spec.base
    seen = 0
    for iz in range(spec.dim.z):
        for iy in range(spec.dim.y):
            for ix in range(spec.dim.x):
                box = (slice(off.z - R, off.z + b.z + R),
                       slice(off.y - R, off.y + b.y + R),
                       slice(off.x - R, off.x + b.x + R))
                mask = ring[iz * b.z:iz * b.z + b.z + 2 * R,
                            iy * b.y:iy * b.y + b.y + 2 * R,
                            ix * b.x:ix * b.x + b.x + 2 * R]
                w = was[iz, iy, ix][box][mask]
                n = now[iz, iy, ix][box][mask]
                assert np.array_equal(w.view(np.uint32), n.view(np.uint32))
                assert (n == 7.0).all()
                seen += int(mask.sum())
    assert seen > 0


def _fixed_domain(part, periodic, faces_only=False, radius=None, z=32):
    dd = DistributedDomain(32, 32, z)
    dd.set_radius(radius or Radius.face_edge_corner(R, 0, 0))
    dd.set_boundary(periodic=periodic, faces_only=faces_only)
    dd.set_devices(jax.devices()[:Dim3(*part).flatten()])
    dd.set_partition(part)
    return dd


@pytest.mark.parametrize("faces_only", [False, True])
def test_a_fixed_split_axis_never_delivers_the_wrap(faces_only):
    """z split four ways and fixed: a sentinel in the last block's top
    planes reaches no one (periodic, it would fill block 0's low halo);
    the inner halos hold their neighbours' planes and the two outer ones
    what they held."""
    dd = _fixed_domain((1, 1, 4), (False, False, False), faces_only, z=64)
    h = dd.add_data("u")
    dd.realize()
    spec = dd.spec
    off, b = spec.compute_offset(), spec.base
    arr = np.full(spec.stacked_shape_zyx(), -1.0, np.float32)   # halos: -1
    for iz in range(4):
        arr[iz, 0, 0, off.z:off.z + b.z, off.y:off.y + b.y,
            off.x:off.x + b.x] = 10.0 * iz + np.arange(b.z)[:, None, None]
    arr[3, 0, 0, off.z + b.z - R:off.z + b.z, off.y:off.y + b.y,
        off.x:off.x + b.x] = 999.0                               # sentinel
    dd.set_curr(h, jax.device_put(arr, dd.sharding()))
    dd.exchange()
    got = np.asarray(dd.get_curr(h))
    own = (slice(off.y, off.y + b.y), slice(off.x, off.x + b.x))
    assert not (got[:3] == 999.0).any(), "the wrap was delivered"
    assert (got[0, 0, 0, off.z - R:off.z][(slice(None),) + own] == -1.0).all()
    assert (got[3, 0, 0, off.z + b.z:off.z + b.z + R][(slice(None),) + own]
            == -1.0).all()
    for iz in range(1, 4):
        low = got[iz, 0, 0, off.z - R:off.z][(slice(None),) + own]
        want = 10.0 * (iz - 1) + np.arange(b.z - R, b.z)[:, None, None]
        assert np.array_equal(low, np.broadcast_to(want, low.shape))
    for iz in range(3):
        high = got[iz, 0, 0, off.z + b.z:off.z + b.z + R][(slice(None),) + own]
        want = 10.0 * (iz + 1) + np.arange(R)[:, None, None]
        assert np.array_equal(high, np.broadcast_to(want, high.shape))
    # x and y have one block each and are fixed: nothing of theirs moved
    assert (got[:, 0, 0, :, :off.y] == -1.0).all()
    assert (got[:, 0, 0, :, :, :off.x] == -1.0).all()
    plan = dd.halo_exchange.plan
    assert [p.axis for p in plan.axis_phases] == ["z"]
    (phase,) = plan.axis_phases
    assert phase.fwd == ((0, 1), (1, 2), (2, 3))
    assert phase.bwd == ((1, 0), (2, 1), (3, 2))
    assert bool(phase.trim) == faces_only


def test_one_axis_fixed_and_the_others_periodic():
    """Periodicity is per axis: y fixed and split, z periodic and split."""
    dd = _fixed_domain((1, 2, 2), (True, False, True),
                       radius=Radius.constant(2))
    dd.add_data("u")
    dd.realize()
    phases = {p.axis: p for p in dd.halo_exchange.plan.axis_phases}
    assert sorted(phases) == ["x", "y", "z"]
    assert phases["y"].fwd == ((0, 1),) and phases["y"].bwd == ((1, 0),)
    assert phases["z"].fwd == ((0, 1), (1, 0))
    assert not phases["y"].periodic and phases["z"].periodic
    assert phases["y"].wire_cells * 2 == build_plan(
        dd.spec, (1, 2, 2), "axis-composed").axis_phases[1].wire_cells


def test_quantities_not_exchanged_keep_their_halos_and_cost_no_bytes():
    dd = _fixed_domain((1, 2, 2), (True, True, True),
                       radius=Radius.constant(2))
    a = dd.add_data("a")
    c = dd.add_data("coeff", exchanged=False, buffered=False)
    dd.realize()
    assert dd.is_exchanged(a) and not dd.is_exchanged(c)
    with pytest.raises(KeyError):
        dd.get_next(c)
    fill = fields.make_fill(dd.spec, dd.sharding())
    words = fields.seed_words(SEED)
    dd.set_curr(a, fill(words, np.uint32(0)))
    dd.set_curr(c, fill(words, np.uint32(1)))
    before = np.asarray(dd.get_curr(c))
    dd.exchange()
    assert np.array_equal(before, np.asarray(dd.get_curr(c)))
    wrong, halo = fields.make_halo_check(dd.spec, dd.sharding())(
        dd.get_curr(a), words, np.uint32(0))
    assert int(wrong) == 0 and int(halo) > 0
    ex = dd.halo_exchange
    assert dd.exchange_bytes_moved() == ex.bytes_moved([4])
    assert dd.exchange_bytes_for_method(dd._method) == ex.bytes_logical([4])
    assert ex.bytes_logical([4, 4]) == 2 * ex.bytes_logical([4])
    dd.swap()                   # passes the unbuffered quantity by
    assert np.array_equal(before, np.asarray(dd.get_curr(c)))


def test_byte_counts_of_the_fixed_faces_only_exchange():
    ex = _exchange((1, 2, 2), n=(2048, 1024, 1024))
    # a chip has one neighbour an axis: one y slab of 8 x 1024 x 1016 and
    # one z slab of 8 x 504 x 1024 cells (ISSUE 35), four chips
    y, z = 8 * 1024 * 1016, 8 * 504 * 1024
    assert ex.plan.wire_bytes([4]) == 4 * 4 * (y + z)
    assert ex.bytes_moved([4]) == 4 * 4 * (y + z)
    assert ex.bytes_logical([4]) == 4 * 4 * (8 * 1008 * 1016 + 8 * 504 * 1008)
    share = ex.plan.wire_bytes([4]) / ops.halo_bytes_if_all(ex, 4) / 4
    assert 0.1 < share < 0.25


@pytest.mark.parametrize("method", ["direct26", "auto-spmd"])
def test_other_methods_refuse_what_they_do_not_lower(method):
    from stencil_tpu.parallel import Method

    spec = GridSpec(Dim3(32, 32, 32), Dim3(1, 1, 2), Radius.constant(2))
    mesh = grid_mesh(spec.dim, jax.devices()[:2])
    with pytest.raises(ValueError, match="axis-composed"):
        HaloExchange(spec, mesh, Method(method), periodic=(True, True, False))
    with pytest.raises(ValueError, match="star"):
        HaloExchange(spec, mesh, faces_only=True)


def test_a_domain_that_says_nothing_builds_the_plan_it_built():
    """Defaults: periodic on every axis, every quantity exchanged, edges
    and corners carried; the plan of a silent domain is the plan of one
    that spells the defaults out, wrap pairs and full padded slabs."""
    spec = GridSpec(Dim3(64, 64, 64), Dim3(2, 2, 2), Radius.constant(3))
    said = build_plan(spec, (2, 2, 2), "axis-composed",
                      periodic=(True, True, True), faces_only=False)
    silent = build_plan(spec, (2, 2, 2), "axis-composed")
    assert said == silent
    p = spec.padded()
    for ph, orth in zip(silent.axis_phases, (p.y * p.z, p.x * p.z, p.x * p.y)):
        assert ph.periodic and ph.trim == ()
        assert ph.fwd == ((0, 1), (1, 0)) and ph.bwd == ((0, 1), (1, 0))
        assert ph.wire_cells == 6 * orth * 8
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    ex = HaloExchange(spec, mesh)
    assert ex.bytes_moved([4]) == sum(
        ph.wire_cells + ph.local_cells for ph in silent.axis_phases) * 4
    like = jax.ShapeDtypeStruct(spec.stacked_shape_zyx(), jnp.float32,
                                sharding=ex.sharding())
    text = ex._compiled.lower({0: like}).as_text()
    # what the fixed path adds is absent: nothing asks which block it is
    assert "partition_id" not in text
    assert text.count("collective_permute") == 6


def test_step_plan_counter_says_what_the_step_exchanges():
    from stencil_tpu.obs import scopes, telemetry

    ex = _exchange((1, 2, 2))
    ops.make_iso3dfd_step(ex, iters=2)
    plan = telemetry.get().records(kind="counter",
                                   name="iso3dfd.step_plan")[-1]
    assert plan["module"] == scopes.ISO3DFD_LOOP and plan["value"] == 2
    assert (plan["blocks"], plan["radius"], plan["quantities"],
            plan["exchanged"], plan["chunk"]) == (4, 8, 3, 1, 2)
    assert plan["periodic"] == [False] * 3 and plan["faces_only"] is True
    assert plan["block_cells"] == 32 * 16 * 16
    assert plan["halo_bytes_sent"] == ex.plan.wire_bytes([4]) // 4
    assert plan["halo_bytes_if_all"] > 5 * plan["halo_bytes_sent"]
