"""NPB MG at small sizes on the CPU: every operator of the iteration on
every level size against the plain numpy reference of the benchmark, on
one, four and eight blocks, every owned cell; whole iterations at class S
in float32 and float64; the published class-S norm; the box kernel
(interpreted); the coarse half as one call, alone and in whole iterations,
every held cell; corners; the lowered iteration's scopes."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import mg as reference
from stencil_tpu.apps import mg as app
from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.obs import scopes, telemetry
from stencil_tpu.ops import mg as ops
from stencil_tpu.ops.pallas_mg import (box_supported, coarse_supported,
                                       make_pallas_mg_box,
                                       make_pallas_mg_coarse,
                                       make_pallas_mg_interp,
                                       make_pallas_mg_rprj3,
                                       transfer_supported)
from stencil_tpu.parallel import HaloExchange, grid_mesh
from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks
from stencil_tpu.parallel.mesh import BLOCK_PSPEC

SEED = 4_000_000_007
PARTS = [(1, 1, 1), (1, 2, 2), (2, 2, 2)]
N = 32                                  # class S: levels 32, 16, 8, 4, 2


@functools.lru_cache(maxsize=None)
def _exchanges(part, n=N, faces_only=False):
    d = Dim3(*part)
    mesh = grid_mesh(d, jax.devices()[:d.flatten()])
    # a faces-only plan is a star's: the plan layer itself refuses one whose
    # radius has an edge or corner direction set
    star = Radius.face_edge_corner(1, 0, 0)
    return tuple(
        HaloExchange(GridSpec(Dim3(m, m, m), d,
                              star if faces_only else ops.level_radius(m, d)),
                     mesh, faces_only=faces_only)
        for m in ops.level_sizes(n))


def _operator(exchanges, name, k, smoother=ops.S_LARGE, dtype="float32"):
    """ONE operator of the iteration with the fill that follows it, alone:
    ``name`` on NPB's level ``k`` of the hierarchy ``exchanges`` (finest
    first), over stacked sharded arrays with valid halos. ``resid``:
    ``fn(u_k, r_k) -> r_k``; ``resid_v`` (finest level): ``fn(u, v, r) ->
    r``; ``psinv``: ``fn(r_k, u_k) -> u_k`` (level 1: ``u = S r``);
    ``rprj3``: ``fn(r_k, r_below) -> r_below``; ``interp``: ``fn(u_below,
    u_k) -> u_k``."""
    levels, built, _ = ops._build(exchanges, smoother, jnp.dtype(dtype),
                                  None, False)
    op = built[(k, name)]
    out = len(levels) - k + (1 if name == "rprj3" else 0)   # level written

    def body(*arrays):
        if name == "psinv" and k == 1:
            new = op(arrays[0], None, arrays[1])
        elif name in ("resid", "psinv"):
            new = op(arrays[0], arrays[1], arrays[1])
        else:
            new = op(*arrays)
        return levels[out].ex.exchange_block(new)

    return jax.jit(jax.shard_map(
        body, mesh=levels[0].ex.mesh,
        in_specs=(BLOCK_PSPEC,) * (3 if name == "resid_v" else 2),
        out_specs=BLOCK_PSPEC))


def _random(m, salt, dtype):
    rng = np.random.RandomState(1000 * m + salt)
    return rng.uniform(-1.0, 1.0, (m, m, m)).astype(dtype)


def _held(ex, global_zyx):
    """A whole level as its domain holds it, halos valid."""
    return ex({0: shard_blocks(global_zyx, ex.spec, ex.mesh)})[0]


def _halos_are_the_wrap(ex, arr):
    """Filling the halos again changes nothing: every halo cell, faces,
    edges and corners, already held the periodic wrap."""
    before = np.asarray(arr)
    again = ex({0: arr})[0]             # donates arr
    np.testing.assert_array_equal(np.asarray(again), before)


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("part", PARTS, ids=str)
@pytest.mark.parametrize("k", [5, 4, 3, 2, 1])
def test_every_operator_on_every_level_against_the_reference(k, part, dtype):
    """Level k of the class-S hierarchy (32^3 .. 2^3), blocks halving with
    the level down to one cell a block: each operator alone with its fill,
    every owned cell against the float64 reference, every halo the wrap."""
    exs = _exchanges(part)
    top = len(exs)
    ex = exs[top - k]
    m = ex.spec.global_size.x
    tol = 1e-13 if dtype == "float64" else 2e-6
    make = functools.partial(_operator, exs, dtype=dtype,
                             smoother=ops.S_SMALL)
    a, b, c = (_random(m, salt, dtype) for salt in (1, 2, 3))
    a64, b64, c64 = (x.astype(np.float64) for x in (a, b, c))
    cases = {}
    below = exs[top - k + 1] if k > 1 else None
    if k == 1:
        cases["psinv"] = ((a, b), reference.psinv(
            reference.grow(a64), np.zeros_like(b64), reference.S_SMALL))
    else:
        cases["psinv"] = ((a, b), reference.psinv(
            reference.grow(a64), b64, reference.S_SMALL))
        cases["resid"] = ((a, b), reference.resid(reference.grow(a64), b64))
        below = exs[top - k + 1]
        small = _random(m // 2, 4, dtype)
        cases["rprj3"] = ((a, small), reference.rprj3(reference.grow(a64)))
        prolonged = reference.interp(reference.grow(small.astype(np.float64)))
        cases["interp"] = ((small, b),
                           b64 + prolonged if k == top else prolonged)
    if k == top:
        cases["resid_v"] = ((a, b, c),
                            reference.resid(reference.grow(a64), b64))
    for name, (args, want) in cases.items():
        out_ex = below if name == "rprj3" else ex
        levels = {"rprj3": (ex, below), "interp": (below, ex)}.get(
            name, (ex,) * len(args))
        got = make(name, k)(*(_held(e, x) for e, x in zip(levels, args)))
        np.testing.assert_allclose(
            unshard_blocks(got, out_ex.spec), want, rtol=0, atol=tol,
            err_msg=f"{name} on level {k} ({m}^3) over {part}")
        _halos_are_the_wrap(out_ex, got)        # last: it donates got


def _class_s(dtype, part, nit=None):
    d = Dim3(*part)
    return app.run(klass="S", nit=nit, dtype=dtype, partition=part,
                   devices=jax.devices()[:d.flatten()])


@functools.lru_cache(maxsize=None)
def _reference_class_s(nit):
    n, _, s, _ = reference.CLASSES["S"]
    plus, minus = reference.zran3(n)
    return reference.run(n, nit, s, reference.charges_field(n, plus, minus))


def test_the_reference_gives_npbs_published_class_s_norm():
    n, nit, _, published = reference.CLASSES["S"]
    assert (n, nit, published) == (32, 4, 0.5307707005734e-04)
    _, _, norm = _reference_class_s(nit)
    assert abs(norm - published) / published < reference.VERIFY_EPSILON
    # the twenty charges are the ten largest and ten smallest draws
    plus, minus = reference.zran3(n)
    field = reference.lcg_field(n)
    assert min(field[c] for c in plus) > np.sort(field.ravel())[-11]
    assert max(field[c] for c in minus) < np.sort(field.ravel())[10]
    assert sorted(app.zran3(n)[0]) == sorted(plus)
    assert sorted(app.zran3(n)[1]) == sorted(minus)


@pytest.mark.parametrize("part", PARTS, ids=str)
@pytest.mark.parametrize("nit", [1, 4])
def test_class_s_in_float64_is_the_references_norm(nit, part):
    r = _class_s("float64", part, nit)
    u, res, norm = _reference_class_s(nit)
    assert abs(r["rnm2"] - norm) / norm < 1e-12
    dd, hs = r["levels"][0]
    np.testing.assert_allclose(unshard_blocks(dd.get_curr(hs["u"]), dd.spec),
                               u, rtol=0, atol=1e-15)
    np.testing.assert_allclose(unshard_blocks(dd.get_curr(hs["r"]), dd.spec),
                               res, rtol=0, atol=1e-15)
    if nit == 4:
        assert r["verified"] is True and r["published"] == 0.5307707005734e-04
        assert app.csv_row(r).endswith(",yes")


@pytest.mark.parametrize("part", PARTS, ids=str)
@pytest.mark.parametrize("nit", [1, 4])
def test_class_s_in_float32_follows_the_reference(nit, part):
    r = _class_s("float32", part, nit)
    u, res, norm = _reference_class_s(nit)
    assert abs(r["rnm2"] - norm) / norm < 2e-3
    dd, hs = r["levels"][0]
    # u's cells reach 0.3, the residual's 4e-4 after four iterations
    np.testing.assert_allclose(unshard_blocks(dd.get_curr(hs["u"]), dd.spec),
                               u, rtol=0, atol=5e-7)
    np.testing.assert_allclose(unshard_blocks(dd.get_curr(hs["r"]), dd.spec),
                               res, rtol=0, atol=2e-6)
    assert r["verified"] is (None if nit == 1 else False) or r["verified"]


@pytest.mark.parametrize("part, refused", [
    ((1, 2, 4), "along z"), ((4, 2, 1), "along x"),
    ((2, 1, 1), None), ((1, 1, 2), None),
], ids=str)
def test_class_s_on_the_splits_the_table_above_leaves_out(part, refused):
    """One axis split alone equals the reference as the even splits do; a
    split the coarsest level (2^3) cannot take is refused by name before
    anything is realized."""
    if refused:
        with pytest.raises(ValueError,
                           match=f"level 2\\^3 does not split.*{refused}"):
            _class_s("float64", part, 4)
        return
    r = _class_s("float64", part, 4)
    u, res, norm = _reference_class_s(4)
    assert abs(r["rnm2"] - norm) / norm < 1e-12 and r["verified"] is True
    dd, hs = r["levels"][0]
    assert tuple(dd.spec.dim) == part
    np.testing.assert_allclose(unshard_blocks(dd.get_curr(hs["u"]), dd.spec),
                               u, rtol=0, atol=1e-15)


def test_run_takes_a_class_or_a_size_and_says_which_it_cannot_split():
    with pytest.raises(ValueError, match="a class or a size"):
        app.run()
    with pytest.raises(ValueError, match="a class or a size"):
        app.run(klass="S", n=32)
    with pytest.raises(ValueError, match="power of two"):
        app.run(n=48)
    with pytest.raises(ValueError, match="does not split"):
        app.run(n=8, partition=(1, 1, 4), devices=jax.devices()[:4])
    r = app.run(n=8, nit=2, partition=(1, 1, 2), devices=jax.devices()[:2])
    assert r["iters_run"] == 2 and r["published"] is None
    assert r["verified"] is None and len(r["levels"]) == 3


def _seeded_state(exs, seed, dtype="float32"):
    """The benchmark's seeded state on a hierarchy, and its charges."""
    n = exs[0].spec.global_size.x
    plus, minus = reference.seeded_charges(seed, n)
    state = {"u": [], "r": []}
    for i, ex in enumerate(exs):
        m = ex.spec.global_size.x
        for q, name in enumerate(("u", "r")):
            level = (reference.seeded_level(seed, q, m, dtype) if i == 0
                     else np.zeros((m, m, m), dtype))
            state[name].append(_held(ex, level))
    v = _held(exs[0], reference.charges_field(n, plus, minus, dtype))
    return state, v


def test_two_hundred_iterations_in_float32_stay_at_a_finite_fixed_point():
    """v sums to 0 exactly and A's weights sum to 0, so no mode grows: from
    the benchmark's dense seeded state the residual falls to float32's
    floor and stays there, and u stops moving."""
    exs = _exchanges((1, 1, 1))
    state, v = _seeded_state(exs, SEED)
    step = ops.make_mg_iter(exs, smoother=ops.S_LARGE, iters=10)
    norms = []
    for _ in range(20):
        before = np.asarray(state["u"][0])
        state = step(state, v)
        r = unshard_blocks(state["r"][0], exs[0].spec)
        norms.append(reference.norm2u3(r))
        moved = float(np.max(np.abs(np.asarray(state["u"][0]) - before)))
    # a dense start sheds a factor of about 12 every ten iterations
    assert all(np.isfinite(norms)) and norms[0] < 0.1
    assert norms[5] < 1e-6 and max(norms[10:]) < 1e-6 and moved < 1e-5
    for q in ("u", "r"):
        for a in state[q]:
            assert bool(jnp.all(jnp.isfinite(a)))


def test_a_faces_only_plan_is_refused_and_would_come_out_wrong():
    """The box reads edges and corners. The plan layer refuses faces-only
    slabs under MG's radius (edges and corners set); a hierarchy on a
    star's faces-only exchanges is refused by the builder; and the same
    operator over halos that such an exchange filled differs from the
    reference exactly where a cell reads a corner or an edge across a
    block boundary or the wrap."""
    d = Dim3(1, 2, 2)
    with pytest.raises(ValueError, match="star stencil"):
        HaloExchange(GridSpec(Dim3(N, N, N), d, ops.level_radius(N, d)),
                     grid_mesh(d, jax.devices()[:4]), faces_only=True)
    with pytest.raises(ValueError, match="26 neighbours"):
        ops.make_mg_iter(_exchanges((1, 2, 2), faces_only=True))
    full, star = _exchanges((1, 2, 2))[0], _exchanges(
        (1, 2, 2), faces_only=True)[0]
    u, v = _random(N, 1, "float64"), _random(N, 2, "float64")
    op = _operator(_exchanges((1, 2, 2)), "resid", 5, dtype="float64")
    want = reference.resid(reference.grow(u), v)
    good = unshard_blocks(op(_held(full, u), _held(full, v)), full.spec)
    bad = unshard_blocks(op(_held(star, u), _held(full, v)), full.spec)
    np.testing.assert_allclose(good, want, rtol=0, atol=1e-13)
    wrong = np.abs(bad - want) > 1e-6
    assert wrong.any()
    # only cells on a block's y or z boundary read an edge or a corner
    inner = np.ones_like(wrong)
    for axis, blocks in ((0, 2), (1, 2)):
        edge = np.zeros(N, bool)
        size = N // blocks
        edge[::size] = edge[size - 1::size] = True
        inner &= ~edge.reshape([-1 if a == axis else 1 for a in range(3)])
    assert not wrong[inner].any()


def _tight_spec(nz=6, ny=16):
    return GridSpec(Dim3(128, ny, nz), Dim3(1, 1, 1),
                    Radius.constant(1).without_x())


@pytest.mark.parametrize("case", [
    ("mg_resid", ops.A, -1.0, False), ("mg_resid", ops.A, -1.0, True),
    ("mg_psinv", ops.S_LARGE, 1.0, False),
    ("mg_psinv", (0.3, 0.2, 0.1, 0.05), 1.0, False)], ids=str)
def test_the_box_kernel_interpreted_against_the_reference(case):
    """One builder for both operators: ``p +- Box(q)`` on a tight-x block
    (x wraps by a lane roll), every class weighted, in place or into a
    third array; halo planes keep what they held."""
    name, w, sign, separate = case
    spec = _tight_spec()
    assert box_supported(spec, jnp.float32)
    pz, py, px = spec.block_shape_zyx()
    rng = np.random.RandomState(7)
    q, p = (rng.uniform(-1, 1, (pz, py, px)).astype(np.float32)
            for _ in range(2))
    dst = np.full((pz, py, px), 7.0, np.float32)
    fn = make_pallas_mg_box(spec, name, w, sign, separate_dst=separate,
                            interpret=True)
    out = np.asarray(fn(q, p, dst) if separate else fn(q, p))
    o, b = spec.compute_offset(), spec.base
    rows = q[o.z - 1:o.z + b.z + 1, o.y - 1:o.y + b.y + 1].astype(np.float64)
    rows = np.concatenate([rows[:, :, -1:], rows, rows[:, :, :1]], axis=2)
    own = (slice(o.z, o.z + b.z), slice(o.y, o.y + b.y))
    want = p[own] + sign * reference.box27(rows, w)
    np.testing.assert_allclose(out[own], want, rtol=0, atol=2e-6)
    kept = dst if separate else p
    np.testing.assert_array_equal(out[0], kept[0])
    np.testing.assert_array_equal(out[-1], kept[-1])


def _block(spec, owned):
    """One padded block holding ``owned`` with its y and z halos wrapped
    and NaN in every other allocated cell."""
    o, b = spec.compute_offset(), spec.base
    a = np.full(spec.block_shape_zyx(), np.nan, np.float32)
    a[o.z - 1:o.z + b.z + 1, o.y - 1:o.y + b.y + 1, :] = np.pad(
        owned, ((1, 1), (1, 1), (0, 0)), mode="wrap")
    return a


def _owned_of(spec, a):
    o, b = spec.compute_offset(), spec.base
    return a[o.z:o.z + b.z, o.y:o.y + b.y, :]


def test_the_transfer_kernels_interpreted_against_the_reference():
    """``rprj3`` and ``interp`` between two tight-x blocks: planes along z,
    rows at a stride of 2 along y, a bfloat16 matrix product in three
    pieces along x (with the wrap in the matrix), float32 all the same."""
    tight = Radius.constant(1).without_x()
    fine = GridSpec(Dim3(256, 16, 8), Dim3(1, 1, 1), tight)
    coarse = GridSpec(Dim3(128, 8, 4), Dim3(1, 1, 1), tight)
    assert transfer_supported(fine, coarse, jnp.float32)
    assert not transfer_supported(fine, fine, jnp.float32)
    rng = np.random.RandomState(3)
    gf = rng.uniform(-1, 1, (8, 16, 256)).astype(np.float32)
    gc = rng.uniform(-1, 1, (4, 8, 128)).astype(np.float32)
    out = make_pallas_mg_rprj3(fine, coarse, interpret=True)(
        _block(fine, gf), np.zeros(coarse.block_shape_zyx(), np.float32))
    np.testing.assert_allclose(
        _owned_of(coarse, np.asarray(out)),
        reference.rprj3(reference.grow(gf.astype(np.float64))),
        rtol=0, atol=5e-7)
    prolonged = reference.interp(reference.grow(gc.astype(np.float64)))
    for add in (False, True):
        old = np.nan_to_num(_block(fine, gf)) if add else np.zeros(
            fine.block_shape_zyx(), np.float32)
        out = make_pallas_mg_interp(coarse, fine, add, interpret=True)(
            _block(coarse, gc), jnp.asarray(old))
        np.testing.assert_allclose(
            _owned_of(fine, np.asarray(out)),
            prolonged + (gf if add else 0.0), rtol=0, atol=5e-7)


def test_the_box_kernel_takes_tight_x_fp32_blocks_only():
    assert not box_supported(_tight_spec(), jnp.float64)
    inline = GridSpec(Dim3(128, 16, 6), Dim3(1, 1, 1), Radius.constant(1))
    assert not box_supported(inline, jnp.float32)
    assert not box_supported(_tight_spec(ny=12), jnp.float32)
    with pytest.raises(ValueError, match="not a box operator"):
        make_pallas_mg_box(_tight_spec(), "mg_rprj3", ops.A, 1.0)
    assert ops.level_radius(128, (1, 2, 2)).x(1) == 0
    assert ops.level_radius(128, (2, 1, 1)).x(1) == 1
    assert ops.level_radius(64, (1, 1, 1)).x(1) == 1


def test_a_tight_x_level_runs_the_kernel_and_an_iteration_matches():
    """128 x 128 x 128 is the smallest hierarchy with a tight-x level: the
    top level takes the (interpreted) box kernel, the six below it and both
    transfers to and from them are the coarse call's, and one iteration
    from the seeded state is the reference's on sampled boxes."""
    exs = _exchanges((1, 1, 1), n=128)
    step = ops.make_mg_iter(exs, use_pallas=True, interpret=True)
    plan = telemetry.get().records(kind="counter", name="mg.cycle_plan")[-1]
    top = plan["levels"][0]
    assert top["layout"] == "tight_x" and top["grid"] == [128] * 3
    assert top["operators"]["mg_resid"]["impl"] == "pallas"
    assert top["operators"]["mg_psinv"]["impl"] == "pallas"
    assert top["operators"]["mg_rprj3"]["impl"] == "resident"
    assert [lv["layout"] for lv in plan["levels"][1:]] == ["inline"] * 6
    state, v = _seeded_state(exs, SEED)
    state = step(state, v)
    origins = [(120, 120, 120), (0, 0, 0), (37, 5, 90)]
    core = (8, 8, 8)
    want = reference.first_iteration_boxes(SEED, 128, reference.S_LARGE,
                                           origins, core)
    for q in ("u", "r"):
        got = unshard_blocks(state[q][0], exs[0].spec)
        for o, ref in zip(origins, want):
            np.testing.assert_allclose(reference._take(got, o, core), ref[q],
                                       rtol=0, atol=4e-6)


_FILL = ("stencil.halo.", "stencil.kernel.self_fill")


def _every_held_cell(spec, a):
    """A block's owned cells with the halo shell round them (what an
    exchange fills): every cell an operator may read."""
    o, b, r = spec.compute_offset(), spec.base, spec.radius
    return np.asarray(a)[0, 0, 0][o.z - 1:o.z + b.z + 1,
                                  o.y - 1:o.y + b.y + 1,
                                  o.x - r.x(-1):o.x + b.x + r.x(1)]


def _wrapped(spec, level):
    """A whole periodic level as ``_every_held_cell`` cuts a block."""
    x = spec.radius.x(1)
    return np.pad(level, ((1, 1), (1, 1), (x, x)), mode="wrap")


@pytest.mark.parametrize("add", [False, True], ids=["over", "added"])
def test_the_coarse_kernel_interpreted_against_the_reference(add):
    """The levels 64^3 .. 2^3 under a 128^3 block as ONE call: ``rprj3`` of
    the level above's r down to 2^3, ``psinv`` there, ``interp``, ``resid``,
    ``psinv`` a level back up and ``interp`` onto the level above's u
    (``add``: as on a hierarchy's finest level). Every level comes back
    with every owned cell the reference's and every halo cell the wrap,
    from slots that held NaN: nothing of them is read."""
    specs = [ex.spec for ex in _exchanges((1, 1, 1), n=128)]
    above, below = specs[0], specs[1:]
    assert coarse_supported(above, below, jnp.float32)
    assert not coarse_supported(above, below, jnp.float64)
    assert not coarse_supported(above, below[1:], jnp.float32)
    r_top, u_top = (_random(128, salt, "float32") for salt in (1, 2))
    nan = [np.full(s.block_shape_zyx(), np.nan, np.float32) for s in below]
    fn = make_pallas_mg_coarse(above, below, ops.A, ops.S_LARGE, add,
                               interpret=True)
    u_out, us, rs = fn(_block(above, r_top), np.nan_to_num(_block(above, u_top)),
                       nan, nan)
    want_r = reference.down(r_top.astype(np.float64))
    want_u, want_r = reference.up(want_r, reference.S_LARGE, to=1)
    for spec, u, r, wu, wr in zip(below, us, rs, want_u, want_r):
        for got, want in ((u, wu), (r, wr)):
            np.testing.assert_allclose(
                _every_held_cell(spec, np.asarray(got)[None, None, None]),
                _wrapped(spec, want), rtol=0, atol=2e-6,
                err_msg=f"{spec.base.x}^3")
            assert np.isfinite(np.asarray(got)).all()
    prolonged = reference.interp(reference.grow(want_u[0]))
    np.testing.assert_allclose(_owned_of(above, np.asarray(u_out)),
                               prolonged + (u_top if add else 0.0),
                               rtol=0, atol=2e-6)


_RESIDENT_ITERS = (1, 3)


@functools.lru_cache(maxsize=None)
def _resident_and_xla_iterations():
    """The 128^3 one-block hierarchy stepped from the seeded state by the
    program with the coarse call (the kernels interpreted), by the all-XLA
    program and by the float64 reference: per iteration count of
    ``_RESIDENT_ITERS`` the two states as numpy arrays, and the
    reference's ``(us, rs)``, finest first, every level."""
    exs = _exchanges((1, 1, 1), n=128)
    steps = {"resident": ops.make_mg_iter(exs, use_pallas=True,
                                          interpret=True),
             "xla": ops.make_mg_iter(exs, use_pallas=False)}
    states = {how: _seeded_state(exs, SEED) for how in steps}
    plus, minus = reference.seeded_charges(SEED, 128)
    v = reference.charges_field(128, plus, minus)
    u, r = (reference.seeded_level(SEED, q, 128) for q in (0, 1))
    out = {}
    for it in range(1, max(_RESIDENT_ITERS) + 1):
        for how, step in steps.items():
            state, held_v = states[how]
            states[how] = (step(state, held_v), held_v)
        # the reference's iteration with its coarse levels kept
        rs = reference.down(r)
        us, rs = reference.up(rs, reference.S_LARGE, to=1)
        u, r = reference.iteration(u, v, r, reference.S_LARGE)
        if it in _RESIDENT_ITERS:
            out[it] = (
                {how: jax.tree.map(np.asarray, state)
                 for how, (state, _) in states.items()},
                ([u] + us, [r] + rs))
    return out


@pytest.mark.parametrize("iters", _RESIDENT_ITERS)
def test_iterations_with_the_coarse_call_match_the_xla_path_and_the_reference(
        iters):
    """After one and after three iterations (a wrong wrap that only the
    NEXT cycle reads shows in the third): every owned and every halo cell
    of u and r of every level is the all-XLA program's to float32 rounding
    and the float64 reference's to the file's tolerance."""
    exs = _exchanges((1, 1, 1), n=128)
    states, (want_u, want_r) = _resident_and_xla_iterations()[iters]
    for q, wants in (("u", want_u), ("r", want_r)):
        for ex, got, xla, want in zip(exs, states["resident"][q],
                                      states["xla"][q], wants):
            m = ex.spec.global_size.x
            np.testing.assert_allclose(
                _every_held_cell(ex.spec, got), _every_held_cell(ex.spec, xla),
                rtol=0, atol=3e-6, err_msg=f"{q} of {m}^3 against XLA")
            np.testing.assert_allclose(
                _every_held_cell(ex.spec, got), _wrapped(ex.spec, want),
                rtol=0, atol=4e-6, err_msg=f"{q} of {m}^3 against float64")


def _plan_of(part, n, use_pallas):
    ops.make_mg_iter(_exchanges(part, n=n), use_pallas=use_pallas)
    return telemetry.get().records(kind="counter", name="mg.cycle_plan")[-1]


def test_the_cycle_plan_says_how_far_the_coarse_call_reaches():
    """Class C on one block, built and not run: the six levels under 128^3
    are resident, 23 of the 34 operator calls and 22 of the 34 fills are
    the coarse call's, and the HBM slots lie as they lay."""
    plan = _plan_of((1, 1, 1), 512, True)
    assert (plan["resident_levels"], plan["resident_calls"],
            plan["resident_fills"]) == (6, 23, 22)
    # u and r of 64^3 .. 2^3, one 128^3 block, matrices, widened planes
    assert 16 << 20 < plan["resident_vmem_bytes"] < 20 << 20
    levels = plan["levels"]
    assert [lv["resident"] for lv in levels] == [False] * 3 + [True] * 6
    assert [lv["layout"] for lv in levels] == ["tight_x"] * 3 + ["inline"] * 6
    for lv in levels[:3]:
        impls = {name: op["impl"] for name, op in lv["operators"].items()}
        between = "resident" if lv["level"] == 7 else "pallas"
        assert impls == {"mg_resid": "pallas", "mg_psinv": "pallas",
                         "mg_rprj3": between, "mg_interp": between}
    assert all(op["impl"] == "resident" for lv in levels[3:]
               for op in lv["operators"].values())
    assert sum(lv["fills_per_iter"] for lv in levels) == 34
    assert sum(op["calls_per_iter"] for lv in levels
               for op in lv["operators"].values()) == 34


@pytest.mark.parametrize("case", [
    ((1, 2, 2), 512, True, 3), ((1, 1, 1), 32, True, 0),
    ((1, 1, 1), 128, False, 0), ((1, 1, 1), 128, None, 0)], ids=str)
def test_a_split_partition_class_s_and_the_xla_path_build_todays_program(case):
    """The coarse call wants ONE block a level (a split partition's coarse
    blocks need the wire), a Pallas tight-x level above (class S has none;
    off a TPU the default builds none) and nothing else is asked: every
    other hierarchy builds the program it built before."""
    part, n, use_pallas, kernels = case
    plan = _plan_of(part, n, use_pallas)
    assert (plan["resident_levels"], plan["resident_calls"],
            plan["resident_fills"], plan["resident_vmem_bytes"]) == (0,) * 4
    for i, lv in enumerate(plan["levels"]):
        assert lv["resident"] is False
        for name, op in lv["operators"].items():
            box = name in ("mg_resid", "mg_psinv")
            pallas = i < kernels if box else i + 1 < kernels
            assert op["impl"] == ("pallas" if pallas else "xla"), (lv, name)


def test_the_one_block_iteration_holds_twelve_operator_calls_and_no_coarse_fill():
    """The class-C iteration on ONE block with the kernels, traced (nothing
    compiles, nothing runs): twelve Pallas operator calls, the eleven of
    the tight-x levels and the coarse call under level 6's tag between the
    last restriction above it and level 7's fill, and under the tags of
    levels 1 to 6 no equation of the halo layer and no other operator."""
    exs = _exchanges((1, 1, 1), n=512)
    step = ops.make_mg_iter(exs, use_pallas=True)
    args = scopes._registry[scopes.MG_ITER][-1]["args"]
    calls, coarse = [], []
    for name, prim in _scoped_equations(jax.make_jaxpr(step)(*args).jaxpr,
                                        primitives=True):
        level, parts = scopes.level_of(name), scopes.scopes_in(name)
        if level is None:
            continue
        kernel = parts[-1][len(scopes.KERNEL_PREFIX):] if parts[-1].startswith(
            scopes.KERNEL_PREFIX) else None
        if prim == "pallas_call" and kernel and kernel.startswith("mg_"):
            calls.append((level, kernel))
        if level <= 6:
            assert not parts[-1].startswith(_FILL), name
            coarse.append((kernel, parts[-1]))
    want = [(9, "mg_rprj3"), (8, "mg_rprj3"), (6, "mg_coarse")]
    for k in (7, 8, 9):
        want += [(k, "mg_interp")] if k > 7 else []
        want += [(k, "mg_resid"), (k, "mg_psinv")]
    want += [(9, "mg_resid")]
    assert len(want) == 12 and calls == want
    # under the coarse levels' tags: the one call and the reshapes round it
    assert {c for c in coarse if c[0]} == {("mg_coarse",
                                           "stencil.kernel.mg_coarse")}
    assert {c[1] for c in coarse if not c[0]} == {scopes.CARRY}



def _scoped_equations(jaxpr, outer="", primitives=False):
    """The name stack of every equation in program order (``primitives``:
    with its primitive's name), nested programs (the jit, the shard_map)
    walked in place."""
    from jax._src import core

    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        inner = list(core.jaxprs_in_params(eqn.params))
        if inner and eqn.primitive.name != "pallas_call":
            for sub in inner:
                yield from _scoped_equations(sub, stack, primitives)
        else:
            yield (stack, eqn.primitive.name) if primitives else stack


def test_the_lowered_iteration_holds_34_operators_each_with_its_fill():
    """The class-C iteration traced for the CPU mesh on (1,2,2) (nothing
    compiles, nothing runs): in program order 34 operator regions under a
    level tag, the source's count and order for nine levels, and after
    each of them equations of the halo layer on the level it wrote."""
    exs = _exchanges((1, 2, 2), n=512)
    step = ops.make_mg_iter(exs)
    args = scopes._registry[scopes.MG_ITER][-1]["args"]
    seq = []
    for name in _scoped_equations(jax.make_jaxpr(step)(*args).jaxpr):
        level = scopes.level_of(name)
        parts = scopes.scopes_in(name)
        if level is None:
            continue
        kind = ("fill" if parts[-1].startswith(_FILL)
                else parts[-1][len("stencil.kernel."):]
                if parts[-1].startswith("stencil.kernel.mg_") else None)
        if kind and (not seq or seq[-1] != (level, kind)):
            seq.append((level, kind))
    ops_seen = [s for s in seq if s[1] != "fill"]
    want = [(k, "mg_rprj3") for k in range(9, 1, -1)] + [(1, "mg_psinv")]
    for k in range(2, 10):
        want += [(k, "mg_interp"), (k, "mg_resid"), (k, "mg_psinv")]
    want += [(9, "mg_resid")]
    assert len(want) == 34 and ops_seen == want
    # one fill after each, on the level the operator wrote
    for i, (level, kind) in enumerate(seq):
        if kind == "fill":
            continue
        wrote = level - 1 if kind == "mg_rprj3" else level
        assert seq[i + 1] == (wrote, "fill"), (i, seq[i:i + 2])
    plan = telemetry.get().records(kind="counter", name="mg.cycle_plan")[-1]
    assert sum(lv["fills_per_iter"] for lv in plan["levels"]) == 34
    assert sum(op["calls_per_iter"] for lv in plan["levels"]
               for op in lv["operators"].values()) == 34
    assert [lv["layout"] for lv in plan["levels"]] == \
        ["tight_x"] * 3 + ["inline"] * 6
    assert plan["levels"][0]["operators"]["mg_resid"]["bytes_min"] == \
        3 * 4 * 512 * 256 * 256
