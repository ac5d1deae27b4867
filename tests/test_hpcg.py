"""HPCG: the program's iteration against the benchmark's float64 reference
(``benchmark/reference/hpcg.py``), the reference against HPCG's stored
matrix and lexicographic sweep, the box kernel on a fixed x, and what the
program says of itself (its plan counters, its lowered module)."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import hpcg as ref  # noqa: E402
from stencil_tpu.apps import hpcg as app  # noqa: E402
from stencil_tpu.domain.grid import GridSpec  # noqa: E402
from stencil_tpu.geometry import Dim3, Radius  # noqa: E402
from stencil_tpu.obs import scopes, telemetry  # noqa: E402
from stencil_tpu.ops import hpcg as ops  # noqa: E402
from stencil_tpu.ops import mg as ops_mg  # noqa: E402
from stencil_tpu.ops.pallas_hpcg import (WEIGHTS, make_pallas_hpcg_prolong,  # noqa: E402
                                         make_pallas_hpcg_restrict,
                                         make_pallas_hpcg_spmv,
                                         make_pallas_hpcg_symgs, symgs_plan)
from stencil_tpu.ops.pallas_mg import make_pallas_mg_box  # noqa: E402

TIGHT = (128, 16, 16)       # x, y, z: the finest level alone is tight-x
# the two finest levels are tight-x: a plane of 16 owned rows, and of 32
TWO_TIGHT = {"16_rows": (256, 16, 16), "32_rows": (256, 32, 16)}


@pytest.fixture
def x64_off():
    """Interpreted kernels are traced as the chip's: x64 off (the session
    turns it on)."""
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", was)


def _owned(dd, a):
    o, b = dd.spec.compute_offset(), dd.spec.base
    return np.asarray(a)[0, 0, 0][o.z:o.z + b.z, o.y:o.y + b.y,
                                  o.x:o.x + b.x]


def _held(dd, a):
    o, b = dd.spec.compute_offset(), dd.spec.base
    out = np.zeros(dd.spec.stacked_shape_zyx(), np.float32)
    out[0, 0, 0][o.z:o.z + b.z, o.y:o.y + b.y, o.x:o.x + b.x] = a
    return jax.device_put(out, dd.sharding())


def _ring_max(dd, a):
    a = np.array(a)[0, 0, 0]
    o, b = dd.spec.compute_offset(), dd.spec.base
    a[o.z:o.z + b.z, o.y:o.y + b.y, o.x:o.x + b.x] = 0
    return float(np.abs(a).max())


def _hierarchy(size, **how):
    levels = app.make_levels(size, jax.devices()[:1], "float32")
    step = ops.make_hpcg_iter([lv.halo_exchange for lv, _ in levels], **how)
    return levels, step


# ------------------------------------------------------------ the reference


def test_the_reference_is_hpcgs_stored_matrix():
    shape = (16, 8, 24)
    a = ref.matrix(shape)
    x = np.random.RandomState(0).uniform(-1, 1, shape)
    np.testing.assert_allclose(ref.spmv(x), (a @ x.ravel()).reshape(shape),
                               rtol=0, atol=1e-13)
    np.testing.assert_array_equal(ref.rhs(shape).ravel(),
                                  a @ np.ones(a.shape[0]))
    lengths = ref.row_lengths(shape)
    np.testing.assert_array_equal(lengths.ravel(), np.diff(a.indptr))
    assert sorted(set(lengths.ravel())) == [8, 12, 18, 27]
    assert (a.diagonal() == 26).all() and abs(a - a.T).max() == 0


def test_the_coloured_sweep_is_a_row_loop_in_colour_order():
    shape = (8, 8, 8)
    rng = np.random.RandomState(1)
    x, r = rng.uniform(-1, 1, shape), rng.uniform(-1, 1, shape)
    xp = ref._ring(x)
    for c in list(range(8)) + list(range(7, -1, -1)):
        new = {}
        for z, y, i in np.ndindex(*shape):
            if ref.colour_of(z, y, i) == c:
                box = xp[z:z + 3, y:y + 3, i:i + 3]
                new[z, y, i] = (r[z, y, i] + box.sum() - box[1, 1, 1]) / 26
        for (z, y, i), v in new.items():
            xp[z + 1, y + 1, i + 1] = v
    np.testing.assert_allclose(ref.symgs(x, r), xp[1:-1, 1:-1, 1:-1], rtol=0,
                               atol=1e-15)
    # and the source's own order is two triangular solves of its matrix
    import scipy.sparse as sp
    from scipy.sparse.linalg import spsolve_triangular

    a = ref.matrix(shape)
    v = spsolve_triangular(sp.tril(a, format="csr"),
                           r.ravel() - sp.triu(a, k=1) @ x.ravel(), lower=True)
    v = spsolve_triangular(sp.triu(a, format="csr"),
                           r.ravel() - sp.tril(a, k=-1) @ v, lower=False)
    np.testing.assert_allclose(ref.symgs_lexicographic(x, r).ravel(), v,
                               rtol=0, atol=1e-15)


def test_hpcgs_symmetry_tests_hold_for_a_and_for_mg():
    """``TestSymmetry``: x'Ay = y'Ax, and the same for the preconditioner
    (a V-cycle of SYMMETRIC sweeps: forward then backward)."""
    shape = (16, 16, 16)
    rng = np.random.RandomState(2)
    x, y = rng.uniform(-1, 1, shape), rng.uniform(-1, 1, shape)
    assert abs((x * ref.spmv(y)).sum() - (y * ref.spmv(x)).sum()) < 1e-10
    assert abs((x * ref.mg(y)).sum() - (y * ref.mg(x)).sum()) < 1e-12
    # the program's own A and MG, in float32
    levels = app.make_levels(shape, jax.devices()[:1], "float32")
    dd = levels[0][0]
    built, fns, _ = ops._build([lv.halo_exchange for lv, _ in levels],
                               jnp.dtype("float32"), None, False)
    state = app.take_state(levels)

    @jax.jit
    def both(v):
        z, _, _ = ops.vcycle(built, fns, state["z"], v, state["t"],
                             state["coarse"])
        return fns[(0, "hpcg_spmv")](v, state["t"]), z

    (ax, mx), (ay, my) = both(_held(dd, x)), both(_held(dd, y))
    for fx, fy in ((ax, ay), (mx, my)):
        lhs = (x * _owned(dd, fy)).sum()
        assert abs(lhs - (y * _owned(dd, fx)).sum()) < 2e-5 * abs(lhs)


def test_the_validity_rule_the_coloured_solve_takes_ten_more_iterations():
    """HPCG holds an optimized sweep to the residual its reference reaches
    in 50 iterations, and charges the extra iterations: in float64 at 16^3
    the eight-colour order needs 60 (the configuration says so)."""
    shape = (16, 16, 16)
    x_lex, lex, normr0 = ref.cg_ref(shape)
    x_col, col, _ = ref.solve(shape, 64, sets=False)
    assert lex[-1] / normr0 < 1e-40 and col[49] / normr0 < 1e-34
    assert next(i + 1 for i, v in enumerate(col) if v <= lex[-1]) == 60
    assert np.abs(x_lex - 1).max() < 1e-14 and np.abs(x_col - 1).max() < 1e-14


# ------------------------------------------------------------ the program


def _three_dispatches(size, **how):
    """From a seeded mid-set state with 48 iterations made: a general
    iteration, the set's last, and the next set's first (the restart)."""
    x, y, z = size
    levels, step = _hierarchy(size, **how)
    dd = levels[0][0]
    want, b = ref.seeded_state(7, (z, y, x))
    want["k"] = 48
    state = app.take_state(levels)
    for q in "xrp":
        state[q] = _held(dd, want[q])
    state.update(rtz=jnp.float32(want["rtz"]), k=jnp.int32(48),
                 normr0=jnp.float32(1))
    held_b = _held(dd, b)
    for count in (49, 50, 1):
        state = step(state, held_b)
        want = ref.cg_iteration(want, b)
        assert int(state["k"]) == want["k"] == count
        for q, tol in (("x", 1e-6), ("r", 4e-6), ("p", 4e-6)):
            assert np.abs(_owned(dd, state[q]) - want[q]).max() < tol, q
        for q in ("alpha", "beta", "normr", "rtz", "normr0"):
            assert float(state[q]) == pytest.approx(float(want[q]),
                                                    rel=2e-5), q
    assert float(state["beta"]) == 0.0          # a set's first: p = z
    # the ring and the padding of every array of every level hold zero
    held = [(levels[0][0], state[q]) for q in ops.FINE]
    for (lv, _), arrays in zip(levels[1:], state["coarse"]):
        held += [(lv, a) for a in arrays.values()]
    assert all(_ring_max(lv, a) == 0.0 for lv, a in held)
    return levels


def test_the_iteration_matches_the_reference_across_a_restart():
    _three_dispatches((16, 16, 16))
    plan = telemetry.get().records(kind="counter", name="hpcg.iter_plan")[-1]
    assert [lv["grid"][0] for lv in plan["levels"]] == [16, 8, 4, 2]
    assert {op["impl"] for lv in plan["levels"]
            for op in lv["operators"].values()} == {"xla"}


def test_the_kernels_match_the_reference_on_a_tight_x_level(x64_off):
    """128 x 16 x 16: the finest level takes the (interpreted) sweep, the
    box kernel and the operator's own; the three below are XLA."""
    _three_dispatches(TIGHT, use_pallas=True, interpret=True)
    plan = telemetry.get().records(kind="counter", name="hpcg.iter_plan")[-1]
    top = plan["levels"][0]
    assert top["layout"] == "tight_x" and top["grid"] == [16, 16, 128]
    assert {top["operators"][n]["impl"] for n in
            ("hpcg_symgs", "hpcg_resid", "hpcg_spmv")} == {"pallas"}
    assert plan["levels"][1]["layout"] == "inline"
    sweeps = telemetry.get().records(kind="counter", name="hpcg.symgs_plan")
    orders = {(s["value"], s["reverse"]): s["order"] for s in sweeps[-4:]}
    assert orders == {(0, False): [0, 1, 2, 3], (1, False): [4, 5, 6, 7],
                      (1, True): [7, 6, 5, 4], (0, True): [3, 2, 1, 0]}


def _dense(lv, rng, ring: bool):
    """A padded block of a level, dense in EVERY cell of the block or
    (``ring`` false) in its owned cells with the ring and padding at zero,
    among it values that need all three bfloat16 pieces of ``_dot3``."""
    a = rng.uniform(-3, 3, lv.block).astype(np.float32)
    a.flat[::7] = np.float32(1 + 2.0 ** -23)
    a.flat[3::11] = np.float32(-3.0000002)
    a.flat[5::13] = np.float32(1e-30)
    if not ring:
        (z, y, x), (nz, ny, nx) = lv.lo, lv.n
        own = np.zeros(lv.block, bool)
        own[z:z + nz, y:y + ny, x:x + nx] = True
        a = np.where(own, a, np.float32(0))
    return jnp.asarray(a)


@pytest.mark.parametrize("grid", sorted(TWO_TIGHT))
@pytest.mark.parametrize("which", ["restrict", "prolong"])
def test_a_transfer_kernel_is_bit_for_bit_the_xla_transfer(which, grid,
                                                           x64_off):
    """Between two tight-x levels the injection and the prolongation are
    Pallas kernels that fetch the even fine planes alone: on the WHOLE
    padded block the bits of the XLA transfer (a picked value is the value,
    the one addition is the one XLA makes), with the odd planes, rows and
    columns of the fine level dense."""
    levels = app.make_levels(TWO_TIGHT[grid], jax.devices()[:1], "float32")
    built, _, impls = ops._build([lv.halo_exchange for lv, _ in levels],
                                 jnp.dtype("float32"), True, True)
    fine, coarse = built[0], built[1]
    assert impls[(0, "hpcg_" + which)] == "pallas"
    rng = np.random.RandomState(5)
    if which == "restrict":
        # the fine level's ring too is dense: the kernel must not read it
        args = _dense(fine, rng, True), _dense(coarse, rng, False)
        want = ops._xla_restrict(fine, coarse)(*args)
        got = make_pallas_hpcg_restrict(fine.ex.spec, coarse.ex.spec,
                                        interpret=True)(*args)
        assert _ring_max(levels[1][0], np.asarray(got)[None, None, None]) == 0
    else:
        args = _dense(coarse, rng, False), _dense(fine, rng, False)
        want = ops._xla_prolong(coarse, fine)(*args)
        got = make_pallas_hpcg_prolong(coarse.ex.spec, fine.ex.spec,
                                       interpret=True)(*args)
        # one cell in eight moved, each by its coarse cell's value
        moved = np.asarray(got) != np.asarray(args[1])
        (z, y, x), (nz, ny, nx) = fine.lo, fine.n
        assert moved.sum() > 0.9 * coarse.n[0] * coarse.n[1] * coarse.n[2]
        moved[z:z + nz:2, y:y + ny:2, x:x + nx:2] = False
        assert not moved.any()
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want).view(np.uint32))


def test_two_tight_x_levels_transfer_in_pallas_and_match_the_reference(
        x64_off):
    """256 x 16 x 16: levels 4 and 3 are tight-x, so the transfers between
    them are the kernels; level 3's own (its coarse level lies inline with
    an x ring) stay XLA, as does everything below."""
    _three_dispatches(TWO_TIGHT["16_rows"], use_pallas=True, interpret=True)
    plan = telemetry.get().records(kind="counter", name="hpcg.iter_plan")[-1]
    assert [lv["layout"] for lv in plan["levels"]] == [
        "tight_x", "tight_x", "inline", "inline"]
    impl = [{n: lv["operators"][n]["impl"] for n in
             ("hpcg_restrict", "hpcg_prolong") if n in lv["operators"]}
            for lv in plan["levels"]]
    assert impl == [{"hpcg_restrict": "pallas", "hpcg_prolong": "pallas"},
                    {"hpcg_restrict": "xla", "hpcg_prolong": "xla"},
                    {"hpcg_restrict": "xla", "hpcg_prolong": "xla"}, {}]
    # what a transfer must move: a quarter of the fine level (its even
    # planes' even rows), twice for the prolongation, and the coarse level
    top, cells = plan["levels"][0]["operators"], 256 * 16 * 16
    assert top["hpcg_restrict"]["bytes_min"] == 4 * (cells // 4 + cells // 8)
    assert top["hpcg_prolong"]["bytes_min"] == 4 * (cells // 2 + cells // 8)


def _tight_spec():
    x, y, z = TIGHT
    return GridSpec(Dim3(x, y, z), Dim3(1, 1, 1),
                    Radius.constant(1).without_x())


def _padded(spec, a):
    o, b = spec.compute_offset(), spec.base
    out = np.zeros(spec.block_shape_zyx(), np.float32)
    out[o.z:o.z + b.z, o.y:o.y + b.y, o.x:o.x + b.x] = a
    return jnp.asarray(out)


def _cut(spec, a):
    o, b = spec.compute_offset(), spec.base
    return np.asarray(a)[o.z:o.z + b.z, o.y:o.y + b.y, o.x:o.x + b.x]


def test_the_box_kernel_drops_the_wrapped_lanes_on_a_fixed_x(x64_off):
    spec = _tight_spec()
    rng = np.random.RandomState(3)
    q = rng.uniform(-1, 1, spec.base.z * spec.base.y * spec.base.x).reshape(
        spec.base.z, spec.base.y, spec.base.x).astype(np.float32)
    p = np.ones_like(q)
    fixed = make_pallas_mg_box(spec, "hpcg_resid", WEIGHTS, -1.0,
                               separate_dst=True, interpret=True,
                               periodic_x=False)
    out = fixed(_padded(spec, q), _padded(spec, p), _padded(spec, 0 * q))
    want = p - ref.spmv(q.astype(np.float64))
    np.testing.assert_allclose(_cut(spec, out), want, rtol=0, atol=2e-5)
    # the two lanes a roll wraps into: the wrapped neighbour is worth up to
    # 9 there, and the periodic kernel (NPB's x axis) does read it
    wrapped = make_pallas_mg_box(spec, "mg_resid", WEIGHTS, -1.0,
                                 separate_dst=True, interpret=True)
    out_w = wrapped(_padded(spec, q), _padded(spec, p), _padded(spec, 0 * q))
    want_w = p - ref.spmv(q.astype(np.float64), wrap_x=True)
    np.testing.assert_allclose(_cut(spec, out_w), want_w, rtol=0, atol=2e-5)
    edge = np.abs(want - want_w)
    assert edge[:, :, 1:-1].max() == 0 and edge[:, :, [0, -1]].max() > 1.0
    # the operator alone, two arrays a call
    alone = make_pallas_hpcg_spmv(spec, interpret=True)(
        _padded(spec, q), _padded(spec, 0 * q))
    np.testing.assert_allclose(_cut(spec, alone), ref.spmv(
        q.astype(np.float64)), rtol=0, atol=2e-5)


# sha256 (16 hex) of the interpreted kernel's whole padded result on the
# block below, recorded on the PARENT commit (8b4c0a4: the builder before
# it was taught the fixed x), q and p from RandomState(4)
PARENT_BOX = {
    ("mg_resid", "A", False): "296e676f6f2f5e24",
    ("mg_resid", "A", True): "2cc1736d62beadac",
    ("mg_psinv", "S_LARGE", False): "f7bdcef2e650eac4",
    ("mg_psinv", "S_SMALL", False): "18727952ee373f48",
}


@pytest.mark.parametrize("name, weights, separate", sorted(PARENT_BOX),
                         ids=lambda v: str(v))
def test_npbs_periodic_box_is_bit_for_bit_the_parents(name, weights,
                                                      separate, x64_off):
    """The builder's default is the periodic x it always had: the same
    rolls and partial sums in the same order, so the same bits as the
    parent commit's kernel gave."""
    import hashlib

    spec = _tight_spec()
    rng = np.random.RandomState(4)
    q, p = (jnp.asarray(rng.uniform(-1, 1, spec.block_shape_zyx())
                        .astype(np.float32)) for _ in range(2))
    fn = make_pallas_mg_box(spec, name, getattr(ops_mg, weights),
                            -1.0 if name == "mg_resid" else 1.0,
                            separate_dst=separate, interpret=True)
    out = fn(q, p, jnp.zeros_like(p)) if separate else fn(q, p)
    assert hashlib.sha256(np.asarray(out).tobytes()).hexdigest()[:16] == \
        PARENT_BOX[(name, weights, separate)]


def test_the_sweeps_plan_counts_the_traced_bodys_rolls(x64_off):
    spec = _tight_spec()
    plan = symgs_plan(spec)
    fn = make_pallas_hpcg_symgs(spec, 1, True, interpret=True)
    block = jax.ShapeDtypeStruct(spec.block_shape_zyx(), jnp.float32)
    text = str(jax.make_jaxpr(fn)(block, block))
    lane = len(re.findall(r"roll\[[^\]]*axis=1", text))
    sub = len(re.findall(r"roll\[[^\]]*axis=0", text))
    # a loop's body is traced once, with a trip's groups in it: the
    # neighbouring planes' part, then one body a colour
    trip = plan["groups_per_trip"]
    assert trip == 2 and plan["lane_rolls_per_vreg_plane"] == 10
    assert lane == trip * plan["lane_rolls_per_vreg_plane"]
    assert lane == trip * (2 + plan["colours_per_call"] * plan[
        "lane_rolls_per_vreg_colour"])
    assert sub == trip * (2 + plan["colours_per_call"] * plan[
        "sublane_shifts_per_vreg_colour"])
    assert plan["scratch_bytes"] == (8 * 32 * 128 + 16 * 128) * 4
    assert (plan["passes_per_sweep"], plan["planes_fetched_per_step"]) == (2, 2)


def test_a_dispatch_is_one_module_with_no_way_to_the_host():
    """No host round trip inside a dispatch, held to the lowered program
    (the plan counter states no such thing: it could not fail): one module,
    no callback, infeed or outfeed, the restart a ``conditional`` inside it
    and every dot's result a value of the same module."""
    levels, step = _hierarchy((16, 16, 16))
    like = ops.state_like([lv.halo_exchange for lv, _ in levels],
                          jnp.dtype("float32"))
    text = step.lower(like, like["x"]).as_text()
    assert text.count("module @jit_stencil_hpcg_iter") == 1
    for word in ("callback", "infeed", "outfeed", "host"):
        assert word not in text.lower(), word
    assert len(re.findall(r"stablehlo\.case|stablehlo\.if", text)) == 1
    assert scopes.registered(scopes.HPCG_ITER) >= 1


def test_run_solves_hpcgs_own_problem_set_after_set():
    r = app.run(n=16, sets=2, devices=jax.devices()[:1])
    assert r["iters_run"] == 100 and len(r["error"]) == 2
    # every set starts from x = 0 inside the program and ends where the
    # one before did: at the solution of ones, to float32's rounding
    assert r["error"][0] == r["error"][1] < 2e-6
    assert r["relative_residual"][0] == r["relative_residual"][1] < 1e-6
    assert app.csv_row(r).startswith("hpcg,1,1,16,16,16,2,100,")
    with pytest.raises(ValueError, match="ONE device"):
        app.run(n=16, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="multiple of 8"):
        app.run(n=20, devices=jax.devices()[:1])
