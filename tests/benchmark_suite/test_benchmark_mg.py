"""What the MG cell brings: its plain reference against a point-by-point
loop and the operators' own identities, its adapter at class S (boxes, the
seeded state, the two controls, a broken timed path), the three kernel
descriptions' bytes and operations, and what holds the program to the
source's weights and to the layout the configuration names."""

import itertools
import json
import os

import numpy as np
import pytest

from _bench_util import BENCH_DIR, bench, open_session, rehearse
from benchmark import control
from benchmark.harness import load_module
from benchmark.reference import mg as ref

CELL = "mg512.steady"


# ------------------------------------------------------------ the reference


def test_the_constants_are_the_sources():
    assert ref.A == (-8 / 3, 0.0, 1 / 6, 1 / 12)
    assert ref.S_SMALL == (-3 / 8, 1 / 32, -1 / 64, 0.0)
    assert ref.S_LARGE == (-3 / 17, 1 / 33, -1 / 61, 0.0)
    assert ref.RESTRICT == (1 / 2, 1 / 4, 1 / 8, 1 / 16)
    assert ref.PROLONG == (1.0, 1 / 2, 1 / 4, 1 / 8)
    assert [len(o) for o in ref._OFFSETS] == [1, 6, 12, 8]
    # A annihilates constants; the restriction's weights sum to 4 (the
    # square of the ratio of the two grids' spacings: A carries no h^2)
    counts = (1, 6, 12, 8)
    assert abs(sum(w * c for w, c in zip(ref.A, counts))) < 1e-15
    assert sum(w * c for w, c in zip(ref.RESTRICT, counts)) == 4.0
    assert ref.CLASSES["C"] == (512, 20, ref.S_LARGE, 0.5706732285740e-06)
    assert ref.levels(512) == [512, 256, 128, 64, 32, 16, 8, 4, 2]
    assert (ref.LCG_A, ref.LCG_SEED, ref.LCG_MOD) == (1220703125, 314159265,
                                                      2 ** 46)
    # 2 x resid + psinv + an eighth of rprj3 and interp at the top, an
    # eighth of one of each more a level down: NPB's 58 to the unit
    top = 2 * ref.FLOPS_RESID + ref.FLOPS_PSINV + (
        ref.FLOPS_RPRJ3 + ref.FLOPS_INTERP) / 8
    below = ref.FLOPS_RESID + ref.FLOPS_PSINV + (
        ref.FLOPS_RPRJ3 + ref.FLOPS_INTERP) / 8
    assert abs(top + below / 7 - ref.FLOPS_PER_CELL_ITER) < 1.0


def _loop_box(q, w):
    """One cell at a time, one term a neighbour."""
    n = [m - 2 for m in q.shape]
    out = np.zeros(n)
    for z, y, x in itertools.product(*(range(m) for m in n)):
        for d in itertools.product((-1, 0, 1), repeat=3):
            out[z, y, x] += w[sum(map(abs, d))] * q[z + 1 + d[0], y + 1 + d[1],
                                                    x + 1 + d[2]]
    return out


def test_the_box_and_the_transfers_match_point_by_point_loops():
    rng = np.random.RandomState(5)
    q = rng.uniform(-1, 1, (8, 8, 8))
    w = (0.7, -0.3, 0.2, 0.05)
    np.testing.assert_allclose(ref.box27(ref.grow(q), w),
                               _loop_box(ref.grow(q), w), rtol=0, atol=1e-14)
    # rprj3: coarse c on fine 2c + 1
    want = np.zeros((4, 4, 4))
    for c in itertools.product(range(4), repeat=3):
        for d in itertools.product((-1, 0, 1), repeat=3):
            f = tuple((2 * a + 1 + b) % 8 for a, b in zip(c, d))
            want[c] += ref.RESTRICT[sum(map(abs, d))] * q[f]
    np.testing.assert_allclose(ref.rprj3(ref.grow(q)), want, rtol=0,
                               atol=1e-14)
    # interp: fine 2c + 1 on coarse c, fine 2c between c - 1 and c
    z = rng.uniform(-1, 1, (4, 4, 4))
    want = np.zeros((8, 8, 8))
    for f in itertools.product(range(8), repeat=3):
        reads = [((a - 1) // 2 % 4, a // 2 % 4) for a in f]
        want[f] = sum(z[c] for c in itertools.product(*reads)) / 8.0
    np.testing.assert_allclose(ref.interp(ref.grow(z)), want, rtol=0,
                               atol=1e-14)
    np.testing.assert_allclose(ref.interp_at(z, (-3,) * 3, (14,) * 3),
                               ref._take(want, (-3,) * 3, (14,) * 3),
                               rtol=0, atol=1e-14)


@pytest.mark.parametrize("n", [4, 8, 32])
def test_boxes_computed_alone_are_what_the_whole_iteration_holds(n):
    seed = 4_000_000_007
    plus, minus = ref.seeded_charges(seed, n)
    assert len(set(plus + minus)) == 20
    v = ref.charges_field(n, plus, minus)
    assert v.sum() == 0 and np.count_nonzero(v) == 20
    u, r = ref.iteration(ref.seeded_level(seed, 0, n), v,
                         ref.seeded_level(seed, 1, n), ref.S_LARGE)
    core = (min(n, 6),) * 3
    origins = [(n - 2,) * 3, (0, 0, 0), tuple(c - 2 for c in plus[0])]
    for o, got in zip(origins, ref.first_iteration_boxes(
            seed, n, ref.S_LARGE, origins, core)):
        np.testing.assert_allclose(got["u"], ref._take(u, o, core), rtol=0,
                                   atol=1e-14)
        np.testing.assert_allclose(got["r"], ref._take(r, o, core), rtol=0,
                                   atol=1e-14)
    # the streamed restriction is the whole level's
    np.testing.assert_array_equal(
        ref.restrict_seeded(seed, n),
        ref.rprj3(ref.grow(ref.seeded_level(seed, 1, n))))


def test_the_lower_precision_and_the_cornerless_references_differ_widely():
    seed, n = 1_000_003, 32
    origins, core = [(0, 0, 0), (20, 9, 30)], (8, 8, 8)
    import ml_dtypes

    exact = ref.first_iteration_boxes(seed, n, ref.S_LARGE, origins, core)
    low = ref.first_iteration_boxes(seed, n, ref.S_LARGE, origins, core,
                                    dtype=ml_dtypes.bfloat16)
    bare = ref.first_iteration_boxes(seed, n, ref.S_LARGE, origins, core,
                                     corners=False)
    for a, b, c in zip(exact, low, bare):
        assert b["u"].dtype == ml_dtypes.bfloat16
        for q in ("u", "r"):
            assert np.abs(a[q] - b[q].astype(np.float64)).max() > 3e-3
            assert np.abs(a[q] - c[q]).max() > 3e-2


# ------------------------------------------------------------ the adapter


@pytest.fixture(scope="module")
def session():
    return open_session(CELL)


def test_the_adapter_drives_the_applications_own_iteration(session):
    facts = session.facts
    assert facts["global_zyx"] == [32, 32, 32] and facts["quantities"] == 3
    assert facts["iters_per_dispatch"] == 1 and facts["dtype"] == "float32"
    assert len(session.levels) == 5
    assert session.smoother == ref.S_SMALL          # class S's own
    chosen = facts["chosen"]
    assert chosen["cycle_plan"].startswith("5:32^3 inline resid=xla")
    assert chosen["cycle_plan"].endswith("1:2^3 inline psinv=xla")
    # the domains hold no u or r while the session does: a dispatch donates
    for lv, hs in session.levels:
        assert lv.get_curr(hs["u"]) is None and lv.get_curr(hs["r"]) is None


def test_boxes_cover_the_wrap_both_corners_and_a_charge():
    apps = load_module("apps", "mg")
    n, seed = 512, 2_147_483_659
    got = apps.boxes(n, [1, 1, 1], seed)
    assert len(got) == 3 + 1 + apps.N_RANDOM_BOXES
    assert got[0] == (504, 504, 504)                # wraps on every axis
    assert got[1] == (0, 0, 0) and got[2] == (496, 496, 496)
    plus, _ = ref.seeded_charges(seed, n)
    inside = [(p - o) % n < c for p, o, c in zip(plus[0], got[3], apps.CORE)]
    assert all(inside)
    assert all(0 <= c < n for o in got for c in o)
    assert apps.boxes(n, [2, 2, 1], seed)[3] == (248, 248, 170)


def test_the_seeded_state_is_the_references_and_halos_hold_the_wrap(session):
    import jax

    seed = 4_300_000_037
    session.seed(seed)
    spec = session.domain.spec
    off, n = spec.compute_offset(), session.n
    plus, minus = ref.seeded_charges(seed, n)
    want_v = ref.charges_field(n, plus, minus, np.float32)
    for q, name in enumerate(("u", "r")):
        block = np.asarray(session.state[name][0])[0, 0, 0]
        held = block[off.z - 1:off.z + n + 1, off.y - 1:off.y + n + 1,
                     off.x - 1:off.x + n + 1]
        np.testing.assert_array_equal(
            held, ref.grow(ref.seeded_level(seed, q, n, np.float32)))
    block = np.asarray(session.v)[0, 0, 0]
    np.testing.assert_array_equal(
        block[off.z - 1:off.z + n + 1, off.y - 1:off.y + n + 1,
              off.x - 1:off.x + n + 1], ref.grow(want_v))
    jax.block_until_ready(session.dispatch())
    checks = dict((n, (v, lim)) for n, v, lim in session.compare(
        session.sample()))
    assert set(checks) == {"first_iter_max_abs_err.u",
                           "first_iter_max_abs_err.r", "v_cells_moved"}
    assert all(v <= lim for v, lim in checks.values())
    assert checks["v_cells_moved"] == (0, 0)
    assert session.finite()


def test_both_controls_fail_on_every_seed(session):
    rows = control.readings(session, [1_000_003, 2_147_483_659])
    assert control.verdict(rows, say=lambda _: None)
    for _, sound, ctrl, faults in rows:
        assert not control.failing(sound)
        assert set(control.failing(ctrl)) == {"first_iter_max_abs_err.u",
                                              "first_iter_max_abs_err.r"}
        (what, checks), = faults
        assert what == "corners left out"
        assert set(control.failing(checks)) == {"first_iter_max_abs_err.u",
                                                "first_iter_max_abs_err.r"}


def _v_written(session):
    """A step that writes v."""
    real = session.dispatch

    def dispatch():
        out = real()
        session.v = session.v.at[0, 0, 0, 5, 9, 3].add(1.0)
        return out

    session.dispatch = dispatch
    return session


def test_a_written_v_comes_out_not_correct(capsys):
    result, rc = rehearse(CELL, wrap_session=_v_written)
    assert rc == 3 and result["correct"] is False
    bad = [l.split()[2].rstrip(":") for l in capsys.readouterr().out.splitlines()
           if "NOT OK" in l]
    assert bad == ["v_cells_moved"]


def test_a_corner_halo_left_wrong_shows_in_the_next_iteration(session):
    """The first dispatch is what ``correct`` compares; that corners are
    READ is shown by dispatching once more from a state with one corner
    halo cell of r altered (the high corner: the restriction, the
    iteration's first operator, reads fine cells 2c, 2c + 1, 2c + 2, so a
    block's high halo and never its low one): owned cells of u move."""
    import jax

    seed = 1_000_003
    off, n = session.domain.spec.compute_offset(), session.n

    def twice(alter):
        session.seed(seed)
        jax.block_until_ready(session.dispatch())
        if alter:
            session.state["r"][0] = session.state["r"][0].at[
                0, 0, 0, off.z + n, off.y + n, off.x + n].add(1.0)
        jax.block_until_ready(session.dispatch())
        # a copy: the buffer is donated at the next dispatch
        return np.array(session.state["u"][0])[0, 0, 0]

    clean, altered = twice(False), twice(True)
    moved = np.abs(clean - altered)[off.z:off.z + n, off.y:off.y + n,
                                    off.x:off.x + n]
    assert moved[-1, -1, -1] > 1e-3 and np.count_nonzero(moved > 1e-6) > 8


def test_the_smoother_is_the_sources_table_for_the_class_asked_for():
    """``correct`` computes the reference with the weights of the
    reference's OWN class table, whatever the program's builder was given:
    class C takes class B's and up (-3/17, 1/33, -1/61), class S the
    small classes' (-3/8, 1/32, -1/64)."""
    apps = load_module("apps", "mg")
    with open(os.path.join(BENCH_DIR, "configs", "npb-mg-c-f32.json")) as f:
        held = json.load(f)
    assert apps.source_smoother(held["args"]) == ref.S_LARGE
    assert apps.source_smoother(held["rehearsal_args"]) == ref.S_SMALL
    assert apps.source_smoother({"n": 64}) == ref.S_LARGE
    assert ref.S_LARGE != ref.S_SMALL


def test_a_program_built_with_another_classes_smoother_is_refused(
        monkeypatch):
    from stencil_tpu.apps import mg as app

    n, nit, _, published = app.CLASSES["S"]
    monkeypatch.setitem(app.CLASSES, "S", (n, nit, app.S_LARGE, published))
    with pytest.raises(RuntimeError, match="the source's table gives"):
        open_session(CELL)


def test_a_wrong_smoother_behind_the_right_arguments_is_not_correct(
        monkeypatch, capsys):
    """A program whose builder is handed the source's weights and whose
    ``psinv`` computes with another class's: the reference does not follow
    it, and both limits fail."""
    from stencil_tpu.ops import mg as ops_mg

    real = ops_mg._build

    def build(exchanges, smoother, *rest):
        assert tuple(smoother) == ref.S_SMALL
        return real(exchanges, ops_mg.S_LARGE, *rest)

    monkeypatch.setattr(ops_mg, "_build", build)
    result, rc = rehearse(CELL)
    assert rc == 3 and result["correct"] is False
    bad = {l.split()[2].rstrip(":") for l in capsys.readouterr().out.splitlines()
           if "NOT OK" in l}
    assert bad == {"first_iter_max_abs_err.u", "first_iter_max_abs_err.r"}


def _plan_levels(impl_of, layouts=None):
    """A class-C plan as ``mg.cycle_plan`` records it: 512 .. 2, the three
    finest tight_x."""
    out = []
    for i in range(9):
        k, m = 9 - i, 512 >> i
        names = (["mg_resid", "mg_psinv", "mg_rprj3", "mg_interp"] if k > 1
                 else ["mg_psinv"])
        layout = (layouts or {}).get(k, "tight_x" if m % 128 == 0
                                     else "inline")
        out.append({"level": k, "grid": [m, m, m], "layout": layout,
                    "operators": {n: {"impl": impl_of(k, n)} for n in names}})
    return out


def _class_c(k, name):
    """What the chip builds: the box on 512, 256, 128, the transfers
    between them; 128 <-> 64 and all below in XLA."""
    if k >= 8 or (k == 7 and name in ("mg_resid", "mg_psinv")):
        return "pallas"
    return "xla"


def test_the_plan_the_chip_builds_is_the_configurations():
    apps = load_module("apps", "mg")
    apps.check_plan(_plan_levels(_class_c), 1)
    # class S, every level inline and in XLA: nothing is claimed of it
    apps.check_plan(_plan_levels(lambda k, n: "xla")[4:], 1)


@pytest.mark.parametrize("impl_of, layouts, said", [
    (lambda k, n: "xla", None, "level 9's mg_resid is xla, not pallas"),
    (lambda k, n: "xla" if (k, n) == (8, "mg_psinv") else _class_c(k, n),
     None, "level 8's mg_psinv is xla, not pallas"),
    (lambda k, n: "xla" if n == "mg_interp" else _class_c(k, n), None,
     "level 9's mg_interp is xla, not pallas"),
    (lambda k, n: "xla" if (k, n) == (8, "mg_rprj3") else _class_c(k, n),
     None, "level 8's mg_rprj3 is xla, not pallas"),
    (lambda k, n: "xla" if k == 7 else _class_c(k, n), {7: "inline"},
     "level 7 (128^3) lies inline, not tight_x"),
], ids=["all-xla", "one-box", "interp", "rprj3-256-128", "128-inline"])
def test_a_level_that_fell_to_xla_is_not_the_cell(impl_of, layouts, said):
    """``ops/mg`` falls to XLA by itself where a kernel does not take a
    block (38.7 ms an iteration against 12.8 with the transfers alone in
    XLA; my chip run, PR 40): as ``correct``, and not the configuration."""
    apps = load_module("apps", "mg")
    with pytest.raises(RuntimeError, match="not the configuration's") as e:
        apps.check_plan(_plan_levels(impl_of, layouts), 1)
    assert said in str(e.value)


# ------------------------------------------------------------ the kernels

F512 = {"block_zyx": [512, 512, 512], "itemsize": 4, "quantities": 3,
        "radius_zyx": [[1, 1], [1, 1], [0, 0]], "padded_zyx": [514, 528, 512]}
CELLS512 = 512 ** 3


def test_the_box_moves_twelve_bytes_a_cell_and_counts_npbs_operations():
    mod = load_module("kernels", "mg_box27")
    assert mod.FAMILIES == ("make_pallas_mg_box",)
    w = mod.work({"out_shapes": [(514, 528, 512)]}, F512)
    assert w["per"] == "call" and w["bytes"] == 12 * CELLS512
    assert w["flops"] == 15 * CELLS512
    # 1.61 GB at 819 GB/s is 1.97 ms; 2.0 GFLOP at 197 TFLOP/s 0.01 ms
    assert 1.96e-3 < w["bytes"] / 819e9 < 1.97e-3
    assert w["bytes"] / 819e9 > 100 * w["flops"] / 197e12
    # a lower tight-x level shares the padding: 256^3 from (258, 272, 256)
    assert mod.work({"out_shapes": [(258, 272, 256)]}, F512)["bytes"] == \
        12 * 256 ** 3
    assert (mod.FLOPS_RESID, mod.FLOPS_PSINV) == (ref.FLOPS_RESID,
                                                  ref.FLOPS_PSINV)


def test_the_transfers_count_the_fine_level_and_an_eighth():
    down = load_module("kernels", "mg_rprj3")
    up = load_module("kernels", "mg_interp")
    assert down.FAMILIES == ("make_pallas_mg_rprj3",)
    assert up.FAMILIES == ("make_pallas_mg_interp",)
    fine, coarse = (514, 528, 512), (258, 272, 256)
    w = down.work({"in_shapes": [fine] * 3 + [(512, 256), coarse],
                   "out_shapes": [coarse]}, F512)
    assert w["bytes"] == 4 * (CELLS512 + CELLS512 // 8)     # 4.5 B a cell
    assert w["flops"] == ref.FLOPS_RPRJ3 * CELLS512 // 8
    w = up.work({"in_shapes": [coarse] * 2 + [(256, 512), fine],
                 "out_shapes": [fine]}, F512)
    assert w["bytes"] == 4 * (CELLS512 + CELLS512 // 8)
    assert w["flops"] == ref.FLOPS_INTERP * CELLS512 // 8


# ------------------------------------------------------------ the entries


def test_the_cell_joins_the_shared_metrics_and_brings_none_of_its_own():
    """``mg_coarse_ms_per_iter`` (ISSUE 40) is its own, entry and reader,
    since PR 53 let an entry be appended after PR 38's four."""
    b = bench()
    assert os.path.isfile(os.path.join(BENCH_DIR, "layer_metrics",
                                       "mg_coarse_ms_per_iter.py"))
    assert [m["workloads"] for m in b["per_layer"]
            if m["name"] == "mg_coarse_ms_per_iter"] == [[CELL]]
    (cell,) = [w for w in b["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "npb-mg-c-f32", "steady", 1)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= \
        len(b["workloads"]) // 2
    joined = {m["name"] for m in b["end_to_end"] + b["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert joined == {
        "mcells_per_s_per_chip", "iter_ms_p95", "setup_s",
        "launch_gap_ms.app", "halo_scope_ms.app",
        "kernel_ms_per_iter", "kernel_scope_ms_per_iter",
        "stencil_kernel_roofline",
        "glue_program_ms_per_iter", "glue_compiler_ms_per_iter",
        "device_idle_share.app", "app_run_host_init_s", "app_run_compile_s",
        "app_run_steps_s", "app_run_trace_s", "app_run_lower_s",
        "app_run_backend_s", "app_run_cache_misses",
        "mg_coarse_ms_per_iter"}
