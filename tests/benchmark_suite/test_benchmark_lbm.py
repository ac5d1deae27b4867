"""What the lattice-Boltzmann cell brings: its plain reference against a
cell-by-cell loop and the update's own invariants, its adapter at the
rehearsal size (boxes, the seeded state, the two controls, a broken timed
path), the kernel description's bytes and operations, and what holds the
program to the source's relaxation rate and to the plan the configuration
names."""

import itertools
import json
import os

import numpy as np
import pytest

from _bench_util import BENCH_DIR, bench, one_more, open_session, rehearse
from benchmark import control, fields
from benchmark.harness import load_module
from benchmark.reference import lbm as ref

CELL = "lbm384.steady"
OMEGA = ref.omega_of(1.0)


# ------------------------------------------------------------ the reference


def test_the_lattice_is_the_sources():
    assert ref.Q == len(ref.VELOCITIES) == len(ref.WEIGHTS) == 19
    assert ref.VELOCITIES[0] == (0, 0, 0)
    assert len(set(ref.VELOCITIES)) == 19
    by_size = [sum(map(abs, c)) for c in ref.VELOCITIES]
    assert by_size.count(1) == 6 and by_size.count(2) == 12
    assert by_size.count(3) == 0                     # no corner vector
    for c, w in zip(ref.VELOCITIES, ref.WEIGHTS):
        assert w == {0: 1 / 3, 1: 1 / 18, 2: 1 / 36}[sum(map(abs, c))]
    # the weights' moments: 1, 0, the lattice's speed of sound 1/3
    assert abs(sum(ref.WEIGHTS) - 1) < 1e-15
    for a in range(3):
        assert sum(w * c[a] for c, w in zip(ref.VELOCITIES,
                                            ref.WEIGHTS)) == 0
        assert abs(sum(w * c[a] * c[a] for c, w in zip(
            ref.VELOCITIES, ref.WEIGHTS)) - 1 / 3) < 1e-15
    # every velocity's opposite is in the set
    assert {tuple(-v for v in c) for c in ref.VELOCITIES} == set(
        ref.VELOCITIES)
    assert OMEGA == 1 / 3.5 and ref.FLOPS_PER_CELL == 260


def _random_state(shape, seed=5):
    rng = np.random.RandomState(seed)
    rho = rng.uniform(0.9, 1.1, shape)
    u = [rng.uniform(-0.05, 0.05, shape) for _ in range(3)]
    return [ref.equilibrium(i, rho, *u) * (1 + rng.uniform(-0.01, 0.01, shape))
            for i in range(ref.Q)]


def test_a_step_matches_a_cell_by_cell_loop():
    n = (4, 5, 6)
    f = _random_state(n)
    got = ref.step(f, OMEGA)
    for z, y, x in itertools.product(*(range(m) for m in n)):
        g = [f[i][(z - c[2]) % n[0], (y - c[1]) % n[1], (x - c[0]) % n[2]]
             for i, c in enumerate(ref.VELOCITIES)]
        rho = sum(g)
        u = [sum(c[a] * gi for c, gi in zip(ref.VELOCITIES, g)) / rho
             for a in range(3)]
        for i, (c, w) in enumerate(zip(ref.VELOCITIES, ref.WEIGHTS)):
            cu = sum(ca * ua for ca, ua in zip(c, u))
            e = w * rho * (1 + 3 * cu + 4.5 * cu * cu
                           - 1.5 * sum(ua * ua for ua in u))
            assert abs(got[i][z, y, x] - (g[i] - OMEGA * (g[i] - e))) < 1e-15


def test_mass_and_momentum_are_kept_to_float64_rounding():
    f = _random_state((12, 10, 8))
    before = ref.invariants(f)
    after = ref.invariants(ref.run(f, OMEGA, 20))
    assert abs(after[0] - before[0]) < 1e-12 * before[0]
    for a, b in zip(before[1:], after[1:]):
        assert abs(a - b) < 1e-12


def test_a_uniform_equilibrium_at_rest_is_a_fixed_point_of_the_reference():
    f = [np.full((6, 6, 6), w) for w in ref.WEIGHTS]
    for a, b in zip(ref.run(f, OMEGA, 3), f):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-16)


@pytest.mark.parametrize("origin", [(28, 29, 10), (0, 0, 0), (13, 30, 31)])
def test_a_box_computed_alone_is_what_the_whole_domain_holds(origin):
    seed, g, core, steps = 4_000_000_007, (32, 32, 32), (6, 5, 7), 5
    z, y, x = np.meshgrid(*(np.arange(n) for n in g), indexing="ij")
    whole = [a.astype(np.float64) for a in ref.seeded_box(
        fields.uniform, seed, z, y, x)]
    for edges in (True, False):
        want = ref.run(whole, OMEGA, steps, edges=edges)
        got = ref.first_chunk_box(fields.uniform, seed, origin, core, steps,
                                  g, OMEGA, edges=edges)
        idx = np.ix_(*[np.arange(o, o + n) % m
                       for o, n, m in zip(origin, core, g)])
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b[idx], rtol=0, atol=1e-15)
    with pytest.raises(ValueError, match="does not fit"):
        ref.first_chunk_box(fields.uniform, seed, origin, (24, 8, 8), steps,
                            g, OMEGA)


def test_the_lower_precision_and_the_edgeless_references_differ_widely():
    import ml_dtypes

    seed, g, core = 1_000_003, (384, 384, 384), (8, 8, 16)
    origin = (380, 380, 100)                # astride the y-z edge at (0, 0)
    exact = ref.first_chunk_box(fields.uniform, seed, origin, core, 5, g,
                                OMEGA)
    low = ref.first_chunk_box(fields.uniform, seed, origin, core, 5, g,
                              OMEGA, dtype=ml_dtypes.bfloat16)
    bare = ref.first_chunk_box(fields.uniform, seed, origin, core, 5, g,
                               OMEGA, edges=False)
    single = ref.first_chunk_box(fields.uniform, seed, origin, core, 5, g,
                                 OMEGA, dtype=np.float32)
    assert low[0].dtype == ml_dtypes.bfloat16

    def far(other):
        return max(np.abs(a - b.astype(np.float64)).max()
                   for a, b in zip(exact, other))

    assert far(single) < 3e-7 < 1e-4 < far(low)
    assert far(bare) > 1e-2
    # away from the domain's y-z edges the planted fault changes nothing
    inner = (100, 200, 380)
    a = ref.first_chunk_box(fields.uniform, seed, inner, core, 5, g, OMEGA)
    b = ref.first_chunk_box(fields.uniform, seed, inner, core, 5, g, OMEGA,
                            edges=False)
    assert all(np.array_equal(p, q) for p, q in zip(a, b))


# ------------------------------------------------------------ the adapter


@pytest.fixture(scope="module")
def session():
    return open_session(CELL)


def test_the_adapter_drives_the_applications_own_step(session):
    facts = session.facts
    assert facts["global_zyx"] == [32, 32, 128] and facts["quantities"] == 19
    assert facts["iters_per_dispatch"] == 5 and facts["dtype"] == "float32"
    chosen = facts["chosen"]
    assert chosen["layout"] == "tight_x" and chosen["kernel"] == "pallas"
    assert chosen["carried"].count(": 5 [") == 4        # y-, y+, z-, z+
    assert 0 < chosen["halo_bytes_sent"] < 0.3 * chosen["halo_bytes_if_all"]
    assert session.omega == OMEGA
    # the domain holds neither lattice while the session does
    dd = session.domain
    assert all(dd.get_curr(h) is None and dd.get_next(h) is None
               for h in session.handles)


def test_boxes_sit_on_the_twelve_edges_the_six_faces_and_the_middle():
    apps = load_module("apps", "lbm")
    g, seed = (384, 384, 384), 2_147_483_659
    got = apps.boxes(g, seed)
    assert len(got) == 12 + 6 + 1 + apps.N_RANDOM_BOXES
    assert all(0 <= c < n for o in got for c, n in zip(o, g))

    def wraps(o):
        return tuple(a + c > n for a, c, n in zip(o, apps.CORE, g))

    edges = [wraps(o) for o in got[:12]]
    assert all(sum(w) == 2 for w in edges)
    assert sorted(set(edges)) == [(False, True, True), (True, False, True),
                                  (True, True, False)]
    assert len(set(got[:12])) == 12
    # the four boxes on the y-z edges hold the four edge lines' cells on
    # both sides of each wrap
    for o in [o for o in got[:12] if wraps(o) == (True, True, False)]:
        assert o[0] in (379, 380) and o[1] in (379, 380)
    faces = [wraps(o) for o in got[12:18]]
    assert all(sum(w) == 1 for w in faces)
    assert [w.index(True) for w in faces] == [0, 0, 1, 1, 2, 2]
    assert wraps(got[18]) == (False, False, False)
    # a core is taller than a chunk of 8 rows is: it crosses a seam
    assert apps.CORE[1] >= 8 and got[18][1] % 8 != 0


def test_the_seeded_state_is_the_references_and_halos_start_as_garbage(
        session):
    import jax

    seed = 4_300_000_037
    session.seed(seed)
    spec = session.domain.spec
    off, b = spec.compute_offset(), spec.base
    z, y, x = np.meshgrid(np.arange(b.z), np.arange(b.y), np.arange(b.x),
                          indexing="ij")
    want = ref.seeded_box(fields.uniform, seed, z, y, x)
    worst = 0.0
    for i in (0, 3, 18):
        block = np.asarray(session.curr[i])[0, 0, 0]
        owned = block[off.z:off.z + b.z, off.y:off.y + b.y,
                      off.x:off.x + b.x]
        worst = max(worst, float(np.abs(owned - want[i]).max()
                                 / want[i].max()))
        # what the low y halo row holds is not the wrap
        assert not np.array_equal(block[off.z:off.z + b.z, off.y - 1],
                                  want[i][:, -1])
    assert worst < 1e-6         # last bits: a compiler may fuse a product and a sum
    rho = sum(a.astype(np.float64) for a in want)
    assert 0.88 < rho.min() and rho.max() < 1.12
    jax.block_until_ready(session.dispatch())
    (name, value, limit), = session.compare(session.sample())
    assert name == "first_chunk_max_abs_err" and value <= limit
    assert value < 5e-7
    assert session.finite() and session.least > 0.02


def test_both_controls_fail_on_every_seed(session):
    rows = control.readings(session, [1_000_003, 2_147_483_659])
    assert control.verdict(rows, say=lambda _: None)
    for _, sound, ctrl, faults in rows:
        assert not control.failing(sound)
        assert control.failing(ctrl) == ["first_chunk_max_abs_err"]
        (what, checks), = faults
        assert what == "edge halos left unfilled"
        assert control.failing(checks) == ["first_chunk_max_abs_err"]
        assert checks[0][1] > 1e-2


def _edge_exchange_off(session):
    """A step that runs on an exchange whose populations have every edge
    gate off: the z slabs leave the y halo rows out."""
    from stencil_tpu.ops import lbm as ops
    from stencil_tpu.parallel.exchange import HaloExchange

    ex = session.domain.halo_exchange
    bare = HaloExchange(ex.spec, ex.mesh, quantity_radius={
        k: ops.population_radius(i, tight_x=True, edges=False)
        for i, k in enumerate(ex.quantity_radius)})
    session.step = ops.make_lbm_step(
        bare, session.omega, iters=session.facts["iters_per_dispatch"],
        use_pallas=True, interpret=True)
    return session


def test_an_exchange_without_the_edge_gates_comes_out_not_correct(capsys):
    result, rc = rehearse(CELL, wrap_session=_edge_exchange_off)
    assert rc == 3 and result["correct"] is False
    bad = [l.split()[2].rstrip(":") for l in capsys.readouterr().out.splitlines()
           if "NOT OK" in l]
    assert bad == ["first_chunk_max_abs_err"]
    assert result["checks"]["first_chunk_max_abs_err"]["value"] > 1e-3


def test_a_population_gone_negative_comes_out_not_correct(capsys):
    def sunk(session):
        real = session.dispatch

        def dispatch():
            out = real()
            off = session.domain.spec.compute_offset()
            session.curr[7] = session.curr[7].at[
                0, 0, 0, off.z + 3, off.y + 3, 5].set(-1e-3)
            return session.curr

        session.dispatch = dispatch
        return session

    result, rc = rehearse(CELL, wrap_session=sunk)
    assert rc == 3 and result["correct"] is False
    assert result["checks"]["nonfinite_after_window"]["value"] == 1


def test_a_program_built_with_another_viscosity_is_refused(monkeypatch):
    from stencil_tpu.apps import lbm as app

    monkeypatch.setattr(app, "omega_of", lambda nu: 1.0 / (3.0 * nu + 0.6))
    with pytest.raises(RuntimeError, match=r"the source's 1 / \(3 nu"):
        open_session(CELL)


def _plan(**over):
    plan = {"layout": "tight_x", "kernel": "pallas",
            "carried": {"y-": [3, 7, 10, 15, 17], "y+": [4, 8, 9, 16, 18],
                        "z-": [5, 11, 14, 15, 18], "z+": [6, 12, 13, 16, 17]}}
    plan.update(over)
    return plan


def test_the_plan_the_chip_builds_is_the_configurations():
    load_module("apps", "lbm").check_plan(_plan())


@pytest.mark.parametrize("over, said", [
    ({"kernel": "xla"}, "the stream-collide pass is xla, not pallas"),
    ({"layout": "inline"}, "the domain lies inline, not tight_x"),
    ({"carried": {"y-": list(range(19)), "y+": list(range(19))}},
     "direction y+ carries 19 populations, over 5"),
], ids=["xla", "inline", "one-radius"])
def test_a_run_that_fell_off_the_plan_is_not_the_cell(over, said):
    apps = load_module("apps", "lbm")
    with pytest.raises(RuntimeError, match="not the configuration's") as e:
        apps.check_plan(_plan(**over))
    assert said in str(e.value)


# ------------------------------------------------------------ the kernel

F384 = {"block_zyx": [384, 384, 384], "itemsize": 4, "quantities": 19,
        "radius_zyx": [[1, 1], [1, 1], [0, 0]], "padded_zyx": [386, 400, 384]}
CELLS384 = 384 ** 3


def test_the_pass_moves_152_bytes_a_cell_and_counts_the_references_terms():
    mod = load_module("kernels", "lbm_d3q19")
    assert mod.FAMILIES == ("make_pallas_lbm_step",)
    w = mod.work({"out_shapes": [(386, 400, 384)] * 19}, F384)
    assert w["per"] == "call" and w["bytes"] == 152 * CELLS384
    assert w["flops"] == ref.FLOPS_PER_CELL * CELLS384
    # 8.61 GB at 819 GB/s is 10.5 ms; 14.7 GFLOP at the table's 197 TFLOP/s
    # (the MXU's, which this kernel cannot use) 0.07 ms: memory by the table
    assert 10.5e-3 < w["bytes"] / 819e9 < 10.52e-3
    assert w["bytes"] / 819e9 > 100 * w["flops"] / 197e12


# ------------------------------------------------------------ the entries


def hold_the_entries(b: dict) -> None:
    """The cell, its configuration and its place in every metric's list
    come right AFTER ``mg512.steady``'s, the cell added before it: its
    order among the cells it knew, not the end of the list, where every
    later cell has to go."""
    names = [w["name"] for w in b["workloads"]]
    cell = b["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lbm-d3q19-384-f32", "steady", 1)
    assert names.index(CELL) == names.index("mg512.steady") + 1
    configs = [c["name"] for c in b["configs"]]
    assert configs.index("lbm-d3q19-384-f32") == \
        configs.index("npb-mg-c-f32") + 1
    assert b["configs"][configs.index("lbm-d3q19-384-f32")]["reduced"] == []
    for group in ("end_to_end", "per_layer"):
        for m in b[group]:
            lists = m.get("workloads")
            if lists is None:
                continue
            # mg512.steady's own metric (its coarse levels') is not shared
            if m["name"] == "mg_coarse_ms_per_iter":
                assert CELL not in lists
                continue
            assert ("mg512.steady" in lists) == (CELL in lists), m["name"]
            if CELL in lists:
                assert lists.index(CELL) == lists.index("mg512.steady") + 1, \
                    m["name"]
    assert not any("lbm" in m["name"] for m in b["per_layer"])


def _reordered(what):
    def make(b):
        b = one_more(b)
        if what == "cell":          # where the end-of-list test wanted it
            cells = b["workloads"]
            cells.append(cells.pop([w["name"] for w in cells].index(CELL)))
        elif what == "config":
            configs = b["configs"]
            configs.append(configs.pop([c["name"] for c in configs].index(
                "lbm-d3q19-384-f32")))
        else:                       # in ONE metric's list
            lists = next(m for m in b["per_layer"]
                         if m["name"] == what)["workloads"]
            lists.remove(CELL)
            lists.insert(0, CELL)
        return b
    return make


@pytest.mark.parametrize("case, make, held", [
    ("as committed", lambda b: b, True),
    ("a cell and a metric appended", one_more, True),
    ("the cell moved to the end", _reordered("cell"), False),
    ("its configuration moved to the end", _reordered("config"), False),
    ("its place in one metric's list moved",
     _reordered("kernel_scope_ms_per_iter"), False)])
def test_the_entries_are_held_to_their_order_not_to_the_end(case, make, held):
    b = make(bench())
    if held:
        hold_the_entries(b)
    else:
        with pytest.raises(AssertionError):
            hold_the_entries(b)


def test_the_cell_joins_mg512s_metrics_and_brings_none_of_its_own():
    hold_the_entries(bench())
    with open(os.path.join(BENCH_DIR, "configs",
                           "lbm-d3q19-384-f32.json")) as f:
        held = json.load(f)
    assert held["reduced"] == [] and held["args"]["n"] == 384
    assert held["kernels"] == {"stencil": ["lbm_d3q19"],
                               "halo": ["self_fill"]}
    assert set(held["args"]) <= set(load_module("apps", "lbm").USER_ARGS)
