"""What the HPCG cell brings: its adapter at the rehearsal size (the seeded
state on host and device, the boxes, the two controls, a broken timed
path), what holds the program to the configuration's plan, the kernel
descriptions' bytes and operations (the two transfers' against the
program's own plan and a recorded build), and the entries."""

import os

import numpy as np
import pytest

from _bench_util import BENCH_DIR, bench, open_session, rehearse
from benchmark import control
from benchmark.harness import load_module
from benchmark.reference import hpcg as ref

CELL = "hpcg512.steady"


@pytest.fixture(scope="module")
def session():
    return open_session(CELL)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH_DIR, "reference", "hpcg.py")) as f:
        text = f.read()
    assert "stencil_tpu" not in text.replace("nothing of ``stencil_tpu``", "")
    assert (ref.DIAGONAL, ref.LEVELS, ref.SET_ITERS, ref.COLOURS) == (
        26.0, 4, 50, 8)
    assert ref.level_shapes((512,) * 3) == [(512,) * 3, (256,) * 3,
                                            (128,) * 3, (64,) * 3]


def test_the_adapter_drives_the_applications_own_iteration(session):
    facts = session.facts
    assert facts["global_zyx"] == [16, 16, 16] and facts["quantities"] == 6
    assert facts["iters_per_dispatch"] == 1 and facts["dtype"] == "float32"
    assert len(session.levels) == 4
    chosen = facts["chosen"]
    assert chosen["iter_plan"].startswith("4:16x16x16 inline symgs=xla")
    # the domains hold none of the state while the session does: a dispatch
    # donates it; b stays where run() put it
    for lv, hs in session.levels:
        assert all(lv.get_curr(h) is None for q, h in hs.items() if q != "b")


def test_boxes_hold_the_corners_an_edge_and_a_face_of_each_axis():
    apps = load_module("apps", "hpcg")
    got = apps.boxes((512, 512, 512), 2_147_483_659)
    assert len(got) == 8 + 6 + 1 + apps.N_RANDOM_BOXES
    assert set(got[:8]) == {(a, b, c) for a in (0, 496) for b in (0, 496)
                            for c in (0, 496)}
    assert got[8:14] == [(248, 0, 0), (496, 248, 248), (0, 248, 0),
                         (248, 496, 248), (0, 0, 248), (248, 248, 496)]
    # odd, across a lane tile (x 128), a row group (y 64) and a plane pair
    assert got[14] == (249, 57, 121)
    assert all(0 <= o and o + 16 <= 512 for box in got for o in box)
    small = apps.boxes((16, 16, 16), 5)
    assert all(box == (0, 0, 0) for box in small)


def test_the_seeded_state_is_the_references_and_zero_on_the_ring(session):
    import jax

    seed = 4_300_000_037
    session.seed(seed)
    spec = session.domain.spec
    o, b = spec.compute_offset(), spec.base
    want, want_b = ref.seeded_state(seed, session.shape, np.float32)
    held = dict(session.state, b=session.b)
    for q in ("x", "r", "p", "b"):
        block = np.array(held[q])[0, 0, 0]
        own = (slice(o.z, o.z + b.z), slice(o.y, o.y + b.y),
               slice(o.x, o.x + b.x))
        np.testing.assert_array_equal(block[own],
                                      want_b if q == "b" else want[q])
        block[own] = 0
        assert not block.any(), q
    rtz, k = ref.seeded_scalars(seed, session.shape)
    assert float(session.state["rtz"]) == np.float32(rtz) > 0
    assert int(session.state["k"]) == k and 1 <= k <= 48
    jax.block_until_ready(session.dispatch())
    checks = {n: (v, lim) for n, v, lim in session.compare(session.sample())}
    assert set(checks) == {
        "first_iter_max_abs_err.x", "first_iter_max_abs_err.r",
        "first_iter_max_abs_err.p", "first_iter_max_rel_err.scalars",
        "restart_max_err", "last_set_normr_over_normr0", "ring_cells_moved",
        "b_cells_moved"}
    assert all(v <= lim for v, lim in checks.values())
    assert checks["ring_cells_moved"] == (0, 0)
    assert checks["b_cells_moved"] == (0, 0)
    # the set in hand was driven to its end and a whole one after it, whose
    # opening dispatch left a set's first iteration to rounding
    assert int(session.state["k"]) == 50
    assert checks["restart_max_err"][0] < 1e-6
    assert session.finite()


def test_both_controls_fail_on_every_seed(session):
    rows = control.readings(session, [1_000_003, 2_147_483_659])
    assert control.verdict(rows, say=lambda _: None)
    for _, sound, ctrl, faults in rows:
        assert not control.failing(sound)
        assert set(control.failing(ctrl)) == {
            "first_iter_max_abs_err.x", "first_iter_max_abs_err.r",
            "first_iter_max_abs_err.p", "first_iter_max_rel_err.scalars"}
        (what, checks), (skipped, restart) = faults
        assert what == "x wrapped"
        assert {"first_iter_max_abs_err.r", "first_iter_max_abs_err.p",
                "first_iter_max_rel_err.scalars"} <= set(
                    control.failing(checks))
        assert skipped == "restart skipped"
        assert control.failing(restart) == ["restart_max_err"]


def _written(what):
    """A step that writes a cell it must not: b, or a ghost cell of x; or
    one that opens a set on the x it held (``kept_x``)."""

    def wrap(session):
        real = session.dispatch

        def dispatch():
            opens = int(session.state["k"]) == 50
            held = session.state["x"] + 0 if what == "kept_x" and opens else 0
            out = real()
            if what == "b":
                session.b = session.b.at[0, 0, 0, 5, 9, 3].add(1.0)
            elif what == "kept_x":
                session.state["x"] = session.state["x"] + held
            else:
                session.state["x"] = session.state["x"].at[
                    0, 0, 0, 0, 3, 3].set(1e-30)
            return out

        session.dispatch = dispatch
        return session

    return wrap


@pytest.mark.parametrize("what, check", [
    # a b that moves under the program is not the b its set restarted from
    ("b", ["restart_max_err", "b_cells_moved"]),
    ("ring", ["ring_cells_moved"]), ("kept_x", ["restart_max_err"])])
def test_a_written_b_or_ring_cell_comes_out_not_correct(what, check, capsys):
    result, rc = rehearse(CELL, wrap_session=_written(what))
    assert rc == 3 and result["correct"] is False
    bad = [l.split()[2].rstrip(":") for l in capsys.readouterr().out.splitlines()
           if "NOT OK" in l]
    # (the wrapper's own additions compile inside the window)
    assert [b for b in bad if b != "compilations_in_window"] == check


def _plan(impl_of=lambda i, name: "pallas"):
    """A 512^3 plan as ``hpcg.iter_plan`` records it."""
    levels = []
    for i in range(4):
        m = 512 >> i
        names = ["hpcg_symgs"] + (["hpcg_resid", "hpcg_restrict",
                                   "hpcg_prolong"] if i < 3 else [])
        if i == 0:
            names.append("hpcg_spmv")
        tight = m % 128 == 0
        levels.append({
            "level": 4 - i, "grid": [m, m, m],
            "layout": "tight_x" if tight else "inline",
            "operators": {n: {"impl": impl_of(i, n) if tight and n in (
                "hpcg_symgs", "hpcg_resid", "hpcg_spmv") else "xla"}
                for n in names}})
    return {"levels": levels}


def test_the_plan_the_chip_builds_is_the_configurations():
    load_module("apps", "hpcg").check_plan(_plan())


@pytest.mark.parametrize("plan, said", [
    (_plan(lambda i, n: "xla"), "level 4's hpcg_symgs is xla, not pallas"),
    (_plan(lambda i, n: "xla" if (i, n) == (1, "hpcg_resid") else "pallas"),
     "level 3's hpcg_resid is xla, not pallas"),
    (_plan(lambda i, n: "xla" if n == "hpcg_spmv" else "pallas"),
     "level 4's hpcg_spmv is xla, not pallas"),
], ids=["all-xla", "one-resid", "spmv"])
def test_a_plan_that_is_not_the_configurations_is_refused(plan, said):
    with pytest.raises(RuntimeError, match="not the configuration's") as e:
        load_module("apps", "hpcg").check_plan(plan)
    assert said in str(e.value)


def test_three_levels_or_a_level_not_half_the_one_above_is_refused():
    apps = load_module("apps", "hpcg")
    plan = _plan()
    plan["levels"] = plan["levels"][:3]
    with pytest.raises(RuntimeError, match="3 levels, not 4"):
        apps.check_plan(plan)
    plan = _plan()
    plan["levels"][2]["grid"] = [100, 128, 128]
    with pytest.raises(RuntimeError, match="is not half"):
        apps.check_plan(plan)


# ------------------------------------------------------------ the kernels

F512 = {"block_zyx": [512, 512, 512], "itemsize": 4, "quantities": 6,
        "radius_zyx": [[1, 1], [1, 1], [0, 0]], "padded_zyx": [514, 528, 512]}
CELLS512 = 512 ** 3


def test_the_operator_alone_moves_eight_bytes_a_cell():
    mod = load_module("kernels", "hpcg_spmv")
    assert mod.FAMILIES == ("make_pallas_hpcg_spmv",)
    w = mod.work({"out_shapes": [(514, 528, 512)]}, F512)
    assert w["per"] == "call" and w["bytes"] == 8 * CELLS512
    assert w["flops"] == 27 * CELLS512
    # counted as the box builder counts its calls (three arrays), a call of
    # 1.8 ms would read 107 % of the HBM rate: the two-array form has a
    # builder and a description of its own
    box = load_module("kernels", "mg_box27").work(
        {"out_shapes": [(514, 528, 512)]}, F512)
    assert box["bytes"] == 12 * CELLS512
    assert w["bytes"] / 819e9 > 20 * w["flops"] / 197e12


def test_half_a_sweep_moves_eight_bytes_a_cell_and_updates_half_the_rows():
    mod = load_module("kernels", "hpcg_symgs")
    assert mod.FAMILIES == ("make_pallas_hpcg_symgs",)
    w = mod.work({"out_shapes": [(514, 528, 512)]}, F512)
    assert w["per"] == "call" and w["bytes"] == 8 * CELLS512
    assert w["flops"] == ref.FLOPS_PER_ROW_SWEEP * CELLS512 // 2
    # 1.07 GB at 819 GB/s is 1.31 ms a call, sixteen calls an iteration
    assert 1.30e-3 < w["bytes"] / 819e9 < 1.32e-3
    # a lower tight-x level shares the padding
    assert mod.work({"out_shapes": [(258, 272, 256)]}, F512)["bytes"] == \
        8 * 256 ** 3


# (description, the RESULT's block, its level's cells, bytes a call by hand)
TRANSFERS = [
    # injection into 256^3: 2 x 256^3 fine cells read, 256^3 written
    ("hpcg_restrict", (258, 272, 256), 256 ** 3, 201_326_592),
    ("hpcg_restrict", (130, 144, 128), 128 ** 3, 25_165_824),
    # prolongation onto 512^3: 256^3 read, 2 x 256^3 read and written back
    ("hpcg_prolong", (514, 528, 512), 512 ** 3, 335_544_320),
    ("hpcg_prolong", (258, 272, 256), 256 ** 3, 41_943_040),
]


@pytest.mark.parametrize("name, block, cells, by_hand", TRANSFERS)
def test_a_transfer_is_charged_what_it_must_move(name, block, cells, by_hand):
    mod = load_module("kernels", name)
    assert mod.FAMILIES == (f"make_pallas_{name}",)
    w = mod.work({"out_shapes": [block], "in_shapes": [block] * 3}, F512)
    assert w["per"] == "call" and w["bytes"] == by_hand
    # a quarter of the fine level and the coarse one, the fine quarter twice
    # for the prolongation: 12 B a coarse cell, 2.5 B a fine cell
    assert by_hand == {"hpcg_restrict": 12 * cells,
                       "hpcg_prolong": 5 * cells // 2}[name]
    assert w["flops"] == {"hpcg_restrict": 0, "hpcg_prolong": cells // 8}[name]
    # what the kernel streams (whole even planes) is said, not charged
    assert "lower bound" in w["note"] and "WHOLE" in w["note"]


def test_the_four_transfer_calls_are_0_738_ms_at_the_hbm_peak():
    """0.201 + 0.336 GB and an eighth of each a level down (PERF.md section
    7, PR 52): charged the box's 12 B a cell of the level their result has
    they read 2.489 ms, more than the 1.644 ms the calls take."""
    least = sum(by_hand for *_, by_hand in TRANSFERS) / 819e9
    assert least == pytest.approx(0.7375e-3, rel=1e-3)
    box = load_module("kernels", "mg_box27")
    as_boxes = sum(box.work({"out_shapes": [block]}, F512)["bytes"]
                   for _, block, _, _ in TRANSFERS) / 819e9
    assert as_boxes == pytest.approx(2.489e-3, rel=1e-3)


def test_the_transfers_bytes_are_the_programs_own_plans():
    """``hpcg.iter_plan`` gives every operator the least bytes a call moves
    (``bytes_min``, by the FINE level of a transfer): the descriptions
    count the same, from the result's block, at 512^3 <-> 256^3 and at
    256^3 <-> 128^3."""
    from types import SimpleNamespace

    from stencil_tpu.ops import hpcg as ops

    levels = [SimpleNamespace(
        n=(n, n, n), number=ref.LEVELS - i, tight=n % 128 == 0,
        ex=SimpleNamespace(spec=SimpleNamespace(
            global_size=SimpleNamespace(x=n, y=n, z=n))))
        for i, n in enumerate((512, 256, 128, 64))]
    impls = {(i, name): "pallas" for i in range(3)
             for name in ("hpcg_restrict", "hpcg_prolong")}
    plan = ops.iter_plan(levels, impls, 4)
    blocks = [(514, 528, 512), (258, 272, 256), (130, 144, 128)]
    down, up = (load_module("kernels", n)
                for n in ("hpcg_restrict", "hpcg_prolong"))
    for i in (0, 1):
        held = plan[i]["operators"]
        assert down.work({"out_shapes": [blocks[i + 1]]}, F512)["bytes"] == \
            held["hpcg_restrict"]["bytes_min"]
        assert up.work({"out_shapes": [blocks[i]]}, F512)["bytes"] == \
            held["hpcg_prolong"]["bytes_min"]


def test_a_recorded_build_of_each_transfer_is_counted_from_its_shapes():
    """The program's own builders between two tight-x levels, 256 x 32 x 16
    onto 128 x 16 x 8, recorded as a run records them
    (``capture.PallasBuilds``): family, ``name=``, operands, and the bytes
    from the recorded result shape and the finest level's facts."""
    import jax
    import jax.numpy as jnp

    from benchmark import capture
    from stencil_tpu.apps import hpcg as app
    from stencil_tpu.ops import hpcg as ops

    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        levels = app.make_levels((256, 32, 16), jax.devices()[:1], "float32")
        with capture.PallasBuilds() as pallas:
            built, fns, impls = ops._build(
                [lv.halo_exchange for lv, _ in levels], jnp.dtype("float32"),
                True, True)
            fine, coarse = (jnp.zeros(lv.ex.spec.stacked_shape_zyx(),
                                      jnp.float32) for lv in built[:2])
            fns[(0, "hpcg_restrict")](fine, coarse)
            fns[(0, "hpcg_prolong")](coarse, fine)
    finally:
        jax.config.update("jax_enable_x64", was)
    assert impls[(0, "hpcg_restrict")] == impls[(0, "hpcg_prolong")] == "pallas"
    spec = built[0].ex.spec
    facts = capture.spec_facts(spec, 1, 4, 6)
    got = {}
    for name in ("hpcg_restrict", "hpcg_prolong"):
        mod = load_module("kernels", name)
        (build,) = [b for b in pallas.builds if b["kernel"] in mod.FAMILIES]
        assert build["name"] == name and build["n_operands"] == 3
        assert build["interpret"] and build["calls_traced"] == 1
        got[name] = mod.work(build, facts)["bytes"]
    fine_cells = 256 * 32 * 16
    assert got == {"hpcg_restrict": 4 * 3 * fine_cells // 8,
                   "hpcg_prolong": 4 * 5 * fine_cells // 8}
    # every other build of the hierarchy carries its own name too
    assert {b["name"] for b in pallas.builds} >= {
        "hpcg_symgs", "hpcg_resid", "hpcg_spmv"}


# ------------------------------------------------------------ the entries


def test_the_cell_joins_the_shared_metrics_and_brings_none_of_its_own():
    """No halo metric (one fixed block: no fill, no wire); the reductions'
    own metric, ``solver_reduce_ms_per_iter``, entry and reader, since PR
    53 let an entry be appended after PR 38's four."""
    b = bench()
    (cell,) = [w for w in b["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "hpcg-512-f32", "steady", 1)
    # the eleventh cell, four of the eleven on four chips; later cells follow
    assert b["workloads"].index(cell) == 10
    assert sum(w["chips"] == 4 for w in b["workloads"][:11]) == 4
    (config,) = [c for c in b["configs"] if c["name"] == "hpcg-512-f32"]
    assert config["reduced"] == ["dtype"]
    for word in ("HPCG 3.1", "GenerateProblem_ref.cpp", "CG_ref.cpp",
                 "ComputeMG_ref.cpp", "ComputeSYMGS_ref.cpp", "4-level", "50"):
        assert word in config["source"], word
    joined = {m["name"] for m in b["end_to_end"] + b["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert joined == {
        "mcells_per_s_per_chip", "iter_ms_p95", "setup_s",
        "launch_gap_ms.app", "kernel_ms_per_iter", "kernel_scope_ms_per_iter",
        "stencil_kernel_roofline",
        "glue_program_ms_per_iter", "glue_compiler_ms_per_iter",
        "device_idle_share.app", "app_run_host_init_s", "app_run_compile_s",
        "app_run_steps_s", "app_run_trace_s", "app_run_lower_s",
        "app_run_backend_s", "app_run_cache_misses",
        "solver_reduce_ms_per_iter"}
    assert os.path.isfile(os.path.join(BENCH_DIR, "layer_metrics",
                                       "solver_reduce_ms_per_iter.py"))
    assert [m["workloads"] for m in b["per_layer"]
            if m["name"] == "solver_reduce_ms_per_iter"] == [[CELL]]
