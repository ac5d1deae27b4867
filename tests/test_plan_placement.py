"""Topology-aware block placement as a PlanChoice dimension (ISSUE 15).

The contracts: placement is a first-class, persisted, schema-migrated
plan field (absent => identity); the wire-volume matrix is the IR's
halo geometry aggregated to mesh positions; the QAP search only fires
on non-uniform fabrics and never returns something worse than identity;
the cost model prices a placement's wire term through the link matrix;
realize() binds mesh position i to ``devices[placement[i]]`` with
bit-identical results across every method/partition shape; and the ckpt
plan-mismatch warning covers the new field without crying wolf over
pre-placement snapshots.
"""

import json

import numpy as np
import pytest

import jax

from stencil_tpu.api import DistributedDomain
from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.parallel import FixedAssignment, Method, link_cost_matrix, qap
from stencil_tpu.plan import cost as plancost
from stencil_tpu.plan import db as plandb
from stencil_tpu.plan.ir import PlanChoice, PlanConfig, validate_placement

PERM8 = (4, 1, 6, 3, 0, 5, 2, 7)


def scrambled_ring_links(n=8, stride=3):
    """A non-uniform fabric where identity is provably suboptimal on a
    1x1xN ring partition: cheap links sit ``stride`` apart."""
    link = np.full((n, n), 7.0)
    for i in range(n):
        link[i, (i + stride) % n] = link[(i + stride) % n, i] = 1.0
    np.fill_diagonal(link, 0.1)
    return link


# -- the PlanChoice field -----------------------------------------------------


def test_choice_placement_roundtrip_and_label():
    ch = PlanChoice(partition=(2, 2, 2), method="axis-composed",
                    placement=PERM8)
    assert PlanChoice.from_json(ch.to_json()) == ch
    assert ch.is_placed
    assert "/p=4-1-6-3-0-5-2-7" in ch.label()
    ident = PlanChoice(partition=(2, 2, 2), method="axis-composed",
                       placement=tuple(range(8)))
    assert not ident.is_placed
    assert "/p=" not in ident.label()


def test_absent_placement_is_identity():
    """Schema migration: every pre-placement JSON choice (DB entries,
    ckpt plan metas) deserializes to placement=None."""
    ch = PlanChoice.from_json({"partition": [2, 2, 2],
                               "method": "axis-composed"})
    assert ch.placement is None and not ch.is_placed


def test_validate_placement():
    assert validate_placement(None, 8) is None
    assert validate_placement(PERM8, 8) is None
    assert "permutation" in validate_placement((0, 0, 1, 2, 3, 4, 5, 6), 8)
    assert "8 mesh" in validate_placement((0, 1, 2), 8)
    assert validate_placement("junk", 8) is not None


# -- the DB (schema v1, migrated) ---------------------------------------------


def test_db_roundtrips_placement(tmp_path):
    path = str(tmp_path / "plans.json")
    cfg = PlanConfig.make((16, 16, 16), Radius.constant(2), ["float32"],
                          8, "cpu")
    ch = PlanChoice(partition=(2, 2, 2), method="axis-composed",
                    placement=PERM8)
    db = plandb.empty_db()
    plandb.record(db, plandb.make_entry(cfg, ch, "static"))
    plandb.save_db(path, db)
    back = plandb.lookup(plandb.load_db(path), cfg)
    assert PlanChoice.from_json(back["choice"]).placement == PERM8


def test_db_rejects_bad_placement(tmp_path):
    cfg = PlanConfig.make((16, 16, 16), Radius.constant(2), ["float32"],
                          8, "cpu")
    ch = PlanChoice(partition=(2, 2, 2), method="axis-composed",
                    placement=PERM8)
    db = plandb.empty_db()
    entry = plandb.record(db, plandb.make_entry(cfg, ch, "static"))
    entry["choice"]["placement"] = [0, 0, 1, 2, 3, 4, 5, 6]
    with pytest.raises(plandb.PlanDBError):
        plandb.save_db(str(tmp_path / "bad.json"), db)


def test_legacy_v0_entry_migrates_to_identity_placement(tmp_path):
    """A v0 flat-layout entry (no placement field anywhere) migrates to
    source='legacy' with identity placement — the plan_tool show
    round-trip the satellite pins."""
    path = str(tmp_path / "v0.json")
    cfg = PlanConfig.make((16, 16, 16), Radius.constant(2), ["float32"],
                          8, "cpu")
    flat = {cfg.key(): {"partition": [2, 2, 2], "method": "axis-composed",
                        "batch_quantities": True}}
    with open(path, "w") as f:
        json.dump(flat, f)
    db = plandb.load_db(path)
    entry = plandb.lookup(db, cfg)
    assert entry["source"] == "legacy"
    ch = PlanChoice.from_json(entry["choice"])
    assert ch.placement is None and not ch.is_placed
    # and show renders it without crashing
    from stencil_tpu.apps.plan_tool import _entry_row

    row = _entry_row(cfg.key(), entry)
    assert "legacy" in row and "/p=" not in row


# -- wire matrix + QAP + pricing ----------------------------------------------


def test_wire_matrix_matches_qap_cost_authority():
    """placement_cost is pinned equal to parallel.qap.cost (the jax-free
    reimplementation must never drift from the solver's objective)."""
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(2))
    w = plancost.placement_wire_matrix(spec, Dim3(2, 2, 2))
    link = scrambled_ring_links()
    for f in (list(range(8)), list(PERM8)):
        assert plancost.placement_cost(w, link, tuple(f)) == pytest.approx(
            qap.cost(w, link, f))


def test_wire_matrix_symmetric_and_excludes_local():
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(2))
    w = plancost.placement_wire_matrix(spec, Dim3(2, 2, 2))
    np.testing.assert_allclose(w, w.T)
    assert np.all(np.diag(w) == 0)
    # oversubscribed: resident (same-slot) traffic never hits the wire —
    # a 2x2x4 partition on a 2x2x2 mesh halves the z-pair count but the
    # self-z traffic is excluded, not attributed
    spec2 = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 4), Radius.constant(1))
    w2 = plancost.placement_wire_matrix(spec2, Dim3(2, 2, 2))
    assert w2.shape == (8, 8)
    assert np.all(np.diag(w2) == 0)


def test_solve_placement_uniform_is_identity():
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(2))
    w = plancost.placement_wire_matrix(spec, Dim3(2, 2, 2))
    uniform = np.ones((8, 8))
    np.fill_diagonal(uniform, 0.0)
    assert plancost.uniform_link_costs(uniform)
    assert plancost.solve_placement(w, uniform) is None
    # the live CPU mesh derives a uniform matrix too
    assert plancost.uniform_link_costs(link_cost_matrix(jax.devices()[:8]))


def test_solve_placement_beats_identity_on_scrambled_ring():
    spec = GridSpec(Dim3(16, 16, 16), Dim3(1, 1, 8), Radius.constant(1))
    w = plancost.placement_wire_matrix(spec, Dim3(1, 1, 8))
    link = scrambled_ring_links()
    f = plancost.solve_placement(w, link)
    assert f is not None and sorted(f) == list(range(8))
    assert (plancost.placement_cost(w, link, f)
            < plancost.placement_cost(w, link))


def test_score_prices_placement_and_ranks_it_first():
    cfg = PlanConfig.make((16, 16, 16), Radius.constant(1), ["float32"],
                          8, "cpu")
    link = scrambled_ring_links()
    cands = plancost.enumerate_candidates(cfg, link_costs=link)
    placed = [c for c in cands if c.is_placed]
    assert placed, "non-uniform links must grow placed candidates"
    ranked = plancost.rank(cfg, cands, link_costs=link)
    ring = [(c, ch) for c, ch in ranked
            if ch.method == "axis-composed" and ch.partition == (1, 1, 8)
            and ch.multistep_k == 1]
    ident = next(t for t in ring if not t[1].is_placed)
    plc = next(t for t in ring if t[1].is_placed)
    assert plc[0].total_s < ident[0].total_s
    # identical non-wire terms: only the wire term scaled
    assert plc[0].collectives == ident[0].collectives
    assert plc[0].wire_bytes == ident[0].wire_bytes


def test_uniform_links_leave_search_space_unchanged():
    cfg = PlanConfig.make((16, 16, 16), Radius.constant(1), ["float32"],
                          8, "cpu")
    uniform = np.ones((8, 8))
    np.fill_diagonal(uniform, 0.0)
    assert (len(plancost.enumerate_candidates(cfg, link_costs=uniform))
            == len(plancost.enumerate_candidates(cfg)))


def test_feasible_rejects_malformed_placement():
    cfg = PlanConfig.make((16, 16, 16), Radius.constant(1), ["float32"],
                          8, "cpu")
    bad = PlanChoice(partition=(2, 2, 2), method="axis-composed",
                     placement=(0, 0, 1, 2, 3, 4, 5, 6))
    assert plancost.feasible(cfg, bad) is None
    short = PlanChoice(partition=(2, 2, 2), method="axis-composed",
                       placement=(1, 0))
    assert plancost.feasible(cfg, short) is None


# -- realize() binding + bit parity -------------------------------------------


def _exchange_once(method, part, placement, dtype="float32", grid=16):
    dd = DistributedDomain(grid, grid, grid)
    dd.set_radius(2)
    dd.set_devices(jax.devices()[:8])
    dd.set_plan(PlanChoice(partition=part, method=method,
                           placement=placement))
    h = dd.add_data("q", dtype)
    dd.realize()
    g = dd.size
    z, y, x = np.meshgrid(np.arange(g.z), np.arange(g.y), np.arange(g.x),
                          indexing="ij")
    field = (x + 100 * y + 10000 * z).astype(dtype)
    dd.set_curr_global(h, field)
    dd.exchange()
    return dd, np.asarray(jax.device_get(dd.get_curr(h)))


@pytest.mark.parametrize("method", ["axis-composed", "direct26",
                                    "auto-spmd"])
def test_placed_exchange_bit_identical_all_methods(method):
    _, ident = _exchange_once(method, (2, 2, 2), None)
    dd, placed = _exchange_once(method, (2, 2, 2), PERM8)
    assert ident.tobytes() == placed.tobytes()
    assert [d.id for d in dd.mesh.devices.flatten()] == list(PERM8)


def test_placed_exchange_uneven_and_oversubscribed():
    # uneven (17^3 over 1x2x4) and oversubscribed (16 blocks on 8 devs)
    _, a = _exchange_once("axis-composed", (1, 2, 4), None, grid=17)
    dd, b = _exchange_once("axis-composed", (1, 2, 4), PERM8, grid=17)
    assert a.tobytes() == b.tobytes()
    _, c = _exchange_once("axis-composed", (2, 2, 4), None)
    dd2, d = _exchange_once("axis-composed", (2, 2, 4), PERM8)
    assert c.tobytes() == d.tobytes()
    assert [dv.id for dv in dd2.mesh.devices.flatten()] == list(PERM8)


def test_realize_rejects_bad_placement():
    dd = DistributedDomain(16, 16, 16)
    dd.set_radius(1)
    dd.set_devices(jax.devices()[:8])
    dd.set_plan(PlanChoice(partition=(2, 2, 2), method="axis-composed",
                           placement=(0, 1)))
    dd.add_data("q", "float32")
    with pytest.raises(ValueError, match="placement"):
        dd.realize()


def test_explicit_strategy_wins_over_tuned_placement(capfd):
    """set_placement (a strategy) overrides the tuned tuple, loudly —
    the set_partition-over-tuned-plan convention."""
    dd = DistributedDomain(16, 16, 16)
    dd.set_radius(1)
    dd.set_devices(jax.devices()[:8])
    dd.set_placement(FixedAssignment(tuple(range(8))))
    dd.set_plan(PlanChoice(partition=(2, 2, 2), method="axis-composed",
                           placement=PERM8))
    dd.add_data("q", "float32")
    dd.realize()
    assert [d.id for d in dd.mesh.devices.flatten()] == list(range(8))
    assert "overrides the tuned" in capfd.readouterr().err


def test_fixed_assignment_validates():
    with pytest.raises(ValueError):
        FixedAssignment((0, 0, 1))
    fa = FixedAssignment((1, 0))
    devs = jax.devices()[:2]
    assert fa.arrange(devs, None) == [devs[1], devs[0]]
    with pytest.raises(ValueError):
        fa.arrange(jax.devices()[:3], None)


def test_plan_meta_records_placement():
    dd, _ = _exchange_once("axis-composed", (2, 2, 2), PERM8)
    meta = dd.plan_meta()
    assert tuple(meta["choice"]["placement"]) == PERM8


# -- ckpt plan-mismatch coverage ----------------------------------------------


def _realized(plan=None, tuned_placement=None):
    dd = DistributedDomain(16, 16, 16)
    dd.set_radius(1)
    dd.set_devices(jax.devices()[:8])
    if plan is not None:
        dd.set_plan(plan)
    dd.add_data("q", "float32")
    dd.realize()
    return dd


def test_ckpt_warns_on_placement_delta(capfd):
    tuned = PlanChoice(partition=(2, 2, 2), method="axis-composed",
                       placement=PERM8)
    dd = _realized(plan=tuned)
    manifest = {"meta": {"plan": dd.plan_meta()}}
    other = _realized(plan=PlanChoice(partition=(2, 2, 2),
                                      method="axis-composed"))
    capfd.readouterr()
    other._warn_plan_mismatch(manifest)
    assert "exchange plan" in capfd.readouterr().err


def test_ckpt_quiet_on_pre_placement_snapshot(capfd):
    """A snapshot written BEFORE the placement field existed (no key in
    its choice dict) must not warn against an identity-placement run."""
    dd = _realized()
    manifest = {"meta": {"plan": dd.plan_meta()}}
    del manifest["meta"]["plan"]["choice"]["placement"]  # old-build shape
    capfd.readouterr()
    dd._warn_plan_mismatch(manifest)
    assert "exchange plan" not in capfd.readouterr().err


def test_ckpt_quiet_on_untuned_placement_only_delta(capfd):
    """Between two UNTUNED runs a placement-only delta stays quiet, like
    the partition-only elastic resume."""
    dd = _realized()
    manifest = {"meta": {"plan": dd.plan_meta()}}
    # hand-edit the saved side to carry a placement (an untuned run
    # whose realize() arranged devices via a strategy)
    manifest["meta"]["plan"]["choice"]["placement"] = list(PERM8)
    capfd.readouterr()
    dd._warn_plan_mismatch(manifest)
    assert "exchange plan" not in capfd.readouterr().err


# -- autotune round-trip ------------------------------------------------------


def test_autotune_persists_and_replays_placement(tmp_path):
    """A non-uniform fabric tunes to a PLACED choice, persists it, and
    the DB hit replays it; realize() binds the replayed assignment."""
    path = str(tmp_path / "plans.json")
    from stencil_tpu.plan.autotune import autotune

    link = scrambled_ring_links()
    first = autotune((16, 16, 16), Radius.constant(1), ["float32"],
                     ndev=8, platform="cpu", db_path=path, probe=False,
                     link_costs=link,
                     methods=("axis-composed",))
    assert first.choice.is_placed, first.choice.label()
    second = autotune((16, 16, 16), Radius.constant(1), ["float32"],
                      ndev=8, platform="cpu", db_path=path, probe=False,
                      link_costs=link, methods=("axis-composed",))
    assert second.cache_hit and second.choice == first.choice


def test_placement_audit_sweep():
    """The verify_plan placement sweep (the CI gate's stage 1) passes on
    the live mesh."""
    from stencil_tpu.analysis.verify_plan import (placement_permutations,
                                                  run_placement_sweep)

    perms = placement_permutations(8, 3)
    assert len(perms) == 3
    assert all(p != tuple(range(8)) for p in perms)
    res = run_placement_sweep(count=3, size=16, radius=2,
                              partition=(2, 2, 2))
    assert res["checked"] == 3 and res["failed"] == 0


def test_placement_permutations_valid_for_odd_ndev():
    """Every emitted fixture must be a real permutation — the naive
    pairwise-swap formula mapped odd ndev's last index out of range, so
    the sweep FAILED (IndexError verdicts) on a healthy build."""
    from stencil_tpu.analysis.verify_plan import placement_permutations

    for ndev in (2, 3, 5, 7, 8):
        for p in placement_permutations(ndev, 3):
            assert validate_placement(p, ndev) is None, (ndev, p)
            assert p != tuple(range(ndev))


def test_replan_failure_rolls_back_to_the_old_plan():
    """A choice that cannot realize must leave the domain EXACTLY as it
    was — the ReplanController's 'rejected, continuing on the old plan'
    contract — not torn with its state dropped."""
    dd = _realized(plan=PlanChoice(partition=(2, 2, 2),
                                   method="axis-composed"))
    h_idx = 0
    field = np.arange(16 ** 3, dtype=np.float32).reshape(16, 16, 16)
    from stencil_tpu.domain import DataHandle

    h = DataHandle(h_idx, "q", "float32")
    dd.set_curr_global(h, field)
    before = dd.get_curr_global(h)
    # 27 blocks on 8 devices: realize() must reject it
    bad = PlanChoice(partition=(3, 3, 3), method="axis-composed")
    with pytest.raises(ValueError):
        dd.replan(bad)
    assert dd._realized and dd.spec.dim == Dim3(2, 2, 2)
    assert dd._method == Method.AXIS_COMPOSED
    np.testing.assert_array_equal(dd.get_curr_global(h), before)
    # and the domain still swaps plans normally afterwards
    dd.replan(PlanChoice(partition=(1, 2, 4), method="axis-composed"))
    np.testing.assert_array_equal(dd.get_curr_global(h), before)
