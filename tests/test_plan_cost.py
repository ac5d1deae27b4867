"""Static cost model — ranking properties the autotuner relies on.

The central property: the ranking is a function of the quantity-dtype
MULTISET, so permuting a domain's quantity declaration order can never
change which plan wins (the DB key is the same multiset — a permuted
config must also HIT the same cache entry). Plus the recorded-economics
sanity pins: batching beats per-quantity at Q>1, direct26 ranks below
composed at the recorded config, infeasible partitions never rank.

Pure geometry — no jax compilation anywhere in this file.
"""

import random

import pytest

from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.plan.autotune import default_choice
from stencil_tpu.plan.cost import (
    enumerate_candidates,
    feasible,
    rank,
    scale_radius,
    score,
)
from stencil_tpu.plan.ir import PlanChoice, PlanConfig


def _config(dtypes, grid=(64, 64, 64), r=2, ndev=8):
    return PlanConfig.make(Dim3.of(grid), Radius.constant(r), dtypes,
                           ndev, "cpu")


def _ranking_labels(cfg):
    return [ch.label() for _c, ch in rank(cfg, enumerate_candidates(cfg))]


@pytest.mark.parametrize("dtypes", [
    ["float32"] * 3 + ["float64"] * 2,
    ["float32", "float64", "float32", "float64", "float32"],
    ["float64", "float32", "int32", "float32"],
])
def test_ranking_invariant_under_quantity_dtype_permutation(dtypes):
    base = _ranking_labels(_config(dtypes))
    rng = random.Random(1234)
    for _ in range(5):
        shuffled = list(dtypes)
        rng.shuffle(shuffled)
        cfg = _config(shuffled)
        # same canonical key -> same cache entry -> same ranking
        assert cfg.key() == _config(dtypes).key()
        assert _ranking_labels(cfg) == base


def test_batched_beats_per_quantity_at_q4():
    cfg = _config(["float32"] * 4, grid=(128, 128, 128))
    ch = dict(partition=(2, 2, 2), method="axis-composed")
    b = score(cfg, PlanChoice(batch_quantities=True, **ch))
    pq = score(cfg, PlanChoice(batch_quantities=False, **ch))
    assert b.total_s < pq.total_s
    assert b.collectives == 6 and pq.collectives == 24
    assert b.wire_bytes == pq.wire_bytes  # same payload, fewer launches


def test_direct26_ranks_below_composed_at_recorded_config():
    # round 7's verdict: exact extents lose to fewer messages here
    cfg = _config(["float32"] * 4, grid=(128, 128, 128))
    ch = dict(partition=(2, 2, 2), batch_quantities=True)
    composed = score(cfg, PlanChoice(method="axis-composed", **ch))
    direct = score(cfg, PlanChoice(method="direct26", **ch))
    assert composed.total_s < direct.total_s
    assert direct.wire_bytes < composed.wire_bytes  # it DOES move less


def test_manual_beats_auto_spmd_at_q_above_1():
    # auto cannot batch (it emits per-quantity permutes today), so the
    # packed manual plan wins on collective count
    cfg = _config(["float32"] * 4, grid=(128, 128, 128))
    ch = dict(partition=(2, 2, 2), batch_quantities=True)
    manual = score(cfg, PlanChoice(method="axis-composed", **ch))
    auto = score(cfg, PlanChoice(method="auto-spmd", **ch))
    assert manual.collectives == 6 and auto.collectives == 24
    assert manual.total_s < auto.total_s


def test_multistep_k_amortizes_collective_overhead():
    cfg = _config(["float32"] * 2, grid=(64, 64, 64), r=1)
    k1 = score(cfg, PlanChoice(partition=(2, 2, 2), method="axis-composed",
                               multistep_k=1))
    k2 = score(cfg, PlanChoice(partition=(2, 2, 2), method="axis-composed",
                               multistep_k=2))
    # same collective count per exchange, but k=2 pays it every other step
    assert k1.collectives == k2.collectives == 6
    assert k2.exchange_s / 2 < k1.exchange_s
    assert k2.compute_overhead_s > 0  # the redundant-compute price is real


def test_infeasible_partitions_are_filtered():
    # 8^3 grid, radius 2: an 8-way split along one axis leaves 1-cell
    # blocks (< radius) — must not rank; 2x2x2 (4-cell blocks) must
    cfg = _config(["float32"], grid=(8, 8, 8), r=2)
    assert score(cfg, PlanChoice(partition=(8, 1, 1),
                                 method="axis-composed")) is None
    assert score(cfg, PlanChoice(partition=(2, 2, 2),
                                 method="axis-composed")) is not None
    labels = _ranking_labels(cfg)
    assert labels and all("8x1x1" not in l for l in labels)


def test_deep_halo_exceeding_interior_is_refused_statically():
    # 16^3 / (1, 2, 4): z blocks are 4 cells; radius 2 at k = 2 realizes
    # a 4-cell halo, exactly feasible; k = 3 (6 cells) is not, and is
    # refused HERE, before any kernel is planned
    cfg = _config(["float32"], grid=(16, 16, 16), r=2)
    assert feasible(cfg, PlanChoice(partition=(1, 2, 4),
                                    method="axis-composed",
                                    multistep_k=2)) is not None
    assert feasible(cfg, PlanChoice(partition=(1, 2, 4),
                                    method="axis-composed",
                                    multistep_k=3)) is None


def test_block_count_must_be_device_multiple():
    cfg = _config(["float32"], ndev=8)
    assert feasible(cfg, PlanChoice(partition=(3, 1, 1),
                                    method="axis-composed")) is None
    # 16 blocks on 8 devices: legal oversubscription (2 residents)
    feas = feasible(cfg, PlanChoice(partition=(2, 2, 4),
                                    method="axis-composed"))
    assert feas is not None
    _spec, mesh_dim, resident = feas
    assert mesh_dim.flatten() == 8 and resident.flatten() == 2


def test_partial_calibration_override_merges_per_method():
    # a probe session may recalibrate ONE method's overhead; the others
    # must fall back to the defaults instead of raising
    cfg = _config(["float32"] * 4, grid=(128, 128, 128))
    cal = {"permute_overhead_s": {"axis-composed": 5e-4}}
    ch = dict(partition=(2, 2, 2), batch_quantities=True)
    composed = score(cfg, PlanChoice(method="axis-composed", **ch), cal)
    direct = score(cfg, PlanChoice(method="direct26", **ch), cal)
    assert composed is not None and direct is not None
    baseline = score(cfg, PlanChoice(method="axis-composed", **ch))
    assert composed.total_s < baseline.total_s  # the override took effect


def test_scale_radius():
    r = Radius.constant(2)
    r3 = scale_radius(r, 3)
    assert r3.x(-1) == 6 and r3.dir((1, 1, 1)) == 6
    assert scale_radius(r, 1) is r


def test_default_choice_is_nodepartition_composed():
    from stencil_tpu.geometry import NodePartition

    cfg = _config(["float32"] * 2, grid=(64, 64, 64))
    ch = default_choice(cfg)
    want = NodePartition(Dim3(64, 64, 64), Radius.constant(2), 1, 8).dim()
    assert Dim3.of(ch.partition) == want
    assert ch.method == "axis-composed" and ch.batch_quantities
