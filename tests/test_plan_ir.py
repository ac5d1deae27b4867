"""ExchangePlan IR — the declarative plan vs the compiled truth.

The refactor contract: every exchange method lowers from the IR
(parallel/exchange.py consumes HaloExchange.plan's phase records), and
the lowering compiles to the SAME programs as the pre-refactor method
branches. Pinned three ways:

- census pins: the IR's predicted collective count must equal the
  compiled program's census for every method / batching / Q (the round-7
  and round-10 recorded counts: 6 composed, <=26 direct26, Q-independent
  when batched, 6*Q per-quantity / auto);
- byte pins: the IR's wire-byte estimate reproduces the RECORDED round-7
  on-wire bytes for the recorded config (pure geometry, no jax);
- parity: the plan-driven lowering still fills every halo correctly on
  uneven + oversubscribed partitions (the test_exchange fixtures, reused
  per the refactor's acceptance).

Runs on the virtual 8-device CPU mesh from conftest.py.
"""

import jax
import numpy as np
import pytest

from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.parallel import HaloExchange, Method, grid_mesh
from stencil_tpu.parallel.exchange import shard_blocks
from stencil_tpu.plan.ir import (
    AxisPhaseIR,
    DirectPhaseIR,
    PlanChoice,
    PlanConfig,
    build_plan,
    radius_dirs,
    radius_from_dirs,
)

from test_exchange import check_halos, coord_field


def _census_permutes(ex, state):
    census = ex.collective_census(state)
    other = sum(c for k, (c, _b) in census.items()
                if k != "collective-permute")
    assert other == 0, f"non-permute collectives snuck in: {census}"
    return census.get("collective-permute", (0, 0))[0]


def _state(spec, mesh, nq, dtype=np.float32):
    g = spec.global_size
    field = np.arange(g.x * g.y * g.z, dtype=dtype).reshape(g.z, g.y, g.x)
    return {i: shard_blocks(field + i, spec, mesh) for i in range(nq)}


@pytest.mark.parametrize("method,batched,nq,expect", [
    (Method.AXIS_COMPOSED, True, 4, 6),    # one carrier pair per phase
    (Method.AXIS_COMPOSED, False, 3, 18),  # 6 per quantity
    (Method.DIRECT26, True, 2, 26),        # one carrier per direction
])
def test_plan_predicts_census(method, batched, nq, expect):
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    ex = HaloExchange(spec, mesh, method, batch_quantities=batched)
    assert ex.plan.collectives_per_exchange(nq, 1) == expect
    assert _census_permutes(ex, _state(spec, mesh, nq)) == expect


def test_auto_plan_predicts_census():
    # round-7 finding, encoded in the IR: the partitioner reinvents the
    # composed schedule per quantity (6*Q permutes)
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    ex = HaloExchange(spec, mesh, Method.AUTO_SPMD)
    nq = 2
    assert ex.plan.synthesized
    assert ex.plan.collectives_per_exchange(nq, 1) == 12
    assert _census_permutes(ex, _state(spec, mesh, nq)) == 12


def test_plan_wire_bytes_reproduce_round7_record():
    # BASELINE.md round 7: 128^3, 2x2x2, uniform r2, 4 fp32 quantities ->
    # 12,484,608 on-wire bytes for the composed plan. Pure geometry.
    spec = GridSpec(Dim3(128, 128, 128), Dim3(2, 2, 2), Radius.constant(2))
    plan = build_plan(spec, Dim3(2, 2, 2), Method.AXIS_COMPOSED)
    assert plan.wire_bytes([4, 4, 4, 4]) == 12_484_608


def test_axis_phase_order_and_geometry():
    spec = GridSpec(Dim3(24, 16, 16), Dim3(2, 1, 2), Radius.constant(1))
    plan = build_plan(spec, Dim3(2, 1, 2), Method.AXIS_COMPOSED)
    assert [p.axis for p in plan.axis_phases] == ["x", "y", "z"]
    x, y, z = plan.axis_phases
    assert isinstance(x, AxisPhaseIR)
    assert (x.ring, x.resident) == (2, 1)
    assert (y.ring, y.resident) == (1, 1)   # self-wrap: no permute pairs
    assert y.collectives() == 0 and y.fwd == ()
    assert x.fwd == ((0, 1), (1, 0))
    assert x.sizes == (12, 12)
    # phases carry the per-exchange byte split: self-wrap y moves only
    # locally, split x/z ride the wire
    assert y.wire_cells == 0 and y.local_cells > 0
    assert x.wire_cells > 0


def test_oversubscribed_plan_ring_and_resident():
    # 2x2x2 partition on 4 devices: stack_residents -> z-heavy (cz=2)
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    plan = build_plan(spec, Dim3(2, 2, 1), Method.AXIS_COMPOSED)
    z = plan.axis_phases[2]
    assert (z.ring, z.resident) == (1, 2)
    assert z.collectives() == 0  # single-device ring: boundary wraps locally
    x = plan.axis_phases[0]
    assert (x.ring, x.resident) == (2, 1)


def test_direct26_phases_uniform():
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    plan = build_plan(spec, Dim3(2, 2, 2), Method.DIRECT26)
    assert len(plan.direct_phases) == 26
    ph = plan.direct_phases[0]
    assert isinstance(ph, DirectPhaseIR)
    assert ph.src is not None and ph.dst is not None
    assert len(ph.pairs) == 8  # flattened 26-neighbor permutation, 8 devs
    assert all(p.collective_count == 1 for p in plan.direct_phases)


def test_direct26_phases_uneven_sorted_and_padded():
    spec = GridSpec(Dim3(17, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    plan = build_plan(spec, Dim3(2, 2, 2), Method.DIRECT26)
    ranks = [abs(p.direction[0]) + abs(p.direction[1]) + abs(p.direction[2])
             for p in plan.direct_phases]
    assert ranks == sorted(ranks), "uneven apply order must be face->edge->corner"
    # orthogonal extents pad to the base block size
    face_x = next(p for p in plan.direct_phases if p.direction == (1, 0, 0))
    assert face_x.shape == (spec.base.z, spec.base.y, 1)
    assert face_x.src is None  # traced per-block starts at lowering time


def test_plan_lowering_parity_uneven_oversubscribed():
    # the refactor's end-to-end pin: the plan-driven lowering still fills
    # every halo on an uneven partition with resident oversubscription
    spec = GridSpec(Dim3(18, 16, 16), Dim3(2, 2, 2), Radius.constant(2))
    mesh = grid_mesh(Dim3(2, 2, 1), jax.devices()[:4])
    ex = HaloExchange(spec, mesh, Method.AXIS_COMPOSED)
    assert ex.plan.resident == (1, 1, 2)
    stacked = shard_blocks(coord_field(spec.global_size), spec, mesh)
    out = ex(stacked)
    check_halos(out, spec)


def test_radius_roundtrip_and_center_excluded():
    r = Radius.constant(2)
    dirs = radius_dirs(r)
    assert all(d[:3] != (0, 0, 0) for d in dirs)
    r2 = radius_from_dirs(dirs)
    for d, v in r._r.items():
        if d != (0, 0, 0):
            assert r2.dir(d) == v


def test_plan_config_key_and_choice_roundtrip():
    cfg = PlanConfig.make(Dim3(24, 24, 24), Radius.constant(2),
                          ["float64", "float32", "float32"], 8, "cpu")
    assert cfg.quantities == (("float32", 2), ("float64", 1))
    assert PlanConfig.from_json(cfg.to_json()) == cfg
    ch = PlanChoice(partition=(2, 2, 2), method="direct26",
                    batch_quantities=False, multistep_k=2,
                    kernel_variant="ring")
    assert PlanChoice.from_json(ch.to_json()) == ch
    assert "k=2" in ch.label() and "ring" in ch.label()


# the choice exactly as PRs 17 to 29 wrote it for a one-level plan
_STORED = {"partition": [2, 2, 2], "method": "axis-composed",
           "batch_quantities": True, "multistep_k": 1,
           "kernel_variant": None, "placement": None}


@pytest.mark.parametrize("extra,refused", [
    ({}, None),
    ({"hierarchy": None, "host_placement": None}, None),
    ({"hierarchy": ["z", 2], "host_placement": None}, "hierarchy"),
    ({"hierarchy": None, "host_placement": [1, 0]}, "host_placement"),
    ({"method": "remote-dma"}, "method"),
    ({"method": "remote-dma", "kernel_variant": "fused"}, "method"),
    ({"kernel_variant": "fused"}, "kernel_variant"),
    ({"kernel_variant": "persistent", "multistep_k": 2}, "kernel_variant"),
    ({"kernel_variant": "ring"}, None),
], ids=["absent", "null", "hierarchy", "host_placement", "remote-dma",
        "remote-dma+fused", "fused", "persistent", "ring-still-loads"])
def test_choice_from_json_of_retired_keys(extra, refused):
    """Plan DBs and checkpoint manifests written since PR 17 carry the
    two keys of the retired two-level exchange: absent or null loads as
    the one-level plan it always was; a choice that USED them is refused
    by name, never replayed as another plan. The same for the method and
    the two kernel variants of the kernel-initiated transport (PRs 10 to
    45); Astaroth's window variant rides ``kernel_variant`` as before."""
    obj = {**_STORED, **extra}
    if refused is None:
        ch = PlanChoice.from_json(obj)
        assert ch == PlanChoice(
            partition=(2, 2, 2), method="axis-composed",
            kernel_variant=extra.get("kernel_variant"))
        assert set(ch.to_json()) == set(_STORED)
    else:
        with pytest.raises(ValueError, match=f"retired key '{refused}'"):
            PlanChoice.from_json(obj)
