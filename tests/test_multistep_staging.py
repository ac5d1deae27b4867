"""What the planner stages for the temporal multistep at the benchmark's
two one-chip jacobi sizes, and the build-time counter that says so
(``kernel.multistep.staging``, recorded in ``ops/jacobi.py`` beside the
``plan_multistep_staging`` call)."""

import jax
import pytest

from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.obs import telemetry
from stencil_tpu.ops.pallas_stencil import (multistep_staging,
                                            plan_multistep_staging,
                                            valid_strip_rows)

BUDGET = 46 * 1024 * 1024       # ops/jacobi.py's
TIGHT = Radius.constant(1).without_x()
FIELDS = dict(telemetry.NAME_FIELDS["kernel.multistep.staging"])


def _cube(n, radius=TIGHT):
    return GridSpec(Dim3(n, n, n), Dim3(1, 1, 1), radius)


def test_planner_at_768_gives_depth_10_on_legal_row_strips():
    """The application's default 10-iteration dispatch: full planes of
    784 x 768 floats cap the depth at 5, row strips reach 10."""
    spec = _cube(768)
    k, rows = plan_multistep_staging(spec, 10, BUDGET)
    assert k == 10 and rows is not None
    assert valid_strip_rows(spec, k, rows)
    assert multistep_staging(spec, k, rows)["vmem_bytes"] <= BUDGET
    # the 4 + 3 (k - 1) + 2 full planes of k = 6 do not fit
    p = spec.padded()
    assert BUDGET // (p.y * p.x * 4) < 4 + 3 * (6 - 1) + 2


def test_multistep_staging_planner_self_caps_never_overflows():
    spec = GridSpec(Dim3(128, 128, 128), Dim3(1, 1, 8), Radius.constant(1))
    # a generous budget reaches the requested depth with full planes
    k, rows = plan_multistep_staging(spec, 4, budget=64 << 20)
    assert k == 4 and rows is None
    # a starved budget CAPS the depth rather than planning an overflow
    k_small, _rows = plan_multistep_staging(spec, 4, budget=1 << 18)
    assert k_small < 4


def test_planner_at_512_keeps_full_planes():
    assert plan_multistep_staging(_cube(512), 10, BUDGET) == (10, None)
    assert plan_multistep_staging(_cube(512), 12, BUDGET) == (12, None)


@pytest.mark.parametrize("n, k, rows, computed, kept", [
    # 768 rows in 2 strips of 384: each stage s computes 384 + 2 (10 - s)
    # rows a strip, 2 x (10 x 384 + 2 x 45) = 7,860 against 10 x 768
    (768, 10, 384, 7860, 7680),
    # 40 rows in 2 strips of 24, the last re-anchored over 8 rows of the
    # first: 2 x (4 x 24 + 2 x (3 + 2 + 1)) = 216 against 4 x 40
    (40, 4, 24, 216, 160),
])
def test_rows_computed_and_kept_by_hand(n, k, rows, computed, kept):
    spec = GridSpec(Dim3(128, n, 32), Dim3(1, 1, 1), TIGHT)
    assert valid_strip_rows(spec, k, rows)
    got = multistep_staging(spec, k, rows)
    assert (got["rows_computed"], got["rows_kept"]) == (computed, kept)
    assert got["strips"] == 2 and got["rows"] == rows
    # two strips, both edges: a wrap DMA each a grid step, fetched a step
    # ahead with the slab
    assert (got["wrap_dmas"], got["wrap_prefetch"]) == (1, 1)
    assert got["halo_rows"] == 2 * 8 * -(-k // 8)      # round8(k) a side


def test_full_planes_compute_what_they_keep():
    got = multistep_staging(_cube(512), 10, None)
    assert got["rows"] == 0 and got["strips"] == 1
    assert got["rows_computed"] == got["rows_kept"] == 10 * 512
    p = _cube(512).padded()
    assert got["halo_rows"] == p.y - 512
    # the y ring is copied in VMEM: no wrap DMA
    assert (got["wrap_dmas"], got["wrap_prefetch"]) == (0, 0)
    assert got["vmem_bytes"] == 4 * 512 * p.y * (4 + 3 * 9 + 2)
    # across a split y axis the deep halo is recomputed: k - s rows a side
    split = GridSpec(Dim3(128, 64, 32), Dim3(1, 2, 1), Radius.constant(4))
    deep = multistep_staging(split, 4, None)
    assert deep["rows_kept"] == 4 * 32
    assert deep["rows_computed"] == 4 * 32 + 2 * (3 + 2 + 1)


@pytest.mark.parametrize("rows", [None, 16])
def test_every_multistep_build_records_the_counter(rows):
    from stencil_tpu.obs import scopes
    from stencil_tpu.ops.jacobi import make_jacobi_loop
    from stencil_tpu.parallel import HaloExchange, grid_mesh

    spec = GridSpec(Dim3(128, 32, 16), Dim3(1, 1, 1), TIGHT)
    ex = HaloExchange(spec, grid_mesh(spec.dim, jax.devices()[:1]))
    rec = telemetry.get()
    before = len(rec.records(kind="counter", name="kernel.multistep.staging"))
    make_jacobi_loop(ex, 4, use_pallas=True, interpret=True,
                     multistep_rows=rows)
    new = rec.records(kind="counter",
                      name="kernel.multistep.staging")[before:]
    assert len(new) == 1
    (r,) = new
    assert not telemetry.validate_record(r), telemetry.validate_record(r)
    for field, kind in FIELDS.items():
        assert isinstance(r[field], kind), field
    assert r["module"] == scopes.JACOBI_LOOP and r["value"] == r["k"] == 4
    assert r["rows"] == (rows or 0) and r["strips"] == (2 if rows else 1)
    want = multistep_staging(spec, 4, rows)
    assert {f: r[f] for f in want} == want
    if rows is None:
        assert r["rows_computed"] == r["rows_kept"]
    else:
        assert r["rows_computed"] > r["rows_kept"]


def test_a_loop_without_a_multistep_records_none():
    from stencil_tpu.ops.jacobi import make_jacobi_loop
    from stencil_tpu.parallel import HaloExchange, grid_mesh

    spec = GridSpec(Dim3(16, 16, 16), Dim3(1, 1, 1), Radius.constant(1))
    ex = HaloExchange(spec, grid_mesh(spec.dim, jax.devices()[:1]))
    rec = telemetry.get()
    before = len(rec.records(kind="counter", name="kernel.multistep.staging"))
    make_jacobi_loop(ex, 4, use_pallas=False)
    assert len(rec.records(kind="counter",
                           name="kernel.multistep.staging")) == before
