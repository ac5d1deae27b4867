"""The row-tiled temporal multistep through ``make_jacobi_loop``, as the
768^3 cell drives it: one k = 10 pass over two y strips whose last one is
re-anchored (``ny % rows != 0``), in interpret mode on the tight-x layout.
Every owned cell is held to the benchmark's float64 reference
(``benchmark/reference/jacobi3d.py``, nothing of ``stencil_tpu`` in it) and
bit for bit to the same number of ``make_jacobi_step`` calls; the rows at
the strip seam, the re-anchored rows and the periodic y wrap are asserted
by name, because the benchmark's ``correct`` places no box on them.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

K, ROWS = 10, 24
NX, NY, NZ = 128, 40, 24        # tight-x wants x % 128 == 0; nz >= 2k + 1
SEED = 4_300_000_037
# 2e-6 is the cell's own limit (benchmark/apps/jacobi3d.py MAX_ABS_ERR)
MAX_ABS_ERR = 2e-6
# y rows (global) by name: strip 0 writes [0, 24), strip 1 is re-anchored
# to [16, 40), so [16, 24) is computed twice and row 24 is the first that
# only the re-anchored strip writes
ROW_SETS = {
    "seam": slice(ROWS - 1, ROWS + 1),
    "re-anchored overlap": slice(NY - ROWS, ROWS),
    "re-anchored strip": slice(NY - ROWS, NY),
    "periodic wrap": [0, NY - 1],
}


@pytest.fixture(scope="module")
def setup():
    import jax

    from benchmark import fields
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.ops.jacobi import sphere_sel
    from stencil_tpu.ops.pallas_stencil import valid_strip_rows
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks

    size = Dim3(NX, NY, NZ)
    spec = GridSpec(size, Dim3(1, 1, 1), Radius.constant(1).without_x())
    assert valid_strip_rows(spec, K, ROWS)
    assert -(-NY // ROWS) == 2 and NY % ROWS, "two strips, the last re-anchored"
    mesh = grid_mesh(spec.dim, jax.devices()[:1])
    ex = HaloExchange(spec, mesh)
    z, y, x = (np.arange(n) for n in (NZ, NY, NX))
    field = fields.uniform(np, SEED, 0, z[:, None, None], y[None, :, None],
                           x[None, None, :])
    return ex, field, shard_blocks(sphere_sel(size), spec, mesh)


def _run(ex, fn, field, sel, calls=1):
    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    curr = shard_blocks(field, ex.spec, ex.mesh)
    nxt = shard_blocks(np.zeros_like(field), ex.spec, ex.mesh)
    for _ in range(calls):
        curr, nxt = fn(curr, nxt, sel)
    return unshard_blocks(curr, ex.spec)


@pytest.mark.parametrize("iters", [10, 11])
def test_row_tiled_pass_over_a_reanchored_last_strip(setup, iters):
    from benchmark.reference import jacobi3d as reference
    from stencil_tpu.obs import telemetry
    from stencil_tpu.ops.jacobi import make_jacobi_loop, make_jacobi_step

    ex, field, sel = setup
    loop = make_jacobi_loop(ex, iters, use_pallas=True, interpret=True,
                            temporal_k=K, multistep_rows=ROWS)
    staged = telemetry.get().records(
        kind="counter", name="kernel.multistep.staging")[-1]
    assert (staged["k"], staged["rows"], staged["strips"]) == (K, ROWS, 2)
    got = _run(ex, loop, field, sel)

    want = reference.box_after(SEED, (0, 0, 0), (NZ, NY, NX), iters,
                               (NZ, NY, NX))
    assert want.dtype == np.float64 and want.shape == got.shape
    err = np.abs(got.astype(np.float64) - want)
    for name, rows in ROW_SETS.items():
        assert err[:, rows, :].max() <= MAX_ABS_ERR, (
            f"{name} rows: max |err| {err[:, rows, :].max():.3g}")
    assert err.max() <= MAX_ABS_ERR

    step = make_jacobi_step(ex, use_pallas=True, interpret=True)
    stepped = _run(ex, step, field, sel, calls=iters)
    for name, rows in ROW_SETS.items():
        assert np.array_equal(got[:, rows, :], stepped[:, rows, :]), name
    assert np.array_equal(got, stepped)
