"""ROADMAP M8: the exchange-first Astaroth step (``overlap=None`` on the
fused path, what both Astaroth cells build) off the cells' own layout: on an
UNEVEN (1,2,2) partition and on resident (oversubscribed) shards, inline
radius-3 halos on every axis, the substep kernels in interpret mode. Every
owned cell of the 8 fields against the benchmark's plain float64 reference,
as ``tests/test_astaroth_x4.py`` holds the cell's layout (its helpers and
tolerance); a file of its own so that the two compiles run beside that
file's and not after them."""

import jax
import numpy as np
import pytest

from test_astaroth_x4 import DT, ITERS, _info, _new_plans, _reference

from stencil_tpu.astaroth.integrate import FIELDS, make_astaroth_step
from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.parallel import HaloExchange, grid_mesh
from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

# (global x, y, z; partition; mesh; devices): inline radius-3 halos on every
# axis, since the tight-x layout wants even blocks and one block a device
OFF_CELL = {
    # y and z = 8 + 7 (the kernel wants the base block's rows a multiple of
    # 8): the four blocks are of four sizes, and three hold dead pad
    "uneven": ((16, 15, 15), (1, 2, 2), (1, 2, 2), 4),
    # two z blocks stacked on each of two devices: a block's z halos come
    # from the block beside it on the same device
    "resident": ((16, 16, 16), (1, 2, 2), (1, 2, 1), 2),
}


@pytest.fixture(scope="module")
def off_cell():
    """``make_astaroth_step(overlap=None)`` on the fused path (interpret
    mode) driven ``ITERS`` iterations on each layout of ``OFF_CELL``:
    ``{name: (fields, seeded, want, plan, exchange)}``."""
    out = {}
    for name, (size, part, mesh_dim, ndev) in OFF_CELL.items():
        nx, ny, nz = size
        spec = GridSpec(Dim3(*size), Dim3(*part), Radius.constant(3))
        mesh = grid_mesh(Dim3(*mesh_dim), jax.devices()[:ndev])
        ex = HaloExchange(spec, mesh)
        rng = np.random.RandomState(46)
        seeded = {k: (rng.randn(nz, ny, nx) * 0.05).astype(np.float32)
                  for k in FIELDS}
        seeded["lnrho"] = seeded["lnrho"] + np.float32(0.5)
        step, (plan,) = _new_plans(lambda: make_astaroth_step(
            ex, _info(nx, ny, nz), dt=DT, dtype="float32", use_pallas=True,
            interpret=True))
        curr = {k: shard_blocks(seeded[k], spec, mesh) for k in FIELDS}
        nxt = {k: shard_blocks(np.zeros((nz, ny, nx), np.float32), spec, mesh)
               for k in FIELDS}
        for _ in range(ITERS):
            curr, nxt = step(curr, nxt)
        out[name] = ({k: unshard_blocks(curr[k], spec) for k in FIELDS},
                     seeded, _reference(seeded, ITERS), plan, ex)
    return out


@pytest.mark.parametrize("layout", sorted(OFF_CELL))
@pytest.mark.parametrize("field", FIELDS)
def test_exchange_first_step_off_the_cells_layout(off_cell, field, layout):
    """Every owned cell of the global field against the plain float64
    reference, on an uneven (1,2,2) partition and on resident shards: the
    plan is ``serial`` with one exchange an iteration, no shells."""
    got, seeded, want, plan, ex = off_cell[layout]
    assert (plan["mode"], plan["pallas"], plan["tight_x"], plan["shells"],
            plan["exchanges_per_iter"]) == ("serial", True, False, 0, 1)
    assert ex.spec.is_uniform() == (layout == "resident")
    assert ex.oversubscribed == (layout == "resident")
    assert got[field].shape == want[field].shape == seeded[field].shape
    np.testing.assert_allclose(got[field], want[field], rtol=2e-4, atol=1e-6,
                               err_msg=f"{layout} {field}")
    assert np.max(np.abs(want[field] - seeded[field])) > 1e-4
