"""Export the multi-block fast-path steps for the TPU platform and report
their overlap dataflow (the machine check of tests/test_overlap_hlo.py).

Runs the full Mosaic kernel lowering without TPU hardware via jax.export.
Executed as a subprocess by the test suite because jax.export's deep
lowering recursion is incompatible with pytest's stack/rewriting; also
usable standalone:

    python scripts/export_overlap_hlo.py jacobi-overlap
    python scripts/export_overlap_hlo.py jacobi-serial
    python scripts/export_overlap_hlo.py astaroth-overlap

Prints one JSON line: the overlap_report() dict.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np

from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.parallel import HaloExchange, grid_mesh
from stencil_tpu.parallel.exchange import shard_blocks
from stencil_tpu.utils.hlo_check import overlap_report


def jacobi_export(overlap: bool) -> str:
    from stencil_tpu.ops.jacobi import make_jacobi_step, sphere_sel

    size = Dim3(32, 32, 32)
    spec = GridSpec(size, Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    ex = HaloExchange(spec, mesh)
    step = make_jacobi_step(ex, overlap=overlap, use_pallas=True, interpret=False)
    z = np.zeros((32, 32, 32), np.float32)
    curr = shard_blocks(z, spec, mesh)
    nxt = shard_blocks(z, spec, mesh)
    sel = shard_blocks(sphere_sel(size), spec, mesh)
    return jax.export.export(step, platforms=["tpu"])(curr, nxt, sel).mlir_module()


def jacobi_sidebuf_export() -> str:
    """Multi-block tight-x (out-of-line side buffers, VERDICT r3 item 5):
    dim 2x2x1, zero x radius — the full sweep must stay independent of the
    y permutes AND the x side-buffer permutes."""
    from stencil_tpu.ops.jacobi import make_jacobi_step, sphere_sel

    size = Dim3(256, 16, 12)
    spec = GridSpec(size, Dim3(2, 2, 1), Radius.constant(1).without_x())
    mesh = grid_mesh(spec.dim, jax.devices()[:4])
    ex = HaloExchange(spec, mesh)
    step = make_jacobi_step(ex, overlap=True, use_pallas=True, interpret=False)
    z = np.zeros((size.z, size.y, size.x), np.float32)
    curr = shard_blocks(z, spec, mesh)
    nxt = shard_blocks(z, spec, mesh)
    sel = shard_blocks(sphere_sel(size), spec, mesh)
    return jax.export.export(step, platforms=["tpu"])(curr, nxt, sel).mlir_module()


def astaroth_export() -> str:
    from stencil_tpu.astaroth import config as ac_config
    from stencil_tpu.astaroth.integrate import FIELDS, make_astaroth_step
    from stencil_tpu.apps.astaroth import DEFAULT_CONF

    n = 32
    info = ac_config.AcMeshInfo()
    with open(DEFAULT_CONF) as f:
        ac_config.parse_config(f.read(), info)
    info.int_params["AC_nx"] = info.int_params["AC_ny"] = info.int_params["AC_nz"] = n
    info.update_builtin_params()
    size = Dim3(n, n, n)
    spec = GridSpec(size, Dim3(2, 2, 2), Radius.constant(3))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    ex = HaloExchange(spec, mesh)
    step = make_astaroth_step(
        ex, info, dt=1e-3, overlap=True, dtype="float32",
        use_pallas=True, interpret=False,
    )
    z = np.zeros((n, n, n), np.float32)
    curr = {k: shard_blocks(z, spec, mesh) for k in FIELDS}
    nxt = {k: shard_blocks(z, spec, mesh) for k in FIELDS}
    return jax.export.export(step, platforms=["tpu"])(curr, nxt).mlir_module()


def main(which: str) -> int:
    if which == "jacobi-overlap":
        txt = jacobi_export(True)
    elif which == "jacobi-serial":
        txt = jacobi_export(False)
    elif which == "jacobi-sidebuf":
        txt = jacobi_sidebuf_export()
    elif which == "astaroth-overlap":
        txt = astaroth_export()
    else:
        raise SystemExit(f"unknown target {which!r}")
    print(json.dumps(overlap_report(txt)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1] if len(sys.argv) > 1 else "jacobi-overlap"))
