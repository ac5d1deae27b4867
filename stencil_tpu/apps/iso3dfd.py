"""iso3dfd — isotropic acoustic wave propagation, one shot across the chips.

TPU-native port of Intel's oneAPI sample (DirectProgramming/C++SYCL/
StructuredGrids/iso3dfd_dpcpp: src/iso3dfd.cpp, src/utils.cpp, include/
iso3dfd.h; the kernel of "Eight Optimizations for 3DFD ISO"): 16th order
in space (a 49-point star of radius ``kHalfLength = 8``), 2nd in time
(leapfrog), three fp32 arrays ``prev``, ``next`` and ``vel`` of ``n1 x n2 x
n3`` cells (n1 fastest), the outer ring of 8 cells INCLUDED in n and never
written. Where the sample runs on one device, the shot is here decomposed
over the devices (as production seismic codes do: Minimod,
arXiv:2007.06048): a ``DistributedDomain`` of the interior ``(n1 - 16, n2 -
16, n3 - 16)`` with radius-8 halos, fixed on all three axes, so that the
ring is the halo beyond the domain's edge and a block boundary an ordinary
halo. A step exchanges ``prev`` alone (``next`` and ``vel`` are read at the
centre), faces only (a star reads no edge and no corner).

  iso3dfd,<processes>,<devices>,<n1>,<n2>,<n3>,<iterations>,<trimean s/iter>,<Mcells/s>

Usage: python -m stencil_tpu.apps.iso3dfd 256 256 256 10 [--cpu 4]
"""

from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..api import DistributedDomain
from ..geometry import Dim3, Radius, decompose_zy
from ..obs import scopes, telemetry
from ..ops.iso3dfd import DT, make_iso3dfd_step
from ..ops.pallas_iso3dfd import RADIUS
from ..utils import logging as log
from ..utils import timer
from ..utils.statistics import Statistics
from ..utils.sync import hard_sync, timed_chunk

QUANTITIES = ("prev", "next", "vel")
VEL_SAMPLE = 2250000.0 * DT * DT        # the sample's constant v^2 dt^2: 9.0


def make_sample_init(spec, sharding, n_xyz: Dim3, dtype):
    """``init() -> (prev, vel)``, born sharded: the sample's own data
    (``Initialize``, src/utils.cpp). A constant velocity, and nested cubes
    of half-width s = 5..0 about the grid's centre set in ``prev`` to 1,
    10, ..., 1e5 over zeros (``next`` is the domain's zeroed buffer as it
    stands). Halos hold what the cell they mirror holds, and the ring
    zero."""
    shape = spec.stacked_shape_zyx()
    off, base = spec.compute_offset(), spec.base

    def init():
        cheb = jnp.zeros(shape, jnp.int32)
        for bdim, (o, b, n) in enumerate(((off.z, base.z, n_xyz.z),
                                          (off.y, base.y, n_xyz.y),
                                          (off.x, base.x, n_xyz.x))):
            block = lax.broadcasted_iota(jnp.int32, shape, bdim)
            local = lax.broadcasted_iota(jnp.int32, shape, bdim + 3)
            grid = block * b + local - o + RADIUS      # ring included
            cheb = jnp.maximum(cheb, jnp.abs(grid - n // 2))
        steps = jnp.asarray([10.0 ** (5 - s) for s in range(6)], dtype)
        prev = jnp.where(cheb <= 5, steps[jnp.minimum(cheb, 5)],
                         jnp.zeros((), dtype))
        return prev, jnp.full(shape, VEL_SAMPLE, dtype)

    return jax.jit(init, out_shardings=(sharding,) * 2)


def run(
    n1: int,
    n2: int,
    n3: int,
    iters: int = 10,
    chunk: Optional[int] = None,
    devices=None,
    partition=None,
    dtype: str = "float32",
) -> dict:
    """Propagate an ``n1 x n2 x n3`` grid (ring included) for ``iters``
    iterations after one untimed warm-up dispatch, in dispatches of
    ``chunk`` steps (default 1: the sample submits one kernel an
    iteration; a count ``chunk`` does not divide is rounded up). The mesh
    is the application's: x whole, the devices over y and z
    (``decompose_zy``), pinned; ``partition`` (x, y, z) overrides it."""
    devices = list(devices) if devices is not None else jax.devices()
    rec = telemetry.get()
    end_realize = rec.open_span("iso3dfd.realize", phase="init")
    grid = Dim3(n1, n2, n3)
    size = Dim3(n1 - 2 * RADIUS, n2 - 2 * RADIUS, n3 - 2 * RADIUS)
    part = Dim3.of(partition) if partition is not None else decompose_zy(
        len(devices))
    for axis, n, blocks in (("n1", size.x, part.x), ("n2", size.y, part.y),
                            ("n3", size.z, part.z)):
        if n < RADIUS or n % blocks or n // blocks < RADIUS:
            raise ValueError(
                f"iso3dfd: the {n} interior cells of {axis} ({axis} - 16) "
                f"do not split into {blocks} equal block(s) of at least "
                f"{RADIUS}: an uneven split is not supported")
    dd = DistributedDomain(size.x, size.y, size.z)
    dd.set_radius(Radius.face_edge_corner(RADIUS, 0, 0))
    dd.set_boundary(periodic=(False, False, False), faces_only=True)
    dd.set_devices(devices)
    # the decomposition the kernel's layout was chosen for: left to itself
    # realize() may split the lane axis
    dd.set_partition(part)
    # prev and next change roles every step, so both hold halos and neither
    # has a second buffer of the domain's; vel is read at the centre only
    handles = {
        "prev": dd.add_data("prev", dtype, buffered=False),
        "next": dd.add_data("next", dtype, buffered=False),
        "vel": dd.add_data("vel", dtype, exchanged=False, buffered=False),
    }
    dd.realize()
    end_realize()

    with rec.span("iso3dfd.init", phase="init"):
        # prev and vel are made anew: the domain's zeroed buffers go first,
        # so that a chip never holds more than the three arrays
        for name in ("prev", "vel"):
            dd.set_curr(handles[name], None)
        prev, vel = make_sample_init(
            dd.spec, dd.sharding(), grid, jnp.dtype(dtype))()
        nxt = dd.get_curr(handles["next"])
        hard_sync(prev)

    chunk = max(1, min(int(chunk or 1), iters))
    with rec.span("iso3dfd.warmup", phase="compile", iters=chunk):
        step = make_iso3dfd_step(dd.halo_exchange, iters=chunk, dtype=dtype)
        prev, nxt = step(prev, nxt, vel)
        hard_sync(prev)

    end_steps = rec.open_span("iso3dfd.steps", phase="step")
    iter_time = Statistics()
    done = 0
    t_loop = time.perf_counter()
    while done < iters:
        (prev, nxt), marks = timed_chunk(scopes.ISO3DFD_LOOP, step, prev, nxt,
                                         vel)
        for _ in range(chunk):
            iter_time.insert(marks.wall_s / chunk)
        rec.chunk_span("iso3dfd.iter", marks, chunk)
        done += chunk
    wall = time.perf_counter() - t_loop
    mcells = size.flatten() * done / wall / 1e6
    if rec.enabled:
        rec.gauge("iso3dfd.iter_trimean_s", iter_time.trimean(),
                  phase="step", unit="s")
        rec.gauge("iso3dfd.mcells_per_s", mcells, phase="step",
                  unit="Mcells/s")
    for name, arr in zip(QUANTITIES, (prev, nxt, vel)):
        dd.set_curr(handles[name], arr)
    end_steps()
    return {
        "processes": jax.process_count(),
        "devices": len(devices),
        "n1": n1, "n2": n2, "n3": n3,
        "iters_run": done,
        "iter_trimean_s": iter_time.trimean(),
        "mcells_per_s": mcells,
        "domain": dd,
        "handles": handles,
    }


def csv_row(r: dict) -> str:
    return (f"iso3dfd,{r['processes']},{r['devices']},{r['n1']},{r['n2']},"
            f"{r['n3']},{r['iters_run']},{r['iter_trimean_s']:e},"
            f"{r['mcells_per_s']:.1f}")


def main(argv: Optional[list] = None) -> int:
    from ..parallel.distributed import maybe_init_from_env
    maybe_init_from_env()
    from ..utils.jax_cache import configure_compile_cache
    configure_compile_cache()
    p = argparse.ArgumentParser(
        description="iso3dfd: acoustic wave propagation, 16th order (TPU)")
    p.add_argument("n1", type=int, help="grid cells along x, ring included")
    p.add_argument("n2", type=int)
    p.add_argument("n3", type=int)
    p.add_argument("iterations", type=int, nargs="?", default=10)
    p.add_argument("--chunk", type=int, default=None,
                   help="steps a dispatch (default 1)")
    p.add_argument("--cpu", type=int, default=0)
    from ._bench_common import add_metrics_flags, start_metrics
    add_metrics_flags(p)
    args = p.parse_args(argv)
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    rec = start_metrics(args, "iso3dfd")
    r = run(args.n1, args.n2, args.n3, iters=args.iterations,
            chunk=args.chunk)
    print(csv_row(r))
    log.info(timer.report())
    if rec.enabled:
        rec.record_timer_buckets()
        rec.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
