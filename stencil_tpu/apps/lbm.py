"""lbm — D3Q19 lattice-Boltzmann (BGK) in a periodic box, a few steps a
dispatch.

TPU-native port of the periodic-box benchmark of FluidX3D
(github.com/ProjectPhysX/FluidX3D: ``src/setup.cpp``'s benchmark set-up
with ``D3Q19``, ``SRT`` and FP32 storage in ``src/defines.hpp``: a box with
no boundary cells, kinematic viscosity 1.0 in lattice units, reported in
MLUPs/s) on the lattice and the two-grid update of SPEC CPU2006 470.lbm
(``lbm.c``, ``LBM_performStreamCollide``). The 19 populations are 19
quantities of one ``DistributedDomain``, each with the radius of the one
side it is read from (``ops/lbm.population_radius``), double-buffered (the
domain's ``curr`` and ``next`` are SPEC's two grids); a dispatch is
``ops/lbm.make_lbm_step``'s one program of ``chunk`` steps.

The application's own initial state is a Taylor-Green vortex at rest
density: ``rho = 1``, ``u = U0 (sin X cos Y cos Z, -cos X sin Y cos Z, 0)``
with ``X = 2 pi x / nx`` (and so on) and ``U0 = 0.05``, every population at
its equilibrium. Mass and the three momenta of the box are printed before
and after: the exact update keeps all four.

  lbm,<processes>,<devices>,<x>,<y>,<z>,<steps>,<trimean s/step>,<MLUPs/s>,<mass before>,<mass after>

Usage: python -m stencil_tpu.apps.lbm 384 [--steps 50] [--cpu 4]
       python -m stencil_tpu.apps.lbm --x 256 --y 256 --z 512
"""

from __future__ import annotations

import argparse
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..api import DistributedDomain
from ..astaroth.reductions import Reductions
from ..geometry import Dim3, decompose_zy
from ..obs import scopes, telemetry
from ..ops.lbm import (Q, VELOCITIES, WEIGHTS, domain_radius, make_lbm_step,
                       omega_of, population_radius)
from ..utils import logging as log
from ..utils import timer
from ..utils.statistics import Statistics
from ..utils.sync import hard_sync, timed_chunk

U0 = 0.05               # the vortex's peak speed, in lattice units
DEFAULT_CHUNK = 5       # steps a dispatch


def make_vortex_init(spec, sharding, dtype):
    """``init() -> [19 arrays]``, born sharded: the Taylor-Green vortex at
    rest density, every population at its equilibrium. Every allocated
    cell holds what the cell it mirrors holds."""
    shape = spec.stacked_shape_zyx()
    off, base, g = spec.compute_offset(), spec.base, spec.global_size

    def init():
        phase = []
        for bdim, (o, b, n) in enumerate(((off.z, base.z, g.z),
                                          (off.y, base.y, g.y),
                                          (off.x, base.x, g.x))):
            block = lax.broadcasted_iota(jnp.int32, shape, bdim)
            local = lax.broadcasted_iota(jnp.int32, shape, bdim + 3)
            cell = jnp.mod(block * b + local - o, n)
            phase.append(cell.astype(jnp.float32) * (2.0 * math.pi / n))
        z, y, x = phase
        u = (U0 * jnp.sin(x) * jnp.cos(y) * jnp.cos(z),
             -U0 * jnp.cos(x) * jnp.sin(y) * jnp.cos(z), 0.0)
        usq = u[0] * u[0] + u[1] * u[1]
        out = []
        for c, w in zip(VELOCITIES, WEIGHTS):
            cu = c[0] * u[0] + c[1] * u[1]
            out.append((w * (1.0 + 3.0 * cu + 4.5 * cu * cu - 1.5 * usq))
                       .astype(dtype))
        return out

    return jax.jit(init, out_shardings=[sharding] * Q)


def make_invariants(ex):
    """``read(lattice) -> (mass, momentum x, y, z)`` over the owned cells
    of the box, through ``astaroth/reductions.py``'s masked sums."""
    red = Reductions(ex)
    moment = jax.jit(lambda arrays, signs: sum(
        s * a for s, a in zip(signs, arrays) if s), static_argnums=1)

    def read(lattice):
        out = [red.scal(moment(lattice, (1,) * Q))["sum"]]
        for axis in range(3):
            out.append(red.scal(moment(
                lattice, tuple(c[axis] for c in VELOCITIES)))["sum"])
        return tuple(out)

    return read


def run(
    n: Optional[int] = None,
    x: Optional[int] = None,
    y: Optional[int] = None,
    z: Optional[int] = None,
    nu: float = 1.0,
    dtype: str = "float32",
    steps: int = 50,
    devices=None,
    partition=None,
    chunk: Optional[int] = None,
) -> dict:
    """Step an ``n^3`` (or ``x`` by ``y`` by ``z``) periodic box of
    viscosity ``nu`` for ``steps`` steps after one untimed warm-up
    dispatch, in dispatches of ``chunk`` steps (default 5: a diagnostic
    every few steps; a count ``chunk`` does not divide is rounded up). The
    mesh is the application's: x whole, the devices over y and z
    (``decompose_zy``), pinned; ``partition`` (x, y, z) overrides it.
    Where x is whole the domain lies tight-x (no x halo: the x wrap is the
    kernel's lane roll)."""
    if (n is None) == (x is None and y is None and z is None):
        raise ValueError("lbm takes n, or x, y and z, one of the two")
    if n is None and None in (x, y, z):
        raise ValueError("lbm takes all three of x, y and z")
    size = Dim3(n, n, n) if n is not None else Dim3(x, y, z)
    devices = list(devices) if devices is not None else jax.devices()
    rec = telemetry.get()
    end_realize = rec.open_span("lbm.realize", phase="init")
    part = Dim3.of(partition) if partition is not None else decompose_zy(
        len(devices))
    for axis, cells, blocks in (("x", size.x, part.x), ("y", size.y, part.y),
                                ("z", size.z, part.z)):
        if cells % blocks:
            raise ValueError(
                f"lbm: the {cells} cells of {axis} do not split into "
                f"{blocks} equal blocks: an uneven split is not supported")
    tight = part.x == 1
    dd = DistributedDomain(size.x, size.y, size.z)
    dd.set_radius(domain_radius(tight))
    dd.set_devices(devices)
    dd.set_partition(part)
    handles = [dd.add_data(f"f{i}", dtype,
                           radius=population_radius(i, tight))
               for i in range(Q)]
    dd.realize()
    ex = dd.halo_exchange
    end_realize()

    def take(which):
        """A lattice out of its domain (a dispatch donates it: the domain
        would be left holding deleted buffers)."""
        get, put = ((dd.get_curr, dd.set_curr) if which == "curr"
                    else (dd.get_next, dd.set_next))
        arrays = [get(h) for h in handles]
        for h in handles:
            put(h, None)
        return arrays

    with rec.span("lbm.init", phase="init"):
        # made anew: the domain's zeroed current lattice goes first, so
        # that a chip never holds more than the two
        take("curr")
        curr = make_vortex_init(dd.spec, dd.sharding(), jnp.dtype(dtype))()
        nxt = take("next")
        invariants = make_invariants(ex)
        before = invariants(curr)
        hard_sync(curr)

    chunk = max(1, min(int(chunk or DEFAULT_CHUNK), steps))
    with rec.span("lbm.warmup", phase="compile", iters=chunk):
        step = make_lbm_step(ex, omega_of(nu), dtype=dtype, iters=chunk)
        curr, nxt = step(curr, nxt)
        hard_sync(curr)

    end_steps = rec.open_span("lbm.steps", phase="step")
    step_time = Statistics()
    done = 0
    t_loop = time.perf_counter()
    while done < steps:
        (curr, nxt), marks = timed_chunk(scopes.LBM_STEP, step, curr, nxt)
        for _ in range(chunk):
            step_time.insert(marks.wall_s / chunk)
        rec.chunk_span("lbm.step", marks, chunk)
        done += chunk
    wall = time.perf_counter() - t_loop
    mlups = size.flatten() * done / wall / 1e6
    after = invariants(curr)
    if rec.enabled:
        rec.gauge("lbm.step_trimean_s", step_time.trimean(), phase="step",
                  unit="s")
        rec.gauge("lbm.mlups", mlups, phase="step", unit="MLUPs/s")
    for h, a, b in zip(handles, curr, nxt):
        dd.set_curr(h, a)
        dd.set_next(h, b)
    end_steps()
    return {
        "processes": jax.process_count(),
        "devices": len(devices),
        "x": size.x, "y": size.y, "z": size.z,
        "steps_run": done + chunk,          # the warm-up's steps too
        "steps_timed": done,
        "step_trimean_s": step_time.trimean(),
        "mlups": mlups,
        "invariants_before": before,
        "invariants_after": after,
        "domain": dd,
        "handles": handles,
    }


def csv_row(r: dict) -> str:
    return (f"lbm,{r['processes']},{r['devices']},{r['x']},{r['y']},{r['z']},"
            f"{r['steps_timed']},{r['step_trimean_s']:e},{r['mlups']:.1f},"
            f"{r['invariants_before'][0]:.9e},{r['invariants_after'][0]:.9e}")


def main(argv: Optional[list] = None) -> int:
    from ..parallel.distributed import maybe_init_from_env
    maybe_init_from_env()
    from ..utils.jax_cache import configure_compile_cache
    configure_compile_cache()
    p = argparse.ArgumentParser(
        description="lbm: D3Q19 lattice-Boltzmann in a periodic box (TPU)")
    p.add_argument("n", type=int, nargs="?", default=None,
                   help="cells an axis (a cube)")
    p.add_argument("--x", type=int, default=None)
    p.add_argument("--y", type=int, default=None)
    p.add_argument("--z", type=int, default=None)
    p.add_argument("--nu", type=float, default=1.0,
                   help="kinematic viscosity, lattice units")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--dtype", default="float32",
                   choices=("float32", "float64"))
    p.add_argument("--chunk", type=int, default=None,
                   help=f"steps a dispatch (default {DEFAULT_CHUNK})")
    p.add_argument("--cpu", type=int, default=0)
    from ._bench_common import add_metrics_flags, start_metrics
    add_metrics_flags(p)
    args = p.parse_args(argv)
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    if args.dtype == "float64":
        jax.config.update("jax_enable_x64", True)
    rec = start_metrics(args, "lbm")
    r = run(n=args.n, x=args.x, y=args.y, z=args.z, nu=args.nu,
            dtype=args.dtype, steps=args.steps, chunk=args.chunk)
    print(csv_row(r))
    for name, a, b in zip(("mass", "momentum x", "momentum y", "momentum z"),
                          r["invariants_before"], r["invariants_after"]):
        print(f"lbm: {name} {a:.9e} -> {b:.9e} over {r['steps_run']} steps")
    log.info(timer.report())
    if rec.enabled:
        rec.record_timer_buckets()
        rec.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
