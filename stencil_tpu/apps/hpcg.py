"""hpcg — HPCG's problem and algorithm: conjugate gradient preconditioned
by a four-level V-cycle with a symmetric Gauss-Seidel smoother, one
iteration a dispatch.

TPU-native port of HPCG 3.1 (Dongarra, Heroux, Luszczek;
``github.com/hpcg-benchmark/hpcg``: ``src/GenerateProblem_ref.cpp``,
``CG_ref.cpp``, ``ComputeMG_ref.cpp``, ``ComputeSYMGS_ref.cpp``,
``main.cpp``): ``A x = b`` on a grid of ``x * y * z`` points, A the
27-point operator with 26 on the diagonal and -1 for every neighbour
inside the grid (homogeneous Dirichlet faces), ``b = A 1`` so that the
exact solution is all ones, ``x0 = 0``, in sets of 50 iterations. NOT an
HPCG rating: the source stores the matrix and forbids using its structure,
this port is matrix-free; it sweeps in eight colours where the source's
reference sweeps lexicographically (its rules allow a reordering); and it
computes in float32. The problem, the algorithm and the iterates are
HPCG's.

Every level of the V-cycle is a ``DistributedDomain`` of its own, fixed on
every axis (``set_boundary(periodic=(False, False, False))``, radius 1,
edges and corners read): the ghost ring is the Dirichlet face, zero and
never written. The iteration is ``ops/hpcg.make_hpcg_iter``'s one program;
the residual norm is read after every dispatch, as a caller that stops on
it would.

  hpcg,<processes>,<devices>,<x>,<y>,<z>,<sets>,<iterations>,<trimean s/iter>,<Mrows/s>,<normr/normr0>,<max |x - 1|>

Usage: python -m stencil_tpu.apps.hpcg 256 [--sets 2] [--cpu 1]
       python -m stencil_tpu.apps.hpcg --x 128 --y 128 --z 256
"""

from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..api import DistributedDomain
from ..geometry import Dim3
from ..obs import scopes, telemetry
from ..ops.hpcg import (COARSE, FINE, LEVELS, SCALARS, SET_ITERS,
                        level_radius, level_sizes, make_hpcg_iter)
from ..ops.pallas_hpcg import DIAGONAL
from ..utils import logging as log
from ..utils import timer
from ..utils.statistics import Statistics
from ..utils.sync import hard_sync, timed_chunk


def make_levels(size, devices, dtype: str):
    """The hierarchy, finest first: one realized ``DistributedDomain`` a
    level on the same device, ONE block, fixed on every axis, the ring by
    ``ops/hpcg.level_radius``; x, r, p, z, t and b on the finest, x, r and
    t below (no t on the coarsest), none with a second buffer. Returns
    ``[(domain, handles)]``."""
    out = []
    sizes = level_sizes(size)
    for i, (x, y, z) in enumerate(sizes):
        dd = DistributedDomain(x, y, z)
        dd.set_radius(level_radius((x, y, z)))
        dd.set_boundary(periodic=(False, False, False))
        dd.set_devices(devices)
        dd.set_partition(Dim3(1, 1, 1))
        names = (FINE + ("b",) if i == 0 else
                 tuple(q for q in COARSE if q != "t" or i + 1 < LEVELS))
        handles = {q: dd.add_data(q, dtype, exchanged=False, buffered=False)
                   for q in names}
        dd.realize()
        out.append((dd, handles))
    return out


def _cells(spec):
    """Per axis (z, y, x) every allocated cell's index in the grid (ONE
    block: local less the ring's offset) with the axis's extent, and the
    mask of the owned cells."""
    shape = spec.stacked_shape_zyx()
    off, base = spec.compute_offset(), spec.base
    cells, owned = [], True
    for bdim, (o, n) in enumerate(((off.z, base.z), (off.y, base.y),
                                   (off.x, base.x))):
        cell = lax.broadcasted_iota(jnp.int32, shape, bdim + 3) - o
        cells.append((cell, n))
        owned = owned & (cell >= 0) & (cell < n)
    return cells, owned


def make_rhs(spec, sharding, dtype):
    """``init() -> b``, born sharded: ``26 - (neighbours inside the
    grid)`` on the owned cells (A applied to all ones), 0 on the ring."""

    def init():
        cells, owned = _cells(spec)
        count = 1
        for cell, n in cells:
            count = count * (3 - (cell == 0) - (cell == n - 1))
        return jnp.where(owned, DIAGONAL + 1.0 - count, 0.0).astype(dtype)

    return jax.jit(init, out_shardings=sharding)


def make_error(spec, sharding):
    """``err(x) -> max |x - 1|`` over the owned cells."""

    def err(x):
        _, owned = _cells(spec)
        return jnp.max(jnp.where(owned, jnp.abs(x - 1.0), 0.0))

    return jax.jit(err, in_shardings=(sharding,))


def take_state(levels, scalars=None) -> dict:
    """The program's state out of the domains that hold its arrays (a
    dispatch donates them: a domain would be left with a deleted buffer),
    with ``scalars`` or those of a state whose next dispatch opens a set."""
    (dd, hs), lower = levels[0], levels[1:]
    state = {q: dd.get_curr(hs[q]) for q in FINE}
    state["coarse"] = [{q: lv.get_curr(h[q]) for q in h} for lv, h in lower]
    for lv, h in levels:
        for q in h:
            if q != "b":
                lv.set_curr(h[q], None)
    dtype = state["x"].dtype
    state.update(scalars or dict(
        {q: device_scalar(dd, 0, dtype) for q in SCALARS},
        rtz=device_scalar(dd, 1, dtype),
        k=device_scalar(dd, SET_ITERS, "int32")))
    return state


def device_scalar(dd, value, dtype):
    """``value`` as the program holds its scalars: committed to the
    domain's mesh, replicated. (A scalar that is merely ``jnp.asarray``
    lies elsewhere in jit's eyes, and the iteration is compiled again for
    it.)"""
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.device_put(np.asarray(value, dtype),
                          NamedSharding(dd.sharding().mesh, PartitionSpec()))


def keep_state(levels, state) -> dict:
    """The arrays back into their domains; returns the scalars."""
    (dd, hs), lower = levels[0], levels[1:]
    for q in FINE:
        dd.set_curr(hs[q], state[q])
    for (lv, h), held in zip(lower, state["coarse"]):
        for q in h:
            lv.set_curr(h[q], held[q])
    return {q: state[q] for q in SCALARS + ("k",)}


def run(
    n: Optional[int] = None,
    x: Optional[int] = None,
    y: Optional[int] = None,
    z: Optional[int] = None,
    sets: int = 1,
    dtype: str = "float32",
    devices=None,
) -> dict:
    """Solve HPCG's own problem on an ``n^3`` (or ``x * y * z``) grid in
    ``sets`` sets of 50 iterations after one untimed warm-up dispatch, one
    iteration a dispatch, the residual norm read after each. Every set
    starts from ``x = 0`` inside the program. Reports ``normr / normr0``
    after each set and the error against the exact solution of ones."""
    if n is not None:
        x = y = z = int(n)
    if None in (x, y, z):
        raise ValueError("hpcg takes n, or x, y and z")
    devices = list(devices) if devices is not None else jax.devices()
    if len(devices) != 1:
        raise ValueError(
            f"hpcg runs on ONE device, not {len(devices)}: the weak-scaled "
            f"form (a halo before every operator and colour, psum under "
            f"every dot) is not written yet; pass devices=[one]")
    rec = telemetry.get()
    end_realize = rec.open_span("hpcg.realize", phase="init")
    levels = make_levels((x, y, z), devices, dtype)
    dd, handles = levels[0]
    end_realize()

    with rec.span("hpcg.init", phase="init"):
        dd.set_curr(handles["b"], None)
        b = make_rhs(dd.spec, dd.sharding(), jnp.dtype(dtype))()
        hard_sync(b)
        dd.set_curr(handles["b"], b)
        error = make_error(dd.spec, dd.sharding())

    with rec.span("hpcg.warmup", phase="compile", iters=1):
        step = make_hpcg_iter([lv.halo_exchange for lv, _ in levels],
                              dtype=dtype)
        # one iteration, then the set again from its start (the state's
        # count says so: nothing is made anew)
        state = step(take_state(levels), b)
        hard_sync(state)
        state["k"] = device_scalar(dd, SET_ITERS, "int32")

    end_steps = rec.open_span("hpcg.steps", phase="step")
    iter_time = Statistics()
    relative, errors = [], []
    t_loop = time.perf_counter()
    for _ in range(int(sets)):
        for _ in range(SET_ITERS):
            # the wait is for what a stopping test reads
            state, marks = timed_chunk(scopes.HPCG_ITER, step, state, b,
                                       scalar=lambda st: st["normr"])
            normr = marks.value
            iter_time.insert(marks.wall_s)
            rec.chunk_span("hpcg.iter", marks, 1)
        normr0 = float(state["normr0"])
        relative.append(normr / normr0)
        errors.append(float(error(state["x"])))
        if rec.enabled:
            rec.gauge("hpcg.normr", normr, phase="step")
            rec.gauge("hpcg.normr0", normr0, phase="step")
    wall = time.perf_counter() - t_loop
    iters = int(sets) * SET_ITERS
    mrows = x * y * z * iters / wall / 1e6 if iters else 0.0
    if rec.enabled and iters:
        rec.gauge("hpcg.iter_trimean_s", iter_time.trimean(), phase="step",
                  unit="s")
        rec.gauge("hpcg.mcells_per_s", mrows, phase="step", unit="Mcells/s")
    scalars = keep_state(levels, state)
    end_steps()
    return {
        "processes": jax.process_count(),
        "devices": len(devices),
        "size": (x, y, z),
        "sets": int(sets),
        "iters_run": iters,
        "iter_trimean_s": iter_time.trimean() if iters else 0.0,
        "mcells_per_s": mrows,
        "relative_residual": relative,
        "error": errors,
        "scalars": scalars,
        "domain": dd,
        "handles": handles,
        "levels": levels,
    }


def csv_row(r: dict) -> str:
    x, y, z = r["size"]
    rel = r["relative_residual"][-1] if r["relative_residual"] else float("nan")
    err = r["error"][-1] if r["error"] else float("nan")
    return (f"hpcg,{r['processes']},{r['devices']},{x},{y},{z},{r['sets']},"
            f"{r['iters_run']},{r['iter_trimean_s']:e},"
            f"{r['mcells_per_s']:.1f},{rel:.6e},{err:.6e}")


def main(argv: Optional[list] = None) -> int:
    from ..parallel.distributed import maybe_init_from_env
    maybe_init_from_env()
    from ..utils.jax_cache import configure_compile_cache
    configure_compile_cache()
    p = argparse.ArgumentParser(
        description="hpcg: HPCG's preconditioned CG, an iteration a "
                    "dispatch (TPU)")
    p.add_argument("n", nargs="?", type=int, default=None,
                   help="points an axis of a cube")
    p.add_argument("--x", type=int, default=None)
    p.add_argument("--y", type=int, default=None)
    p.add_argument("--z", type=int, default=None)
    p.add_argument("--sets", type=int, default=1,
                   help="sets of 50 iterations")
    p.add_argument("--dtype", default="float32",
                   choices=("float32", "float64"))
    p.add_argument("--cpu", type=int, default=0)
    from ._bench_common import add_metrics_flags, start_metrics
    add_metrics_flags(p)
    args = p.parse_args(argv)
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    if args.dtype == "float64":
        jax.config.update("jax_enable_x64", True)
    rec = start_metrics(args, "hpcg")
    r = run(n=args.n, x=args.x, y=args.y, z=args.z, sets=args.sets,
            dtype=args.dtype, devices=jax.devices()[:1])
    for i, (rel, err) in enumerate(zip(r["relative_residual"], r["error"])):
        log.info(f"set {i + 1}: normr/normr0 {rel:.6e}, max |x - 1| "
                 f"{err:.6e}")
    print(csv_row(r))
    log.info(timer.report())
    if rec.enabled:
        rec.record_timer_buckets()
        rec.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
