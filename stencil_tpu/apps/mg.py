"""mg — NAS Parallel Benchmarks MG: a multigrid V-cycle for a periodic
Poisson problem, one iteration a dispatch.

TPU-native port of NPB 3.x's kernel MG (``NPB3.x-SER/MG/mg.f``; Bailey et
al., "The NAS Parallel Benchmarks", RNR-94-007, section 2.3): ``lap(u) = v``
on a periodic cube of ``n = 2^lt`` cells an axis by ``nit`` V-cycles over
the levels ``n, n/2, ..., 2``, with v = +1 at ten cells and -1 at ten
(``zran3``: the ten largest and ten smallest draws of the benchmark's own
linear congruential generator), u = 0, and the L2 norm of the last residual
verified against the class's published value. Every level is a
``DistributedDomain`` of its own on the same devices and partition (radius
1, periodic, quantities u and r, and v on the finest), its halos filled by
its own ``HaloExchange`` after every operator (the source's ``comm3``); the
iteration is ``ops/mg.make_mg_iter``'s one program.

  mg,<processes>,<devices>,<class>,<n>,<iterations>,<trimean s/iter>,<Mcells/s>,<norm>,<NPB's norm>,<verified>

Usage: python -m stencil_tpu.apps.mg C [--cpu 4]
       python -m stencil_tpu.apps.mg --n 64 --nit 4
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
from ..astaroth.reductions import Reductions
from ..geometry import Dim3, decompose_zy
from ..obs import scopes, telemetry
from ..ops.mg import (S_LARGE, S_SMALL, level_radius, level_sizes,
                      make_mg_iter)
from ..utils import logging as log
from ..utils import timer
from ..utils.statistics import Statistics
from ..utils.sync import hard_sync, timed_chunk

# class -> (cells an axis, iterations, the smoother's weights, the norm
# NPB's verify holds the run to, to 1e-8 relative, in double precision)
CLASSES = {
    "S": (32, 4, S_SMALL, 0.5307707005734e-04),
    "W": (128, 4, S_SMALL, 0.6467329375339e-05),
    "A": (256, 4, S_SMALL, 0.2433365309069e-05),
    "B": (256, 20, S_LARGE, 0.1800564401355e-05),
    "C": (512, 20, S_LARGE, 0.5706732285740e-06),
    "D": (1024, 50, S_LARGE, 0.1583275060440e-09),
}
VERIFY_EPSILON = 1e-8
_LCG_A, _LCG_SEED, _LCG_BITS = 5 ** 13, 314159265, 46
CHARGES = 10


def make_levels(n: int, devices, partition: Dim3, dtype: str):
    """The hierarchy, finest first: one realized ``DistributedDomain`` a
    level on the same devices and partition, periodic, halos by
    ``ops/mg.level_radius``, u and r on every level and v on the finest,
    none with a second buffer (every operator writes in place). Returns
    ``[(domain, handles)]``."""
    out = []
    for m in level_sizes(n):
        for axis, blocks in zip("xyz", (partition.x, partition.y,
                                        partition.z)):
            if m % blocks:
                raise ValueError(
                    f"mg: level {m}^3 does not split into {blocks} equal "
                    f"blocks along {axis}: take a partition whose every "
                    f"axis divides 2 (the coarsest level)")
        dd = DistributedDomain(m, m, m)
        dd.set_radius(level_radius(m, partition))
        dd.set_devices(devices)
        dd.set_partition(partition)
        names = ("u", "r", "v") if m == n else ("u", "r")
        handles = {q: dd.add_data(q, dtype, buffered=False) for q in names}
        dd.realize()
        out.append((dd, handles))
    return out


def _mulmod(a: int, x):
    """``a x mod 2^46`` for a Python int and a uint64 array, in 23-bit
    halves (the products fit 64 bits), as NPB's ``randlc`` splits them."""
    mask = np.uint64((1 << 23) - 1)
    a1, a2 = np.uint64(a >> 23), np.uint64(a & ((1 << 23) - 1))
    x1, x2 = x >> np.uint64(23), x & mask
    t = (a1 * x2 + a2 * x1) & mask
    return ((t << np.uint64(23)) + a2 * x2) & np.uint64((1 << _LCG_BITS) - 1)


def zran3(n: int):
    """``(plus, minus)``: the (z, y, x) cells of the ten largest and ten
    smallest of the n^3 draws, cell (x, y, z) taking draw ``1 + x + n y +
    n^2 z`` of ``s <- 5^13 s mod 2^46`` from 314159265. A plane at a time:
    a plane's first state by a power of the multiplier, the rest by
    doubling, and only the twenty candidates kept (ascending)."""
    mod = 1 << _LCG_BITS
    plane = n * n
    states = np.empty(plane, np.uint64)
    empty = np.zeros(0, np.uint64), np.zeros(0, np.int64)
    best = {True: empty, False: empty}          # largest / smallest so far

    def merge(largest, picked, z):
        vals = np.concatenate([best[largest][0], states[picked]])
        cells = np.concatenate([best[largest][1], picked + z * plane])
        order = np.argsort(vals, kind="stable")
        keep = order[-CHARGES:] if largest else order[:CHARGES]
        best[largest] = vals[keep], cells[keep]

    for z in range(n):
        states[0] = (pow(_LCG_A, z * plane + 1, mod) * _LCG_SEED) % mod
        done = 1
        while done < plane:
            m = min(done, plane - done)
            states[done:done + m] = _mulmod(pow(_LCG_A, done, mod),
                                            states[:m])
            done += m
        # a draw competes only past the weakest of the ten kept so far
        # (the generator's period is 2^44: no two draws are equal)
        for largest in (True, False):
            kept = best[largest][0]
            if len(kept) < CHARGES:
                picked = np.arange(plane)
            elif largest:
                picked = np.flatnonzero(states > kept[0])
            else:
                picked = np.flatnonzero(states < kept[-1])
            if len(picked):
                merge(largest, picked, z)

    def zyx(cells):
        return [(int(c) // plane, int(c) % plane // n, int(c) % n)
                for c in cells]

    return zyx(best[True][1]), zyx(best[False][1])


def make_charges(spec, sharding, dtype):
    """``init(cells, signs) -> v``, born sharded: ``signs[i]`` at the
    (z, y, x) cell ``cells[i]`` and at every halo cell that mirrors it,
    0 elsewhere."""
    shape = spec.stacked_shape_zyx()
    off, base, g = spec.compute_offset(), spec.base, spec.global_size

    def init(cells, signs):
        coords = []
        for bdim, (o, b, m) in enumerate(((off.z, base.z, g.z),
                                          (off.y, base.y, g.y),
                                          (off.x, base.x, g.x))):
            block = lax.broadcasted_iota(jnp.int32, shape, bdim)
            local = lax.broadcasted_iota(jnp.int32, shape, bdim + 3)
            coords.append(jnp.mod(block * b + local - o, m))

        def add(i, v):
            hit = ((coords[0] == cells[i, 0]) & (coords[1] == cells[i, 1])
                   & (coords[2] == cells[i, 2]))
            return jnp.where(hit, signs[i], v)

        return lax.fori_loop(0, cells.shape[0], add,
                             jnp.zeros(shape, dtype))

    return jax.jit(init, out_shardings=sharding)


def run(
    klass: Optional[str] = None,
    n: Optional[int] = None,
    nit: Optional[int] = None,
    dtype: str = "float32",
    devices=None,
    partition=None,
    chunk: Optional[int] = None,
) -> dict:
    """Solve class ``klass`` (or an ``n^3`` cube for ``nit`` iterations
    with class B's smoother) after one untimed warm-up dispatch, in
    dispatches of ``chunk`` iterations (default 1: the residual can be read
    between cycles; a count ``chunk`` does not divide is rounded up), and
    take the norm of the last residual once, at the end. The mesh is the
    application's: x whole, the devices over y and z (``decompose_zy``),
    pinned; ``partition`` (x, y, z) overrides it."""
    if (klass is None) == (n is None):
        raise ValueError("mg takes a class or a size, one of the two")
    if klass is not None:
        if klass not in CLASSES:
            raise ValueError(f"class {klass!r} is not one of {list(CLASSES)}")
        n, class_nit, smoother, published = CLASSES[klass]
        nit = class_nit if nit is None else nit
    else:
        smoother, published = S_LARGE, None
        nit = 4 if nit is None else nit
    devices = list(devices) if devices is not None else jax.devices()
    rec = telemetry.get()
    end_realize = rec.open_span("mg.realize", phase="init")
    part = Dim3.of(partition) if partition is not None else decompose_zy(
        len(devices))
    levels = make_levels(n, devices, part, dtype)
    dd, handles = levels[0]
    end_realize()

    with rec.span("mg.init", phase="init"):
        plus, minus = zran3(n)
        cells = np.asarray(plus + minus, np.int32)
        signs = np.asarray([1.0] * len(plus) + [-1.0] * len(minus), dtype)
        dd.set_curr(handles["v"], None)
        v = make_charges(dd.spec, dd.sharding(), jnp.dtype(dtype))(
            cells, signs)
        hard_sync(v)

    fresh = jax.jit(lambda v: (jnp.zeros_like(v), jnp.copy(v)),
                    out_shardings=(dd.sharding(),) * 2)

    def start():
        """The hierarchy's arrays, taken out of their domains: u = 0 and
        r = v - A u = v on the finest level, made anew once the old pair
        has gone; a lower level's u and r as they stand (each is written
        before it is read)."""
        state = {q: [lv.get_curr(hs[q]) for lv, hs in levels]
                 for q in ("u", "r")}
        for lv, hs in levels:
            for q in ("u", "r"):
                lv.set_curr(hs[q], None)
        state["u"][0] = state["r"][0] = None
        state["u"][0], state["r"][0] = fresh(v)
        return state

    def keep(state):
        for (lv, hs), u, r in zip(levels, state["u"], state["r"]):
            lv.set_curr(hs["u"], u)
            lv.set_curr(hs["r"], r)

    chunk = max(1, min(int(chunk or 1), nit))
    with rec.span("mg.warmup", phase="compile", iters=chunk):
        step = make_mg_iter([lv.halo_exchange for lv, _ in levels],
                            smoother=smoother, dtype=dtype, iters=chunk)
        # the source's own start-up: one iteration, then the data again
        state = step(start(), v)
        hard_sync(state)
        keep(state)
        del state       # or the old finest pair outlives the making of the new
        state = start()

    end_steps = rec.open_span("mg.steps", phase="step")
    iter_time = Statistics()
    done = 0
    t_loop = time.perf_counter()
    while done < nit:
        state, marks = timed_chunk(scopes.MG_ITER, step, state, v)
        for _ in range(chunk):
            iter_time.insert(marks.wall_s / chunk)
        rec.chunk_span("mg.iter", marks, chunk)
        done += chunk
    wall = time.perf_counter() - t_loop
    mcells = n ** 3 * done / wall / 1e6
    norm = Reductions(dd.halo_exchange).scal(state["r"][0])["rms"]
    if rec.enabled:
        rec.gauge("mg.iter_trimean_s", iter_time.trimean(), phase="step",
                  unit="s")
        rec.gauge("mg.mcells_per_s", mcells, phase="step", unit="Mcells/s")
        rec.gauge("mg.rnm2", norm, phase="step")
    keep(state)
    dd.set_curr(handles["v"], v)
    end_steps()
    verified = None
    if published is not None and done == CLASSES[klass][1]:
        verified = abs(norm - published) / published <= VERIFY_EPSILON
    return {
        "processes": jax.process_count(),
        "devices": len(devices),
        "klass": klass or "-",
        "n": n,
        "iters_run": done,
        "iter_trimean_s": iter_time.trimean(),
        "mcells_per_s": mcells,
        "rnm2": norm,
        "published": published,
        "verified": verified,
        "domain": dd,
        "handles": handles,
        "levels": levels,
    }


def csv_row(r: dict) -> str:
    published = "-" if r["published"] is None else f"{r['published']:.13e}"
    verified = {None: "-", True: "yes", False: "no"}[r["verified"]]
    return (f"mg,{r['processes']},{r['devices']},{r['klass']},{r['n']},"
            f"{r['iters_run']},{r['iter_trimean_s']:e},"
            f"{r['mcells_per_s']:.1f},{r['rnm2']:.13e},{published},"
            f"{verified}")


def main(argv: Optional[list] = None) -> int:
    from ..parallel.distributed import maybe_init_from_env
    maybe_init_from_env()
    from ..utils.jax_cache import configure_compile_cache
    configure_compile_cache()
    p = argparse.ArgumentParser(
        description="mg: NPB MG, a multigrid V-cycle an iteration (TPU)")
    p.add_argument("klass", nargs="?", default=None, choices=list(CLASSES),
                   help="NPB class: grid, iterations and smoother")
    p.add_argument("--n", type=int, default=None,
                   help="cells an axis, in place of a class")
    p.add_argument("--nit", type=int, default=None, help="iterations")
    p.add_argument("--dtype", default="float32",
                   choices=("float32", "float64"))
    p.add_argument("--chunk", type=int, default=None,
                   help="iterations a dispatch (default 1)")
    p.add_argument("--cpu", type=int, default=0)
    from ._bench_common import add_metrics_flags, start_metrics
    add_metrics_flags(p)
    args = p.parse_args(argv)
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    if args.dtype == "float64":
        jax.config.update("jax_enable_x64", True)
    rec = start_metrics(args, "mg")
    r = run(klass=args.klass, n=args.n, nit=args.nit, dtype=args.dtype,
            chunk=args.chunk)
    print(csv_row(r))
    log.info(timer.report())
    if rec.enabled:
        rec.record_timer_buckets()
        rec.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
