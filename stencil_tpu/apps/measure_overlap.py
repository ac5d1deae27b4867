"""measure_overlap — does the fused step actually hide the exchange?

TPU-native analogue of the reference's ``measure-buf-exchange``
(reference: bin/measure_buf_exchange.cu:10-19), which timed a spin kernel
concurrent with peer copies to demonstrate stream overlap. Here overlap is
XLA's scheduling of the halo ``ppermute``s concurrently with the interior
sweep inside one jitted step, so the measurement is four timed variants of
the same jacobi workload on the same mesh:

- ``compute``:  full sweep, no exchange at all (the compute floor)
- ``exchange``: exchange only (the communication cost)
- ``serial``:   exchange-then-full-sweep in one jit (overlap=False path)
- ``overlap``:  interior sweep / exchange / exterior sweeps in one jit
                (overlap=True path — the reference's signature structure,
                bin/jacobi3d.cu:296-368)

Reported: ``hidden = t_serial - t_overlap`` (the exchange time the
overlapped structure recovers) and ``hidden_frac = hidden / t_exchange``
(1.0 = the exchange is fully hidden behind interior compute; <= 0 = the
structure hides nothing). ``--trace DIR`` additionally writes a
``jax.profiler`` trace of one overlapped chunk for inspection in
TensorBoard/Perfetto — the nsys-workflow analogue (reference:
README.md:91-130).

CSV: devices,x,y,z,radius,iters,compute_s,exchange_s,serial_s,overlap_s,
hidden_s,hidden_frac

Note: with --pallas the serial/overlap variants run the fused-kernel fast
path (the overlap variant is the full-sweep-on-pre-exchange-data + shell
patch structure of ops/jacobi.py; its dataflow independence is machine-
checked by tests/test_overlap_hlo.py). Pallas kernels execute on TPU
only, so --pallas requires real chips — the default XLA path is what the
virtual CPU mesh can run.

Usage: python -m stencil_tpu.apps.measure_overlap --cpu 8 --x 64
"""

from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp

from ..api import DistributedDomain
from ..geometry import Dim3, Rect3
from ..ops.jacobi import INIT_TEMP, jacobi_sweep, make_jacobi_loop, sphere_sel
from ..parallel.exchange import BLOCK_PSPEC, shard_blocks
from ..utils import logging as log
from ..utils import timer
from ..utils.statistics import Statistics
from ..utils.sync import hard_sync
from .jacobi3d import weak_scale


def _compute_only_loop(dd: DistributedDomain, iters: int):
    """Full-region sweep with NO exchange — the compute floor."""
    spec = dd.spec
    off = spec.compute_offset()
    compute = Rect3(off, off + spec.base)

    def body(curr, nxt):
        out = jacobi_sweep(curr, nxt, compute)
        return out, curr

    def many(curr, nxt):
        return jax.lax.fori_loop(0, iters, lambda _, cn: body(*cn), (curr, nxt))

    fn = jax.shard_map(
        many,
        mesh=dd.mesh,
        in_specs=(BLOCK_PSPEC, BLOCK_PSPEC),
        out_specs=(BLOCK_PSPEC, BLOCK_PSPEC),
    )
    return jax.jit(fn, donate_argnums=(0, 1))


def _time(fn, state, rounds: int, bucket: str):
    state = fn(*state) if isinstance(state, tuple) else fn(state)
    hard_sync(state)
    st = Statistics()
    for _ in range(rounds):
        t0 = time.perf_counter()
        with timer.timed(bucket):
            state = fn(*state) if isinstance(state, tuple) else fn(state)
            hard_sync(state)
        st.insert(time.perf_counter() - t0)
    return st.trimean(), state


def run(
    x: int = 64,
    y: int = 64,
    z: int = 64,
    radius: int = 1,
    iters: int = 10,
    rounds: int = 3,
    devices=None,
    weak: bool = True,
    use_pallas: Optional[bool] = False,
    trace_dir: str = "",
) -> dict:
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    size = weak_scale(x, y, z, n) if weak else Dim3(x, y, z)

    dd = DistributedDomain(size.x, size.y, size.z)
    dd.set_radius(radius)
    dd.set_devices(devices)
    h = dd.add_data("temperature", "float32")
    dd.realize()
    sharding = dd.sharding()
    shape = dd.spec.stacked_shape_zyx()
    dd.set_curr(h, jax.device_put(jnp.full(shape, INIT_TEMP, jnp.float32), sharding))
    sel = shard_blocks(sphere_sel(size), dd.spec, dd.mesh)
    curr, nxt = dd.get_curr(h), dd.get_next(h)

    ex = dd.halo_exchange
    t_comp, (curr, nxt) = _time(
        _compute_only_loop(dd, iters), (curr, nxt), rounds, "overlap.compute"
    )
    t_exch, state = _time(ex.make_loop(iters), {0: curr}, rounds, "overlap.exchange")
    curr = state[0]
    serial_fn = make_jacobi_loop(ex, iters, overlap=False, use_pallas=use_pallas)
    t_serial, (curr, nxt) = _time(
        lambda c, x_: serial_fn(c, x_, sel), (curr, nxt), rounds, "overlap.serial"
    )
    overlap_fn = make_jacobi_loop(ex, iters, overlap=True, use_pallas=use_pallas)
    t_overlap, (curr, nxt) = _time(
        lambda c, x_: overlap_fn(c, x_, sel), (curr, nxt), rounds, "overlap.overlap"
    )

    if trace_dir:
        with jax.profiler.trace(trace_dir):
            curr, nxt = overlap_fn(curr, nxt, sel)
            hard_sync(curr)
        log.info(f"profiler trace written under {trace_dir}")

    hidden = t_serial - t_overlap
    hidden_frac = hidden / t_exch if t_exch > 0 else 0.0
    return {
        "devices": n,
        "x": size.x,
        "y": size.y,
        "z": size.z,
        "radius": radius,
        "iters": iters,
        "compute_s": t_comp,
        "exchange_s": t_exch,
        "serial_s": t_serial,
        "overlap_s": t_overlap,
        "hidden_s": hidden,
        "hidden_frac": hidden_frac,
        "domain": dd,
    }


def csv_row(r: dict) -> str:
    return (
        f"measure_overlap,{r['devices']},{r['x']},{r['y']},{r['z']},{r['radius']},"
        f"{r['iters']},{r['compute_s']:.6f},{r['exchange_s']:.6f},"
        f"{r['serial_s']:.6f},{r['overlap_s']:.6f},{r['hidden_s']:.6f},"
        f"{r['hidden_frac']:.3f}"
    )


def main(argv: Optional[list] = None) -> int:
    from ..parallel.distributed import maybe_init_from_env
    maybe_init_from_env()
    from ..utils.jax_cache import configure_compile_cache
    configure_compile_cache()
    p = argparse.ArgumentParser(description="comm/compute overlap measurement (TPU)")
    p.add_argument("--x", type=int, default=64)
    p.add_argument("--y", type=int, default=64)
    p.add_argument("--z", type=int, default=64)
    p.add_argument("--radius", type=int, default=1)
    p.add_argument("--iters", type=int, default=10, help="iterations per fused chunk")
    p.add_argument("--rounds", type=int, default=3, help="timed chunks per variant")
    p.add_argument("--no-weak", action="store_true")
    p.add_argument("--pallas", action="store_true",
                   help="measure the Pallas sweep path instead of XLA")
    p.add_argument("--trace", type=str, default="",
                   help="write a jax.profiler trace of one overlapped chunk here")
    p.add_argument("--cpu", type=int, default=0, help="force N virtual CPU devices")
    from ._bench_common import add_metrics_flags, finish_metrics, start_metrics
    add_metrics_flags(p)
    args = p.parse_args(argv)
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    rec = start_metrics(args, "measure_overlap")
    r = run(
        args.x, args.y, args.z,
        radius=args.radius,
        iters=args.iters,
        rounds=args.rounds,
        devices=jax.devices()[: args.cpu] if args.cpu else None,
        weak=not args.no_weak,
        use_pallas=True if args.pallas else False,
        trace_dir=args.trace,
    )
    print(csv_row(r))
    log.info(
        f"exchange {r['exchange_s']*1e3:.2f} ms/chunk, hidden "
        f"{r['hidden_s']*1e3:.2f} ms ({r['hidden_frac']*100:.0f}% of exchange)"
    )
    log.info(timer.report())
    for key in ("compute_s", "exchange_s", "serial_s", "overlap_s", "hidden_s"):
        rec.gauge(f"overlap.{key}", r[key], phase="step", unit="s")
    rec.gauge("overlap.hidden_frac", r["hidden_frac"], phase="step")
    finish_metrics(rec)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
