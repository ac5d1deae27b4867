"""exchange-weak — pure halo-exchange benchmark, weak-scaled.

TPU-native port of the reference benchmark (reference: bin/exchange_weak.cu):
radius-3 halos, four float quantities, domain weak-scaled by the prime
factors of the device count, trimean over N exchanges. CSV row matches the
reference header (bin/exchange_weak.cu:184-196):

  exchange,<method>,<naive>,x,y,z,s,ldx,ldy,ldz,<bytes>,iters,gpus,nodes,ranks,trimean(s)

Usage: python -m stencil_tpu.apps.exchange_weak 512 512 512 30 [--naive|--random]
"""

from __future__ import annotations

import argparse
from typing import Optional

import jax

from ..geometry import Dim3
from ..parallel import Method
from ._bench_common import (
    add_metrics_flags, placement_from_flags, start_metrics, time_exchange,
)
from .jacobi3d import weak_scale
from ..geometry import Radius
from ..utils import logging as log


def run(
    x: int,
    y: int,
    z: int,
    iters: int = 30,
    naive: bool = False,
    random_: bool = False,
    method: Method = Method.AXIS_COMPOSED,
    devices=None,
    weak: bool = True,
    radius: int = 3,
    prefix: str = "",
    chunk: int = 10,
) -> dict:
    devices = list(devices) if devices is not None else jax.devices()
    size = weak_scale(x, y, z, len(devices)) if weak else Dim3(x, y, z)
    r = time_exchange(
        size,
        Radius.constant(radius),
        iters,
        method=method,
        devices=devices,
        placement=placement_from_flags(naive, random_),
        quantities=4,
        prefix=prefix,
        chunk=chunk,
    )
    r.update(
        app="exchange",
        method=method.value,
        naive=int(naive),
        x=size.x,
        y=size.y,
        z=size.z,
        iters=iters,
        nodes=jax.process_count(),
        ranks=jax.process_count(),
    )
    return r


def csv_row(r: dict) -> str:
    ld = r["local_size"]
    return (
        f"{r['app']},{r['method']},{r['naive']},{r['x']},{r['y']},{r['z']},"
        f"{r['x'] * r['y'] * r['z']},{ld.x},{ld.y},{ld.z},"
        f"{r['bytes_logical']},{r['iters']},{r['devices']},{r['nodes']},"
        f"{r['ranks']},{r['trimean_s']:e}"
    )


def main(argv: Optional[list] = None) -> int:
    from ..parallel.distributed import maybe_init_from_env
    maybe_init_from_env()
    from ..utils.jax_cache import configure_compile_cache
    configure_compile_cache()
    p = argparse.ArgumentParser(description="weak-scaled halo exchange benchmark")
    p.add_argument("x", type=int)
    p.add_argument("y", type=int)
    p.add_argument("z", type=int)
    p.add_argument("iters", type=int)
    p.add_argument("--prefix", default="")
    p.add_argument("--naive", action="store_true", help="Trivial placement")
    p.add_argument("--random", action="store_true", help="IntraNodeRandom placement")
    p.add_argument("--direct26", action="store_true")
    p.add_argument("--cpu", type=int, default=0)
    add_metrics_flags(p)
    args = p.parse_args(argv)
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    start_metrics(args, "exchange_weak")
    r = run(
        args.x,
        args.y,
        args.z,
        iters=args.iters,
        naive=args.naive,
        random_=args.random,
        method=Method.DIRECT26 if args.direct26 else Method.AXIS_COMPOSED,
        prefix=args.prefix,
    )
    print(csv_row(r))
    log.info(f"exchange {r['gb_per_s']:.2f} GB/s logical halo bytes")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
