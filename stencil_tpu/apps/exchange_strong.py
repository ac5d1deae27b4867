"""exchange-strong — pure halo-exchange benchmark, fixed total domain.

TPU-native port of the reference benchmark (reference:
bin/exchange_strong.cu): same measurement and CSV row as exchange-weak but
without weak scaling, for strong-scaling curves.

Usage: python -m stencil_tpu.apps.exchange_strong 512 512 512 30 [--naive|--random]
"""

from __future__ import annotations

import argparse
from typing import Optional

import jax

from ..parallel import Method
from ..utils import logging as log
from . import exchange_weak


def run(x, y, z, iters=30, **kw) -> dict:
    return exchange_weak.run(x, y, z, iters=iters, weak=False, **kw)


def main(argv: Optional[list] = None) -> int:
    from ..parallel.distributed import maybe_init_from_env
    maybe_init_from_env()
    from ..utils.jax_cache import configure_compile_cache
    configure_compile_cache()
    p = argparse.ArgumentParser(description="strong-scaled halo exchange benchmark")
    p.add_argument("x", type=int)
    p.add_argument("y", type=int)
    p.add_argument("z", type=int)
    p.add_argument("iters", type=int)
    p.add_argument("--prefix", default="")
    p.add_argument("--naive", action="store_true")
    p.add_argument("--random", action="store_true")
    p.add_argument("--direct26", action="store_true")
    p.add_argument("--cpu", type=int, default=0)
    from ._bench_common import add_metrics_flags, start_metrics
    add_metrics_flags(p)
    args = p.parse_args(argv)
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    start_metrics(args, "exchange_strong")
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
    print(exchange_weak.csv_row(r))
    log.info(f"exchange {r['gb_per_s']:.2f} GB/s logical halo bytes")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
