"""pingpong — inter-device transfer latency/bandwidth microbenchmark.

TPU-native analogue of the reference's MPI ping-pong (reference:
bin/pingpong.cu): instead of MPI_Send/Recv between ranks, a buffer is
``ppermute``d from device 0 to device 1 and back inside one compiled loop
over a 2-device mesh. Reports per-hop latency and bandwidth per message
size — the raw cost of the collective the whole transport layer rides on.

Usage: python -m stencil_tpu.apps.pingpong --min-bytes 8 --max-bytes 16777216
"""

from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils.sync import hard_sync
from ..utils import logging as log


def run(min_bytes=8, max_bytes=1 << 24, iters=100, devices=None):
    devices = list(devices) if devices is not None else jax.devices()
    nd = min(2, len(devices))
    if nd < 2:
        log.warn("pingpong needs 2 devices; measuring self-permute on 1")
    perm = [(0, 1), (1, 0)] if nd == 2 else [(0, 0)]
    mesh = Mesh(np.asarray(devices[:nd]), ("p",))
    pspec = P("p")

    rows = []
    nbytes = min_bytes
    while nbytes <= max_bytes:
        n = max(1, nbytes // 4)

        def body(x):
            def it(_, x):
                x = lax.ppermute(x, "p", perm)  # ping
                return lax.ppermute(x, "p", perm)  # pong

            return lax.fori_loop(0, iters, it, x)

        fn = jax.jit(
            jax.shard_map(body, mesh=mesh, in_specs=pspec, out_specs=pspec),
            donate_argnums=0,
        )
        x = jax.device_put(
            jnp.zeros((nd, n), jnp.float32), NamedSharding(mesh, pspec)
        )
        x = fn(x)  # compile + warm
        hard_sync(x)
        t0 = time.perf_counter()
        x = fn(x)
        hard_sync(x)
        dt = time.perf_counter() - t0
        hops = 2 * iters
        rows.append(
            {
                "bytes": n * 4,
                "latency_us": dt / hops * 1e6,
                "gb_per_s": n * 4 * hops / dt / 1e9,
            }
        )
        nbytes *= 4
    return rows


def main(argv: Optional[list] = None) -> int:
    from ..parallel.distributed import maybe_init_from_env
    maybe_init_from_env()
    from ..utils.jax_cache import configure_compile_cache
    configure_compile_cache()
    p = argparse.ArgumentParser(description="ppermute ping-pong microbenchmark")
    p.add_argument("--min-bytes", type=int, default=8)
    p.add_argument("--max-bytes", type=int, default=1 << 24)
    p.add_argument("--iters", type=int, default=100)
    p.add_argument("--cpu", type=int, default=0)
    from ._bench_common import add_metrics_flags, finish_metrics, start_metrics
    add_metrics_flags(p)
    args = p.parse_args(argv)
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    rec = start_metrics(args, "pingpong")
    print("bytes,latency (us),GB/s")
    for row in run(args.min_bytes, args.max_bytes, args.iters):
        print(f"{row['bytes']},{row['latency_us']:.2f},{row['gb_per_s']:.3f}")
        rec.gauge("pingpong.latency_us", row["latency_us"], phase="exchange",
                  unit="us", bytes=row["bytes"])
        rec.gauge("pingpong.gb_per_s", row["gb_per_s"], phase="exchange",
                  bytes=row["bytes"])
    finish_metrics(rec)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
