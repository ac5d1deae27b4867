"""bench_alltoall — all-to-all collective throughput, two strategies.

TPU-native analogue of the reference's bench-alltoallv (reference:
bin/bench_alltoallv.cu:12-60), which compared cudaMemcpyPeerAsync
all-to-all against MPI_Alltoallv. The TPU strategies:

- ``all_to_all``: XLA's native ``lax.all_to_all`` collective — one fused
  transpose over the mesh (the MPI_Alltoallv analogue).
- ``ring``: n-1 ``lax.ppermute`` ring rotations delivering one peer's
  payload per step (the hand-rolled peer-copy analogue) — measures what
  the collective buys over composed point-to-points.

Each device exchanges ``bytes`` with every other device; reported GB/s is
per-device egress (n-1 peer payloads / time).

CSV: bench_alltoall,<strategy>,<devices>,<bytes_per_pair>,<trimean_s>,<gb_per_s>

Usage: python -m stencil_tpu.apps.bench_alltoall --cpu 8
"""

from __future__ import annotations

import argparse
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import logging as log
from ..utils.statistics import Statistics
from ..utils.sync import hard_sync


def _alltoall_body(n: int):
    def body(x):  # x: (1, n, k) — this device's row of payloads
        v = x[0]
        y = lax.all_to_all(v, "i", split_axis=0, concat_axis=0, tiled=True)
        return y[None]

    return body


def _ring_body(n: int):
    def body(x):  # x: (1, n, k)
        v = x[0]
        me = lax.axis_index("i")
        out = v
        for s in range(1, n):
            # send my payload for peer (me+s) forward s hops; receive the
            # payload of peer (me-s) destined to me into its row
            perm = [(i, (i + s) % n) for i in range(n)]
            sent = jnp.take(v, jnp.mod(me + s, n), axis=0)
            got = lax.ppermute(sent, "i", perm)
            out = lax.dynamic_update_index_in_dim(
                out, got, jnp.mod(me - s, n), axis=0
            )
        return out[None]

    return body


def run(
    sizes_kb: Sequence[int] = (64, 256, 1024),
    devices=None,
    iters: int = 10,
    rounds: int = 3,
) -> list:
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    if n < 2:
        raise ValueError("all-to-all needs at least 2 devices")
    mesh = Mesh(np.asarray(devices), ("i",))
    rows = []
    for strategy, make_body in (("all_to_all", _alltoall_body), ("ring", _ring_body)):
        for kb in sizes_kb:
            k = max(1, kb * 1024 // 4)
            body = make_body(n)

            def many(x):
                return lax.fori_loop(0, iters, lambda _, b: body(b), x)

            fn = jax.jit(
                jax.shard_map(
                    many, mesh=mesh, in_specs=P("i", None, None),
                    out_specs=P("i", None, None),
                ),
                donate_argnums=0,
            )
            buf = jax.device_put(
                jnp.zeros((n, n, k), jnp.float32),
                NamedSharding(mesh, P("i", None, None)),
            )
            buf = fn(buf)
            hard_sync(buf)
            st = Statistics()
            for _ in range(rounds):
                t0 = time.perf_counter()
                buf = fn(buf)
                hard_sync(buf)
                st.insert(time.perf_counter() - t0)
            per_pair = k * 4
            egress = per_pair * (n - 1)
            rows.append(
                {
                    "strategy": strategy,
                    "devices": n,
                    "bytes_per_pair": per_pair,
                    "trimean_s": st.trimean() / iters,
                    "gb_per_s": egress * iters / st.trimean() / 1e9,
                }
            )
    return rows


def csv_row(r: dict) -> str:
    return (
        f"bench_alltoall,{r['strategy']},{r['devices']},{r['bytes_per_pair']},"
        f"{r['trimean_s']:e},{r['gb_per_s']:.3f}"
    )


def main(argv: Optional[list] = None) -> int:
    from ..parallel.distributed import maybe_init_from_env
    maybe_init_from_env()
    from ..utils.jax_cache import configure_compile_cache
    configure_compile_cache()
    p = argparse.ArgumentParser(description="all-to-all throughput (TPU)")
    p.add_argument("--sizes-kb", type=str, default="64,256,1024")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--cpu", type=int, default=0, help="force N virtual CPU devices")
    from ._bench_common import add_metrics_flags, finish_metrics, start_metrics
    add_metrics_flags(p)
    args = p.parse_args(argv)
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    rec = start_metrics(args, "bench_alltoall")
    sizes = tuple(int(s) for s in args.sizes_kb.split(","))
    for r in run(sizes_kb=sizes):
        print(csv_row(r))
        rec.gauge("bench_alltoall.gb_per_s", r["gb_per_s"], phase="exchange",
                  strategy=r["strategy"], bytes=r["bytes_per_pair"],
                  devices=r["devices"])
    finish_metrics(rec)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
