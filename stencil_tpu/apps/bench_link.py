"""bench_link — per-mesh-axis neighbor-shift bandwidth sweep.

TPU-native analogue of the reference's bench-mpi point-to-point bandwidth
survey by node pair (reference: bin/bench_mpi.cu): on TPU the links that
matter are the mesh axes the halo exchange shifts along, so this measures
``lax.ppermute`` ring-shift bandwidth per mesh axis over a range of
message sizes. Every device sends one message per shift, so the reported
GB/s is per-device unidirectional throughput on that axis — the number to
compare against the ICI roofline and against ``pingpong`` latency.

CSV: bench_link,<axis>,<devices_on_axis>,<bytes>,<trimean_s>,<gb_per_s>

Usage: python -m stencil_tpu.apps.bench_link --cpu 8 --sizes-kb 64,1024
"""

from __future__ import annotations

import argparse
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..geometry import Dim3, RankPartition
from ..parallel.mesh import MESH_AXES, grid_mesh
from ..utils import logging as log
from ..utils.statistics import Statistics
from ..utils.sync import hard_sync


def run(
    sizes_kb: Sequence[int] = (64, 256, 1024, 4096),
    dim=None,
    devices=None,
    iters: int = 20,
    rounds: int = 3,
) -> list:
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    if dim is None:
        dim = RankPartition(Dim3(256, 256, 256), n).dim()
    dim = Dim3.of(dim)
    mesh = grid_mesh(dim, devices)
    rows = []
    for axis in MESH_AXES:
        n_axis = mesh.shape[axis]
        if n_axis < 2:
            continue
        fwd = [(i, (i + 1) % n_axis) for i in range(n_axis)]
        for kb in sizes_kb:
            count = max(1, kb * 1024 // 4)

            def many(block):
                return lax.fori_loop(
                    0, iters, lambda _, b: lax.ppermute(b, axis, fwd), block
                )

            fn = jax.jit(
                jax.shard_map(
                    many,
                    mesh=mesh,
                    in_specs=P(*MESH_AXES, None),
                    out_specs=P(*MESH_AXES, None),
                ),
                donate_argnums=0,
            )
            buf = jax.device_put(
                jnp.zeros((dim.z, dim.y, dim.x, count), jnp.float32),
                NamedSharding(mesh, P(*MESH_AXES, None)),
            )
            buf = fn(buf)
            hard_sync(buf)
            st = Statistics()
            for _ in range(rounds):
                t0 = time.perf_counter()
                buf = fn(buf)
                hard_sync(buf)
                st.insert(time.perf_counter() - t0)
            nbytes = count * 4
            rows.append(
                {
                    "axis": axis,
                    "devices_on_axis": n_axis,
                    "bytes": nbytes,
                    "trimean_s": st.trimean() / iters,
                    "gb_per_s": nbytes * iters / st.trimean() / 1e9,
                }
            )
    return rows


def csv_row(r: dict) -> str:
    return (
        f"bench_link,{r['axis']},{r['devices_on_axis']},{r['bytes']},"
        f"{r['trimean_s']:e},{r['gb_per_s']:.3f}"
    )


def main(argv: Optional[list] = None) -> int:
    from ..parallel.distributed import maybe_init_from_env
    maybe_init_from_env()
    from ..utils.jax_cache import configure_compile_cache
    configure_compile_cache()
    p = argparse.ArgumentParser(description="per-mesh-axis shift bandwidth (TPU)")
    p.add_argument("--sizes-kb", type=str, default="64,256,1024,4096")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--cpu", type=int, default=0, help="force N virtual CPU devices")
    from ._bench_common import add_metrics_flags, finish_metrics, start_metrics
    add_metrics_flags(p)
    args = p.parse_args(argv)
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    rec = start_metrics(args, "bench_link")
    sizes = tuple(int(s) for s in args.sizes_kb.split(","))
    for r in run(sizes_kb=sizes):
        print(csv_row(r))
        rec.gauge("bench_link.gb_per_s", r["gb_per_s"], phase="exchange",
                  axis=r["axis"], bytes=r["bytes"],
                  devices_on_axis=r["devices_on_axis"])
    finish_metrics(rec)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
