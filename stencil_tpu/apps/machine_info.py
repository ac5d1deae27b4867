"""machine-info — print the cluster/device inventory and link matrices.

TPU-native analogue of the reference's machine-info executable
(reference: bin/machine_info.cu:49-75, machine.hpp:106-140): dumps the
Machine model (nodes, processes, devices with ICI coords) plus the
distance and bandwidth matrices the NodeAware placement consumes — the
introspection needed to trust placement on real hardware.

Also prints the default partition the framework would choose for these
devices (NodePartition hosts x devices-per-host), closing the loop from
inventory to decomposition.

``--json`` emits the same inventory machine-readably — one telemetry
record per line in the metrics JSONL schema (stencil_tpu/obs/telemetry.py)
— the analogue of the reference's NVML dump, consumable by the same
tooling as ``--metrics-out`` files (apps/report.py validates it).

Usage: python -m stencil_tpu.apps.machine_info [--cpu 8] [--size 256] [--json]
"""

from __future__ import annotations

import argparse
import sys
from typing import Optional

import jax
import numpy as np

from ..geometry import Dim3, NodePartition, Radius
from ..obs import telemetry
from ..parallel.machine import Machine
from ..utils import logging as log


def run(devices=None, size: int = 256, radius: int = 1) -> dict:
    m = Machine.detect(devices)
    n = len(m.devices)
    hosts = max(1, m.process_count)
    part = NodePartition(
        Dim3(size, size, size), Radius.constant(radius), hosts, max(1, n // hosts)
    )
    return {
        "machine": m,
        "dist": m.distance_matrix(),
        "bw": m.bandwidth_matrix(),
        "partition": part.dim(),
        "size": size,
    }


def fabric_fingerprint(machine: Optional[Machine] = None,
                       devices=None) -> dict:
    """The scalar identity of the fabric a measurement ran on: process
    count, host count, device count and platform. Attribution records
    (obs/attribution.emit_phase) embed these as ``fabric_*`` extras so a fitted calibration row can
    be traced to the fabric whose constants it encodes — a row fitted on
    an 8-device single-host CPU mesh must not silently price a 2-host
    TPU run."""
    m = machine if machine is not None else Machine.detect(devices)
    platform = m.devices[0].platform if m.devices else "unknown"
    return {
        "processes": int(m.process_count),
        "hosts": int(m.num_nodes()),
        "devices": len(m.devices),
        "platform": str(platform),
    }


def report(r: dict) -> str:
    m: Machine = r["machine"]
    with np.printoptions(precision=2, suppress=True, linewidth=200):
        return "\n".join(
            [
                m.summary(),
                f"default partition for {r['size']}^3: {r['partition']} "
                "(hosts x devices/host min-interface split)",
                "distance matrix (hops; self=0.1, remote=7.0):",
                str(r["dist"]),
                "bandwidth matrix (1/distance):",
                str(r["bw"]),
            ]
        )


def emit_records(r: dict, rec: "telemetry.Recorder") -> list:
    """The inventory as telemetry records (one JSON object per line in the
    sink): the machine-readable NVML-dump analogue."""
    m: Machine = r["machine"]
    out = [rec.meta(
        "machine",
        nodes=m.num_nodes(),
        processes=m.process_count,
        devices=len(m.devices),
        hostnames={str(k): v for k, v in sorted(m.hostnames.items())},
    )]
    for d in m.devices:
        out.append(rec.meta(
            "machine.device",
            index=d.index,
            platform=d.platform,
            device_kind=d.kind,
            process=d.process_index,
            coords=list(d.coords) if d.coords is not None else None,
            core_on_chip=d.core_on_chip,
        ))
    out.append(rec.meta("machine.fabric", **fabric_fingerprint(m)))
    part = r["partition"]
    out.append(rec.meta(
        "machine.partition",
        dim=[part.x, part.y, part.z],
        size=r["size"],
    ))
    out.append(rec.meta("machine.distance_matrix",
                        matrix=np.asarray(r["dist"]).tolist()))
    out.append(rec.meta("machine.bandwidth_matrix",
                        matrix=np.asarray(r["bw"]).tolist()))
    return out


def main(argv: Optional[list] = None) -> int:
    from ..parallel.distributed import maybe_init_from_env
    maybe_init_from_env()
    p = argparse.ArgumentParser(description="cluster/device inventory (TPU)")
    p.add_argument("--size", type=int, default=256, help="domain for the partition hint")
    p.add_argument("--radius", type=int, default=1)
    p.add_argument("--cpu", type=int, default=0, help="force N virtual CPU devices")
    p.add_argument("--json", action="store_true",
                   help="emit the inventory as telemetry JSONL on stdout "
                        "(and to --metrics-out when given) instead of text")
    from ._bench_common import add_metrics_flags, start_metrics
    add_metrics_flags(p)
    args = p.parse_args(argv)
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    rec = start_metrics(args, "machine_info")
    r = run(size=args.size, radius=args.radius)
    if args.json:
        stdout_rec = telemetry.Recorder(sink=sys.stdout, app="machine_info",
                                        run_id=rec.run_id)
        emit_records(r, stdout_rec)
        if rec.enabled:
            emit_records(r, rec)
        return 0
    if rec.enabled:
        emit_records(r, rec)
    print(report(r))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
