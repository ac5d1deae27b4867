"""Shared machinery for the exchange benchmarks (exchange_weak,
exchange_strong, bench_exchange): build a domain, run fused exchange loops,
report trimean statistics — the structure of the reference's timed exchange
loop (reference: bin/exchange_weak.cu:140-196)."""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ..api import DistributedDomain
from ..geometry import Dim3, Radius
from ..obs import scopes, telemetry
from ..parallel import IntraNodeRandom, Method, NodeAware, Trivial
from ..utils.statistics import Statistics
from ..utils.sync import hard_sync, timed_chunk


def add_metrics_flags(p, dma: bool = False) -> None:
    """The flight-recorder flags every bench app shares; ``dma=True`` adds
    the static-DMA-truth opt-in for apps with a Pallas fast path."""
    p.add_argument(
        "--metrics-out",
        default=os.environ.get("STENCIL_METRICS_OUT", ""),
        help="append telemetry records (one JSON object per line; schema "
             "stencil_tpu/obs/telemetry.py, aggregated by apps/report.py) "
             "to this file",
    )
    p.add_argument("--run-id", default="",
                   help="telemetry run id (default: generated)")
    if dma:
        p.add_argument(
            "--metrics-dma", action="store_true",
            help="also record the compiled Mosaic kernels' static per-pass "
                 "DMA bytes (a full TPU lowering; needs the Pallas fast "
                 "path)",
        )


def start_metrics(args, app: str) -> "telemetry.Recorder":
    """Install the process-default recorder from parsed flags.

    The run's argv config rides along as the first meta record, so a
    metrics file is self-describing. Apps call this AFTER any --cpu
    backend configuration (recording must never pin the platform)."""
    return telemetry.configure(
        metrics_out=getattr(args, "metrics_out", "") or None,
        app=app,
        run_id=getattr(args, "run_id", "") or None,
        config=vars(args),
    )


def add_live_flags(p) -> None:
    """The live-observability flags (obs/live.py + obs/status.py) the
    guarded apps share: an in-run anomaly sentinel over the chunk-cycle
    step latency, and an atomic run-status snapshot file."""
    p.add_argument(
        "--status-file", default=os.environ.get("STENCIL_STATUS_FILE", ""),
        help="rewrite an atomic run-status snapshot here every chunk "
             "(step, throughput, health counts, anomalies; read it with "
             "`report --status FILE [--follow]`)",
    )
    p.add_argument(
        "--live-sentinel", action="store_true",
        help="in-run anomaly detection: judge each chunk's per-step "
             "latency against a streaming trimean±MAD band (obs/live.py); "
             "excursions emit anomaly.detected / replan.requested records "
             "mid-run and show in the status snapshot",
    )
    p.add_argument(
        "--live-config", default="",
        help="sentinel knobs as JSON (inline '{...}' or a file path): "
             "{\"*\": {window, min_history, mad_k, rel_tol, abs_tol, "
             "direction, clear_after}, \"<key>\": {...}} — the perf_tool "
             "--leg-config shape",
    )


def load_live_config(value: str) -> dict:
    """Parse --live-config: an inline JSON object or a JSON file path.
    Raises OSError/ValueError — the apps pre-validate at parse time and
    map both to a clean argparse error, never a traceback."""
    import json

    if not value:
        return {}
    if value.lstrip()[:1] in ("{", "["):  # inline JSON, not a path
        text = value
    else:
        with open(value) as f:
            text = f.read()
    cfg = json.loads(text)  # JSONDecodeError is a ValueError
    if not isinstance(cfg, dict):
        raise ValueError("--live-config must be a JSON object")
    from ..obs.live import validate_config

    errs = validate_config(cfg)
    if errs:
        raise ValueError("; ".join(errs))
    return cfg


def canonicalize_live_config(args) -> dict:
    """Parse-time validation of ``--live-config`` that ALSO rewrites the
    flag to canonical inline JSON, so ``make_live`` never re-reads a
    file (the validated content is what runs — no window for the file
    to change or vanish between argparse and backend init). Returns the
    parsed config; raises OSError/ValueError for the app's ``p.error``."""
    import json

    cfg = load_live_config(getattr(args, "live_config", ""))
    args.live_config = json.dumps(cfg) if cfg else ""
    return cfg


def make_live(args, rec: "telemetry.Recorder", app: str):
    """Build the (sentinel, status writer) pair from parsed flags —
    (None, None) when neither live flag is set."""
    sentinel = status = None
    if getattr(args, "live_sentinel", False):
        from ..obs.live import LiveSentinel

        sentinel = LiveSentinel(
            load_live_config(getattr(args, "live_config", "")), rec=rec)
    if getattr(args, "status_file", ""):
        from ..obs.status import StatusWriter

        status = StatusWriter(args.status_file, app=app, run=rec.run_id)
    return sentinel, status


def finish_live(rec: "telemetry.Recorder", sentinel, status,
                outcome: Optional[str] = None, gauge: bool = True) -> None:
    """The live epilogue: the run's anomaly count lands as a gauge (so
    metrics-JSONL ingest puts in-run instability in the LEDGER, where
    the cross-run sentinel sees it), and the final status snapshot gets
    its outcome. ``gauge=False`` for callers whose engine already
    emitted the count (the campaign driver does)."""
    if gauge and sentinel is not None and rec.enabled:
        rec.gauge("live.anomaly_count", float(sentinel.detected_total),
                  phase="live")
    if status is not None:
        status.update(outcome=outcome,
                      anomalies=(sentinel.summary()
                                 if sentinel is not None else None))


def finish_metrics(rec: "telemetry.Recorder") -> None:
    """The apps' shared exit epilogue: snapshot the global timer buckets
    as gauges and close the sink (no-op on a disabled recorder)."""
    if rec.enabled:
        rec.record_timer_buckets()
        rec.close()


def resume_from_checkpoint(dd, ckpt_dir: str, iters: int) -> int:
    """The apps' shared resume policy (jacobi3d, astaroth): restore the
    newest valid compatible snapshot, warn when it is beyond the run's
    target (and never re-label it — step accounting stays truthful),
    record the resumed-from-step gauge, and return the start step
    (0 = fresh start)."""
    from ..utils import logging as log

    restored = dd.restore_checkpoint(ckpt_dir)
    if restored is None:
        return 0
    if restored > iters:
        log.warn(f"checkpoint step {restored} is beyond the target {iters}; "
                 "nothing to run and the snapshot is NOT relabeled")
    start = min(restored, iters)
    telemetry.get().gauge("ckpt.resumed_from_step", start, phase="ckpt")
    log.info(f"resuming from checkpointed step {start}")
    return start


def coord_state(dd, quantities: int):
    """Deterministic per-quantity coordinate fields on a realized domain
    (value = z*1e6 + y*1e3 + x + quantity index) — the bit-for-bit
    agreement fixture shared by the method-ablation harness and the
    exchange tests (same idiom as tests/test_exchange.py; reference:
    test_cuda_mpi_distributed_domain.cu:11-17)."""
    import numpy as np

    from ..parallel.exchange import shard_blocks

    g = dd.spec.global_size
    coord = (
        np.arange(g.z)[:, None, None] * 1_000_000.0
        + np.arange(g.y)[None, :, None] * 1_000.0
        + np.arange(g.x)[None, None, :]
    ).astype(np.float32)
    return {
        i: shard_blocks(coord + i, dd.spec, dd.mesh) for i in range(quantities)
    }


def placement_from_flags(naive: bool, random_: bool):
    """--naive -> Trivial, --random -> IntraNodeRandom, default NodeAware
    (reference: bin/exchange_weak.cu:149-153, exchange_strong.cu)."""
    if naive:
        return Trivial()
    if random_:
        return IntraNodeRandom()
    return NodeAware()


def time_exchange(
    size: Dim3,
    radius: Radius,
    iters: int,
    method: Method = Method.AXIS_COMPOSED,
    devices: Optional[Sequence] = None,
    placement=None,
    quantities: int = 4,
    dtype: str = "float32",
    chunk: int = 10,
    prefix: str = "",
    batch_quantities: bool = True,
    partition=None,
    wire_dtype=None,
) -> dict:
    """Realize a domain with ``quantities`` quantities and time ``iters``
    exchanges in fused chunks. Returns stats + the domain.

    ``batch_quantities=False`` times the historical
    one-collective-per-quantity program (the ``--batched-ab`` baseline);
    ``partition`` forces the block grid (e.g. ``(2, 2, 2)``) so A/B runs
    pin the mesh instead of trusting the auto-partitioner; ``wire_dtype``
    turns on the (lossy) bf16/fp8-on-the-wire carrier compression.
    ``placement`` is a Placement
    strategy OR a plain assignment tuple (``PlanChoice.placement`` —
    wrapped in :class:`~stencil_tpu.parallel.FixedAssignment` so placed
    plan candidates probe on exactly their tuned mesh)."""
    devices = list(devices) if devices is not None else jax.devices()
    if placement is not None and not hasattr(placement, "arrange"):
        from ..parallel import FixedAssignment

        placement = FixedAssignment(placement)
    # covered by spans without a hole: realize, warmup (build, compile or
    # cache load, first call), steps
    rec = telemetry.get()
    end_realize = rec.open_span("exchange.realize", phase="init")
    dd = DistributedDomain(size.x, size.y, size.z)
    dd.set_radius(radius)
    dd.set_methods(method)
    dd.set_quantity_batching(batch_quantities)
    if wire_dtype:
        dd.set_wire_dtype(wire_dtype)
    if partition is not None:
        dd.set_partition(partition)
    dd.set_devices(devices)
    if placement is not None:
        dd.set_placement(placement)
    if prefix:
        dd.set_output_prefix(prefix)
    for i in range(quantities):
        dd.add_data(f"d{i}", dtype)
    dd.realize()
    end_realize()

    itemsizes = [jnp.dtype(dtype).itemsize] * quantities
    state = dd.curr_state()
    chunk = max(1, min(chunk, iters))
    tail = iters % chunk
    # the wire tag keeps a --wire-ab run's legs separable in aggregation
    # (report._agg_key splits on it, like method/batched)
    wtag = {"wire": str(wire_dtype)} if wire_dtype else {}
    # compile + warm every loop size OUTSIDE the timed region
    with rec.span("exchange.warmup", phase="compile", method=method.value,
                  batched=batch_quantities, **wtag):
        loops = {chunk: dd.halo_exchange.make_loop(chunk, like=state)}
        if tail:
            loops[tail] = dd.halo_exchange.make_loop(tail, like=state)
        for fn in loops.values():
            state = fn(state)
        hard_sync(state)
    end_steps = rec.open_span("exchange.steps", phase="exchange")
    census = None
    if rec.enabled:
        # compile-time truth: census the compiled single-exchange program
        # (exact on-wire volume) alongside the measured times below; the
        # census rides the result so callers (ablate) never recompile it
        # the batched tag keeps A/B runs separable in the aggregated
        # gauges: without it the permutes_per_quantity tripwire would
        # average the batched leg with its per-quantity baseline
        census = telemetry.record_exchange_truth(
            dd.halo_exchange, state, itemsizes, batched=batch_quantities,
            **wtag)

    stats = Statistics()
    samples = []
    done = 0
    while done < iters:
        k = min(chunk, iters - done)
        state, marks = timed_chunk(scopes.EXCHANGE_LOOP, loops[k], state)
        per = marks.wall_s / k
        stats.insert(per)
        samples.append(per)
        rec.chunk_span("exchange.iter", marks, k, phase="exchange",
                       method=method.value, batched=batch_quantities, **wtag)
        done += k
    dd._curr = dict(state)  # the loops donated the original buffers
    if rec.enabled:
        # per-phase attribution: pair the installed cost model's
        # prediction for THIS realized plan with the measured samples
        # above — one plan.attrib.phase record per sample, the raw
        # material of `plan_tool calibrate` and `perf_tool drift`
        from ..obs import attribution
        from ..plan.ir import PlanChoice, PlanConfig
        from .machine_info import fabric_fingerprint

        pm = dd.plan_meta()
        pchoice = PlanChoice.from_json(pm["choice"])
        attribution.attribute_and_judge(
            rec,
            PlanConfig.from_json(pm["key"]),
            pchoice,
            samples,
            phase="exchange.iter",
            fabric=fabric_fingerprint(devices=devices),
        )
        # the run's plan identity — the join key between this metrics
        # file, the plan DB, and any fitted calibration row
        rec.meta("plan.fingerprint", fingerprint=pchoice.fingerprint(),
                 choice=pchoice.label(), calibration="modeled(default)",
                 **wtag)
    if rec.enabled:
        rec.gauge("exchange.trimean_s", stats.trimean(), phase="exchange",
                  unit="s", method=method.value, batched=batch_quantities,
                  **wtag)
        rec.gauge(
            "exchange.gb_per_s",
            dd.halo_exchange.bytes_logical(itemsizes) / stats.trimean() / 1e9,
            phase="exchange", method=method.value, batched=batch_quantities,
            **wtag,
        )
    end_steps()
    return {
        "domain": dd,
        "census": census,
        "stats": stats,
        "trimean_s": stats.trimean(),
        "min_s": stats.min(),
        "bytes_logical": dd.halo_exchange.bytes_logical(itemsizes),
        "bytes_moved": dd.halo_exchange.bytes_moved(itemsizes),
        "gb_per_s": dd.halo_exchange.bytes_logical(itemsizes) / stats.trimean() / 1e9,
        "local_size": dd.spec.base,
        "devices": len(devices),
    }
