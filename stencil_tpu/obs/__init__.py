"""obs — the flight recorder: telemetry, watchdog, ledger, trace export.

Four parts, deliberately decoupled:

- :mod:`stencil_tpu.obs.telemetry` — a structured recorder of spans,
  counters, and gauges flushed as one-JSON-object-per-line to a metrics
  sink (the ``--metrics-out`` flag every bench app grows), riding the
  existing :mod:`stencil_tpu.utils.timer` buckets + profiler annotations.
- :mod:`stencil_tpu.obs.watchdog` — the revival watcher for stall-prone
  measurement runs: supervises a child process on heartbeat
  + total-budget deadlines, distinguishes stall from crash, retries with
  backoff, archives logs. Pure stdlib, importable WITHOUT importing jax
  (a supervising parent loads it by file path and must never touch a JAX
  backend).
- :mod:`stencil_tpu.obs.ledger` — the cross-run performance ledger:
  append-only schema-validated entries keyed by (metric, platform,
  config fingerprint, git rev, label), ingested from bench payloads and
  metrics-JSONL gauge trimeans; ``apps/perf_tool.py`` renders trends and
  runs the trimean ± MAD regression sentinel over it. Pure stdlib by the
  same contract.
- :mod:`stencil_tpu.obs.trace_export` — metrics JSONL ->
  Chrome-trace/Perfetto timeline JSON (one lane per (run, proc),
  fault/checkpoint instant markers); ``apps/report.py --trace-out``.
- :mod:`stencil_tpu.obs.live` — the IN-run sentinel: streaming
  trimean ± MAD anomaly detection over bounded per-metric windows
  (the perf_tool band semantics applied online), emitting
  ``anomaly.detected`` / ``anomaly.cleared`` / ``replan.requested``
  mid-run; fed per-chunk by ``fault/recover.run_guarded`` and the
  campaign driver.
- :mod:`stencil_tpu.obs.status` — atomic run-status snapshots (one
  small JSON rewritten per chunk through tmp+fsync+rename): step,
  throughput, health counts, anomaly state, per-lane tenant SLO
  states; ``apps/report.py --status`` is the top-like reader. Pure
  stdlib by the watchdog contract.

This package intentionally imports nothing at package level so that the
stdlib-weight modules stay loadable directly.
"""

__all__ = ["telemetry", "watchdog", "ledger", "trace_export", "live",
           "status"]
