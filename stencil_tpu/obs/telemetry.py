"""Structured telemetry: spans, counters, gauges → one-JSON-per-line sink.

The reference keeps its performance story honest with global timer buckets
(timer.hpp:44-47), NVTX ranges throughout src/stencil.cu, and Allreduced
per-method byte counters (src/stencil.cu:139-161,620-627). This module
unifies the TPU port's analogues of all three — ``utils/timer.py`` buckets
+ ``jax.profiler`` annotations, ``utils/hlo_check.collective_census``, and
``utils/mosaic_traffic`` — behind one recorder whose records land as one
JSON object per line in a metrics sink (``--metrics-out`` on every bench
app), machine-readable by ``apps/report.py`` and CI.

Record schema (v1) — every line carries:

- ``v``:     schema version (1)
- ``run``:   run id (shared by every record of one measurement run)
- ``proc``:  JAX process index (0 when no backend is up — resolved lazily,
             same discipline as utils/logging: recording a line must never
             initialize a backend)
- ``kind``:  ``span`` | ``counter`` | ``gauge`` | ``meta`` | ``heartbeat``
- ``name``:  record name (e.g. ``jacobi.iter``, ``census.collective-permute``)
- ``t``:     unix wall time of emission

plus per kind: spans carry ``seconds`` (and usually ``phase``), and those
opened through :meth:`Recorder.span` also ``t0_ns``/``t1_ns`` (unix
nanoseconds, ``time.time_ns()``: the clock the profiler's host and device
events are on, so a span can be laid beside them) and ``parent`` (the span
that was open when it started); counters
carry ``value`` (a count) and/or ``bytes`` (a byte total — "bytes where
applicable"); gauges carry ``value``; heartbeats carry ``seq``; metas are
free-form. Anything else (``app``, ``phase``, ``method``, ``iters``, ...)
is an optional tag. :func:`validate_record` is the one schema authority —
CI validates every emitted line through it (``apps/report.py --validate``).

Spans ride :func:`stencil_tpu.utils.timer.timed` (global buckets keep
accumulating exactly as before) and ``timer.trace_range`` (so
``jax.profiler`` gets the same named range for free).

Heartbeats close the loop with :mod:`stencil_tpu.obs.watchdog`: when the
supervisor set ``STENCIL_HEARTBEAT_FILE``, every emitted record (and a
background thread, for long silent stretches like a 3-minute kernel
compile) touches that file; the watchdog reads only its mtime.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import sys
import threading
import time
import uuid
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from ..utils import timer
from .watchdog import HEARTBEAT_FILE_ENV, HEARTBEAT_INTERVAL_ENV

SCHEMA_VERSION = 1
KINDS = ("span", "counter", "gauge", "meta", "heartbeat")
REQUIRED_KEYS = ("v", "run", "proc", "kind", "name", "t")

# Name-specific vocabulary (still schema v1): the fault/health/recover
# records the self-healing layer (stencil_tpu/fault/) emits carry typed
# payload fields the CI fault gate greps for — validate them here so a
# renamed or untyped field fails the schema gate, not a post-mortem.
# The campaign.* and compile.* names are the multi-tenant layer's
# vocabulary (stencil_tpu/campaign/): eviction/backfill provenance and
# the compile-cache economics the campaign CI gate pins.
NAME_FIELDS = {
    "fault.injected": (("fault_kind", str), ("step", int)),
    "health.fault": (("fault_kind", str), ("quantity", str), ("step", int)),
    "health.check": (("step", int),),
    "recover.fault": (("fault_kind", str), ("step", int)),
    "recover.rollback": (("from_step", int), ("to_step", int),
                         ("fault_step", int)),
    "recover.aborted": (("reason", str), ("step", int)),
    "ckpt.save_skipped": (("reason", str),),
    "campaign.slot": (("slot", int),),
    "campaign.retire": (("tenant", str), ("step", int), ("lane", int)),
    "campaign.backfill": (("tenant", str), ("lane", int)),
    "campaign.evict": (("tenant", str), ("step", int), ("rc", int)),
    "campaign.step_latency_s": (("mode", str),),
    "campaign.summary": (("slots", int), ("tenants", int)),
    "compile.cache_hit": (("key", str),),
    "compile.build": (("key", str),),
    "compile.build_s": (("key", str),),
    # the live-observability vocabulary (obs/live.py + campaign SLO
    # tracking): in-run anomaly detect/clear, deadline violations, and
    # the replan trigger ROADMAP #6's hot-swap will consume
    "anomaly.detected": (("metric", str), ("step", int)),
    "anomaly.cleared": (("metric", str), ("step", int)),
    "slo.violation": (("tenant", str), ("step", int)),
    "replan.requested": (("reason", str), ("step", int)),
    # the always-on serving vocabulary (stencil_tpu/serve/): intake
    # admission verdicts (admit / quota-defer / priced rejection),
    # per-tenant result streaming, and the drain/park/revival
    # provenance the serve CI gate greps for
    "serve.admitted": (("job", str),),
    "serve.rejected": (("job", str), ("reason", str)),
    "serve.deferred": (("job", str), ("reason", str)),
    "serve.retired": (("job", str), ("outcome", str)),
    "serve.parked": (("job", str), ("step", int)),
    "serve.drain": (("reason", str),),
    "serve.revived": (("jobs", int),),
    # the capacity engine's decision records: every packed slot names
    # its bucket/width/winner, every preemption (and every veto) names
    # its priced gain against the victims' resume cost, every resize
    # names both widths — "what was chosen and why" is a record, not a
    # log line
    "serve.packed": (("bucket", str), ("width", int)),
    "serve.preempted": (("job", str), ("gain_ms", float),
                        ("resume_cost_ms", float)),
    "serve.preempt_veto": (("job", str), ("gain_ms", float),
                           ("resume_cost_ms", float)),
    "serve.resized": (("from_width", int), ("to_width", int),
                      ("reason", str)),
    # the hot-swap half of ROADMAP #6 (plan/replan.ReplanController):
    # a mid-run replan either installs a new compiled plan (applied —
    # old/new choice labels + the static model's predicted gain rides
    # as an optional modeled_gain tag) or degrades loudly onto the old
    # one (rejected — a throwing autotuner/apply must never kill a run)
    "replan.applied": (("old", str), ("new", str), ("step", int)),
    "replan.rejected": (("reason", str), ("step", int)),
    # the fused compute+exchange vocabulary (ops/fused_stencil +
    # the host-orchestrated fused loops in ops/jacobi /
    # astaroth/integrate): the overlap split of one fused substep —
    # pack+start, interior compute (the hiding window), the recv-
    # semaphore wait, boundary compute — variant-tagged spans so the
    # PR-12 live sentinel and the trace export see where wire time
    # goes; no extra required fields beyond the span schema
    "fused.pack": (),
    "fused.interior": (),
    "fused.dma_wait": (),
    "fused.boundary": (),
    # the static-analysis vocabulary (stencil_tpu/analysis/): per-config
    # plan-auditor verdicts, the audit summaries the CI static gate
    # archives, and the lint summary — schema-gated like every other
    # subsystem's records
    "analysis.plan_verdict": (("method", str), ("ok", int)),
    "analysis.plan_mismatch": (("method", str),),
    "analysis.plan_sweep": (("checked", int), ("failed", int),
                            ("skipped", int)),
    "analysis.jit_audit": (("ok", int), ("recompiles", int),
                           ("transfers", int)),
    "analysis.lint": (("findings", int), ("new", int)),
    # the plan-observatory vocabulary (obs/attribution.py +
    # plan/calibrate.py): per-exchange-phase measured seconds mapped
    # back onto the ExchangePlan IR's prediction under the installed
    # calibration — the samples plan_tool calibrate fits and perf_tool
    # drift judges. `phase` is the trace_range name of the measured
    # region; `collectives` carries the plan's collective count for the permute
    # methods and its DMA count for remote-dma (the per-copy overhead
    # is what the fit recovers there).
    "plan.attrib.phase": (("phase", str), ("method", str),
                          ("predicted_s", float), ("measured_s", float),
                          ("residual", float), ("collectives", int),
                          ("wire_bytes", int)),
    # the active plan + calibration stamp every instrumented run carries
    # (jacobi3d/bench/_bench_common): LEDGER entries become attributable
    # to the plan and calibration provenance that produced them
    "plan.fingerprint": (("fingerprint", str), ("choice", str),
                         ("calibration", str)),
    # a calibrate run's fitted-row summary (plan_tool calibrate)
    "calibration.fitted": (("platform", str), ("n", int),
                           ("provenance", str)),
    # the drift sentinel's in-run verdict: the installed calibration's
    # prediction fell outside the measured phase's trimean±MAD band
    "calibration.drift": (("phase", str), ("predicted_s", float),
                          ("measured_s", float)),
    # what ops/double_buffer.jit_in_place built, once per build: the
    # exchanging steps of a ping-pong program, how many two-step trips
    # hold them, how many run outside a trip, and whether the loop swaps
    # the two handles on the host (an odd count)
    "loop.pingpong": (("module", str), ("steps", int),
                      ("steps_per_trip", int), ("trips", int),
                      ("tail_steps", int), ("host_swap", bool)),
    # what ops/jacobi.py staged for the temporal multistep it built, once
    # per build (ops/pallas_stencil.multistep_staging): depth, strip height
    # (0 = full planes), strips, the rows a staged strip holds beyond its
    # own, the rows one pass computes over all stages and strips against
    # the k * ny it keeps, and the VMEM scratch (benchmark reader
    # multistep_recompute_share)
    "kernel.multistep.staging": (("module", str), ("k", int), ("rows", int),
                                 ("strips", int), ("halo_rows", int),
                                 ("rows_computed", int), ("rows_kept", int),
                                 ("vmem_bytes", int)),
    # once a jacobi3d.run(): the depth k (value) its halos were realized
    # for, the steps a dispatch runs, how they divide into deep-halo
    # passes of k and single steps (k = 1: no pass, the loop builder's own
    # choice), the realized radius and what set k: "chunk", "cap", "vmem",
    # "block", "mesh" (ops/pallas_stencil.pick_temporal_depth) or
    # "explicit" (--deep-halo). No benchmark reader:
    # kernel_scope_ms_per_iter and halo_scope_ms.app show the effect
    "jacobi.temporal_depth": (("chunk", int), ("passes", int),
                              ("single_steps", int), ("halo_zyx", list),
                              ("bound", str)),
    # what astaroth/integrate.make_astaroth_step built, once per build
    # (value: iterations a dispatch): the branch its iteration takes
    # ("overlap": substep 0 over the whole block from pre-exchange data
    # beside the exchange, then the shells; "dyn_overlap": the same on an
    # uneven partition; "serial": exchange, then compute; "per_substep":
    # swap_per_substep), fused kernels or XLA, the layout, blocks of the
    # mesh, exchanges an iteration, the rects integrated from exchanged
    # halos beside the whole-block or interior pass and the cells they hold
    # (a block an iteration), a block's owned cells, and the bytes a chip
    # sends an exchange by the plan (benchmark reader shell_ns_per_cell)
    "astaroth.step_plan": (("module", str), ("mode", str), ("pallas", bool),
                           ("tight_x", bool), ("blocks", int),
                           ("quantities", int), ("exchanges_per_iter", int),
                           ("shells", int), ("shell_cells", int),
                           ("block_cells", int), ("halo_bytes_sent", int)),
}

# The sanctioned metric-name vocabulary: every LITERAL name the library
# passes to a Recorder record site (span/counter/gauge/meta/emit). The
# repo lint's `telemetry-vocab` rule (analysis/astlint.py) checks record
# sites against this set, so a typo'd metric name fails the static gate
# instead of silently validating (schema v1 constrains record SHAPE, not
# names — a `recover.rollbck` counter is a perfectly valid record that no
# dashboard will ever aggregate). Dynamically-built names (f-strings like
# ``census.{kind}``/``timer.{k}``/``dma.{kernel}.*``) are explicitly
# generic and exempt from the check. Grow this list alongside new
# subsystems — the lint names the site that needs the entry.
KNOWN_NAMES = frozenset(NAME_FIELDS) | frozenset({
    "ablate.bit_for_bit_agreement",
    "analysis.verify_plan", "analysis.jit_warmup", "analysis.jit_audit_loop",
    "astaroth.exch_trimean_s", "astaroth.exchange", "astaroth.init",
    "astaroth.iter", "astaroth.iter_trimean_s", "astaroth.warmup",
    "batched_ab.bit_for_bit_agreement", "batched_ab.q_independent",
    "bench_alltoall.gb_per_s", "bench_link.gb_per_s", "bench_pack.gb_per_s",
    "ckpt.bytes_read", "ckpt.bytes_written", "ckpt.files_written",
    "ckpt.quarantined", "ckpt.restore", "ckpt.restore_skipped",
    "ckpt.resumed", "ckpt.resumed_from_step", "ckpt.save", "ckpt.write",
    "config",
    "dma.capture_error", "dma.skipped",
    "exchange.bytes_logical", "exchange.bytes_moved",
    "exchange.bytes_on_wire", "exchange.bytes_on_wire_per_quantity",
    "exchange.gb_per_s", "exchange.iter", "exchange.launches_per_chunk",
    "exchange.permutes_per_quantity",
    "exchange.trimean_s", "exchange.warmup",
    # interior-compute time over total fused-substep time: how much of
    # the wire the fused schedule actually hid (gauge, variant-tagged)
    "fused.overlap_fraction",
    "hb",
    "jacobi.exchange", "jacobi.exchange_bytes", "jacobi.exchange_warmup",
    "jacobi.init", "jacobi.iter", "jacobi.iter_trimean_s",
    "jacobi.loop_wall_s", "jacobi.mcells_per_s", "jacobi.mcells_per_s_per_dev",
    "jacobi.warmup",
    "live.anomaly_count",
    "machine", "machine.bandwidth_matrix", "machine.device",
    "machine.distance_matrix", "machine.fabric", "machine.partition",
    "overlap.hidden_frac",
    "pingpong.gb_per_s", "pingpong.latency_us",
    "plan.autotune", "plan.cache_hit", "plan.candidates", "plan.chosen",
    "plan.probe", "plan.probe_trimean_s", "plan.probes_run",
    # the placement leg (bench_qap --derived + the plan hot-swap): QAP
    # solver wall/cost rows, the derived-matrix placement cost, and the
    # modeled identity-over-placed improvement ratio
    "qap.cost", "qap.improvement", "qap.placement_cost", "qap.solve_s",
    "recover.backoff_s",
    # the serving daemon's exit gauges: sustained completion rate and
    # per-step tail latency under open-loop arrivals (the ROADMAP #4
    # bench leg), plus the queue-depth gauge the dashboard trends
    "serve.p99_ms", "serve.queue_depth", "serve.slot_width",
    "serve.tenants_per_hour",
    "wire_ab.bytes_ratio", "wire_ab.max_abs_err", "wire_ab.max_rel_err",
    "wire_ab.max_ulp_err",
    # what an application's run() spends before its first timed chunk
    # (benchmark readers app_run_host_init_s / _compile_s / _steps_s) and
    # the HBM bytes one call of a self-fill kernel reads and writes,
    # counted where its DMAs are built (self_fill_moved_roofline)
    "astaroth.realize", "astaroth.steps",
    "exchange.realize", "exchange.steps",
    "jacobi.realize", "jacobi.steps",
    "halo.self_fill.bytes_dma", "halo.split_x.bytes_dma",
})

# how many records a recorder keeps in memory (oldest dropped first)
KEEP_RECORDS = 4096


def new_run_id() -> str:
    return time.strftime("%Y%m%dT%H%M%S") + "-" + uuid.uuid4().hex[:8]


class Recorder:
    """One measurement run's telemetry channel.

    ``sink`` is a path (opened append) or a file-like object, or None — a
    disabled recorder still accumulates timer buckets in spans and still
    beats the watchdog heartbeat file, so supervision works even when no
    metrics file was requested. Whatever the sink, the last
    ``KEEP_RECORDS`` records stay in memory for :meth:`records` (an
    in-process reader, e.g. the benchmark's ``app_run_*`` metrics).
    """

    def __init__(
        self,
        sink=None,
        run_id: Optional[str] = None,
        app: Optional[str] = None,
        clock=time.time,
    ):
        self.run_id = run_id or new_run_id()
        self.app = app
        self._clock = clock
        self._owns_sink = isinstance(sink, (str, os.PathLike))
        self._sink = open(sink, "a", buffering=1) if self._owns_sink else sink
        self._lock = threading.Lock()
        self._kept: collections.deque = collections.deque(maxlen=KEEP_RECORDS)
        self._proc: Optional[int] = None
        self._hb_path = os.environ.get(HEARTBEAT_FILE_ENV) or None
        self._hb_interval = float(
            os.environ.get(HEARTBEAT_INTERVAL_ENV, "5") or 5
        )
        self._hb_last = 0.0
        self._hb_seq = 0
        self._hb_thread: Optional[threading.Thread] = None
        self._hb_stop = threading.Event()
        # progress the heartbeat payload quotes (obs/watchdog contract:
        # readers that only stat the mtime keep working; JSON-aware ones
        # can say WHERE the run stalled). Shared with the beat thread —
        # plain dict reads/writes, races are benign (a beat quotes either
        # the old or the new step, both true recently).
        self._progress: Dict[str, object] = {}

    @property
    def enabled(self) -> bool:
        """True when records are actually written somewhere."""
        return self._sink is not None

    # -- emission ------------------------------------------------------------
    def emit(self, kind: str, name: str, *, phase: Optional[str] = None,
             **fields) -> dict:
        """Build one record, write it to the sink, touch the heartbeat.

        Returns the record dict either way, so callers (machine_info
        ``--json``) can route records themselves.
        """
        if self._proc is None:
            # cache only once a backend answered; 0 from a backend-less
            # process stays re-resolvable (utils/logging._prefix discipline)
            proc = 0
            jax = sys.modules.get("jax")
            if jax is not None:
                try:
                    proc = jax.process_index()
                    self._proc = proc
                except Exception:
                    pass
        else:
            proc = self._proc
        rec = {
            "v": SCHEMA_VERSION,
            "run": self.run_id,
            "proc": proc,
            "kind": kind,
            "name": name,
            "t": self._clock(),
        }
        if self.app:
            rec["app"] = self.app
        if phase is not None:
            rec["phase"] = phase
        for k, v in fields.items():
            if v is not None:
                rec[k] = v
        self._kept.append(rec)
        if self._sink is not None:
            line = json.dumps(rec, default=str)
            with self._lock:
                self._sink.write(line + "\n")
                try:
                    self._sink.flush()
                except (OSError, ValueError):
                    pass
        self._maybe_beat()
        return rec

    def records(self, kind: Optional[str] = None,
                name: Optional[str] = None) -> List[dict]:
        """The records kept in memory, oldest first, of one ``kind`` and
        one ``name`` where given."""
        return [r for r in list(self._kept)
                if (kind is None or r["kind"] == kind)
                and (name is None or r["name"] == name)]

    @contextlib.contextmanager
    def span(self, name: str, phase: Optional[str] = None,
             bucket: Optional[str] = None, **tags):
        """Timed region: timer bucket + profiler range + one span record.

        The record is emitted even when the body raises (the failed span
        is evidence), and the exception propagates — same discipline as
        ``timer.trace_range``. ``bucket=False``: no timer bucket (the
        exit-time ``timers:`` line stays as it is).
        """
        t0_ns = time.time_ns()
        t0 = time.perf_counter()
        prev_span = self._progress.get("span")
        self._progress["span"] = name  # the heartbeat payload quotes this
        timed = (contextlib.nullcontext() if bucket is False
                 else timer.timed(bucket or name))
        try:
            with timed, timer.trace_range(name):
                yield
        finally:
            self._progress["span"] = prev_span
            seconds = time.perf_counter() - t0
            self.emit("span", name, phase=phase, seconds=seconds,
                      t0_ns=t0_ns, t1_ns=t0_ns + int(seconds * 1e9),
                      parent=prev_span, **tags)

    def open_span(self, name: str, phase: Optional[str] = None, **tags):
        """:meth:`span` for a stretch of a long function that no ``with``
        block fits round: opens it now and returns the function that
        closes it. It covers other spans' buckets, so it adds none."""
        cm = self.span(name, phase=phase, bucket=False, **tags)
        cm.__enter__()
        return lambda: cm.__exit__(None, None, None)

    def counter(self, name: str, value: Optional[int] = None,
                bytes: Optional[int] = None, phase: Optional[str] = None,
                **tags) -> dict:
        return self.emit("counter", name, phase=phase, value=value,
                         bytes=bytes, **tags)

    def gauge(self, name: str, value: float, phase: Optional[str] = None,
              unit: Optional[str] = None, **tags) -> dict:
        return self.emit("gauge", name, phase=phase, value=value, unit=unit,
                         **tags)

    def meta(self, name: str, **fields) -> dict:
        return self.emit("meta", name, **fields)

    # -- heartbeat (watchdog contract) ---------------------------------------
    def heartbeat(self) -> None:
        """Touch the watchdog heartbeat file + emit a heartbeat record."""
        self._hb_seq += 1
        self._touch_hb()
        if self._sink is not None:
            self.emit("heartbeat", "hb", seq=self._hb_seq)
        else:
            self._hb_last = time.monotonic()

    def note_step(self, step: int) -> None:
        """Record the last completed step for the heartbeat payload
        (the guarded loop calls this per chunk): a stall report can then
        say "stalled at step 412 in exchange" instead of a bare age."""
        self._progress["step"] = int(step)

    def _touch_hb(self) -> None:
        if not self._hb_path:
            return
        # the body is a tiny JSON note (last step, current span) the
        # watchdog's stall report quotes; the LIVENESS contract is still
        # mtime-only, so pure-stdlib readers that just stat() keep
        # working and a hand-touched beat file stays a valid beat
        note = {"t": time.time()}
        note.update({k: v for k, v in self._progress.items()
                     if v is not None})
        try:
            with open(self._hb_path, "w") as f:
                f.write(json.dumps(note) + "\n")
        except (OSError, TypeError, ValueError):
            pass  # a torn-down supervisor must not crash the measurement

    def _maybe_beat(self) -> None:
        """Rate-limited beat on every emission: a chatty child never needs
        an explicit heartbeat call."""
        if not self._hb_path:
            return
        now = time.monotonic()
        if now - self._hb_last >= self._hb_interval:
            self._hb_last = now
            self._touch_hb()

    def start_heartbeat_thread(self, interval_s: Optional[float] = None) -> bool:
        """Beat from a daemon thread so long silent stretches (multi-minute
        XLA compiles) do not read as stalls. A hard wedge that freezes the
        interpreter freezes this thread too — which is exactly when the
        watchdog SHOULD fire. No-op (returns False) without a supervisor.
        """
        if not self._hb_path or self._hb_thread is not None:
            return False
        interval = interval_s or self._hb_interval

        def beat():
            while not self._hb_stop.wait(interval):
                self._hb_seq += 1
                self._touch_hb()

        self._touch_hb()  # first beat immediately: starts the stall clock
        self._hb_thread = threading.Thread(
            target=beat, name="stencil-heartbeat", daemon=True
        )
        self._hb_thread.start()
        return True

    # -- convenience ---------------------------------------------------------
    def record_timer_buckets(self, phase: Optional[str] = None) -> None:
        """Snapshot utils/timer's global buckets as gauges (the machine
        analogue of the apps' exit-time ``timers:`` line)."""
        for k, v in sorted(timer.buckets.items()):
            self.gauge(f"timer.{k}", v, phase=phase, unit="s")

    def close(self) -> None:
        self._hb_stop.set()
        if self._owns_sink and self._sink is not None:
            try:
                self._sink.close()
            finally:
                self._sink = None


# -- module-level default recorder -------------------------------------------

_recorder: Optional[Recorder] = None


def configure(metrics_out: Optional[str] = None, app: Optional[str] = None,
              run_id: Optional[str] = None, config: Optional[dict] = None,
              heartbeat_thread: bool = True) -> Recorder:
    """Install the process-default recorder (what ``--metrics-out`` wires).

    Emits the run's identity/config meta record first so every metrics
    file is self-describing, and starts the watchdog heartbeat thread when
    a supervisor is attached.
    """
    global _recorder
    if _recorder is not None:
        _recorder.close()
    _recorder = Recorder(sink=metrics_out or None, app=app, run_id=run_id)
    if config:
        clean = {k: v for k, v in config.items()
                 if isinstance(v, (str, int, float, bool, type(None)))}
        _recorder.meta("config", config=clean)
    if heartbeat_thread:
        _recorder.start_heartbeat_thread()
    return _recorder


def get() -> Recorder:
    """The process-default recorder (a disabled one before configure())."""
    global _recorder
    if _recorder is None:
        _recorder = Recorder(sink=None)
    return _recorder


def enabled() -> bool:
    return _recorder is not None and _recorder.enabled


# -- static truth: what the compiled artifacts say moves ---------------------


def record_census(census: Dict[str, Tuple[int, int]],
                  rec: Optional[Recorder] = None, **tags) -> None:
    """Record a ``collective_census`` result ({kind: (count, bytes)}) —
    one counter line per collective kind."""
    rec = rec or get()
    for kind, (count, nbytes) in sorted(census.items()):
        rec.counter(f"census.{kind}", value=count, bytes=nbytes,
                    phase="exchange", **tags)


def record_exchange_truth(ex, state, itemsizes: Sequence[int],
                          rec: Optional[Recorder] = None, **tags) -> dict:
    """Attach one exchange method's compile-time truth to the run: the
    collective census of the compiled program (exact on-wire volume — the
    analogue of the reference's Allreduced per-method byte counters,
    src/stencil.cu:139-161) plus the logical/moved byte accounting.

    Compiles one single-exchange program; callers gate on
    :func:`enabled` so metric-less runs pay nothing.

    Besides the raw census, records the packed on-wire totals and the
    ``exchange.permutes_per_quantity`` gauge — permute ops divided by the
    quantity count. With quantity batching this reads ~6/Q for the
    composed plan (one packed carrier pair per axis phase, Q-independent
    count); a reading that scales back up toward 6 (or 26) per quantity
    at Q > 1 flags a regression to per-quantity collectives
    (apps/report.py surfaces the gauge).
    """
    rec = rec or get()
    census = ex.collective_census(state)
    method = getattr(ex.method, "value", str(ex.method))
    nq = max(1, len(itemsizes))
    record_census(census, rec, method=method, **tags)
    from ..utils.hlo_check import census_per_quantity

    on_wire = sum(b for _c, b in census.values())
    rec.counter("exchange.bytes_on_wire", bytes=on_wire, phase="exchange",
                method=method, quantities=nq, **tags)
    per_q = census_per_quantity(census, nq)
    rec.counter(
        "exchange.bytes_on_wire_per_quantity",
        bytes=sum(b for _c, b in per_q.values()),
        phase="exchange", method=method, quantities=nq, **tags,
    )
    cp_count = census.get("collective-permute", (0, 0))[0]
    rec.gauge("exchange.permutes_per_quantity", cp_count / nq,
              phase="exchange", method=method, quantities=nq, **tags)
    # launch-count census (ROADMAP #7): the step driver's measured host
    # dispatches per chunk when a persistent/multistep loop ran
    # (ops/jacobi sets last_launches_per_chunk), else the plan's static
    # prediction — tagged so the auditor and the CI pin can tell a
    # measurement from a model (utils/hlo_check.kernel_launch_census is
    # the compiled-module side of the same evidence)
    lpc = getattr(ex, "last_launches_per_chunk", 0)
    src = "measured"
    if not lpc:
        plan = getattr(ex, "plan", None)
        lpc = plan.launches_per_chunk() if plan is not None else 0
        src = "modeled"
    if lpc:
        rec.gauge("exchange.launches_per_chunk", lpc, phase="exchange",
                  method=method, source=src, **tags)
    rec.counter("exchange.bytes_logical", bytes=ex.bytes_logical(itemsizes),
                phase="exchange", method=method, **tags)
    rec.counter("exchange.bytes_moved", bytes=ex.bytes_moved(itemsizes),
                phase="exchange", method=method, **tags)
    return census


def record_dma_traffic(build, rec: Optional[Recorder] = None,
                       **tags) -> list:
    """Attach the Mosaic kernels' static DMA truth: lower ``build()``'s
    Pallas kernels for the TPU platform (utils/mosaic_traffic) and record
    per-kernel HBM input/output bytes per grid pass.

    Expensive (a full TPU lowering) and not reentrant — callers gate it
    behind an explicit flag. A capture failure records a meta line instead
    of raising: the DMA truth is evidence, never the measurement.
    """
    rec = rec or get()
    from ..utils.mosaic_traffic import capture_traffic

    try:
        kernels = capture_traffic(build)
    except Exception as e:
        rec.meta("dma.capture_error", error=f"{type(e).__name__}: {e}"[:400],
                 **tags)
        return []
    for kt in kernels:
        rec.counter(f"dma.{kt.name}.in", bytes=kt.input_bytes(),
                    value=kt.steps, phase="compute", grid=list(kt.grid),
                    **tags)
        rec.counter(f"dma.{kt.name}.out", bytes=kt.output_bytes(),
                    value=kt.steps, phase="compute", grid=list(kt.grid),
                    **tags)
    return kernels


# -- schema validation (the authority apps/report.py + CI use) ---------------


def validate_record(rec) -> List[str]:
    """Return the list of schema violations (empty = valid v1 record)."""
    errs: List[str] = []
    if not isinstance(rec, dict):
        return [f"not an object: {type(rec).__name__}"]
    for k in REQUIRED_KEYS:
        if k not in rec:
            errs.append(f"missing required key {k!r}")
    if errs:
        return errs
    if rec["v"] != SCHEMA_VERSION:
        errs.append(f"unknown schema version {rec['v']!r}")
    if not isinstance(rec["run"], str) or not rec["run"]:
        errs.append("run must be a non-empty string")
    if not isinstance(rec["proc"], int):
        errs.append("proc must be an int")
    if not isinstance(rec["name"], str) or not rec["name"]:
        errs.append("name must be a non-empty string")
    if not isinstance(rec["t"], (int, float)):
        errs.append("t must be a number")
    kind = rec["kind"]
    if kind not in KINDS:
        errs.append(f"unknown kind {kind!r}")
    elif kind == "span":
        if not isinstance(rec.get("seconds"), (int, float)):
            errs.append("span requires numeric 'seconds'")
    elif kind == "counter":
        if not isinstance(rec.get("value"), int) and not isinstance(
                rec.get("bytes"), int):
            errs.append("counter requires integer 'value' and/or 'bytes'")
    elif kind == "gauge":
        if not isinstance(rec.get("value"), (int, float)):
            errs.append("gauge requires numeric 'value'")
    elif kind == "heartbeat":
        if not isinstance(rec.get("seq"), int):
            errs.append("heartbeat requires integer 'seq'")
    if "bytes" in rec and not isinstance(rec["bytes"], int):
        errs.append("'bytes' must be an integer where present")
    for fld, typ in NAME_FIELDS.get(rec["name"], ()):
        v = rec.get(fld)
        if not isinstance(v, typ) or (typ is int and isinstance(v, bool)):
            errs.append(
                f"{rec['name']} requires {typ.__name__} {fld!r}")
    return errs


def validate_jsonl(lines: Iterable[str]) -> Tuple[int, List[str]]:
    """Validate an iterable of JSONL lines; returns (n_valid, errors)."""
    n_ok = 0
    errors: List[str] = []
    for i, line in enumerate(lines, 1):
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError as e:
            errors.append(f"line {i}: unparseable JSON ({e})")
            continue
        errs = validate_record(rec)
        if errs:
            errors.extend(f"line {i}: {e}" for e in errs)
        else:
            n_ok += 1
    return n_ok, errors
