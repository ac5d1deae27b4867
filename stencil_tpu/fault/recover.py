"""Rollback-with-backoff recovery: the guarded step loop.

The policy layer of the ``fault/`` stack: health.py detects, inject.py
manufactures, this module recovers. :func:`run_guarded` drives an app's
fused-chunk step loop and, on a :class:`~.health.NumericalFault`,

1. records the fault (``recover.fault``),
2. restores the newest *valid* checkpoint through the app's restore hook
   (``DistributedDomain.restore_checkpoint`` → ``ckpt/restore.find_resume``'s
   layered validation — a truncated newest snapshot falls back to the
   previous good one),
3. health-checks the *restored* state too; a poisoned snapshot is
   quarantined (``ckpt/restore.quarantine_snapshot``) and the next
   candidate is tried — a rollback must never reinstall the disease,
4. backs off exponentially on repeated faults at the same step, and
5. after ``max_rollbacks`` at one step (or with no checkpoint to roll
   back to), degrades LOUDLY: writes a JSON evidence bundle, records
   ``recover.aborted``, and raises :class:`RecoveryExhausted` — the apps
   exit with :data:`FAULT_RC`, which the watchdog classifies as the
   ``fault`` outcome (rc-distinct from stall/crash/the ckpt kill hook).

Ordering contract per chunk: **step → inject → health check → checkpoint**.
The check runs before the save, so a poisoned state is never persisted —
the checkpoints stay a clean rollback target by construction.

With no guard, injector, or restore hook configured the engine degrades
to the apps' historical plain chunk loop: same step programs (the engine
never wraps or recompiles them — zero HLO change), same checkpoint
cadence, same telemetry.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..obs import telemetry
from ..obs.watchdog import FAULT_RC  # noqa: F401  (re-exported contract)
from ..utils import logging as log
from .health import HealthGuard, NumericalFault
from .inject import FaultPlan

EVIDENCE_ENV = "STENCIL_FAULT_EVIDENCE"
EVIDENCE_NAME = "fault-evidence.json"


class RecoveryExhausted(RuntimeError):
    """Recovery gave up: no checkpoint to roll back to, or the same step
    faulted more than ``max_rollbacks`` times. Apps exit
    :data:`FAULT_RC` on this."""

    def __init__(self, fault: NumericalFault, rollbacks: int,
                 evidence_path: Optional[str], reason: str):
        self.fault = fault
        self.rollbacks = rollbacks
        self.evidence_path = evidence_path
        self.reason = reason
        super().__init__(
            f"recovery exhausted after {rollbacks} rollback(s): {reason} "
            f"(last fault: {fault}; evidence: {evidence_path or 'unwritten'})"
        )


@dataclass
class RecoveryPolicy:
    """Rollback budget + backoff shape."""

    max_rollbacks: int = 3      # per fault step
    backoff_s: float = 0.25     # first-retry sleep; doubles per repeat
    backoff_max_s: float = 30.0

    def backoff(self, attempt: int) -> float:
        return min(self.backoff_max_s, self.backoff_s * (2 ** (attempt - 1)))


def chunk_plan(start: int, iters: int, chunk: int,
               every: Sequence[int] = (), at: Sequence[int] = ()) -> List[int]:
    """Fused-chunk schedule from ``start`` to ``iters``: chunks of at most
    ``chunk`` steps, additionally broken at every multiple of each nonzero
    cadence in ``every`` (checkpoint / health boundaries) and at each
    absolute step in ``at`` (injection steps — a fault must land at its
    exact step regardless of chunking)."""
    bounds = sorted(b for b in set(at) if start < b < iters)
    plan: List[int] = []
    d = start
    while d < iters:
        k = min(chunk, iters - d)
        for e in every:
            if e and e > 0:
                k = min(k, e - d % e)
        for b in bounds:
            if b > d:
                k = min(k, b - d)
                break
        plan.append(k)
        d += k
    return plan


def _crossed(prev: int, step: int, every: int) -> bool:
    return every > 0 and step // every > prev // every


def write_evidence(payload: dict, evidence_dir: Optional[str]) -> Optional[str]:
    """Persist the abort evidence bundle (best-effort: evidence must never
    mask the abort itself). ``STENCIL_FAULT_EVIDENCE`` overrides the full
    path; the default is ``<evidence_dir>/fault-evidence.json``."""
    path = os.environ.get(EVIDENCE_ENV) or os.path.join(
        evidence_dir or ".", EVIDENCE_NAME)
    try:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1, default=str)
        os.replace(tmp, path)
    except OSError as e:
        log.warn(f"fault: could not write the evidence bundle {path}: {e}")
        return None
    return path


def run_guarded(
    state: Dict[str, "object"],
    *,
    start: int,
    iters: int,
    plan_fn: Callable[[int], Sequence[int]],
    step_fn: Callable[[Dict, int], Dict],
    guard: Optional[HealthGuard] = None,
    injector: Optional[FaultPlan] = None,
    policy: Optional[RecoveryPolicy] = None,
    save_fn: Optional[Callable[[int, Dict], None]] = None,
    ckpt_every: int = 0,
    restore_fn: Optional[Callable[[], Optional[Tuple[int, Dict]]]] = None,
    quarantine_fn: Optional[Callable[[int], None]] = None,
    flush_fn: Optional[Callable[[], None]] = None,
    on_chunk: Optional[Callable[[Dict, int, float, int], Optional[Dict]]] = None,
    spec=None,
    ckpt_dir: Optional[str] = None,
    evidence_dir: Optional[str] = None,
    app: Optional[str] = None,
    sentinel=None,
    sentinel_key: str = "step.latency_s",
    status=None,
    replan=None,
) -> Tuple[Dict, int]:
    """Drive the step loop from ``start`` to ``iters``; returns the final
    ``(state, step)``.

    - ``plan_fn(step)`` rebuilds the fused-chunk schedule from any step
      (called again after every rollback).
    - ``step_fn(state, k)`` advances ``k`` steps and must block until the
      result is real (the engine times it).
    - ``save_fn(step, state)`` persists a checkpoint; called when a chunk
      crosses a ``ckpt_every`` boundary, strictly AFTER the health check.
    - ``restore_fn() -> (step, state) | None`` is the rollback source
      (``None`` = nothing valid left → abort).
    - ``flush_fn()`` drains an async checkpoint writer; called before any
      read-back of the checkpoint dir (rollback restore, disk-level
      injections) so "newest snapshot" never races the writer thread.
    - ``quarantine_fn(step)`` renames a restored-but-poisoned snapshot
      aside so the next restore attempt skips it.
    - ``on_chunk(state, k, per_iter_s, step)`` observes each timed chunk
      (statistics, telemetry, dumps); may return a replacement state.
    - ``sentinel`` (:class:`~stencil_tpu.obs.live.LiveSentinel`) observes
      each chunk's whole-cycle per-step latency under ``sentinel_key`` —
      step + injection + health check + checkpoint, deliberately WIDER
      than the per-chunk step span (an injected slowdown or a slow save
      must be visible to the in-run sentinel the way it is to the
      wall-clock ledger leg). Detection emits ``anomaly.detected`` /
      ``replan.requested`` mid-run.
    - ``status`` (:class:`~stencil_tpu.obs.status.StatusWriter`) gets an
      atomic snapshot rewrite per chunk: current step, rolling latency,
      health counts, anomaly state — the file ``report --status`` polls.
    - ``replan`` (:class:`~stencil_tpu.plan.replan.ReplanController`)
      closes the ROADMAP #6 loop: when the sentinel's ``on_replan`` hook
      latched a request, the engine finishes the current chunk and then
      performs the swap — retune, install the new compiled plan, emit
      ``replan.applied``/``replan.rejected`` — BETWEEN chunks, where a
      rebuild cannot tear a step; a rejected swap continues on the old
      plan. The controller may return a re-sharded state (the new
      plan's partition may differ), which replaces ``state`` for the
      remaining chunks.
    """
    rec = telemetry.get()
    policy = policy or RecoveryPolicy()
    done = int(start)
    if injector is not None:
        dead = [s for s in injector.steps() if s <= start]
        if dead:
            log.warn(f"fault: injection step(s) {dead} are <= the start "
                     f"step {start} and will never fire (resumed past "
                     "them?)")
    rollbacks: Dict[int, int] = {}
    fault_log: List[dict] = []
    health_checks = 0
    # a campaign calls run_guarded once per slot segment on ONE shared
    # status writer: the health section accumulates on top of whatever
    # the snapshot already shows, so counts never regress mid-campaign
    base_health = {"checks": 0, "faults": 0, "rollbacks": 0}
    if status is not None and isinstance(status.doc.get("health"), dict):
        prev_h = status.doc["health"]
        base_health = {k: int(prev_h.get(k, 0)) for k in base_health}

    def _status_update(step: int, per: Optional[float] = None) -> None:
        if status is None:
            return
        status.update(
            step=int(step), iters=int(iters), per_step_s=per,
            steps_per_s=(1.0 / per if per and per > 0 else None),
            health={
                "checks": base_health["checks"] + health_checks,
                "faults": base_health["faults"] + len(fault_log),
                "rollbacks": (base_health["rollbacks"]
                              + sum(rollbacks.values())),
            },
            anomalies=sentinel.summary() if sentinel is not None else None,
        )

    def _abort(fault: NumericalFault, reason: str) -> None:
        payload = {
            "kind": "stencil-fault-evidence",
            "app": app,
            "t": time.time(),
            "rc": FAULT_RC,
            "reason": reason,
            "policy": {"max_rollbacks": policy.max_rollbacks,
                       "backoff_s": policy.backoff_s},
            "faults": fault_log,
            "rollbacks": {str(k): v for k, v in rollbacks.items()},
            "injections": injector.describe() if injector else [],
            "ckpt_dir": ckpt_dir,
            "metrics": os.environ.get("STENCIL_METRICS_OUT"),
        }
        path = write_evidence(payload, evidence_dir or ckpt_dir)
        rec.meta("recover.aborted", reason=reason, step=int(fault.step),
                 rollbacks=sum(rollbacks.values()), evidence=path)
        log.error(f"fault: recovery exhausted at step {fault.step} "
                  f"({reason}); evidence: {path}; exiting rc={FAULT_RC}")
        raise RecoveryExhausted(fault, sum(rollbacks.values()), path, reason)

    while True:
        plan = plan_fn(done)
        try:
            for k in plan:
                prev = done
                t0 = time.perf_counter()
                state = step_fn(state, k)
                per = (time.perf_counter() - t0) / k
                done = prev + k
                rec.note_step(done)  # heartbeat payload: last step reached
                if injector is not None:
                    state = injector.fire_due(state, prev, done, spec=spec,
                                              ckpt_dir=ckpt_dir,
                                              ckpt_flush=flush_fn)
                save_due = (save_fn is not None and done < iters
                            and _crossed(prev, done, ckpt_every))
                if guard is not None and (guard.due(prev, done) or save_due
                                          or done >= iters):
                    # a due save forces a check even off the health cadence:
                    # a poisoned state must never become a rollback target
                    guard.check(state, step=done)
                    health_checks += 1
                if save_due:
                    save_fn(done, state)
                cycle = per
                if sentinel is not None:
                    # the whole chunk cycle per step (step + injection +
                    # health + save): what the run actually sustains —
                    # an injected slowdown lands HERE, not in `per`
                    cycle = (time.perf_counter() - t0) / k
                    sentinel.observe(sentinel_key, cycle, step=done,
                                     unit="s")
                if on_chunk is not None:
                    state = on_chunk(state, k, per, done) or state
                # status AFTER on_chunk: a section owner riding on_chunk
                # (the campaign driver stages lanes via status.set) gets
                # its sections into the SAME atomic write — one
                # fsync+rename per chunk, not two
                _status_update(done, cycle)
                if replan is not None and replan.pending:
                    # the chunk is finished and its status is durable:
                    # the one safe point to swap the compiled plan.
                    # Remaining chunk sizes stay valid (they are step
                    # counts, not programs); the next step_fn call runs
                    # the new plan's compiled loop.
                    swapped = replan.maybe_swap(state, done)
                    if swapped is not None:
                        state = swapped
            return state, done
        except NumericalFault as f:
            n = rollbacks.get(f.step, 0) + 1
            rollbacks[f.step] = n
            fault_log.append({
                "kind": f.kind, "quantity": f.quantity, "step": f.step,
                "value": f.value, "t": time.time(), "attempt": n,
            })
            rec.meta("recover.fault", fault_kind=f.kind, quantity=f.quantity,
                     step=int(f.step), attempt=n)
            log.warn(f"fault: {f} (occurrence {n} at this step)")
            if restore_fn is None:
                _abort(f, "no checkpointing configured: cannot roll back")
            if n > policy.max_rollbacks:
                _abort(f, f"max rollbacks ({policy.max_rollbacks}) exceeded "
                          f"at step {f.step}")
            backoff = policy.backoff(n)
            rec.gauge("recover.backoff_s", backoff, phase="recover",
                      step=int(f.step), unit="s")
            log.warn(f"fault: backing off {backoff:g}s before rollback "
                     f"{n}/{policy.max_rollbacks}")
            time.sleep(backoff)
            # restore; the async writer is drained first so every save
            # already handed off is visible on disk. A restored state that
            # itself fails the guard is a poisoned snapshot — quarantine
            # it and fall further back
            if flush_fn is not None:
                flush_fn()
            restored = None
            for _ in range(policy.max_rollbacks + 8):
                found = restore_fn()
                if found is None:
                    _abort(f, "no valid checkpoint to roll back to")
                rstep, rstate = found
                try:
                    if guard is not None:
                        guard.check(rstate, step=rstep)
                except NumericalFault as g:
                    if quarantine_fn is None:
                        _abort(g, f"restored snapshot (step {rstep}) is "
                                  "poisoned and quarantine is unavailable")
                    log.warn(f"fault: restored step {rstep} is poisoned "
                             f"({g.kind} in {g.quantity!r}); quarantining")
                    quarantine_fn(rstep)
                    continue
                restored = (rstep, rstate)
                break
            if restored is None:
                _abort(f, "every restore candidate was poisoned")
            rstep, state = restored
            rec.counter("recover.rollback", value=1, phase="recover",
                        from_step=int(done), to_step=int(rstep),
                        fault_step=int(f.step))
            log.warn(f"fault: rolled back from step {done} to checkpointed "
                     f"step {rstep}")
            done = rstep
            rec.note_step(done)
            _status_update(done)  # the snapshot shows the rollback, live
