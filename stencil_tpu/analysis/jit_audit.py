"""Step-loop audit: no recompiles, no host syncs, after warmup.

The two canonical silent perf bugs of any jit-compiled step loop:

1. **post-warmup recompilation** — a shape/dtype/static-arg churn makes
   XLA compile *inside the timed region*. The repo's discipline is "one
   chunk-size plan drives both warmup and the timed loop, so no compile
   can land in a timed region" (PR 4); this audit enforces it
   mechanically with a compile counter fed by ``jax.monitoring``'s
   ``backend_compile`` events.
2. **implicit host transfer** — a stray ``.item()``/``np.asarray``/
   print pulls a device value mid-loop, serializing the pipeline. The
   audited chunks run under ``jax.transfer_guard("disallow")``; the
   loop's ONE sanctioned sync (``utils/sync.hard_sync``, per chunk)
   runs *outside* the guard, so anything else that touches the host
   trips it.

The audited loop is the real thing: a jacobi domain built through
``DistributedDomain``, stepped with ``ops/jacobi.make_jacobi_loop``
fused chunks on the local device mesh — the same programs the apps
time. ``inject="recompile"`` skips warming the tail chunk size (the
exact historical bug class) and ``inject="host-sync"`` pulls a value
inside the guard; both must FAIL the audit — the CI gate's proof that
it can detect what it claims to.

Results land as the schema-valid ``analysis.jit_audit`` telemetry
record; the CLI front end is ``lint_tool jit-audit``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

from ..obs import telemetry

INJECT_MODES = ("recompile", "host-sync")

# -- compile counter (jax.monitoring backend_compile events) ------------------


def compile_count() -> int:
    """Backend compiles observed since the process's compile watcher
    (``obs/telemetry.watch_compiles``: the one ``jax.monitoring``
    listener of the package) was installed; installs it where jax is
    imported and it is not there yet."""
    watcher = telemetry.watch_compiles()
    return 0 if watcher is None else watcher.backend_compiles


@dataclass
class AuditResult:
    ok: bool
    recompiles: int
    transfer_trips: List[str] = field(default_factory=list)
    steps: int = 0
    chunks: int = 0
    warmup_compiles: int = 0
    inject: Optional[str] = None

    def to_json(self) -> dict:
        return {
            "kind": "jit-audit", "ok": self.ok,
            "recompiles": self.recompiles,
            "transfer_trips": self.transfer_trips,
            "steps": self.steps, "chunks": self.chunks,
            "warmup_compiles": self.warmup_compiles,
            "inject": self.inject,
        }


def run_audit(size: int = 16, iters: int = 10, chunk: int = 4,
              inject: Optional[str] = None, devices=None,
              rec: Optional["telemetry.Recorder"] = None) -> AuditResult:
    """Audit the jacobi guarded chunk loop on the local mesh.

    Warmup compiles every distinct chunk size of the plan (the apps'
    checkpointed-run discipline), then the audited chunks run under
    ``transfer_guard("disallow")`` with the compile counter armed. Any
    post-warmup ``backend_compile`` event or disallowed transfer fails
    the audit.
    """
    if inject is not None and inject not in INJECT_MODES:
        raise ValueError(f"unknown inject mode {inject!r} "
                         f"(known: {', '.join(INJECT_MODES)})")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..api import DistributedDomain
    from ..fault.recover import chunk_plan
    from ..ops.jacobi import INIT_TEMP, make_jacobi_loop, make_jacobi_step, \
        sphere_sel
    from ..parallel.exchange import shard_blocks
    from ..utils.sync import hard_sync

    rec = rec or telemetry.get()
    devices = list(devices) if devices is not None else jax.devices()

    dd = DistributedDomain(size, size, size)
    dd.set_radius(1)
    dd.set_devices(devices)
    h = dd.add_data("temperature")
    dd.realize()
    sharding = dd.sharding()
    shape = dd.spec.stacked_shape_zyx()
    curr = jax.device_put(jnp.full(shape, INIT_TEMP, jnp.float32), sharding)
    nxt = jax.device_put(jnp.zeros(shape, jnp.float32), sharding)
    sel = shard_blocks(sphere_sel(dd.spec.global_size), dd.spec, dd.mesh)

    chunk = max(1, min(chunk, iters))
    plan = chunk_plan(0, iters, chunk)
    loops = {}

    def get_loop(k: int):
        if k not in loops:
            loops[k] = (make_jacobi_loop(dd.halo_exchange, k)
                        if k > 1 else
                        make_jacobi_step(dd.halo_exchange))
        return loops[k]

    # warmup: every distinct chunk size of the plan — UNLESS the
    # injected-recompile fixture is on, which deliberately leaves the
    # tail size cold (the historical compile-in-a-timed-region bug)
    warm_sizes = list(dict.fromkeys(plan))
    if inject == "recompile":
        warm_sizes = warm_sizes[:1]
        if len(set(plan)) < 2:
            raise ValueError(
                f"inject='recompile' needs a chunk plan with >= 2 "
                f"distinct sizes; iters={iters} chunk={chunk} gives "
                f"{plan} — pick iters not divisible by chunk")
    c0 = compile_count()
    with rec.span("analysis.jit_warmup", phase="compile"):
        for k in warm_sizes:
            curr, nxt = get_loop(k)(curr, nxt, sel)
        # hard_sync's scalar-fetch program must also be warm, or its
        # first gather compile would read as a step-loop recompile
        hard_sync(curr)
    warmup_compiles = compile_count() - c0

    trips: List[str] = []
    baseline = compile_count()
    done = 0
    with rec.span("analysis.jit_audit_loop", phase="step"):
        for i, k in enumerate(plan):
            loop = get_loop(k)
            try:
                with jax.transfer_guard("disallow"):
                    curr, nxt = loop(curr, nxt, sel)
                    if inject == "host-sync" and i == 1:
                        # the injected bug: a mid-loop scalar pull
                        # (float(x[0,...]) — the .item() bug class). The
                        # guard trips on the un-jitted host interaction
                        # (on CPU, the index upload; on TPU, the pull
                        # itself)
                        float(curr[(0,) * curr.ndim])
            except Exception as e:
                msg = str(e)
                if "isallow" in msg or "transfer" in msg.lower():
                    trips.append(
                        f"chunk {i} (k={k}): {msg.splitlines()[0][:200]}")
                    continue  # the chunk is evidence; keep auditing
                raise
            hard_sync(curr)  # the ONE sanctioned sync, outside the guard
            done += k
    recompiles = compile_count() - baseline

    ok = recompiles == 0 and not trips
    result = AuditResult(ok=ok, recompiles=recompiles,
                         transfer_trips=trips, steps=done,
                         chunks=len(plan), warmup_compiles=warmup_compiles,
                         inject=inject)
    rec.meta("analysis.jit_audit", ok=int(ok), recompiles=int(recompiles),
             transfers=len(trips), steps=done, inject=inject)
    return result
