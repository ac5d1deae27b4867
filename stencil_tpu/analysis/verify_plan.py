"""ExchangePlan IR vs compiled-HLO conformance auditor.

The ExchangePlan IR (plan/ir.py) *predicts* what each lowering puts on
the interconnect — ``collectives_per_exchange``, ``wire_bytes`` — and
the autotuner ranks candidates on those predictions without compiling
them. The lowering (parallel/exchange.py) is required to compile to
exactly what the plan says; historically that contract was pinned by a handful of hand-written counts in
tests/test_plan_ir.py. This module makes it a *sweepable gate*: for a
grid of partition x method x dtype x Q configs it compiles each lowering
and cross-checks the IR's predictions against the compiled truth:

- predicted ``collectives_per_exchange`` == the compiled program's
  ``collective-permute`` census count (``utils/hlo_check``), for every
  method — composed / direct26 / auto-spmd (the round-7 "partitioner
  reinvents the composed schedule per quantity" finding, encoded);
- predicted ``wire_bytes`` == the census byte total for the ppermute
  methods (exact on one-block-per-device meshes — the scope this sweep
  stays in; the model documents its oversubscription overestimate);
- no collective kind beyond ``collective-permute`` ever appears.

One schema-valid JSON verdict per config (``analysis.plan_verdict``
records through obs/telemetry when a recorder is attached; the same
dicts via :func:`run_sweep`'s return), so drift between plan/ir.py and
parallel/exchange.py trips a sweep instead of a post-mortem.

Infeasible configs (not enough local devices, radius too thick for the
partition) are SKIPPED loudly via ``plan/cost.feasible`` — the same
constraint authority realize() uses — and a sweep that analyzed nothing
is exit code 2 at the CLI, never a silent pass.

``perturb_*`` knobs offset a prediction before comparison — the CI
gate's proof that the auditor actually trips when the IR drifts.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from ..obs import telemetry

# Default sweep: every method on the canonical 2x2x2 partition plus an
# anisotropic (1, 2, 4) split (self-wrap x phase), at Q = 1, a batched
# Q = 3, and a mixed fp32+fp64 dict (two dtype groups) — the corners
# where the carrier-count predictions differ per method. All
# one-block-per-device: the scope where the byte model is exact.
DEFAULT_PARTITIONS: Tuple[Tuple[int, int, int], ...] = ((2, 2, 2), (1, 2, 4))
DEFAULT_QSETS: Tuple[Tuple[str, ...], ...] = (
    ("float32",),
    ("float32", "float32", "float32"),
    ("float32", "float32", "float64"),
)
DEFAULT_SIZE = 16
DEFAULT_RADIUS = 2


@dataclass
class Verdict:
    """One config's audit outcome. ``checks`` rows are
    ``{name, predicted, actual, ok}``; ``skipped`` configs carry the
    infeasibility reason instead."""

    label: str
    method: str
    ok: bool = True
    skipped: bool = False
    reason: str = ""
    checks: List[dict] = field(default_factory=list)

    def to_json(self) -> dict:
        return {
            "kind": "plan-verdict", "label": self.label,
            "method": self.method, "ok": self.ok,
            "skipped": self.skipped, "reason": self.reason,
            "checks": self.checks,
        }


def sweep_configs(
    size: int = DEFAULT_SIZE,
    radius: int = DEFAULT_RADIUS,
    partitions: Sequence[Tuple[int, int, int]] = DEFAULT_PARTITIONS,
    methods: Optional[Sequence[str]] = None,
    qsets: Sequence[Sequence[str]] = DEFAULT_QSETS,
) -> List[dict]:
    """The sweep grid as plain dicts (label, size, radius, partition,
    method, dtypes). Default methods: every ``plan.ir.METHODS`` entry."""
    from ..plan.ir import METHODS

    methods = list(methods or METHODS)
    unknown = sorted(set(methods) - set(METHODS))
    if unknown:
        raise ValueError(f"unknown method(s): {', '.join(unknown)} "
                         f"(known: {', '.join(METHODS)})")
    out = []
    for part in partitions:
        for dtypes in qsets:
            for method in methods:
                px, py, pz = part
                short = "+".join(
                    f"{n}x{dt.replace('float', 'f')}"
                    for dt, n in sorted(
                        {d: list(dtypes).count(d) for d in set(dtypes)}
                        .items()))
                out.append({
                    "label": f"{size}^3/{px}x{py}x{pz}/{method}/{short}",
                    "size": int(size), "radius": int(radius),
                    "partition": tuple(part), "method": method,
                    "dtypes": tuple(dtypes),
                })
    return out


def _check(checks: List[dict], name: str, predicted, actual) -> bool:
    ok = predicted == actual
    checks.append({"name": name, "predicted": predicted,
                   "actual": actual, "ok": ok})
    return ok


def audit_config(cfg: dict, devices=None,
                 perturb_collectives: int = 0,
                 perturb_wire: int = 0) -> Verdict:
    """Compile one config's exchange and cross-check the IR predictions.

    Feasibility goes through ``plan/cost.feasible`` (the realize()
    constraint authority): an infeasible config returns a skipped
    verdict with the reason, never a traceback.
    """
    import jax

    from ..parallel import HaloExchange, Method, grid_mesh
    from ..parallel.exchange import shard_blocks
    from ..plan.cost import feasible
    from ..plan.ir import PlanChoice, PlanConfig

    devices = list(devices) if devices is not None else jax.devices()
    v = Verdict(label=cfg["label"], method=cfg["method"])
    method = cfg["method"]
    size, dtypes = cfg["size"], list(cfg["dtypes"])
    import numpy as np

    from ..geometry import Dim3, Radius

    radius = Radius.constant(cfg["radius"])
    nblocks = cfg["partition"][0] * cfg["partition"][1] * cfg["partition"][2]
    if nblocks > len(devices):
        v.skipped = True
        v.ok = False
        v.reason = (f"partition {cfg['partition']} needs {nblocks} "
                    f"devices; {len(devices)} available")
        return v
    config = PlanConfig.make(Dim3(size, size, size), radius, dtypes,
                             nblocks, devices[0].platform)
    choice = PlanChoice(partition=cfg["partition"], method=method)
    feas = feasible(config, choice)
    if feas is None:
        v.skipped = True
        v.ok = False
        v.reason = (f"infeasible for this config (plan/cost.feasible: "
                    f"partition {cfg['partition']} with radius "
                    f"{cfg['radius']} on {nblocks} device(s))")
        return v
    spec, mesh_dim, _resident = feas
    mesh = grid_mesh(spec.dim, devices[:nblocks])
    ex = HaloExchange(spec, mesh, Method(method))
    g = spec.global_size
    base = np.arange(g.x * g.y * g.z, dtype=np.float64).reshape(
        g.z, g.y, g.x)
    state = {i: shard_blocks((base + i).astype(dt), spec, mesh)
             for i, dt in enumerate(dtypes)}
    census = ex.collective_census(state)
    plan = ex.plan
    nq = len(dtypes)
    ngroups = len(set(dtypes))
    itemsizes = [np.dtype(d).itemsize for d in dtypes]
    floating = [bool(np.issubdtype(np.dtype(d), np.floating))
                for d in dtypes]

    predicted_coll = plan.collectives_per_exchange(nq, ngroups) \
        + perturb_collectives
    predicted_wire = plan.wire_bytes(itemsizes, floating=floating) \
        + perturb_wire

    actual_coll = census.get("collective-permute", (0, 0))[0]
    actual_bytes = sum(b for _c, b in census.values())
    stray = {k: c for k, (c, _b) in census.items()
             if k != "collective-permute" and c}

    ok = _check(v.checks, "collectives_per_exchange",
                predicted_coll, actual_coll)
    ok &= _check(v.checks, "stray_collective_kinds", {}, stray)
    ok &= _check(v.checks, "wire_bytes", predicted_wire, actual_bytes)
    v.ok = bool(ok)
    return v


# -- placement conformance (the topology-aware PlanChoice leg) ---------------


def placement_permutations(ndev: int, count: int = 3):
    """``count`` deterministic NON-identity permutations of ``ndev``
    mesh positions: reversal, rotation by one, and pairwise swaps —
    the fixed fixture set the placement-parity gate sweeps (no RNG: a
    CI failure must reproduce)."""
    from ..plan.ir import validate_placement

    perms = []
    rev = tuple(range(ndev - 1, -1, -1))
    rot = tuple((i + 1) % ndev for i in range(ndev))
    swap = list(range(ndev))
    for i in range(0, ndev - 1, 2):
        # adjacent pairs swap; an odd ndev leaves the tail FIXED (the
        # naive i+1/i-1 formula maps the last even index out of range —
        # not a permutation at all)
        swap[i], swap[i + 1] = swap[i + 1], swap[i]
    candidates = [rev, rot, tuple(swap)]
    k = 2
    while k < ndev:
        candidates.append(tuple((i + k) % ndev for i in range(ndev)))
        k += 1
    for p in candidates:
        if len(perms) >= count:
            break
        # a broken fixture must never reach the auditor as a FAILED
        # verdict on a healthy build
        if (p != tuple(range(ndev)) and p not in perms
                and validate_placement(p, ndev) is None):
            perms.append(p)
    return perms


def _expected_flat_pairs(plan, mesh_dim):
    """The compiled program's predicted collective-permute pair sets —
    one frozenset of flattened (src, tgt) logical ids per expected op —
    derived from the plan's axis phases (the logical schedule is
    placement-INVARIANT: a placement rebinds which physical device sits
    behind each logical id, never the schedule). AXIS_COMPOSED,
    single-resident scope."""
    from ..geometry import Dim3

    md = Dim3.of(mesh_dim)

    def lin(x, y, z):
        return x + y * md.x + z * md.x * md.y

    out = []
    axis_n = {"x": md.x, "y": md.y, "z": md.z}
    for phase in plan.axis_phases:
        if axis_n[phase.axis] <= 1 or not phase.active:
            continue
        for step, active in ((1, phase.rm > 0), (-1, phase.rp > 0)):
            if not active:
                continue
            pairs = set()
            for z in range(md.z):
                for y in range(md.y):
                    for x in range(md.x):
                        c = {"x": x, "y": y, "z": z}
                        d = dict(c)
                        d[phase.axis] = ((c[phase.axis] + step)
                                         % axis_n[phase.axis])
                        pairs.add((lin(x, y, z),
                                   lin(d["x"], d["y"], d["z"])))
            out.append(frozenset(pairs))
    return out


def audit_placement(size: int, radius: int,
                    partition: Tuple[int, int, int],
                    placement: Tuple[int, ...],
                    devices=None) -> Verdict:
    """One permutation's placement-conformance audit (AXIS_COMPOSED):

    - the realized mesh's device order IS the permuted assignment
      (mesh position i hosts ``devices[placement[i]]``);
    - the compiled ``source_target_pairs`` match the plan's predicted
      logical pair sets — so pair (s, t) rides the physical link
      ``devices[placement[s]] -> devices[placement[t]]``, i.e. the
      compiled schedule lands exactly on the permuted assignment;
    - the exchanged field is bit-identical to the identity placement
      (placement moves BLOCKS, never values).
    """
    import jax
    import numpy as np

    from ..geometry import Dim3, Radius
    from ..parallel import HaloExchange, Method, grid_mesh
    from ..parallel.exchange import shard_blocks, unshard_blocks
    from ..utils.hlo_check import collective_permute_pairs

    devices = list(devices) if devices is not None else jax.devices()
    px, py, pz = partition
    ndev = px * py * pz
    label = (f"{size}^3/{px}x{py}x{pz}/placement="
             + "-".join(str(v) for v in placement))
    v = Verdict(label=label, method="axis-composed")
    if ndev > len(devices):
        v.skipped = True
        v.ok = False
        v.reason = (f"partition {partition} needs {ndev} devices; "
                    f"{len(devices)} available")
        return v
    from ..domain.grid import GridSpec

    spec = GridSpec(Dim3(size, size, size), Dim3(*partition),
                    Radius.constant(radius))
    base = devices[:ndev]
    arranged = [base[placement[i]] for i in range(ndev)]
    mesh = grid_mesh(spec.dim, arranged, ordered=True)
    mesh_id = grid_mesh(spec.dim, base, ordered=True)

    actual_order = [d.id for d in mesh.devices.flatten()]
    expected_order = [base[placement[i]].id for i in range(ndev)]
    ok = _check(v.checks, "mesh_device_order", expected_order,
                actual_order)

    ex = HaloExchange(spec, mesh, Method.AXIS_COMPOSED)
    ex_id = HaloExchange(spec, mesh_id, Method.AXIS_COMPOSED)
    g = spec.global_size
    field = np.arange(g.x * g.y * g.z, dtype=np.float32).reshape(
        g.z, g.y, g.x)
    state = {0: shard_blocks(field, spec, mesh)}
    state_id = {0: shard_blocks(field, spec, mesh_id)}

    txt = ex._compiled.lower(state).compile().as_text()
    actual_pairs = sorted(collective_permute_pairs(txt),
                          key=lambda s: sorted(s))
    expected_pairs = sorted(_expected_flat_pairs(ex.plan, spec.dim),
                            key=lambda s: sorted(s))
    ok &= _check(v.checks, "source_target_pairs",
                 [sorted(p) for p in expected_pairs],
                 [sorted(p) for p in actual_pairs])

    out = unshard_blocks(ex(state)[0], spec)
    out_id = unshard_blocks(ex_id(state_id)[0], spec)
    ok &= _check(v.checks, "bit_identical_to_identity", True,
                 bool(out.tobytes() == out_id.tobytes()))
    v.ok = bool(ok)
    return v


def run_placement_sweep(count: int = 3, size: int = DEFAULT_SIZE,
                        radius: int = DEFAULT_RADIUS,
                        partition: Tuple[int, int, int] = (2, 2, 2),
                        devices=None,
                        rec: Optional["telemetry.Recorder"] = None) -> Dict:
    """Audit ``count`` non-identity placements (the ISSUE-15 gate:
    census pairs must match the permuted assignment, results bit-
    identical). Emits the same ``analysis.plan_verdict`` vocabulary as
    the method sweep."""
    rec = rec or telemetry.get()
    ndev = partition[0] * partition[1] * partition[2]
    verdicts: List[Verdict] = []
    for perm in placement_permutations(ndev, count):
        with rec.span("analysis.verify_plan", phase="analysis",
                      method="axis-composed"):
            try:
                v = audit_placement(size, radius, partition, perm,
                                    devices=devices)
            except Exception as e:  # an auditor crash is a FAILED config
                v = Verdict(
                    label=f"placement={'-'.join(str(i) for i in perm)}",
                    method="axis-composed", ok=False,
                    reason=f"{type(e).__name__}: {e}")
        verdicts.append(v)
        rec.meta("analysis.plan_verdict", method=v.method, ok=int(v.ok),
                 label=v.label, skipped=int(v.skipped),
                 reason=v.reason or None)
        if not v.ok and not v.skipped:
            rec.counter("analysis.plan_mismatch", value=1,
                        phase="analysis", method=v.method)
    checked = [v for v in verdicts if not v.skipped]
    failed = [v for v in checked if not v.ok]
    skipped = [v for v in verdicts if v.skipped]
    rec.meta("analysis.plan_sweep", checked=len(checked),
             failed=len(failed), skipped=len(skipped))
    return {
        "verdicts": verdicts,
        "checked": len(checked),
        "failed": len(failed),
        "skipped": len(skipped),
    }


# -- timed audit (the ISSUE-18 drift leg: seconds, not just structure) -------


def audit_time(cfg: dict, devices=None, iters: int = 6,
               calibration: Optional[dict] = None,
               mad_k: float = 3.0, rel_tol: float = 0.75,
               rec: Optional["telemetry.Recorder"] = None,
               slow_s: float = 0.0) -> Verdict:
    """Time one config's exchange and judge the cost model's PREDICTION
    against the measured samples' band (``obs/attribution.judge_drift``
    — the perf_tool band authority). The structural audits check what
    the lowering puts on the wire; this one checks the seconds the
    autotuner ranked it by.

    The default ``rel_tol`` is wide (0.75 — "within [0.25x, 1.75x] of
    measured"): a handful of in-process samples on a shared CPU box
    judges multiple-x calibration staleness, not 5% drift; tighten it
    on quiet fabrics, but keep it below 1 (at 1 the low band edge hits
    zero and an under-prediction can never trip).
    ``slow_s`` sleeps that long inside ONE timed iteration — the CI
    proof knob that the timed auditor trips, like ``perturb_*`` for the
    structural checks."""
    import time as _time

    import jax
    import numpy as np

    from ..geometry import Dim3, Radius
    from ..obs import attribution
    from ..parallel import HaloExchange, Method, grid_mesh
    from ..parallel.exchange import shard_blocks
    from ..plan.cost import feasible
    from ..plan.ir import PlanChoice, PlanConfig
    from ..utils.sync import hard_sync

    rec = rec or telemetry.get()
    devices = list(devices) if devices is not None else jax.devices()
    v = Verdict(label=cfg["label"], method=cfg["method"])
    method = cfg["method"]
    size, dtypes = cfg["size"], list(cfg["dtypes"])
    radius = Radius.constant(cfg["radius"])
    nblocks = cfg["partition"][0] * cfg["partition"][1] * cfg["partition"][2]
    if nblocks > len(devices):
        v.skipped = True
        v.ok = False
        v.reason = (f"partition {cfg['partition']} needs {nblocks} "
                    f"devices; {len(devices)} available")
        return v
    config = PlanConfig.make(Dim3(size, size, size), radius, dtypes,
                             nblocks, devices[0].platform)
    choice = PlanChoice(partition=cfg["partition"], method=method)
    feas = feasible(config, choice)
    if feas is None:
        v.skipped = True
        v.ok = False
        v.reason = "infeasible for this config (plan/cost.feasible)"
        return v
    pred = attribution.predict_exchange(config, choice, calibration)
    if pred is None:
        v.skipped = True
        v.ok = False
        v.reason = "cost model prices this choice as infeasible"
        return v
    spec, mesh_dim, _resident = feas
    mesh = grid_mesh(spec.dim, devices[:nblocks])
    ex = HaloExchange(spec, mesh, Method(method))
    g = spec.global_size
    base = np.arange(g.x * g.y * g.z, dtype=np.float64).reshape(
        g.z, g.y, g.x)
    state = {i: shard_blocks((base + i).astype(dt), spec, mesh)
             for i, dt in enumerate(dtypes)}
    state = ex(state)  # compile + warm outside the timed window
    hard_sync(state)
    samples: List[float] = []
    for i in range(max(2, iters)):
        t0 = _time.perf_counter()
        state = ex(state)
        hard_sync(state)
        if slow_s and i == 0:
            _time.sleep(slow_s)  # the seeded-staleness proof knob
        samples.append(_time.perf_counter() - t0)
        attribution.emit_phase(rec, pred, samples[-1],
                               phase="stencil.exchange",
                               kernel_variant=choice.kernel_variant)
    dv = attribution.judge_drift("stencil.exchange", pred.predicted_s,
                                 samples, mad_k=mad_k, rel_tol=rel_tol)
    attribution.emit_drift(rec, dv)
    v.checks.append({
        "name": "predicted_s_within_band",
        "predicted": f"{dv.predicted_s:.3e}s",
        "actual": f"measured band [{dv.lo:.3e}, {dv.hi:.3e}] "
                  f"(center {dv.center:.3e}s, n={dv.n})",
        "ok": dv.ok,
    })
    v.ok = bool(dv.ok)
    if not dv.ok:
        v.reason = dv.describe()
    return v


def run_time_sweep(configs: Sequence[dict], devices=None,
                   iters: int = 6, calibration: Optional[dict] = None,
                   mad_k: float = 3.0, rel_tol: float = 0.75,
                   slow_s: float = 0.0,
                   rec: Optional["telemetry.Recorder"] = None) -> Dict:
    """Timed-audit every config; same result/telemetry shape as
    :func:`run_sweep` (one ``analysis.plan_verdict`` per config, the
    ``analysis.plan_sweep`` rollup)."""
    rec = rec or telemetry.get()
    verdicts: List[Verdict] = []
    for cfg in configs:
        with rec.span("analysis.verify_plan", phase="analysis",
                      method=cfg["method"]):
            try:
                v = audit_time(cfg, devices=devices, iters=iters,
                               calibration=calibration, mad_k=mad_k,
                               rel_tol=rel_tol, rec=rec, slow_s=slow_s)
            except Exception as e:  # an auditor crash is a FAILED config
                v = Verdict(label=cfg["label"], method=cfg["method"],
                            ok=False, reason=f"{type(e).__name__}: {e}")
        verdicts.append(v)
        rec.meta("analysis.plan_verdict", method=v.method,
                 ok=int(v.ok), label=v.label,
                 skipped=int(v.skipped), reason=v.reason or None)
        if not v.ok and not v.skipped:
            rec.counter("analysis.plan_mismatch", value=1,
                        phase="analysis", method=v.method)
    checked = [v for v in verdicts if not v.skipped]
    failed = [v for v in checked if not v.ok]
    skipped = [v for v in verdicts if v.skipped]
    rec.meta("analysis.plan_sweep", checked=len(checked),
             failed=len(failed), skipped=len(skipped))
    return {
        "verdicts": verdicts,
        "checked": len(checked),
        "failed": len(failed),
        "skipped": len(skipped),
    }


def run_sweep(configs: Sequence[dict], devices=None,
              perturb_collectives: int = 0, perturb_wire: int = 0,
              rec: Optional["telemetry.Recorder"] = None) -> Dict:
    """Audit every config; returns ``{verdicts, checked, failed,
    skipped}`` and emits the ``analysis.*`` telemetry vocabulary when a
    recorder is attached."""
    rec = rec or telemetry.get()
    # without x64, fp64 state silently downcasts to fp32 and the whole
    # dtype-group prediction audits the wrong program; restored after
    # the sweep so the flip never leaks into the rest of the process
    # (jit-audit in the same `lint_tool all` run must audit the apps'
    # actual fp32 programs)
    x64_prev = None
    if any("64" in dt for cfg in configs for dt in cfg["dtypes"]):
        import jax

        x64_prev = bool(jax.config.jax_enable_x64)
        jax.config.update("jax_enable_x64", True)
    try:
        return _run_sweep(configs, devices, perturb_collectives,
                          perturb_wire, rec)
    finally:
        if x64_prev is False:
            import jax

            jax.config.update("jax_enable_x64", False)


def _run_sweep(configs, devices, perturb_collectives, perturb_wire,
               rec) -> Dict:
    verdicts: List[Verdict] = []
    for cfg in configs:
        with rec.span("analysis.verify_plan", phase="analysis",
                      method=cfg["method"]):
            try:
                v = audit_config(
                    cfg, devices=devices,
                    perturb_collectives=perturb_collectives,
                    perturb_wire=perturb_wire)
            except Exception as e:  # an auditor crash is a FAILED config
                v = Verdict(label=cfg["label"], method=cfg["method"],
                            ok=False,
                            reason=f"{type(e).__name__}: {e}")
        verdicts.append(v)
        rec.meta("analysis.plan_verdict", method=v.method,
                 ok=int(v.ok), label=v.label,
                 skipped=int(v.skipped), reason=v.reason or None)
        if not v.ok and not v.skipped:
            rec.counter("analysis.plan_mismatch", value=1,
                        phase="analysis", method=v.method)
    checked = [v for v in verdicts if not v.skipped]
    failed = [v for v in checked if not v.ok]
    skipped = [v for v in verdicts if v.skipped]
    rec.meta("analysis.plan_sweep", checked=len(checked),
             failed=len(failed), skipped=len(skipped))
    return {
        "verdicts": verdicts,
        "checked": len(checked),
        "failed": len(failed),
        "skipped": len(skipped),
    }
