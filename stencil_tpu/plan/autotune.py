"""The partition/method autotuner: static rank -> probe top-N -> persist.

One call answers "which exchange plan should THIS config run?" the way
the reference's L3 answers it with ``RankPartition``/``NodePartition``
search + ``NodeAware`` placement costing (PAPER.md §2.4) — except the
winners persist: the on-disk plan DB (plan/db.py) is consulted first,
and a hit replays the tuned choice with ZERO probe runs. The telemetry
trail proves which path ran:

- ``plan.cache_hit`` gauge: 1 on a pure DB hit, 0 on a tuning run;
- ``plan.probes_run`` counter: measured probes this call executed;
- ``plan.candidates`` gauge: feasible static candidates ranked;
- ``plan.chosen`` meta: the winning choice + its provenance.

scripts/ci_plan_gate.py pins the contract end-to-end: autotune twice at
the same config — the second run must be a pure DB hit — and the chosen
plan must produce bit-identical halos to the default program.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

from ..geometry import Dim3, Radius
from ..utils import logging as log
from . import db as plandb
from .cost import enumerate_candidates, rank
from .ir import METHODS, PlanChoice, PlanConfig


@dataclass
class AutotuneResult:
    config: PlanConfig
    choice: PlanChoice
    source: str                 # 'db' | 'probe' | 'static' | 'seed'...
    cache_hit: bool
    probes_run: int
    candidates: int
    entry: Optional[dict] = None
    ranked: List[Tuple[object, PlanChoice]] = field(default_factory=list)
    probes: List[dict] = field(default_factory=list)
    # what priced the ranking: the override dict (None = defaults) and
    # its provenance string — stamped into plan.chosen and the run's
    # plan.fingerprint meta so ledger entries say which constants ranked
    calibration: Optional[dict] = None
    calibration_provenance: str = "modeled(default)"


def default_choice(config: PlanConfig) -> PlanChoice:
    """What a plan-less realize() would do: NodePartition's min-interface
    split on every device, AXIS_COMPOSED, batching on — the baseline the
    ``plan_autotuned_over_default`` bench leg compares against."""
    from ..geometry import NodePartition

    part = NodePartition(Dim3.of(config.grid), config.radius_obj(),
                         1, config.ndev)
    d = part.dim()
    return PlanChoice(partition=(d.x, d.y, d.z), method="axis-composed",
                      batch_quantities=True)


def autotune(
    size,
    radius: Radius,
    dtypes: Sequence[str],
    ndev: Optional[int] = None,
    devices=None,
    db_path: Optional[str] = None,
    platform: Optional[str] = None,
    top_n: int = 3,
    probe_iters: int = 4,
    probe: bool = True,
    force: bool = False,
    methods: Sequence[str] = METHODS,
    ks: Sequence[int] = (1,),
    calibration: Optional[dict] = None,
    link_costs=None,
    rec=None,
) -> AutotuneResult:
    """Choose (and persist) the exchange plan for one config.

    ``probe=False`` keeps the run static-only (no compiles — usable
    backend-less); ``force=True`` re-tunes through an existing DB entry
    (the entry is replaced). A corrupt DB degrades loudly: the tuning
    still runs, but nothing is persisted over the damaged file.

    ``link_costs`` feeds the topology-aware placement search (an ndev x
    ndev device-pair distance matrix; ``plan/cost.enumerate_candidates``
    grows each partition's QAP-solved placement candidate from it and
    ``score`` prices wire time through it). When omitted and live
    ``devices`` were given, it is derived from them
    (``parallel/topology.link_cost_matrix`` — ICI hops on TPU, process
    boundaries elsewhere); a uniform matrix (the single-process CPU
    mesh) changes nothing."""
    import importlib

    from ..obs import telemetry

    rec = rec or telemetry.get()
    if devices is not None:
        devices = list(devices)
        ndev = len(devices)
        platform = platform or devices[0].platform
    if ndev is None or platform is None:
        # resolve from the live backend only when the caller gave neither
        jax = importlib.import_module("jax")
        devs = jax.devices()
        if devices is None:
            devices = devs
        ndev = ndev if ndev is not None else len(devs)
        platform = platform or devs[0].platform
    config = PlanConfig.make(size, radius, dtypes, ndev, platform)
    if link_costs is None and devices is not None:
        from ..parallel.topology import link_cost_matrix

        link_costs = link_cost_matrix(devices)
    db = None
    db_ok = False
    if db_path:
        try:
            db = plandb.load_db(db_path)
            db_ok = True
        except plandb.PlanDBError as e:
            log.warn(f"plan DB {db_path} rejected ({e}); tuning without "
                     "persistence — fix or remove the file")
    # the observatory loop's install half: a fitted calibration row in
    # the DB (plan_tool calibrate) prices this platform's rankings until
    # the caller overrides it explicitly
    cal_provenance = ("modeled(default)" if calibration is None
                      else str(calibration.get("provenance", "override")))
    if calibration is None and db is not None:
        cal_row = plandb.lookup_calibration(db, platform)
        if cal_row is not None:
            calibration = cal_row["calibration"]
            cal_provenance = str(cal_row.get("provenance", "fitted"))
            log.info(f"plan calibration: {cal_provenance} "
                     f"(from {db_path})")
    if db is not None and not force:
        entry = plandb.lookup(db, config)
        if entry is not None:
            choice = PlanChoice.from_json(entry["choice"])
            rec.gauge("plan.cache_hit", 1, phase="plan")
            rec.counter("plan.probes_run", value=0, phase="plan")
            rec.meta("plan.chosen", choice=entry["choice"], source="db",
                     db_source=entry.get("source"), key=config.key(),
                     calibration=cal_provenance)
            log.info(f"plan DB hit: {choice.label()} "
                     f"(tuned by {entry.get('source')}) — zero probes")
            return AutotuneResult(
                config=config, choice=choice, source="db", cache_hit=True,
                probes_run=0, candidates=0, entry=entry,
                calibration=calibration,
                calibration_provenance=cal_provenance,
            )

    with rec.span("plan.autotune", phase="plan"):
        candidates = enumerate_candidates(config, methods=methods,
                                          ks=ks,
                                          link_costs=link_costs)
        ranked = rank(config, candidates, calibration,
                      link_costs=link_costs)
        if not ranked:
            raise ValueError(
                f"no feasible exchange plan for {config.key()} — grid too "
                f"small for every partition of {config.ndev} devices?"
            )
        rec.gauge("plan.candidates", len(ranked), phase="plan")
        probes: List[dict] = []
        measured = None
        if probe:
            from .probe import refine

            measured, probes = refine(config, ranked, top_n=top_n,
                                      iters=probe_iters, devices=devices)
        n_probes = sum(1 for p in probes if "trimean_s" in p)
        rec.counter("plan.probes_run", value=n_probes, phase="plan")
        rec.gauge("plan.cache_hit", 0, phase="plan")
        if measured is not None:
            choice, source = measured, "probe"
            measured_s = min(p["trimean_s"] for p in probes
                             if "trimean_s" in p
                             and p["label"] == choice.label())
        else:
            choice, source = ranked[0][1], "static"
            measured_s = None
        static_cost = next(
            (c.total_s for c, ch in ranked if ch == choice), None)
        rec.meta("plan.chosen", choice=choice.to_json(), source=source,
                 key=config.key(), calibration=cal_provenance)
        log.info(f"plan autotuned: {choice.label()} via {source} "
                 f"({n_probes} probes over {len(ranked)} candidates)")

    entry = plandb.make_entry(config, choice, source,
                              static_cost_s=static_cost,
                              measured_s=measured_s, probes=probes)
    if db is not None and db_ok:
        plandb.record(db, entry)
        plandb.save_db(db_path, db)
    return AutotuneResult(
        config=config, choice=choice, source=source, cache_hit=False,
        probes_run=n_probes, candidates=len(ranked), entry=entry,
        ranked=ranked, probes=probes, calibration=calibration,
        calibration_provenance=cal_provenance,
    )
