"""plan-tool — inspect, seed, prune, and run the exchange-plan DB.

The operator's window into the plan/ subsystem (the analogue of
``ckpt_tool`` for checkpoints):

- ``show``      list every tuned entry (config -> choice, provenance);
- ``explain``   one config's DB entry + static cost ranking + the chosen
                plan's ExchangePlan IR (phases, permute pairs, bytes);
- ``prune``     drop entries by platform / source / age, and every entry
                that uses a retired choice key;
- ``seed``      insert the RECORDED CPU-mesh verdicts (BASELINE.md
                rounds 7/10) so fresh deployments replay them without
                re-benching;
- ``autotune``  tune one config now (the CI plan gate's entry point) —
                a DB hit performs zero probes and says so;
- ``calibrate`` fit calibration constants from a run's attribution
                records (``plan.attrib.phase``) and install the
                ``fitted(n=…, r2=…)`` row in the DB — the
                predict→measure→refit loop's refit step;
- ``calibration`` show/diff the installed fitted rows vs the modeled
                defaults.

``show``/``explain``/``prune``/``seed``/``calibrate``/``calibration``
are jax-free: they run without a backend (the cost model is pure
geometry and the fit is pure stdlib). Only ``autotune`` compiles.

Usage: python -m stencil_tpu.apps.plan_tool show --db plans.json
       python -m stencil_tpu.apps.plan_tool explain --db plans.json \
           --x 128 --y 128 --z 128 --radius 2 --quantities 4 --ndev 8
       python -m stencil_tpu.apps.plan_tool seed --db plans.json
       python -m stencil_tpu.apps.plan_tool autotune --db plans.json \
           --cpu 8 --x 24 --y 24 --z 24 --quantities 4
"""

from __future__ import annotations

import argparse
import json
from typing import Optional

from ..plan import db as plandb
from ..plan.ir import PlanChoice, PlanConfig


def _add_config_flags(p) -> None:
    p.add_argument("--x", type=int, default=24)
    p.add_argument("--y", type=int, default=24)
    p.add_argument("--z", type=int, default=24)
    p.add_argument("--radius", type=int, default=2,
                   help="uniform radius of the config key")
    p.add_argument("--quantities", type=int, default=1)
    p.add_argument("--dtype", default="float32")
    p.add_argument("--ndev", type=int, default=8)
    p.add_argument("--platform", default="cpu")


def _config_from(args) -> PlanConfig:
    from ..geometry import Dim3, Radius

    return PlanConfig.make(
        Dim3(args.x, args.y, args.z), Radius.constant(args.radius),
        [args.dtype] * args.quantities, args.ndev, args.platform,
    )


def _entry_row(key: str, entry: dict) -> str:
    cfg = json.loads(key)
    retired = plandb.retired_key(entry)
    label = (f"retired({retired})" if retired is not None
             else PlanChoice.from_json(entry["choice"]).label())
    g = cfg["grid"]
    qs = ",".join(f"{n}x{dt}" for dt, n in cfg["quantities"])
    measured = entry.get("measured_s")
    return (
        f"{g[0]}x{g[1]}x{g[2]},{qs},{cfg['ndev']},{cfg['platform']},"
        f"{label},{entry.get('source')},"
        f"{'' if measured is None else f'{measured:.6f}'}"
    )


def cmd_show(args) -> int:
    db = plandb.load_db(args.db)
    print("grid,quantities,ndev,platform,choice,source,measured_s")
    for key in sorted(db["entries"]):
        print(_entry_row(key, db["entries"][key]))
    print(f"# {len(db['entries'])} entries")
    return 0


def cmd_explain(args) -> int:
    from ..plan.cost import enumerate_candidates, feasible, rank
    from ..plan.ir import build_plan

    config = _config_from(args)
    print(f"config key: {config.key()}")
    entry = None
    calibration = None
    cal_note = "modeled(default)"
    if args.db:
        db = plandb.load_db(args.db)
        entry = plandb.lookup(db, config)
        # price the ranking with the DB's installed calibration, exactly
        # as an autotune run against this DB would (plan/autotune.py)
        cal_row = plandb.lookup_calibration(db, args.platform)
        if cal_row is not None:
            calibration = cal_row["calibration"]
            cal_note = str(cal_row.get("provenance", "fitted"))
    if entry is not None:
        print(f"DB entry: {PlanChoice.from_json(entry['choice']).label()} "
              f"(source {entry['source']}, measured_s "
              f"{entry.get('measured_s')})")
    else:
        print("DB entry: none (an --autotune run would probe)")
    ranked = rank(config, enumerate_candidates(config), calibration)
    print(f"static ranking ({len(ranked)} feasible candidates; "
          f"calibration: {cal_note}):")
    for cost, choice in ranked[: args.top]:
        print(f"  {choice.label():45s} {cost.total_s * 1e3:9.3f} ms/step  "
              f"permutes={cost.collectives} wire={cost.wire_bytes}")
    if args.method:
        # explain one method's plan IR explicitly (e.g. direct26 with
        # its census prediction and the wire_dtype-compressed byte
        # model) instead of the ranked best
        best = next((ch for _c, ch in ranked if ch.method == args.method),
                    None)
        if best is None:
            print(f"no feasible {args.method} candidate for this config")
            return 1
    else:
        best = (PlanChoice.from_json(entry["choice"]) if entry is not None
                else ranked[0][1] if ranked else None)
    if best is not None:
        feas = feasible(config, best)
        if feas is not None:
            spec, mesh_dim, resident = feas
            plan = build_plan(spec, mesh_dim, best.method,
                              best.batch_quantities, resident,
                              wire_dtype=args.wire_dtype or None)
            print("plan IR of the "
                  + (f"requested {args.method}" if args.method
                     else "DB" if entry is not None else "best static")
                  + " choice:")
            print(plan.describe())
            if args.placement:
                _explain_placement(args, config, best, spec, mesh_dim)
    return 0


def _explain_placement(args, config, choice, spec, mesh_dim) -> None:
    """The ``explain --placement`` table: the choice's block→device
    assignment plus the per-pair wire-bytes x link-cost products the
    QAP minimized. Jax-free: link costs come from ``--link-costs``
    (a JSON ndev x ndev matrix, e.g. a dumped
    ``parallel.topology.link_cost_matrix``) or default to uniform —
    under which every placement prices identically, and the table says
    so instead of implying a win."""
    import numpy as np

    from ..geometry import Dim3
    from ..plan.cost import placement_cost, placement_wire_matrix

    md = Dim3.of(mesh_dim)
    n = md.flatten()
    w = placement_wire_matrix(spec, md,
                              per_cell_bytes=sum(config.itemsizes()))
    if args.link_costs:
        with open(args.link_costs) as fh:
            link = np.asarray(json.load(fh), dtype=np.float64)
        if link.shape != (n, n):
            raise SystemExit(
                f"--link-costs matrix is {link.shape}; the mesh has "
                f"{n} positions")
        src = args.link_costs
    else:
        link = np.ones((n, n))
        np.fill_diagonal(link, 0.0)
        src = "uniform default (pass --link-costs for a real fabric)"
    f = (list(choice.placement) if choice.placement is not None
         else list(range(n)))
    print(f"placement ({'tuned' if choice.placement is not None else 'identity'}; link costs: {src}):")
    for i in range(n):
        iz, rem = divmod(i, md.x * md.y)
        iy, ix = divmod(rem, md.x)
        print(f"  mesh ({ix},{iy},{iz}) -> device {f[i]}")
    print("per-pair wire-bytes x link-cost (placed devices):")
    print("  pair(mesh),devices,wire_bytes,link_cost,product")
    for a in range(n):
        for b in range(n):
            if b <= a or (w[a, b] == 0 and w[b, a] == 0):
                continue
            wb = w[a, b] + w[b, a]
            lc = link[f[a], f[b]]
            print(f"  {a}-{b},{f[a]}-{f[b]},{int(wb)},{lc:g},"
                  f"{wb * lc:g}")
    ident = placement_cost(w, link)
    placed = placement_cost(w, link, f)
    print(f"total modeled wire cost: placed {placed:g} vs identity "
          f"{ident:g}"
          + (f" ({ident / placed:.3f}x better)" if placed < ident else
             " (identity-equivalent)" if placed == ident else
             " (WORSE than identity — re-tune)"))


def cmd_prune(args) -> int:
    db = plandb.load_db(args.db)
    n = plandb.prune_db(
        db, platform=args.platform or None, source=args.source or None,
        older_than_s=args.older_than_days * 86400.0
        if args.older_than_days is not None else None,
    )
    plandb.save_db(args.db, db)
    print(f"pruned {n} entries ({len(db['entries'])} remain)")
    return 0


# The recorded CPU-mesh verdicts (BASELINE.md rounds 7/10): 128^3,
# uniform radius 2, fp32, 2x2x2 partition on the 8-device CPU mesh.
# axis-composed + batching won every measured comparison there:
# manual-over-auto ~4% (47.6 vs 49.5 ms), direct26 4.2x slower on 1.9x
# fewer bytes, batched-over-per-quantity 1.43x at Q=4 / 1.65x at Q=8.
_SEED_ROWS = (
    (1, 8.85e-3, "round 10: Q=1 batched == per-quantity (same program)"),
    (4, 26.2e-3, "round 7/10: per-quantity 37.4 ms (1.43x); direct26 "
                 "4.2x slower on 1.9x fewer bytes; manual over auto ~4%"),
    (8, 42.9e-3, "round 10: per-quantity 70.6 ms (1.65x); astaroth "
                 "8-field exchange 1.46x by the same mechanism"),
)


def cmd_seed(args) -> int:
    from ..geometry import Dim3, Radius

    db = plandb.load_db(args.db)
    n = 0
    for q, measured_s, note in _SEED_ROWS:
        config = PlanConfig.make(Dim3(128, 128, 128), Radius.constant(2),
                                 ["float32"] * q, 8, args.platform)
        if plandb.lookup(db, config) is not None and not args.force:
            continue
        choice = PlanChoice(partition=(2, 2, 2), method="axis-composed",
                            batch_quantities=True)
        plandb.record(db, plandb.make_entry(
            config, choice, "seed", measured_s=measured_s,
            note=f"BASELINE.md recorded verdict — {note}",
        ))
        n += 1
    plandb.save_db(args.db, db)
    print(f"seeded {n} entries into {args.db} "
          f"({len(db['entries'])} total)")
    return 0


def cmd_calibrate(args) -> int:
    """Fit a calibration row from attribution evidence and install it
    in the plan DB (the predict→measure→refit loop's refit step).
    Jax-free: the evidence is a metrics JSONL or the LEDGER, the fit is
    pure stdlib, and the DB write is the same atomic-rename path every
    other subcommand uses."""
    from ..obs import telemetry
    from ..plan import calibrate as cal
    from ..plan.cost import DEFAULT_CALIBRATION

    if bool(args.from_metrics) == bool(args.from_ledger):
        raise SystemExit(
            "calibrate needs exactly one evidence source: "
            "--from-metrics METRICS.jsonl or --from-ledger LEDGER.jsonl")
    if args.from_metrics:
        with open(args.from_metrics) as f:
            lines = f.readlines()
        n_ok, errs = telemetry.validate_jsonl(lines)
        if errs:
            raise SystemExit(
                f"{args.from_metrics}: {len(errs)} schema-invalid records "
                f"(first: {errs[0]}) — refusing to fit from a corrupt "
                "metrics file")
        records = [json.loads(ln) for ln in lines if ln.strip()]
        samples = cal.samples_from_records(records)
        src = args.from_metrics
    else:
        from ..obs.ledger import load_ledger

        samples = cal.samples_from_ledger(load_ledger(args.from_ledger))
        src = args.from_ledger
    if getattr(args, "phase", None):
        # one phase = one measurement population: probe chunks and the
        # epilogue loop amortize dispatch overhead differently, and a
        # fit across both prices neither correctly
        want = set(args.phase)
        samples = [s for s in samples if s.phase in want]
        if not samples:
            raise SystemExit(
                f"no attribution samples match --phase "
                f"{sorted(want)} in {src}")
    try:
        row = cal.fit(samples, platform=args.platform)
    except cal.CalibrationError as e:
        raise SystemExit(f"calibration fit refused: {e}")
    db = plandb.load_db(args.db)
    plandb.record_calibration(db, args.platform, row)
    plandb.save_db(args.db, db)
    print(f"fitted {args.platform} calibration from {len(samples)} "
          f"samples ({src}) -> {args.db}")
    print(f"provenance: {row['provenance']}"
          + ("" if row["bandwidth_fit"]
             else "  [bandwidth pinned at the modeled default: the "
                  "samples share one (collectives, bytes) point]"))
    for name, fitted, base_v in cal.diff_rows(row, DEFAULT_CALIBRATION):
        print(f"  {name:45s} {fitted:.6e}  (modeled {base_v:.6e}, "
              f"{fitted / base_v:.2f}x)")
    if getattr(args, "metrics_out", ""):
        rec = telemetry.configure(metrics_out=args.metrics_out,
                                  app="plan_tool",
                                  run_id=getattr(args, "run_id", "") or None,
                                  config=vars(args))
        rec.meta("calibration.fitted", platform=args.platform,
                 n=int(row["n"]), provenance=row["provenance"],
                 r2=float(row["r2"]))
        rec.close()
    return 0


def cmd_calibration(args) -> int:
    """``calibration show``: the DB's fitted rows. ``calibration diff``:
    fitted constants vs the modeled defaults, one line per constant."""
    from ..plan import calibrate as cal
    from ..plan.cost import DEFAULT_CALIBRATION

    db = plandb.load_db(args.db)
    cals = db.get("calibrations") or {}
    if args.action == "show":
        if not cals:
            print("no fitted calibrations (modeled defaults apply)")
            return 0
        print("platform,provenance,n,r2,bandwidth_fit")
        for platform in sorted(cals):
            row = cals[platform]
            print(f"{platform},{row['provenance']},{row['n']},"
                  f"{row['r2']:.4f},{row.get('bandwidth_fit', False)}")
        return 0
    # diff
    platforms = [args.platform] if args.platform else sorted(cals)
    if not platforms:
        print("no fitted calibrations to diff (modeled defaults apply)")
        return 0
    for platform in platforms:
        row = cals.get(platform)
        if row is None:
            print(f"{platform}: no fitted row (modeled defaults apply)")
            continue
        print(f"{platform} ({row['provenance']}):")
        print("  constant,fitted,modeled,ratio")
        for name, fitted, base_v in cal.diff_rows(row, DEFAULT_CALIBRATION):
            print(f"  {name},{fitted:.6e},{base_v:.6e},"
                  f"{fitted / base_v:.3f}")
    return 0


def cmd_autotune(args) -> int:
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    from ._bench_common import start_metrics

    start_metrics(args, "plan_tool")
    from ..geometry import Dim3, Radius
    from ..plan.autotune import autotune
    from ..plan.ir import METHODS

    methods = tuple(t for t in args.methods.split(",") if t) or METHODS
    for m in methods:
        if m not in METHODS:
            raise SystemExit(f"unknown method {m!r} (choose from {METHODS})")
    ks = tuple(int(t) for t in args.ks.split(",") if t.strip()) or (1,)
    for k in ks:
        if k < 1:
            raise SystemExit(f"--ks depths must be >= 1, got {k}")
    res = autotune(
        Dim3(args.x, args.y, args.z), Radius.constant(args.radius),
        [args.dtype] * args.quantities,
        devices=jax.devices()[: args.ndev] if args.ndev else None,
        db_path=args.db or None, top_n=args.top_n,
        probe_iters=args.probe_iters, probe=not args.no_probe,
        force=args.force, methods=methods, ks=ks,
    )
    print(f"chosen: {res.choice.label()}")
    print(f"source: {res.source}  cache_hit: {res.cache_hit}  "
          f"probes_run: {res.probes_run}  candidates: {res.candidates}")
    for p in res.probes:
        if "trimean_s" in p:
            print(f"  probe {p['label']:45s} {p['trimean_s'] * 1e3:9.3f} ms")
        else:
            print(f"  probe {p['label']:45s} FAILED: {p.get('error')}")
    from ._bench_common import finish_metrics
    from ..obs import telemetry

    finish_metrics(telemetry.get())
    return 0


def main(argv: Optional[list] = None) -> int:
    p = argparse.ArgumentParser(description="exchange-plan DB tool")
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("show", help="list tuned entries")
    sp.add_argument("--db", required=True)

    sp = sub.add_parser("explain",
                        help="DB entry + static ranking + plan IR of one config")
    sp.add_argument("--db", default="")
    sp.add_argument("--top", type=int, default=8)
    sp.add_argument("--method", default="",
                    choices=("",) + plandb.METHODS,
                    help="dump THIS method's plan IR instead of the "
                         "ranked best")
    sp.add_argument("--wire-dtype", default="",
                    help="render the plan's wire bytes under this "
                         "wire-compression dtype (e.g. bfloat16)")
    sp.add_argument("--placement", action="store_true",
                    help="also render the choice's block→device "
                         "assignment and the per-pair wire-bytes x "
                         "link-cost table the placement QAP minimized")
    sp.add_argument("--link-costs", default="",
                    help="JSON ndev x ndev link-cost matrix for "
                         "--placement (e.g. a dumped "
                         "parallel.topology.link_cost_matrix); default "
                         "uniform")
    _add_config_flags(sp)

    sp = sub.add_parser("prune", help="drop entries by filter, and "
                        "every entry that uses a retired key")
    sp.add_argument("--db", required=True)
    sp.add_argument("--platform", default="")
    sp.add_argument("--source", default="",
                    choices=("",) + plandb.SOURCES)
    sp.add_argument("--older-than-days", type=float, default=None)

    sp = sub.add_parser("seed",
                        help="insert the recorded BASELINE.md verdicts")
    sp.add_argument("--db", required=True)
    sp.add_argument("--platform", default="cpu")
    sp.add_argument("--force", action="store_true",
                    help="overwrite existing entries at the seed keys")

    sp = sub.add_parser(
        "calibrate",
        help="fit calibration constants from attribution records and "
             "install them in the DB (jax-free)")
    sp.add_argument("--db", required=True)
    sp.add_argument("--from-metrics", default="",
                    help="metrics JSONL with plan.attrib.phase records "
                         "(a --metrics-out file)")
    sp.add_argument("--from-ledger", default="",
                    help="LEDGER.jsonl with ingested plan.attrib.* "
                         "entries (lower resolution: one trimean per "
                         "run/phase)")
    sp.add_argument("--phase", action="append", default=None,
                    help="fit only samples of this phase (repeatable). "
                         "One phase = one measurement population: probe "
                         "chunks and the epilogue exchange loop amortize "
                         "dispatch overhead differently")
    sp.add_argument("--platform", default="cpu",
                    help="platform key the fitted row serves (autotune "
                         "installs it for matching configs)")
    sp.add_argument("--metrics-out", default="",
                    help="also append a calibration.fitted telemetry "
                         "record here")
    sp.add_argument("--run-id", default="")

    sp = sub.add_parser("calibration",
                        help="show or diff the DB's fitted calibrations "
                             "(jax-free)")
    sp.add_argument("action", choices=("show", "diff"))
    sp.add_argument("--db", required=True)
    sp.add_argument("--platform", default="",
                    help="restrict diff to one platform (default: all)")

    sp = sub.add_parser("autotune", help="tune one config now")
    sp.add_argument("--db", default="")
    sp.add_argument("--cpu", type=int, default=0)
    sp.add_argument("--top-n", type=int, default=3)
    sp.add_argument("--probe-iters", type=int, default=4)
    sp.add_argument("--no-probe", action="store_true",
                    help="static ranking only (no compiles)")
    sp.add_argument("--force", action="store_true",
                    help="re-tune through an existing DB entry")
    sp.add_argument("--methods", default="",
                    help="comma list restricting the searched exchange "
                         "methods (e.g. 'direct26' to tune/persist a "
                         "direct26-keyed entry); default: all")
    sp.add_argument("--ks", default="1",
                    help="comma list of temporal multistep depths to "
                         "search (deep-halo k; e.g. '1,2,4')")
    _add_config_flags(sp)
    from ._bench_common import add_metrics_flags

    add_metrics_flags(sp)

    args = p.parse_args(argv)
    return {
        "show": cmd_show,
        "explain": cmd_explain,
        "prune": cmd_prune,
        "seed": cmd_seed,
        "calibrate": cmd_calibrate,
        "calibration": cmd_calibration,
        "autotune": cmd_autotune,
    }[args.cmd](args)


if __name__ == "__main__":
    raise SystemExit(main())
