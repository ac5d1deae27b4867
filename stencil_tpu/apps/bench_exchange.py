"""bench-exchange — radius-shape sweep + method ablation of the halo exchange.

TPU-native port of the reference sweep (reference: bin/bench_exchange.cu):
five radius shapes (+x-leaning, x-only, faces-only, face+edge, uniform) at a
fixed per-run extent, reporting trimean seconds and aggregate B/s.

``compare_methods``/``ablate`` row out the three exchange strategies on the
uniform shape — the data-movement-strategy ablation that stands in for the
reference's bench-mpi-pack pack-kernel-vs-derived-datatype comparison
(reference: bin/bench_mpi_pack.cu:18-80): composed full-extent slabs (6
hand-written collectives) vs exact-extent per-direction messages (26) vs
``auto-spmd``, where the SPMD partitioner synthesizes the collectives from
a globally-sharded shifted-slice program. ``--ablate`` additionally pulls
each compiled program's collective census (op counts + interconnect bytes,
utils/hlo_check.collective_census) and asserts all three methods produce
bit-identical halos — the CI gate for the strategy family.

Usage: python -m stencil_tpu.apps.bench_exchange --x 256 --y 256 --z 256 --iters 30
       python -m stencil_tpu.apps.bench_exchange --cpu 8 --ablate
"""

from __future__ import annotations

import argparse
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..geometry import Dim3, Radius
from ..obs import telemetry
from ..parallel import Method
from ._bench_common import (
    add_metrics_flags, coord_state, start_metrics, time_exchange,
)

# ablation order: manual composed, manual direct, partitioner-synthesized
ABLATE_METHODS = (Method.AXIS_COMPOSED, Method.DIRECT26, Method.AUTO_SPMD)


def sweep_radii(face: int = 2, edge: int = 1):
    """The five shapes of the reference sweep (bin/bench_exchange.cu:126-195)."""
    px = Radius.constant(0)
    px.set_dir((1, 0, 0), face)

    x_only = Radius.constant(0)
    x_only.set_dir((1, 0, 0), face)
    x_only.set_dir((-1, 0, 0), face)

    faces = Radius.constant(0)
    faces.set_face(face)

    face_edge = Radius.constant(face)
    face_edge.set_corner(edge)

    uniform = Radius.constant(2)
    return [
        (f"px/{face}", px),
        (f"x/{face}", x_only),
        (f"faces/{face}", faces),
        (f"face&edge/{face}/{edge}", face_edge),
        ("uniform/2", uniform),
    ]


def run(x, y, z, iters=30, quantities=4, devices=None, method=Method.AXIS_COMPOSED,
        chunk=10, wire_dtype=None):
    devices = list(devices) if devices is not None else jax.devices()
    rows = []
    for name, radius in sweep_radii():
        r = time_exchange(
            Dim3(x, y, z), radius, iters, method=method, devices=devices,
            quantities=quantities, chunk=chunk, wire_dtype=wire_dtype,
        )
        rows.append(
            {
                "config": f"{x}-{y}-{z}/{name}",
                "bytes": r["bytes_logical"],
                "trimean_s": r["trimean_s"],
                "bytes_per_s": r["bytes_logical"] / r["trimean_s"],
            }
        )
    return rows


def compare_methods(x, y, z, iters=30, quantities=4, devices=None, radius=2,
                    methods=ABLATE_METHODS):
    """The three exchange strategies at a uniform radius — the pack-strategy
    ablation (see module docstring)."""
    devices = list(devices) if devices is not None else jax.devices()
    rows = []
    for method in methods:
        try:
            r = time_exchange(
                Dim3(x, y, z), Radius.constant(radius), iters, method=method,
                devices=devices, quantities=quantities,
            )
        except ValueError as e:
            # a method constraint (e.g. block size < radius after the
            # NodePartition's split) should report the skip instead of
            # crashing after the main sweep
            print(f"# skipping {method.value}: {e}")
            continue
        rows.append(
            {
                "config": f"{x}-{y}-{z}/method={method.value}",
                "bytes": r["bytes_logical"],
                "trimean_s": r["trimean_s"],
                "bytes_per_s": r["bytes_logical"] / r["trimean_s"],
                "domain": r["domain"],
                "census": r["census"],
            }
        )
    return rows


def ablate(x, y, z, iters=30, quantities=4, devices=None, radius=2):
    """Run all three methods back-to-back at a uniform radius: wall-clock,
    collective census (counts + interconnect bytes from the compiled HLO),
    and a bit-for-bit agreement check of one exchange on coordinate fields.

    Returns ``(rows, agree)``; each row carries ``cp_count``/``cp_bytes``
    (collective-permutes) and ``other_collectives`` (any all-gather/
    all-reduce/... the partitioner snuck in — 0 for a pure permute plan).
    Bitwise agreement across ALL methods is only guaranteed at a uniform
    radius: under anisotropic gating DIRECT26 skips inactive directions
    that the composed full-extent slabs incidentally fill."""
    rows = compare_methods(
        x, y, z, iters=iters, quantities=quantities, devices=devices,
        radius=radius,
    )
    rec = telemetry.get()
    outs = {}
    for row in rows:
        dd = row.pop("domain")
        ex = dd.halo_exchange
        state = coord_state(dd, quantities)
        # the census is a STATIC truth (shapes + method, not values), so a
        # metrics-enabled run reuses the one time_exchange already compiled
        # and recorded; otherwise lower/compile it here — the same state
        # then feeds (and is donated to) the agreement exchange
        census = row.pop("census", None)
        if census is None:
            census = ex.collective_census(state)
            if rec.enabled:
                telemetry.record_census(census, rec, method=ex.method.value)
        cp = census.get("collective-permute", (0, 0))
        row["cp_count"] = cp[0]
        row["cp_bytes"] = cp[1]
        row["other_collectives"] = sum(
            c for k, (c, _b) in census.items() if k != "collective-permute"
        )
        out = ex(state)
        outs[row["config"]] = np.stack(
            [np.asarray(jax.device_get(out[i])) for i in sorted(out)]
        )
    vals = list(outs.values())
    agree = all(np.array_equal(vals[0], v) for v in vals[1:])
    if rec.enabled:
        rec.gauge("ablate.bit_for_bit_agreement", int(agree), phase="verify")
    return rows, agree


def batched_ab(x, y, z, iters=30, quantities=(1, 4, 8), devices=None,
               radius=2, partition=None):
    """Quantity-batching A/B: at each Q, time the batched exchange (one
    packed ``(Q, ...)`` carrier per collective — Q-independent permute
    count) against the historical per-quantity program on the SAME domain
    shape, with the collective census of both compiled programs and a
    field-for-field bit-parity check of one exchange on coordinate fields.

    Returns ``(rows, q_independent, parity)``: ``q_independent`` is True
    iff the batched permute count is identical across every Q (the
    tentpole claim — e.g. 6 at Q=1 and Q=8 on a 2×2×2 mesh, where the
    per-quantity column reads 6·Q); ``parity`` is True iff batched and
    per-quantity results agree bitwise at every Q."""
    devices = list(devices) if devices is not None else jax.devices()
    rec = telemetry.get()
    rows = []
    batched_counts = {}
    parity = True
    for q in quantities:
        outs = {}
        for batched in (True, False):
            r = time_exchange(
                Dim3(x, y, z), Radius.constant(radius), iters,
                devices=devices, quantities=q, batch_quantities=batched,
                partition=partition,
            )
            dd = r["domain"]
            ex = dd.halo_exchange
            state = coord_state(dd, q)
            census = r.pop("census", None)
            if census is None:
                # metrics disabled (census is non-None exactly when the
                # recorder is on — time_exchange already recorded it,
                # batched-tagged, in that case): compile it for the table
                census = ex.collective_census(state)
            cp = census.get("collective-permute", (0, 0))
            label = "batched" if batched else "per-quantity"
            rows.append({
                "config": f"{x}-{y}-{z}/q={q}/{label}",
                "bytes": r["bytes_logical"],
                "trimean_s": r["trimean_s"],
                "bytes_per_s": r["bytes_logical"] / r["trimean_s"],
                "cp_count": cp[0],
                "cp_bytes": cp[1],
                "other_collectives": sum(
                    c for k, (c, _b) in census.items()
                    if k != "collective-permute"
                ),
            })
            if batched:
                batched_counts[q] = cp[0]
            # one exchange on coordinate fields for the parity gate (the
            # state is donated to it, so gather the result immediately)
            out = ex(state)
            outs[batched] = np.stack(
                [np.asarray(jax.device_get(out[i])) for i in sorted(out)]
            )
        if not np.array_equal(outs[True], outs[False]):
            parity = False
    q_independent = len(set(batched_counts.values())) == 1
    if rec.enabled:
        rec.gauge("batched_ab.q_independent", int(q_independent),
                  phase="verify")
        rec.gauge("batched_ab.bit_for_bit_agreement", int(parity),
                  phase="verify")
    return rows, q_independent, parity


def wire_gate(wire: str):
    """(byte-ratio threshold, relative error bound) the wire A/B gates
    one compression dtype on, derived from the dtype itself so every
    tier shares one rule: the on-wire byte reduction must reach 95% of
    the ideal fp32-native ratio (bf16 → 1.9x, the fp8 tier → 3.8x), and
    the measured max relative error must sit within the wire dtype's
    rounding half-ulp, 2^-(mantissa bits incl. implicit) (bf16 → 2^-8,
    float8_e4m3fn → 2^-4). ``jnp.finfo`` resolves the ml_dtypes types
    (bfloat16/float8_*) that numpy's finfo rejects."""
    wdt = jnp.dtype(wire)
    ratio_thr = 0.95 * (4.0 / wdt.itemsize)
    rel_bound = 2.0 ** -(jnp.finfo(wdt).nmant + 1)
    return ratio_thr, rel_bound


def wire_ab(x, y, z, iters=30, quantities=4, devices=None, radius=2,
            wire="bfloat16", method=Method.AXIS_COMPOSED, partition=None):
    """Wire-compression A/B (bf16 or the fp8 tier): the same exchange
    with native carriers vs ``wire``-compressed ones, reporting the
    on-wire byte reduction and the measured error the compression pays
    for it.

    Narrow-range wire dtypes (float8_e4m3fn tops out at 448 and maps
    overflow to NaN) get the coordinate fixture scaled into their finite
    range first — the same policy user data must follow: fp8 wire is
    for fields whose halos live inside the format's range.

    Bytes come from :func:`~stencil_tpu.utils.hlo_check.stablehlo_wire_census`
    over each leg's LOWERED program — the pre-backend-optimization truth.
    (The compiled-HLO census is still recorded when metrics are on, but
    the CPU backend's float-normalization pass widens bf16 collectives
    back to f32, so only a TPU's compiled census can confirm the ratio
    in silicon; the lowered module is what the exchange asks the wire to
    carry, and is exact for the hand-written permute methods.)

    Error gauges (vs the full-precision leg, on coordinate fields):
    ``wire_ab.max_abs_err``, ``wire_ab.max_rel_err`` and
    ``wire_ab.max_ulp_err`` (float32 ULPs between the two results).
    Returns ``(rows, bytes_ratio, err)``."""
    from ..utils.hlo_check import stablehlo_wire_census

    if method == Method.AUTO_SPMD:
        raise ValueError(
            "--wire-ab has no meaning for auto-spmd: the partitioner owns "
            "the schedule and packs no carriers to compress"
        )
    devices = list(devices) if devices is not None else jax.devices()
    rec = telemetry.get()
    rows = []
    outs = {}
    wire_bytes = {}
    # narrow-range wire dtypes: scale the coordinate fixture so no halo
    # value exceeds the format's finite range (overflow is NaN there)
    peak = (z - 1) * 1e6 + (y - 1) * 1e3 + (x - 1) + quantities
    fin_max = float(jnp.finfo(jnp.dtype(wire)).max)
    scale = min(1.0, fin_max / (2.0 * peak))
    for wd in (None, wire):
        r = time_exchange(
            Dim3(x, y, z), Radius.constant(radius), iters, method=method,
            devices=devices, quantities=quantities, wire_dtype=wd,
            partition=partition,
        )
        dd = r["domain"]
        ex = dd.halo_exchange
        state = coord_state(dd, quantities)
        if scale < 1.0:
            state = {k: v * jnp.asarray(scale, v.dtype)
                     for k, v in state.items()}
        # the lowered-module wire truth (see docstring)
        census = stablehlo_wire_census(ex._compiled.lower(state).as_text())
        cp = census.get("collective-permute", (0, 0))
        wire_bytes[wd] = cp[1]
        label = f"wire={wd or 'native'}"
        rows.append({
            "config": f"{x}-{y}-{z}/q={quantities}/{label}",
            "bytes": r["bytes_logical"],
            "trimean_s": r["trimean_s"],
            "bytes_per_s": r["bytes_logical"] / r["trimean_s"],
            "cp_count": cp[0],
            "cp_bytes": cp[1],
            "other_collectives": 0,
        })
        out = ex(state)
        outs[wd] = np.stack(
            [np.asarray(jax.device_get(out[i])) for i in sorted(out)]
        )
    ratio = (wire_bytes[None] / wire_bytes[wire]
             if wire_bytes[wire] else 0.0)
    a, b = outs[None].astype(np.float32), outs[wire].astype(np.float32)
    abs_err = float(np.max(np.abs(a - b)))
    rel_err = float(np.max(np.abs(a - b) / np.maximum(np.abs(a), 1.0)))
    # ULP distance in float32: adjacent-representable steps between the
    # two results (monotone int reinterpretation; same-sign values here)
    ai = a.view(np.int32).astype(np.int64)
    bi = b.view(np.int32).astype(np.int64)
    ulp_err = float(np.max(np.abs(ai - bi)))
    err = {"max_abs_err": abs_err, "max_rel_err": rel_err,
           "max_ulp_err": ulp_err}
    if rec.enabled:
        rec.gauge("wire_ab.bytes_ratio", ratio, phase="verify", wire=wire)
        rec.gauge("wire_ab.max_abs_err", abs_err, phase="verify", wire=wire)
        rec.gauge("wire_ab.max_rel_err", rel_err, phase="verify", wire=wire)
        rec.gauge("wire_ab.max_ulp_err", ulp_err, phase="verify", wire=wire)
    return rows, ratio, err


def report_header() -> str:
    return "config,bytes,trimean (s),B/s"


def report_row(row: dict) -> str:
    return f"{row['config']},{row['bytes']},{row['trimean_s']:e},{row['bytes_per_s']:e}"


def ablate_header() -> str:
    return "config,bytes,trimean (s),B/s,collective-permutes,cp bytes,other collectives"


def ablate_row(row: dict) -> str:
    return (
        f"{report_row(row)},{row['cp_count']},{row['cp_bytes']},"
        f"{row['other_collectives']}"
    )


def main(argv: Optional[list] = None) -> int:
    from ..parallel.distributed import maybe_init_from_env
    maybe_init_from_env()
    from ..utils.jax_cache import configure_compile_cache
    configure_compile_cache()
    p = argparse.ArgumentParser(description="halo exchange radius-shape sweep")
    p.add_argument("--x", type=int, default=256)
    p.add_argument("--y", type=int, default=256)
    p.add_argument("--z", type=int, default=256)
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--method", choices=[m.value for m in Method],
                   default=Method.AXIS_COMPOSED.value,
                   help="exchange strategy for the radius sweep")
    p.add_argument("--methods", action="store_true",
                   help="also compare the three strategies (pack ablation)")
    p.add_argument("--ablate", action="store_true",
                   help="run ONLY the three-method ablation, with collective "
                        "census columns and a bit-for-bit agreement gate "
                        "(exit 1 on disagreement)")
    p.add_argument("--quantities", default="",
                   help="quantity count for the sweeps (single int; default "
                        "4), or a comma list of Qs for --batched-ab "
                        "(default 1,4,8)")
    p.add_argument("--batched-ab", action="store_true",
                   help="run ONLY the quantity-batching A/B: batched vs "
                        "per-quantity collectives at each Q with census "
                        "columns; exit 1 unless the batched permute count "
                        "is Q-independent and results agree bit-for-bit")
    p.add_argument("--partition", default="",
                   help="force the partition grid as XxYxZ (e.g. 2x2x2) "
                        "for --batched-ab / --wire-ab")
    p.add_argument("--wire-ab", action="store_true",
                   help="run ONLY the bf16-on-the-wire A/B: native vs "
                        "--wire-dtype compressed carriers, with on-wire "
                        "byte columns (lowered-module census) and the "
                        "measured max abs/rel/ulp error vs full precision; "
                        "exit 1 unless the byte reduction is >= 1.9x and "
                        "the error sits within the wire dtype's rounding "
                        "bound")
    p.add_argument("--wire-dtype", default="",
                   help="wire-compression dtype (bfloat16 or the fp8 "
                        "tier float8_e4m3fn): the radius sweep runs "
                        "with it on; --wire-ab A/Bs it against native "
                        "(default bfloat16 there)")
    p.add_argument("--cpu", type=int, default=0)
    add_metrics_flags(p)
    args = p.parse_args(argv)
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    start_metrics(args, "bench_exchange")
    qs = [int(t) for t in str(args.quantities).split(",") if t.strip()]
    if args.wire_ab:
        partition = None
        if args.partition:
            partition = tuple(int(t) for t in args.partition.split("x"))
        if len(qs) > 1:
            p.error("--wire-ab takes a single --quantities value")
        wire = args.wire_dtype or "bfloat16"
        rows, ratio, err = wire_ab(
            args.x, args.y, args.z, iters=args.iters,
            quantities=qs[0] if qs else 4, wire=wire,
            method=Method(args.method), partition=partition,
        )
        print(ablate_header())
        for row in rows:
            print(ablate_row(row))
        print(f"# on-wire byte reduction ({wire}): {ratio:.3f}x")
        print(f"# max abs err {err['max_abs_err']:.6g}  max rel err "
              f"{err['max_rel_err']:.3e}  max f32-ulp err "
              f"{err['max_ulp_err']:.0f}")
        # dtype-derived gate (wire_gate): >= 95% of the ideal fp32-native
        # byte ratio (bf16 1.9x, fp8 3.8x), error within the wire dtype's
        # rounding half-ulp, and an UNCHANGED permute count — the
        # compression must never change what moves, only how wide
        ratio_thr, rel_bound = wire_gate(wire)
        count_ok = len({row["cp_count"] for row in rows}) == 1
        ok = (ratio >= ratio_thr and err["max_rel_err"] <= rel_bound
              and count_ok)
        print(f"# wire A/B gate (>={ratio_thr:g}x bytes, rel err <= "
              f"{rel_bound:g}, count unchanged): "
              f"{'PASS' if ok else 'FAIL'}")
        return 0 if ok else 1
    if args.batched_ab:
        partition = None
        if args.partition:
            partition = tuple(int(t) for t in args.partition.split("x"))
        rows, q_indep, parity = batched_ab(
            args.x, args.y, args.z, iters=args.iters,
            quantities=tuple(qs) if qs else (1, 4, 8), partition=partition,
        )
        print(ablate_header())
        for row in rows:
            print(ablate_row(row))
        print(f"# batched permute count Q-independent: "
              f"{'PASS' if q_indep else 'FAIL'}")
        print(f"# batched vs per-quantity bit-for-bit: "
              f"{'PASS' if parity else 'FAIL'}")
        return 0 if q_indep and parity else 1
    if len(qs) > 1:
        # a silent truncation to qs[0] would print plausible rows for a
        # configuration the user did not ask for
        p.error("a comma list of --quantities requires --batched-ab")
    nq = qs[0] if qs else 4
    if args.ablate:
        rows, agree = ablate(args.x, args.y, args.z, iters=args.iters,
                             quantities=nq)
        print(ablate_header())
        for row in rows:
            print(ablate_row(row))
        print(f"# bit-for-bit agreement: {'PASS' if agree else 'FAIL'}")
        return 0 if agree and len(rows) == len(ABLATE_METHODS) else 1
    print(report_header())
    for row in run(args.x, args.y, args.z, iters=args.iters,
                   method=Method(args.method), quantities=nq,
                   wire_dtype=args.wire_dtype or None):
        print(report_row(row))
    if args.methods:
        for row in compare_methods(args.x, args.y, args.z, iters=args.iters,
                                   quantities=nq):
            row.pop("domain", None)
            print(report_row(row))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
