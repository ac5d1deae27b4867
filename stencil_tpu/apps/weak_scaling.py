"""weak_scaling — the day-1 multi-chip harness for the north-star table.

Given an N-chip slice this runs the three BASELINE.json multi-chip configs
and emits one CSV plus weak-scaling efficiencies against recorded
single-chip numbers, so the first hardware session produces the scaling
table instead of engineering (reference workflow:
scripts/summit/512node_weak_exchange.sh:17-29 — one submission per scale,
CSV rows appended per run):

- config 2: exchange, 256^3 *global*, radius 2, 4 quantities (2x2x2
  partition at 8 chips; whatever partition N chips realize otherwise)
- config 3: exchange_weak, 512^3 *per chip*, radius 3, 4 quantities
- config 5: jacobi3d overlap step, 256^3 per chip (1024^3 global at 64
  chips), plus the measure_overlap hidden-fraction instrument at the same
  per-chip size

Efficiency definitions (vs the ``--base`` JSON, by default the repo's
recorded single-chip numbers, re-recordable with ``--record-base`` on one
chip):

- jacobi:   eff = (Mcells/s/chip at N) / (Mcells/s/chip at 1) — the >90%
            north star (BASELINE.json).
- exchange: t(1 chip)/t(N chips) per exchange at the same per-chip load
            (config 3); reported as a ratio, not a percentage, because the
            1-chip "exchange" is self-wrap halo fill, a different physical
            operation than ICI permutes — the absolute GB/s column is the
            number that matters.
- overlap:  hidden_frac from measure_overlap (1.0 = exchange fully hidden).

Usage:
  python -m stencil_tpu.apps.weak_scaling                  # real chips
  python -m stencil_tpu.apps.weak_scaling --cpu 8 --smoke  # virtual mesh
  python -m stencil_tpu.apps.weak_scaling --record-base    # on 1 chip

Dispatch-overhead caveat: iterations run in fused chunks of ``iters // 3``.
Every chunk pays one host dispatch, so the efficiency columns are only
apples-to-apples when runs use the same ``--iters`` as ``--record-base``
(default 360).
"""

from __future__ import annotations

import argparse
import json
import os
from typing import Optional

import jax

from ..geometry import Dim3
from ..obs import telemetry
from ..parallel import Method
from ..utils import logging as log
from . import bench_exchange, exchange_weak, jacobi3d, measure_overlap

# Single-chip anchors (v5e; see BASELINE.md). --record-base overwrites
# these with freshly measured values. The jacobi anchor is the
# 256^3-per-chip config-5 configuration itself (fused loop, deep_halo=4 =>
# temporal depth PINNED at k=4 on every device count, same as the scaled
# runs — ADVICE r3), NOT the 512^3 headline, so the efficiency column
# compares like with like.
#
# Recorded round 5 (2026-07-31; log deleted in PR 21, older unverified
# figure) at the pinned k=4 via --record-base; scripts/weak_base.json holds
# the full-precision values and takes precedence whenever it exists.
DEFAULT_BASE = {
    "jacobi_mcells_per_s_per_dev": 14337.0,  # 256^3 deep_halo=4 (k=4 pin)
    "exchange_weak_trimean_s": 5.41e-3,      # 512^3 radius-3 4q self-wrap fill
    "config2_trimean_s": 2.21e-3,            # 256^3 radius-2 4q self-wrap fill
}


def _base_path() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "scripts", "weak_base.json")


def run(
    devices=None,
    iters: int = 30,
    jacobi_iters: int = 60,
    per_chip: Dim3 = Dim3(256, 256, 256),
    exw_per_chip: Dim3 = Dim3(512, 512, 512),
    config2_global: Dim3 = Dim3(256, 256, 256),
    base: Optional[dict] = None,
    use_pallas: Optional[bool] = None,
    overlap_rounds: int = 3,
    deep_halo: int = 4,
    chunk: Optional[int] = None,
) -> dict:
    """Run configs 2/3/5 on ``devices`` and return rows + efficiencies.

    ``chunk`` (iterations fused per dispatch) defaults to ``iters // 3`` —
    the anchors are recorded with large chunks, and a small chunk makes the
    efficiency columns measure dispatch overhead instead of scaling."""
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    missing = sorted(set(DEFAULT_BASE) - set(base or {}))
    if missing:
        # ADVICE r4: make it visible when built-in constants (not a
        # measured scripts/weak_base.json) anchor any efficiency column —
        # including a partial --base dict
        log.warn(
            "weak-scaling efficiency columns "
            f"{missing} anchored to built-in DEFAULT_BASE constants; run "
            "--record-base (or pass a full --base) for measured anchors"
        )
    base = dict(DEFAULT_BASE, **(base or {}))
    if chunk is None:
        chunk = max(1, iters // 3)
    rows = []

    # -- config 2: fixed global exchange ------------------------------------
    c2 = bench_exchange.run(
        config2_global.x, config2_global.y, config2_global.z,
        iters=iters, quantities=4, devices=devices, chunk=chunk,
    )[-1]  # the "uniform/2" row — config 2's radius-2 halo
    c2_eff = base["config2_trimean_s"] / c2["trimean_s"]
    rows.append(("config2_exchange", config2_global.x, config2_global.y,
                 config2_global.z, n, c2["trimean_s"],
                 c2["bytes_per_s"] / 1e9, c2_eff))

    # -- config 3: weak-scaled exchange -------------------------------------
    c3 = exchange_weak.run(
        exw_per_chip.x, exw_per_chip.y, exw_per_chip.z,
        iters=iters, devices=devices, weak=True, chunk=chunk,
    )
    c3_eff = base["exchange_weak_trimean_s"] / c3["trimean_s"]
    rows.append(("config3_exchange_weak", c3["x"], c3["y"], c3["z"], n,
                 c3["trimean_s"], c3["gb_per_s"], c3_eff))

    # -- config 5: overlapped jacobi + hidden fraction ----------------------
    # deep_halo lets the fused loop temporally block across chips (one
    # radius-k exchange per k steps); the anchor is a 256^3 single-chip run
    # of the SAME configuration so the efficiency column measures scaling,
    # not temporal-blocking availability
    c5 = jacobi3d.run(
        per_chip.x, per_chip.y, per_chip.z,
        iters=jacobi_iters, overlap=True, devices=devices, weak=True,
        deep_halo=deep_halo, chunk=min(chunk, jacobi_iters),
    )
    jac_eff = c5["mcells_per_s_per_dev"] / base["jacobi_mcells_per_s_per_dev"]
    rows.append(("config5_jacobi_overlap", c5["x"], c5["y"], c5["z"], n,
                 c5["iter_trimean_s"], c5["mcells_per_s_per_dev"], jac_eff))

    ov = measure_overlap.run(
        per_chip.x, per_chip.y, per_chip.z,
        radius=1, iters=max(10, iters // 3), rounds=overlap_rounds,
        devices=devices, weak=True, use_pallas=use_pallas,
    )
    rows.append(("config5_hidden_frac", ov["x"], ov["y"], ov["z"], n,
                 ov["overlap_s"], ov["hidden_s"], ov["hidden_frac"]))

    rec = telemetry.get()
    if rec.enabled:
        for name, _x, _y, _z, _n, secs, thr, eff in rows:
            rec.gauge(f"weak.{name}.seconds", secs, phase="scaling", unit="s")
            rec.gauge(f"weak.{name}.efficiency", eff, phase="scaling")
    return {
        "devices": n,
        "rows": rows,
        "results": {"config2": c2, "config3": c3, "config5": c5,
                    "overlap": ov},
    }


# `metric` is per-row heterogeneous (GB/s for the exchange configs,
# Mcells/s/chip for jacobi, hidden seconds for the overlap instrument) —
# rows are keyed by `config`, so never aggregate the column across rows.
CSV_HEADER = "config,x,y,z,devices,seconds,metric,efficiency"


def csv_rows(res: dict) -> list:
    out = [CSV_HEADER]
    for name, x, y, z, n, secs, thr, eff in res["rows"]:
        out.append(f"{name},{x},{y},{z},{n},{secs:e},{thr:.3f},{eff:.4f}")
    return out


def record_base(devices=None, iters: int = 360, path: str = "") -> dict:
    """Measure the single-chip anchors and write them to ``path``.

    Large fused chunks, so the per-dispatch host cost does not dominate
    the anchors."""
    devices = list(devices) if devices is not None else jax.devices()
    if len(devices) != 1:
        raise ValueError("--record-base wants exactly one device")
    chunk = max(1, iters // 3)
    c2 = bench_exchange.run(256, 256, 256, iters=iters, quantities=4,
                            devices=devices, chunk=chunk)[-1]  # "uniform/2"
    c3 = exchange_weak.run(512, 512, 512, iters=iters, devices=devices,
                           chunk=chunk)
    # same shape as run()'s config 5: 256^3 per chip, deep_halo fused loop
    c5 = jacobi3d.run(256, 256, 256, iters=iters, overlap=True,
                      devices=devices, weak=False, deep_halo=4, chunk=chunk)
    base = {
        "jacobi_mcells_per_s_per_dev": c5["mcells_per_s_per_dev"],
        "exchange_weak_trimean_s": c3["trimean_s"],
        "config2_trimean_s": c2["trimean_s"],
    }
    path = path or _base_path()
    # tmp+fsync+rename: the recorded base anchors every later weak-scaling
    # column — a torn write must never replace a good one
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(base, f, indent=1)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    log.info(f"single-chip base recorded to {path}: {base}")
    return base


def main(argv: Optional[list] = None) -> int:
    from ..parallel.distributed import maybe_init_from_env
    maybe_init_from_env()
    from ..utils.jax_cache import configure_compile_cache
    configure_compile_cache()
    p = argparse.ArgumentParser(description="weak-scaling day-1 harness")
    p.add_argument("--cpu", type=int, default=0, help="virtual CPU devices")
    p.add_argument("--iters", type=int, default=None,
                   help="timed iterations (default 30; 360 for --record-base "
                        "— anchors need large fused chunks)")
    p.add_argument("--jacobi-iters", type=int, default=60)
    p.add_argument("--smoke", action="store_true",
                   help="tiny sizes for the virtual-mesh smoke test")
    p.add_argument("--base", default="", help="single-chip anchors JSON")
    p.add_argument("--record-base", action="store_true",
                   help="measure + write the single-chip anchors (1 chip)")
    p.add_argument("--out", default="", help="also append CSV to this file")
    p.add_argument("--pallas", dest="use_pallas", action="store_true",
                   default=None, help="force the Pallas overlap variant")
    from ._bench_common import add_metrics_flags, start_metrics
    add_metrics_flags(p)
    args = p.parse_args(argv)
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    # the config 2/3/5 sub-apps all record through this process recorder
    start_metrics(args, "weak_scaling")

    if args.record_base:
        record_base(iters=args.iters or 360, path=args.base)
        return 0

    base = None
    base_path = args.base or _base_path()
    if os.path.exists(base_path):
        with open(base_path) as f:
            base = json.load(f)

    kw = {}
    if args.smoke:
        kw = dict(per_chip=Dim3(32, 32, 32), exw_per_chip=Dim3(32, 32, 32),
                  config2_global=Dim3(32, 32, 32), iters=4, jacobi_iters=4,
                  overlap_rounds=1)
    else:
        kw = dict(iters=args.iters or 30, jacobi_iters=args.jacobi_iters)
    res = run(base=base, use_pallas=args.use_pallas, **kw)

    lines = csv_rows(res)
    for line in lines:
        print(line)
    if args.out:
        new = not os.path.exists(args.out)
        with open(args.out, "a") as f:
            for line in lines if new else lines[1:]:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
