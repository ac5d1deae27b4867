#!/usr/bin/env python3
"""The benchmark of record: one cell, once, in one new process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is the contract's JSON object. Without a TPU, or
with fewer chips than the cell's ``chips``, there is no result line and the
exit code is not 0: never a CPU number. ``--rehearsal`` (tests and the
builder only) walks the same code on the CPU at the configuration's tiny
rehearsal size; it never prints the result line and never exits 0.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU walk-through at a tiny size; never a result")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import harness

    return harness.main(args, T_START)


if __name__ == "__main__":
    raise SystemExit(main())
