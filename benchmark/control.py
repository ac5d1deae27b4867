#!/usr/bin/env python3
"""How ``correct`` was set: sound readings over many seeds, and the control.

    python3 benchmark/control.py --workload <cell> --seeds 12

One process, one set-up: for each seed the state is seeded, the first chunk
is driven through the window's own dispatch and every number that decides
``correct`` is read (the sound reading); then the control is read on the
same sample: the plain reference computed in the nearest precision below
the configuration's (bfloat16 for float32), put in the program's place;
and, where the adapter plants faults of its own (``faults``: one equation
left out), each of them. Prints, per number, the largest sound reading, the
smallest control reading and the limit; exits non-zero unless every sound
reading passes and the control and every fault fail at least one number on
every seed.
The benchmark's own runs never call this; ``tests/benchmark_suite`` runs it
at the rehearsal size.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

FIRST_SEED = 1_000_003
SEED_STEP = 2_000_000_011   # seeds pass 2**31, as the driver's do


def readings(session, seeds):
    """[(seed, sound checks, control checks, [(fault, checks)])], each
    ``checks`` a list of (name, value, limit)."""
    import jax

    rows = []
    for seed in seeds:
        session.seed(seed)
        jax.block_until_ready(session.dispatch())
        sample = session.sample()
        faults = session.faults(sample) if hasattr(session, "faults") else []
        rows.append((seed, list(session.compare(sample)),
                     list(session.control(sample)), faults))
    return rows


def failing(checks) -> list:
    return [name for name, value, limit in checks
            if not value <= limit]


def verdict(rows, say=print) -> bool:
    names = [n for n, _, _ in rows[0][1]]
    ok = True
    for i, name in enumerate(names):
        sound = [r[1][i][1] for r in rows]
        ctrl = [r[2][i][1] for r in rows]
        limit = rows[0][1][i][2]
        say(f"{name}: sound max {max(sound)!r} (min {min(sound)!r}) over "
            f"{len(rows)} seeds; control min {min(ctrl)!r} (max "
            f"{max(ctrl)!r}); limit {limit!r}")
        ok = ok and all(v <= limit for v in sound)
    always = set.intersection(*(set(failing(r[2])) for r in rows))
    say(f"the control fails {sorted(always)} on every seed")
    for seed, _, ctrl, faults in rows:
        for what, checks in [("the control", ctrl)] + list(faults):
            if not failing(checks):
                say(f"seed {seed}: {what} PASSED every number: no limit "
                    f"holds it out")
                ok = False
    for j, (what, _) in enumerate(rows[0][3]):
        hit = set.intersection(*(set(failing(r[3][j][1])) for r in rows))
        worst = {n: min(v for r in rows for m, v, _ in r[3][j][1] if m == n)
                 for n in sorted(hit)}
        say(f"fault {what}: fails {worst} (smallest reading) on every seed")
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import harness

    args.seed, args.seconds, args.trace = 0, 0.0, 0
    opened, rc = harness.open_session(args, T_START)
    if rc:
        return rc
    seeds = [FIRST_SEED + i * SEED_STEP for i in range(args.seeds)]
    rows = readings(opened.session, seeds)
    for seed, sound, ctrl, _ in rows:
        harness.say(f"seed {seed}: sound {[(n, v) for n, v, _ in sound]} "
                    f"control {[(n, v) for n, v, _ in ctrl]}")
    ok = verdict(rows, harness.say)
    harness.say("control: limits hold" if ok else "control: LIMITS DO NOT HOLD")
    if args.rehearsal:
        return harness.REHEARSAL_RC if ok else 1
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
