"""A cell's traced run with the scope table split by level and by op.

    python scripts/scope_ops.py hpcg512.steady 2147483659 hpcg_restrict hpcg_prolong

Runs ``benchmark/run.py --workload <cell> --seed <n> --seconds 20 --trace 1``
in this process, from the root of the checkout it is started in, and adds
to the scope table's lines (``[bench] scopes: ...``) one line for every
device op on chip 0 whose scope holds one of the words given (none: every
op over 0.05 ms an iteration): ms an iteration, the multigrid level its
``op_name`` is tagged with, its class, opcode, instruction and result
shapes, and a total a scope and level. The result line comes last, as the
benchmark prints it. A chip run: without a TPU nothing is printed.
"""

from __future__ import annotations

import os
import sys
from collections import defaultdict

FLOOR_MS = 0.05


def main(argv) -> int:
    cell, seed = argv[0], int(argv[1])
    words = [a for a in argv[2:] if not a.startswith("--")]
    passed = [a for a in argv[2:] if a.startswith("--")]    # --rehearsal
    root = os.getcwd()
    sys.path.insert(0, root)
    from benchmark import run, scope_lib
    from stencil_tpu.obs import scopes

    tables = scope_lib._tables

    def by_op(ctx, out):
        tables(ctx, out)
        say, iters = ctx["say"], ctx["window"]["iterations"]
        omap = out["omap"]
        ops, totals = defaultdict(float), defaultdict(float)
        for op in ctx["trace"]["chips"][0]["ops"]:
            scope = op["scope"] or "(no scope)"
            if words and not any(w in scope for w in words):
                continue
            info = omap.get(op["instr"]) or {}
            level = scopes.level_of(info.get("op_name", ""))
            ops[(scope, level, op["scoped"], op["opcode"], op["instr"],
                 str(op["results"]))] += op["self"]
            totals[(scope, level)] += op["self"]
        for key, ns in sorted(ops.items(), key=lambda kv: (
                kv[0][0], -(kv[0][1] or 0), -kv[1])):
            ms = ns / iters / 1e6
            if words or ms >= FLOOR_MS:
                scope, level, cls, opcode, instr, results = key
                say(f"scopes: by op {ms:9.4f}  level {level}  {scope}  "
                    f"{cls} {opcode} {instr} {results}")
        for (scope, level), ns in sorted(totals.items(), key=lambda kv: (
                kv[0][0], -(kv[0][1] or 0))):
            say(f"scopes: by level {ns / iters / 1e6:9.4f}  level {level}  "
                f"{scope}")

    scope_lib._tables = by_op
    return run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     "20", "--trace", "1"] + passed)


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
