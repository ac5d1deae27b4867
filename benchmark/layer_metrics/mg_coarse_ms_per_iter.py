"""Device self time per iteration in everything that runs on the coarse
levels of a multigrid cycle, mean over chips: every op (operator, fill,
glue) whose ``op_name`` carries the tag ``stencil.mg.level<k>`` of a level
that the program's newest ``mg.cycle_plan`` record for the traced loop lays
out ``inline`` (rows narrower than a lane tile: the levels bound by latency,
not by bytes). Since the coarse half of the V-cycle is ONE Pallas call under
the first such level's tag, that call is what this sums, both transfers to
and from the last tight-x level included. Printed with it: ms an iteration
by level and innermost scope, for every level. Nothing where the program
recorded no plan, the plan has no inline level, or no op carries their
tags."""

from collections import defaultdict

from benchmark import scope_lib

COUNTER = "mg.cycle_plan"


def newest(records, module):
    """The newest cycle plan of ``module``, ``None`` where the program
    recorded none."""
    mine = [r for r in records if r.get("module") == module
            and r.get("levels")]
    return mine[-1] if mine else None


def read(ctx):
    out = scope_lib.scoped(ctx)
    if out is None:
        return None
    scopes, telemetry = scope_lib.program()
    plan = newest(telemetry.get().records(kind="counter", name=COUNTER),
                  out["module"])
    if plan is None:
        return None
    inline = {lv["level"] for lv in plan["levels"]
              if lv["layout"] == "inline"}
    chips = ctx["trace"]["chips"]
    per = len(chips) * ctx["window"]["iterations"] * 1e6
    by_level = defaultdict(float)
    for chip in chips:
        for op in chip["ops"]:
            info = out["omap"].get(op["instr"]) or {}
            level = scopes.level_of(info.get("op_name", ""))
            if level is not None:
                by_level[(level, op["scope"] or "(no scope)")] += op["self"]
    for (level, scope), ns in sorted(by_level.items(),
                                     key=lambda kv: (-kv[0][0], -kv[1])):
        ctx["say"](f"mg levels: {ns / per:9.4f} ms  level {level} "
                   f"({'inline' if level in inline else 'tight_x'})  {scope}")
    mine = [ns for (level, _), ns in by_level.items() if level in inline]
    if not mine:
        return None
    return sum(mine) / per
