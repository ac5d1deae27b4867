"""The layers read from inside: the trace's ops joined, by instruction
name, with the program's own map of its compiled loop
(``stencil_tpu.obs.scopes.op_map``), and the program's own records
(``stencil_tpu.obs.telemetry``). Each reader under ``layer_metrics/`` that
uses this file is a few lines over it.

Every op of a chip gets one class:

- ``kernel``: a Pallas custom-call under ``stencil.kernel.<name>`` whose
  name the vocabulary counts with the stencil kernels;
- ``halo``: any op under a scope of the halo layer (``stencil.halo.*``, the
  self-fill and remote-DMA kernels), be it Pallas, a collective or XLA code;
- ``glue_program``: any other op under a ``stencil.*`` scope: orchestration
  the program wrote;
- ``glue_compiler``: no ``stencil.*`` scope on the op: what the compiler
  added (loop-carried copies, layout changes), and the self time of the
  containers (``while``, ``call``, ``conditional``).

The four are a partition of the chip's summed op self time. A program that
lacks the map, the records or the counter (the parent of the PR that brought
them, a CPU rehearsal with no TPU plane) gives every reader ``None``: the
metric is left out of the line and nothing raises.
"""

from __future__ import annotations

import re
from collections import defaultdict

from benchmark import trace_reduce as tr

CLASSES = ("kernel", "halo", "glue_program", "glue_compiler")
_MODULE = re.compile(r"^jit_(.+?)(?:\(\d+\))?$")
COPY_TABLE_MS = 0.1          # copies over this much an iteration are named
_SELF_FILL = "stencil.kernel.self_fill_"


def program():
    """The program's ``scopes`` and ``telemetry`` modules, or ``None``
    where it has none (an older program)."""
    try:
        from stencil_tpu.obs import scopes, telemetry
    except ImportError:
        return None
    if not hasattr(scopes, "op_map") or not hasattr(
            telemetry.Recorder, "records"):
        return None
    return scopes, telemetry


def module_name(trace: dict):
    """The program that fills the first chip's ``XLA Modules`` line, as the
    builder named it (``jit_stencil_jacobi_loop(1192...)`` ->
    ``stencil_jacobi_loop``); ``None`` for an empty line."""
    chips = trace["chips"]
    if not chips or not chips[0]["modules"]:
        return None
    held = defaultdict(float)
    for name, _start, dur in chips[0]["modules"]:
        held[name] += dur
    m = _MODULE.match(max(held, key=held.get))
    return m.group(1) if m else None


def classify_op(op: dict, info) -> str:
    """One of ``CLASSES`` for a trace op, given its row of the op map."""
    if op["opcode"] in tr.CONTAINERS or info is None or not info["scope"]:
        return "glue_compiler"
    if info["layer"] == "Halo exchange":
        return "halo"
    if info["layer"] == "Stencil kernels" and tr.is_pallas(op):
        return "kernel"
    return "glue_program"


def best_map(scopes, module: str, names: set, say):
    """The op map, among the loops registered under ``module``, that knows
    most of the trace's instruction names (newest first; the first that
    knows them all ends the search)."""
    best, best_hit = None, -1
    for entry in range(scopes.registered(module) - 1, -1, -1):
        omap = scopes.op_map(module, entry)
        hit = len(names & set(omap))
        say(f"scopes: op_map({module!r}, entry {entry}) took "
            f"{scopes.op_map_seconds(module, entry):.3f} s, knows {hit} of "
            f"the trace's {len(names)} instruction names")
        if hit > best_hit:
            best, best_hit = omap, hit
        if hit == len(names):
            break
    return best


def join(trace: dict, omap: dict) -> None:
    """Give every op of the trace ``scope`` and ``scoped`` (its class)."""
    for chip in trace["chips"]:
        for op in chip["ops"]:
            info = omap.get(op["instr"])
            op["scope"] = info["scope"] if info else None
            op["scoped"] = classify_op(op, info)


def scoped(ctx):
    """``{"ms": {class: ms per iteration, mean over chips}, "total_ms",
    "omap", "module"}``, computed once per run and kept in ``ctx``; the
    tables are printed then. ``None`` without a TPU plane, an iteration or
    a program that can map its loop."""
    if "scoped" in ctx:
        return ctx["scoped"]
    ctx["scoped"] = None
    trace, iters = ctx["trace"], ctx["window"]["iterations"]
    prog = program()
    if not trace["chips"] or iters <= 0 or prog is None:
        return None
    scopes, _ = prog
    say = ctx["say"]
    module = module_name(trace)
    if module is None or not scopes.registered(module):
        say(f"scopes: the trace's module {module!r} is not a registered "
            f"loop of the program; nothing to read")
        return None
    names = {op["instr"] for op in trace["chips"][0]["ops"]}
    try:
        omap = best_map(scopes, module, names, say)
    except Exception as e:  # a reader reports, it never fails the run
        say(f"scopes: op_map({module!r}) failed: {type(e).__name__}: {e}")
        return None
    join(trace, omap)
    n = len(trace["chips"])
    ms = {c: sum(op["self"] for chip in trace["chips"] for op in chip["ops"]
                 if op["scoped"] == c) / n / iters / 1e6 for c in CLASSES}
    total = sum(op["self"] for chip in trace["chips"]
                for op in chip["ops"]) / n / iters / 1e6
    ctx["scoped"] = out = {"ms": ms, "total_ms": total, "omap": omap,
                           "module": module}
    _tables(ctx, out)
    return out


def class_ms(ctx, cls: str):
    """One class's device self time per iteration, ``None`` where nothing
    can be read."""
    out = scoped(ctx)
    return None if out is None else out["ms"][cls]


def _tables(ctx, out) -> None:
    say, iters = ctx["say"], ctx["window"]["iterations"]
    chip = ctx["trace"]["chips"][0]
    omap = out["omap"]
    by_scope, by_instr = defaultdict(float), defaultdict(float)
    unknown = 0.0
    for op in chip["ops"]:
        by_scope[(op["scoped"], op["scope"] or "(no scope)")] += op["self"]
        by_instr[op["instr"]] += op["self"]
        if op["instr"] not in omap:
            unknown += op["self"]
    say(f"scopes: module {out['module']}; ms per iteration on chip 0, by "
        f"class and scope:")
    for (cls, scope), ns in sorted(by_scope.items(), key=lambda kv: -kv[1]):
        say(f"scopes:   {ns / iters / 1e6:9.4f}  {cls:13s} {scope}")
    parts = " + ".join(f"{c} {out['ms'][c]:.4f}" for c in CLASSES)
    say(f"scopes: partition (mean over chips) {parts} = "
        f"{sum(out['ms'].values()):.4f} ms; summed op self time "
        f"{out['total_ms']:.4f} ms; {unknown / iters / 1e6:.4f} ms in ops "
        f"the map does not know (counted as glue_compiler)")
    # by name against by shape: the stencil kernels both ways
    lost = [op for op in chip["ops"]
            if (op["scoped"] == "kernel") != (op.get("cls") == "stencil")]
    for label in sorted({tr.label(op) for op in lost}):
        ops = [op for op in lost if tr.label(op) == label]
        say(f"scopes: kernel by name and by shape disagree on {label}: "
            f"scope {ops[0]['scope']}, shape match says "
            f"{ops[0].get('cls')}/{ops[0].get('kernel')}, "
            f"{sum(o['self'] for o in ops) / iters / 1e6:.4f} ms/iter")
    pallas = [op for op in chip["ops"] if tr.is_pallas(op)]
    unnamed = sorted({op["instr"] for op in pallas if not (
        op["scope"] or "").startswith("stencil.kernel.")})
    say(f"scopes: Pallas custom-calls on chip 0: "
        f"{sorted({op['scope'] for op in pallas if op['scope']})}; "
        f"without a stencil.kernel.* name: {unnamed or 'none'}")
    for instr, ns in sorted(by_instr.items(), key=lambda kv: -kv[1]):
        info = omap.get(instr)
        if ns / iters / 1e6 < COPY_TABLE_MS or not instr.startswith("copy"):
            continue
        if info is None:
            say(f"scopes: copy {instr}: {ns / iters / 1e6:.4f} ms/iter, not "
                f"in the map")
            continue
        feeds = ", ".join(map(_who, info.get("consumers") or []))
        say(f"scopes: copy {instr}: {ns / iters / 1e6:.4f} ms/iter, "
            f"{info['scope'] or 'compiler'}; from {_who(info.get('producer'))}"
            f"; feeds {feeds or 'nothing named'}")


def _who(ref) -> str:
    if not ref:
        return "a parameter or constant"
    where = "" if ref["operand"] is None else f" operand {ref['operand']}"
    return (f"{ref['instr']} ({ref['opcode']}, "
            f"{ref['scope'] or 'no scope'}){where}")


# ------------------------------------------------------------ bytes moved


def self_fill_moved(ctx):
    """Share of the HBM roofline of the self-fill kernels on the bytes
    their DMAs really move: the program's ``halo.self_fill.bytes_dma`` of
    every self-fill call in the trace over the peak rate, over those calls'
    device time. ``None`` where the program counts no such bytes."""
    out = scoped(ctx)
    if out is None:
        return None
    _, telemetry = program()
    builds = telemetry.get().records(kind="counter",
                                     name="halo.self_fill.bytes_dma")
    bw = ctx["peak"]["hbm_bytes_per_s"]
    moved = spent = 0.0
    axes = defaultdict(lambda: [0.0, 0.0])
    for chip in ctx["trace"]["chips"]:
        for op in chip["ops"]:
            scope = op["scope"] or ""
            if not (tr.is_pallas(op) and scope.startswith(_SELF_FILL)):
                continue
            axis, nq = scope[len(_SELF_FILL):], len(op["results"])
            hit = [b for b in builds if b["axis"] == axis
                   and b["quantities"] == nq
                   and tuple(b["shape"]) == tuple(op["results"][0])]
            if not hit:
                ctx["say"](f"bytes moved: no halo.self_fill.bytes_dma for "
                           f"{scope} x{nq} {op['results'][0]}")
                return None
            moved += hit[-1]["bytes"]
            spent += op["dur"]
            axes[axis][0] += hit[-1]["bytes"]
            axes[axis][1] += op["dur"]
    if not spent:
        return None
    per = len(ctx["trace"]["chips"]) * ctx["window"]["iterations"]
    for axis, (b, ns) in sorted(axes.items()):
        ctx["say"](f"bytes moved: {axis} fill {b / per / 1e9:.4f} GB and "
                   f"{ns / per / 1e6:.4f} ms an exchange: "
                   f"{100 * b / bw / (ns / 1e9):.1f} % of the HBM rate")
    logical = sum(m.work(None, ctx["facts"])["bytes"]
                  for m in ctx["kernels"].get("halo", {}).values())
    ctx["say"](f"bytes moved: {moved / per / 1e9:.4f} GB an exchange by the "
               f"program's count, {logical / 1e9:.4f} GB logical: useful "
               f"share {100 * logical * per / moved:.1f} %")
    return 100.0 * (moved / bw) / (spent / 1e9)


# ------------------------------------------------------------ app_run

APP_RUN = {"host_init": (".realize", ".init"), "compile": (".warmup",),
           "steps": (".steps",)}


def app_run_seconds(ctx, part: str):
    """Seconds of the application's own top-level spans inside ``run()``:
    ``host_init`` (realize, host-side initial data and its transfer),
    ``compile`` (build, compile or cache load, the first call and the
    application's warm-up) or ``steps`` (its own timed chunks). ``None``
    without a TPU plane, and where the program keeps no spans."""
    if not ctx["trace"]["chips"]:
        return None
    if "app_run" not in ctx:
        ctx["app_run"] = None
        prog = program()
        spans = [] if prog is None else [
            r for r in prog[1].get().records(kind="span")
            if "t0_ns" in r and not r.get("parent")]
        if spans:
            got = {p: sum(r["seconds"] for r in spans
                          if r["name"].endswith(ends))
                   for p, ends in APP_RUN.items()}
            whole = ctx["phases"].get("app_run", 0.0)
            ctx["say"]("app_run: " + ", ".join(
                f"{r['name']} {r['seconds']:.3f}" for r in spans))
            ctx["say"](f"app_run: host_init {got['host_init']:.3f} + compile "
                       f"{got['compile']:.3f} + steps {got['steps']:.3f} s of "
                       f"the phase's {whole:.3f} s; no span covers "
                       f"{whole - sum(got.values()):.3f} s")
            ctx["app_run"] = got
    return None if ctx["app_run"] is None else ctx["app_run"][part]
