"""Set-up read from inside: what the application's ``run()`` spent tracing,
lowering and compiling (or loading from the persistent cache), by the
program's own records (``stencil_tpu.obs.telemetry``).

Since the PR that brought this file the program records every OUTERMOST
compile stage jax reports as a span whose ``parent`` is the span open when
it happened: ``compile.trace`` / ``compile.lower`` / ``compile.backend``
for a loop named from ``scopes.MODULES``, one ``compile.other`` per parent
and stage for everything else, and ``kernel.trace`` for a Pallas kernel
body traced inside. The four readers under ``layer_metrics/`` that use this
file sum them where an ancestor is a top-level span of ``run()``
(``*.realize`` / ``.init`` / ``.warmup`` / ``.steps``), and the table is
printed once a run. An older program, or a CPU rehearsal without a TPU
plane, gives every reader ``None``, as ``scope_lib`` does.
"""

from __future__ import annotations

from collections import defaultdict

from benchmark import scope_lib

STAGES = ("trace", "lower", "backend")
RUN_SPANS = (".realize", ".init", ".warmup", ".steps")
_STAGE_OF = {f"compile.{s}": s for s in STAGES}
INSIDE = "INSIDE THE MEASURED WINDOW"


def stage_of(rec: dict):
    """``trace`` / ``lower`` / ``backend`` for a stage record, folded or
    not; ``None`` for any other record."""
    if rec["name"] == "compile.other":
        return rec.get("stage")
    return _STAGE_OF.get(rec["name"])


def is_run_span(rec: dict) -> bool:
    return (not rec.get("parent") and "t0_ns" in rec
            and rec["name"].endswith(RUN_SPANS))


def top_ancestor(rec: dict, spans: list):
    """The top-level span a record lies under: its ``parent`` by name,
    among the spans that cover its start, and so on upwards; ``None`` for
    a record with no parent."""
    t, name = rec["t0_ns"], rec.get("parent")
    found = None
    for _ in range(16):
        if not name:
            return found
        over = [s for s in spans if s["name"] == name and "t0_ns" in s
                and s["t0_ns"] <= t <= s["t1_ns"]]
        if not over:
            # the parent's own record is gone (or never closed): its name
            # is all there is
            return {"name": name, "seconds": None, "t0_ns": None}
        found = min(over, key=lambda s: s["t1_ns"] - s["t0_ns"])
        name = found.get("parent")
    return found


def split(records: list) -> dict:
    """``{"stages": [(stage record, its top-level span or None)],
    "run": top-level spans of run() in time order, "seconds": {stage: s
    under run()}, "misses": count under run(), "kernels": the
    ``kernel.trace`` spans}`` of a list of span records."""
    spans = [r for r in records if r.get("kind", "span") == "span"]
    stages = [(r, top_ancestor(r, spans)) for r in spans
              if stage_of(r) in STAGES and "t0_ns" in r]
    seconds = dict.fromkeys(STAGES, 0.0)
    misses = 0
    for r, top in stages:
        if top is None or not top["name"].endswith(RUN_SPANS):
            continue
        seconds[stage_of(r)] += r["seconds"]
        if stage_of(r) == "backend":
            misses += (r.get("misses", 0) if r["name"] == "compile.other"
                       else r.get("cache") == "miss")
    run = sorted((r for r in spans if is_run_span(r)),
                 key=lambda r: r["t0_ns"])
    return {"stages": stages, "run": run, "seconds": seconds,
            "misses": int(misses),
            "kernels": [r for r in spans if r["name"] == "kernel.trace"]}


def read(ctx):
    """:func:`split` of the program's records, computed once a run and
    kept in ``ctx``; the table is printed then. ``None`` without a TPU
    plane, and for a program that records no compile stages."""
    if "compile_split" in ctx:
        return ctx["compile_split"]
    ctx["compile_split"] = None
    if not ctx["trace"]["chips"]:
        return None
    prog = scope_lib.program()
    if prog is None or not hasattr(prog[1], "flush_compile_stages"):
        return None
    telemetry = prog[1]
    try:
        telemetry.flush_compile_stages()
        out = split(telemetry.get().records(kind="span"))
        if not out["stages"]:
            return None
        table(out, ctx["window"], ctx["say"])
    except Exception as e:  # a reader reports, it never fails the run
        ctx["say"](f"compile: failed: {type(e).__name__}: {e}")
        return None
    ctx["compile_split"] = out
    return out


def stage_seconds(ctx, stage: str):
    out = read(ctx)
    return None if out is None else out["seconds"][stage]


def cache_misses(ctx):
    out = read(ctx)
    return None if out is None else out["misses"]


# ------------------------------------------------------------ the table


def _gb(n) -> str:
    return f"{n / 1e9:.3f} GB"


def _cache(rec: dict) -> str:
    if rec["name"] == "compile.other":
        return f"{rec.get('hits', 0)} hit, {rec.get('misses', 0)} miss"
    load = rec.get("retrieval_s")
    return rec.get("cache", "?") + (
        "" if load is None else f", read {load:.3f}")


def _kernels_in(recs, kernels) -> str:
    """The ``kernel.trace`` spans inside a program's own trace stages, by
    kernel: count and seconds."""
    by_kernel = defaultdict(lambda: [0, 0.0])
    for k in kernels:
        if any(r["t0_ns"] <= k["t0_ns"] <= r["t1_ns"] for r in recs):
            by_kernel[k["kernel"]][0] += 1
            by_kernel[k["kernel"]][1] += k["seconds"]
    if not by_kernel:
        return ""
    return " [of it kernel.trace " + ", ".join(
        f"{n} x{c} {s:.3f}" for n, (c, s) in sorted(by_kernel.items())) + "]"


def _say_rows(say, stages, kernels) -> None:
    """One line a direct parent and program: seconds by stage, the cache's
    verdict, the kernel bodies' share of the trace; a fold's line is
    followed by its dearest functions."""
    rows = defaultdict(lambda: defaultdict(list))
    for r, _ in stages:
        who = "(other)" if r["name"] == "compile.other" else r["module"]
        rows[(r.get("parent") or "(no span)", who)][stage_of(r)].append(r)
    for (parent, who), cells in sorted(
            rows.items(), key=lambda kv: -sum(
                r["seconds"] for recs in kv[1].values() for r in recs)):
        parts = []
        for stage in STAGES:
            recs = cells.get(stage)
            if not recs:
                continue
            n = sum(r.get("count", 1) for r in recs)
            part = f"{stage} {sum(r['seconds'] for r in recs):.3f} (x{n}"
            if stage == "backend":
                part += "; " + "; ".join(_cache(r) for r in recs)
            parts.append(part + ")")
            if stage == "trace" and who != "(other)":
                parts[-1] += _kernels_in(recs, kernels)
        say(f"compile:   {who} under {parent}: " + ", ".join(parts))
        for stage in STAGES:
            for rec in cells.get(stage, ()):
                funs = rec.get("funs")
                if not funs:
                    continue
                top = sorted(funs.items(), key=lambda kv: -kv[1])[:6]
                missed = rec.get("missed") or []
                say(f"compile:     {stage}: " + ", ".join(
                    f"{n} {sec:.3f}" for n, sec in top)
                    + (f"; missed: {', '.join(missed)}" if missed else ""))


def table(out: dict, window, say) -> None:
    """By top-level span of ``run()``: its seconds and memory, each
    program's stages under it, and what no stage covers; then the stages
    outside ``run()``, by where they lie against ``run()`` and (``window``
    given: the measured window of a traced run, ``t0_ns`` on the clock the
    program's spans carry and ``seconds``) against the window."""
    under = defaultdict(list)
    outside = []
    for r, top in out["stages"]:
        if top is not None and top["name"].endswith(RUN_SPANS):
            under[(top["name"], top["t0_ns"])].append((r, top))
        else:
            outside.append((r, top))
    say(f"compile: run() spent trace {out['seconds']['trace']:.3f} + lower "
        f"{out['seconds']['lower']:.3f} + backend "
        f"{out['seconds']['backend']:.3f} s in {len(out['stages'])} "
        f"outermost stage records, {out['misses']} cache miss(es)")
    for span in out["run"]:
        mine = under.pop((span["name"], span["t0_ns"]), [])
        mem = ""
        if "mem_bytes_in_use" in span:
            mem = (f"; device memory at its end {_gb(span['mem_bytes_in_use'])}"
                   f" in use, peak so far {_gb(span['mem_peak_bytes'])}")
        covered = sum(r["seconds"] for r, _ in mine)
        say(f"compile: {span['name']} {span['seconds']:.3f} s{mem}; stages "
            f"cover {covered:.3f}, no stage covers "
            f"{span['seconds'] - covered:.3f}"
            + (" (the first call's execution and its sync, the builder's "
               "own Python)" if span["name"].endswith(".warmup") else ""))
        _say_rows(say, mine, out["kernels"])
    for (name, _), mine in under.items():   # a parent whose record is gone
        say(f"compile: {name} (its own record is not kept):")
        _say_rows(say, mine, out["kernels"])
    if not outside:
        return
    t_first = min((s["t0_ns"] for s in out["run"]), default=None)
    t_last = max((s["t1_ns"] for s in out["run"]), default=None)
    opens = closes = None
    if window is not None and t_last is not None:
        opens = window["t0_ns"]
        closes = opens + int(window["seconds"] * 1e9)

    def where(r):
        if t_first is None:
            return "no run() span kept"
        if r["t0_ns"] < t_first:
            return "before run() (import)"
        if r["t0_ns"] < t_last:
            return "inside run(), under no span of it"
        if opens is None:
            return "after run()"
        if r["t1_ns"] <= opens:
            return "after run(), before the window (seed, first_chunk_check, warmup)"
        if r["t0_ns"] >= closes:
            return "after the window (checks, readers, op_map)"
        return INSIDE

    groups = defaultdict(list)
    for r, top in outside:
        groups[where(r)].append((r, top))
    for label, mine in groups.items():
        secs = {s: sum(r["seconds"] for r, _ in mine if stage_of(r) == s)
                for s in STAGES}
        say(f"compile: outside run(), {label}: " + ", ".join(
            f"{s} {secs[s]:.3f}" for s in STAGES))
        _say_rows(say, mine, out["kernels"])
    if opens is not None:
        say(f"compile: the measured window opened {(opens - t_last) / 1e9:.3f}"
            f" s after run() and lasted {window['seconds']:.3f} s: "
            + (f"{len(groups[INSIDE])} STAGE RECORD(S) LIE INSIDE THE WINDOW"
               if groups.get(INSIDE) else "no stage record lies inside it"))
