"""The orchestration layer read from inside: what the application's own
chunk loop did in ``run()``, by the program's chunk spans, held against the
window's loop.

The window calls the compiled loop itself and waits with
``block_until_ready``; a user of the application runs the program's loop:
``utils/sync.timed_chunk`` (the compiled call, then ``hard_sync`` or a
scalar the loop reads), statistics and a span. That loop runs once a
benchmark run, inside ``run()`` and BEFORE the window, and every chunk of
it leaves a span under the run's ``*.steps`` span with ``t0_ns`` /
``t1_ns``, ``iters``, ``enqueue_s`` (until the compiled call returned),
``wait_s`` (from there until the wait returned), ``sync`` and ``module``.
A STEP chunk advances the state (``jacobi.iter``, ``exchange.iter``,
``lbm.step`` ...); an exchange-only chunk (``astaroth.exchange``,
``jacobi.exchange``) is something the loop does between two step chunks.

The four readers under ``layer_metrics/`` that use this file read
``over_window_ms`` and ``between_ms``; the table is printed once a run. An
older program (chunk spans without ``enqueue_s``, or no spans at all) and a
CPU rehearsal without a TPU plane give every reader ``None``, as
``scope_lib`` does.
"""

from __future__ import annotations

import statistics

from benchmark import scope_lib

STEPS = ".steps"
EXCHANGE_ONLY = ".exchange"


def _wall_s(rec: dict) -> float:
    return (rec["t1_ns"] - rec["t0_ns"]) / 1e9


def split(records: list):
    """``{"steps": the newest top-level ``*.steps`` span, "chunks": the
    chunk spans under it in time order, "step_chunks": those that advance
    the state, "between_s": host seconds from one step chunk's end to the
    next one's start, "head_s" / "tail_s": what of the steps span lies
    before the first chunk and after the last}`` of a list of span records;
    ``None`` where there is no such span, no chunk under it, or a chunk
    without ``enqueue_s`` (an older program)."""
    tops = [r for r in records if r["name"].endswith(STEPS)
            and not r.get("parent") and "t0_ns" in r]
    if not tops:
        return None
    steps = tops[-1]
    chunks = sorted((r for r in records
                     if r.get("parent") == steps["name"] and "iters" in r
                     and "t0_ns" in r
                     and steps["t0_ns"] <= r["t0_ns"] <= steps["t1_ns"]),
                    key=lambda r: r["t0_ns"])
    if not chunks or any("enqueue_s" not in r or "wait_s" not in r
                         for r in chunks):
        return None
    step_chunks = [r for r in chunks
                   if not r["name"].endswith(EXCHANGE_ONLY)]
    return {"steps": steps, "chunks": chunks, "step_chunks": step_chunks,
            "between_s": [(b["t0_ns"] - a["t1_ns"]) / 1e9
                          for a, b in zip(step_chunks, step_chunks[1:])],
            "head_s": (chunks[0]["t0_ns"] - steps["t0_ns"]) / 1e9,
            "tail_s": (steps["t1_ns"] - chunks[-1]["t1_ns"]) / 1e9}


def read(ctx):
    """:func:`split` of the program's records, once a run and kept in
    ``ctx``, with ``over_ms`` and ``between_ms`` (``None`` where they cannot
    be read); the table is printed then. ``None`` without a TPU plane, a
    program or its chunk spans."""
    if "chunks" in ctx:
        return ctx["chunks"]
    ctx["chunks"] = None
    prog = scope_lib.program()
    if not ctx["trace"]["chips"] or prog is None:
        return None
    out = split(prog[1].get().records(kind="span"))
    if out is None:
        ctx["say"]("chunks: the program left no chunk spans with enqueue_s "
                   "under a *.steps span; nothing to read")
        return None
    window = ctx["window"]
    k = window["iters_per_dispatch"]
    like = [_wall_s(r) for r in out["step_chunks"] if r["iters"] == k]
    out["over_ms"] = out["between_ms"] = None
    if like and window["dispatch_s"]:
        out["over_ms"] = 1e3 * (statistics.median(like)
                                - statistics.median(window["dispatch_s"]))
    if out["between_s"]:
        out["between_ms"] = 1e3 * statistics.median(out["between_s"])
    ctx["chunks"] = out
    _table(ctx, out)
    return out


def _table(ctx, out) -> None:
    say, window, steps = ctx["say"], ctx["window"], out["steps"]
    between = out["between_ms"]
    say(f"chunks: {steps['name']} {steps['seconds']:.4f} s holds "
        f"{len(out['chunks'])} chunk(s) of the program's own loop; before "
        f"the first {1e3 * out['head_s']:.3f} ms, after the last "
        f"{1e3 * out['tail_s']:.3f} ms, between two step chunks "
        + ("nothing to read (under two)" if between is None else
           f"{between:.3f} ms (median of {len(out['between_s'])})"))
    say(f"chunks: {'span':<22}{'count':>6}{'iters':>6}{'wall ms':>10}"
        f"{'enqueue ms':>12}{'wait ms':>10}  waits with (module)")
    groups = {}
    for r in out["chunks"]:
        groups.setdefault((r["name"], r["iters"], r.get("sync"),
                           r.get("module")), []).append(r)
    for (name, iters, how, module), rs in groups.items():
        say(f"chunks: {name:<22}{len(rs):>6}{iters:>6}"
            f"{1e3 * statistics.median(map(_wall_s, rs)):>10.3f}"
            f"{1e3 * statistics.median(r['enqueue_s'] for r in rs):>12.3f}"
            f"{1e3 * statistics.median(r['wait_s'] for r in rs):>10.3f}"
            f"  {how} ({module})")
    times, enqueue = window["dispatch_s"], window["enqueue_s"]
    if times:
        waits = [t - e for t, e in zip(times, enqueue)]
        say(f"chunks: {'the window, bench.*':<22}{len(times):>6}"
            f"{window['iters_per_dispatch']:>6}"
            f"{1e3 * statistics.median(times):>10.3f}"
            f"{1e3 * statistics.median(enqueue):>12.3f}"
            f"{1e3 * statistics.median(waits):>10.3f}  block_until_ready")
    if out["over_ms"] is not None:
        say(f"chunks: a step chunk of the program's loop costs "
            f"{out['over_ms']:+.3f} ms against the window's dispatch of the "
            f"same {window['iters_per_dispatch']} iteration(s)")


def over_window_ms(ctx):
    """Median wall of the program's step chunks of as many iterations as
    the window dispatches, less the window's median dispatch."""
    out = read(ctx)
    return None if out is None else out["over_ms"]


def between_ms(ctx):
    """Median host time from a step chunk's end to the next one's start
    (exchange-only chunks between them included); ``None`` under two."""
    out = read(ctx)
    return None if out is None else out["between_ms"]
