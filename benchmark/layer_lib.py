"""What the per-layer readers share: each reader under ``layer_metrics/``
is a few lines over these. A reader that finds nothing to read returns
``None`` and the harness leaves its metric out of the line.

``ctx`` is what the harness hands every reader: ``trace`` (the classified
trace of ``trace_reduce``), ``window`` (dispatches, iterations, seconds),
``facts`` (the realized layout), ``kernels`` (role -> {name: description}),
``peak`` (the device's row of ``peaks.json``) and ``say``.
"""

from __future__ import annotations

import statistics

from benchmark import trace_reduce as tr


def call_cells(shape, facts: dict) -> int:
    """The cells ONE CALL computes, from a result shape of its own recorded
    build: each dimension less what the block's allocation holds beyond
    its cells on that axis (halos and alignment pad, ``padded_zyx`` less
    ``block_zyx``). A call over a part of the block counts its part, so
    the calls of a split sweep add up to one block and never to more; a
    part that carries less padding than the whole block errs low."""
    cells = 1
    for n, padded, block in zip(shape[-3:], facts["padded_zyx"],
                                facts["block_zyx"]):
        cells *= max(int(n) - (padded - block), 0)
    return cells


def _chips(ctx):
    chips = ctx["trace"]["chips"]
    return chips if chips and ctx["window"]["iterations"] > 0 else None


def _per_iter_ms(ctx, ns_per_chip, worst: bool = False):
    pick = max if worst else statistics.mean
    return pick(ns_per_chip) / ctx["window"]["iterations"] / 1e6


def class_ms_per_iter(ctx, classes, worst: bool = False):
    """Device self time per iteration in ops of ``classes``; ``None`` when
    the trace holds no such op."""
    chips = _chips(ctx)
    if not chips:
        return None
    ns = [sum(tr.class_ns(c, cls) for cls in classes) for c in chips]
    if not any(ns):
        return None
    return _per_iter_ms(ctx, ns, worst)


def launch_gap_ms(ctx):
    chips = _chips(ctx)
    if not chips:
        return None
    gaps = tr.launch_gaps_ns(chips[0])
    return statistics.median(gaps) / 1e6 if gaps else None


def collective_exposed_ms(ctx):
    chips = _chips(ctx)
    if not chips or len(chips) < 2:
        return None
    return _per_iter_ms(ctx, [tr.exposed_collective_ns(c) for c in chips],
                        worst=True)


def idle_share(ctx):
    chips = _chips(ctx)
    if not chips:
        return None
    window_ns = ctx["window"]["seconds"] * 1e9
    return 100.0 * (1.0 - min(tr.busy_ns(c) for c in chips) / window_ns)


def roofline_share(ctx, role: str):
    """Share of the roofline of the configuration's kernels of one role:
    the least time the chip could take for what they must move or compute
    (the larger of bytes over peak bytes/s and operations over peak
    FLOP/s), over their device time. Counted per call from the recorded
    build, or per iteration where the kernel's description says so."""
    chips = _chips(ctx)
    if not chips:
        return None
    peak, facts = ctx["peak"], ctx["facts"]
    bw, fl = peak["hbm_bytes_per_s"], peak["flops_per_s_bf16"]
    least = spent = 0.0
    bound = {}
    for chip in chips:
        seen = {}
        for op in chip["ops"]:
            if op.get("cls") != role:
                continue
            spent += op["dur"] / 1e9
            w = ctx["kernels"][role][op["kernel"]].work(op["build"], facts)
            t_mem, t_op = w["bytes"] / bw, w["flops"] / fl
            bound[op["kernel"]] = ("memory" if t_mem >= t_op else "compute",
                                   w["note"])
            if w["per"] == "call":
                least += max(t_mem, t_op)
            else:
                seen[op["kernel"]] = max(t_mem, t_op)
        least += sum(seen.values()) * ctx["window"]["iterations"]
    if not spent:
        return None
    for name, (which, note) in sorted(bound.items()):
        ctx["say"](f"roofline {role}/{name}: bound by {which} under the "
                   f"table; {note}")
    return 100.0 * least / spent
