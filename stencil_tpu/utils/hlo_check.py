"""Machine check of the comm/compute-overlap dataflow structure.

The TPU scheduler can only run a ``collective-permute`` concurrently with
the interior compute if neither depends on the other — the property the
reference achieves with streams + CPU polling (reference:
src/stencil.cu:1002-1186, bin/jacobi3d.cu:296-368) and this framework
achieves by construction (the fast-path kernel reads pre-exchange data).
No hardware can *demonstrate* the overlap without a real multi-chip slice
(BASELINE.md config 5), but the enabling dataflow property is checkable on
any host: export the ≥2-device step for the TPU platform
(``jax.export``), parse the StableHLO SSA graph, and verify that no
``collective_permute`` transitively consumes the stencil kernel's output
and the kernel consumes no ``collective_permute`` result.

Used by tests/test_overlap_hlo.py (the machine gate) via the subprocess
runner scripts/export_overlap_hlo.py, which is also the standalone entry.
"""

from __future__ import annotations

import re
from typing import Dict, List, Set, Tuple

_ID_RE = re.compile(r"%[A-Za-z0-9_]+")


def _func_bodies(mlir_text: str) -> List[Tuple[str, List[str]]]:
    """(header line, body lines) of every ``func.func`` in the module."""
    lines = mlir_text.splitlines()
    out: List[Tuple[str, List[str]]] = []
    header = None
    body: List[str] = []
    depth = 0
    for ln in lines:
        if header is None:
            if re.search(r"func\.func .*@\w+", ln):
                header = ln
                body = []
                depth = ln.count("{") - ln.count("}")
            continue
        depth += ln.count("{") - ln.count("}")
        body.append(ln)
        if depth <= 0:
            out.append((header, body))
            header = None
    return out


def _main_body(mlir_text: str) -> List[str]:
    """Lines of the function body holding the step's dataflow.

    On a current jax the shard_map'd step inlines into ``@main`` (as an
    ``sdy.manual_computation`` region); older releases lower shard_map to
    a CALL of a private callee, leaving ``@main`` without the collectives.
    Analyze ``@main`` when it contains them, otherwise the function with
    the most ``collective_permute`` ops (SSA ids are function-local, so
    the graph must never mix functions)."""
    funcs = _func_bodies(mlir_text)
    main = next(
        (b for h, b in funcs if re.search(r"@main\b", h)), []
    )
    if any("collective_permute" in ln for ln in main):
        return main
    best = max(
        funcs,
        key=lambda hb: sum("collective_permute" in ln for ln in hb[1]),
        default=(None, main),
    )
    if sum("collective_permute" in ln for ln in best[1]):
        return best[1]
    return main


def build_graph(mlir_text: str) -> Dict[str, Tuple[str, List[str]]]:
    """SSA graph of @main (including regions nested in it, e.g. the
    ``sdy.manual_computation`` a shard_map lowers to): result id ->
    (op line, operand ids on that line).

    Parsing is per-line: every op this check cares about
    (collective_permute, the Mosaic custom_call, slices/updates) is a
    single-line op. Multi-result ops (``%a:2 = ...``) are keyed by their
    base id; uses ``%a#1`` are normalized to ``%a``. Block arguments of
    nested regions terminate closures (their binding to outer operands is
    not tracked), which can only MISS dependence edges through region
    boundaries — acceptable because the step under test is a single
    straight-line iteration (no fori_loop), asserted by the caller seeing
    the expected op counts.
    """
    graph: Dict[str, Tuple[str, List[str]]] = {}
    for ln in _main_body(mlir_text):
        m = re.match(r"^\s*(%[A-Za-z0-9_]+)(?::\d+)?\s*=\s*(.*)$", ln)
        if not m:
            continue
        res, rhs = m.group(1), m.group(2)
        operands = [t.split("#")[0] for t in _ID_RE.findall(rhs)]
        graph[res] = (rhs, [o for o in operands if o != res])
    return graph


def _closure(graph, seeds: List[str]) -> Set[str]:
    seen: Set[str] = set()
    stack = list(seeds)
    while stack:
        s = stack.pop()
        if s in seen or s not in graph:
            continue
        seen.add(s)
        stack.extend(graph[s][1])
    return seen


def overlap_report(mlir_text: str, kernel_marker: str = "tpu_custom_call") -> dict:
    """Analyze permute/kernel dataflow in an exported step.

    Returns ``n_permutes``, ``n_kernels``, and the two independence
    violations: ``permutes_consume_kernel`` (a collective transitively
    reads a kernel result — comm serialized behind compute) and
    ``kernels_consume_permutes`` (the kernel reads exchanged data — compute
    serialized behind comm)."""
    graph = build_graph(mlir_text)
    permutes = [k for k, (op, _) in graph.items() if "collective_permute" in op]
    kernels = [k for k, (op, _) in graph.items() if kernel_marker in op]
    perm_inputs = _closure(graph, [o for p in permutes for o in graph[p][1]])
    kernels_indep = [
        k
        for k in kernels
        if not _closure(graph, graph[k][1]).intersection(permutes)
    ]
    return {
        "n_permutes": len(permutes),
        "n_kernels": len(kernels),
        # a collective transitively reading a kernel result would serialize
        # comm behind compute
        "permutes_consume_kernel": bool(perm_inputs.intersection(kernels)),
        # kernels free to run concurrently with the permutes (for RK3 this
        # is substep 0; later substeps legitimately read exchanged data)
        "n_kernels_independent_of_permutes": len(kernels_indep),
    }


# -- collective census (bench_mpi_pack ablation accounting) ------------------

# HLO element sizes in bytes for the dtypes this framework traffics in
# (f8* are the fp8 wire-compression tier's carrier types).
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

COLLECTIVE_KINDS = (
    "collective-permute",
    "all-gather",
    "all-reduce",
    "all-to-all",
    "reduce-scatter",
    "collective-broadcast",
)

# `= RESULT_TYPE KIND(`: matches both sync ops and the `-start` half of
# async pairs (`-done` consumes no extra interconnect); group 1 is the
# result type text the payload is read from.
_COLLECTIVE_OP_RE = re.compile(
    r"=\s*([^=]*?)\b(" + "|".join(COLLECTIVE_KINDS) + r")(-start)?\("
)
# dtype token may carry interior digits (f8e4m3fn) — [a-z][a-z0-9]*
_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_PAIR_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")
_GROUPS_RE = re.compile(r"replica_groups=\{((?:\{[\d,]*\},?)*)\}")


def _tensor_bytes(dtype: str, dims: str) -> int:
    n = _DTYPE_BYTES.get(dtype, 0)
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def collective_census(hlo_text: str) -> Dict[str, Tuple[int, int]]:
    """``{op kind: (count, bytes)}`` over a compiled (post-SPMD-partitioning)
    HLO module — the per-method data-movement accounting of the
    bench_mpi_pack ablation (reference: bin/bench_mpi_pack.cu:18-80).

    Scans every computation in the module (while-loop bodies and called
    computations included — the callee-aware discipline of
    :func:`_main_body`), so shard_map-lowered hand-written ppermutes and
    partitioner-synthesized collectives are counted identically. Counts are
    STATIC op instances: an op inside a fori_loop body counts once, so
    census a single-exchange program, not a fused loop, when comparing
    strategies.

    Bytes are the interconnect payload per op instance, summed per kind.
    The per-shard payload is read from the op's RESULT type — compiled HLO
    text prints operands by name only (``collective-permute(%slice)``), and
    for every kind this framework emits the result buffer has the
    operand's shape. An async ``-start`` op returns a tuple whose first
    element is that buffer (the rest are aliases and sync flags), and
    ``-start``/``-done`` pairs count once, at the start op. For
    ``collective-permute`` the payload is multiplied by the number of
    ``source_target_pairs`` (each pair carries one payload across a link —
    the exact figure the ablation table wants); for the other kinds by the
    participant count in ``replica_groups`` (a first-order upper bound for
    ring/tree implementations)."""
    out: Dict[str, Tuple[int, int]] = {}
    for ln in hlo_text.splitlines():
        m = _COLLECTIVE_OP_RE.search(ln)
        if not m:
            continue
        kind = m.group(2)
        shapes = _SHAPE_RE.findall(m.group(1))
        if m.group(3):
            shapes = shapes[:1]
        payload = sum(_tensor_bytes(d, dims) for d, dims in shapes)
        pm = _PAIR_RE.search(ln)
        if kind == "collective-permute" and pm:
            fanout = pm.group(1).count("{")
        else:
            gm = _GROUPS_RE.search(ln)
            fanout = (
                sum(1 for t in re.split(r"[{},]", gm.group(1)) if t) if gm else 1
            )
        count, nbytes = out.get(kind, (0, 0))
        out[kind] = (count + 1, nbytes + payload * max(1, fanout))
    return out


def collective_permute_pairs(hlo_text: str):
    """Every ``collective-permute``'s ``source_target_pairs``, one
    frozenset of (src, tgt) logical-device pairs per op instance, in
    module order — the placement-conformance auditor's raw material
    (analysis/verify_plan): logical ids index the computation's device
    assignment, i.e. the mesh's device order, so mapping a pair through
    ``mesh.devices.flatten()`` yields the physical link it rides."""
    out = []
    for ln in hlo_text.splitlines():
        m = _COLLECTIVE_OP_RE.search(ln)
        if not m or m.group(2) != "collective-permute":
            continue
        pm = _PAIR_RE.search(ln)
        if not pm:
            out.append(frozenset())
            continue
        pairs = re.findall(r"\{(\d+),(\d+)\}", pm.group(1))
        out.append(frozenset((int(a), int(b)) for a, b in pairs))
    return out


_STABLEHLO_OP_RE = re.compile(
    r'"stablehlo\.(collective_permute|all_gather|all_reduce|all_to_all|'
    r'reduce_scatter|collective_broadcast)"'
)
_STABLEHLO_RESULT_RE = re.compile(r"->\s*tensor<([0-9x]+)x([a-zA-Z0-9]+)>")
_STABLEHLO_PAIRS_RE = re.compile(r"source_target_pairs\s*=[^:]*:\s*tensor<(\d+)x2xi64>")
_STABLEHLO_DTYPE_BYTES = {
    "i1": 1, "i8": 1, "ui8": 1, "f8E4M3FN": 1, "f8E5M2": 1,
    "i16": 2, "ui16": 2, "f16": 2, "bf16": 2,
    "i32": 4, "ui32": 4, "f32": 4, "i64": 8, "ui64": 8, "f64": 8,
}


def stablehlo_wire_census(mlir_text: str) -> Dict[str, Tuple[int, int]]:
    """``{op kind: (count, bytes)}`` over a LOWERED (pre-backend-
    optimization) StableHLO module — what the program *asks* the wire to
    carry, counted like :func:`collective_census` (per-shard payload ×
    source_target pairs for permutes).

    Why a second census exists: backend optimization passes may rewrite
    payload dtypes — the CPU backend's float-normalization widens a bf16
    ``collective_permute`` back to f32 (bf16 is not a native CPU type),
    so a compiled-HLO census on the 8-device CPU mesh cannot see the
    bf16-on-the-wire compression that a TPU (native bf16) actually
    ships. This census reads the module BEFORE those passes: the
    wire-dtype the exchange requested, exact for the hand-written
    permute methods whose collectives exist pre-partitioning."""
    out: Dict[str, Tuple[int, int]] = {}
    for ln in mlir_text.splitlines():
        m = _STABLEHLO_OP_RE.search(ln)
        if not m:
            continue
        kind = m.group(1).replace("_", "-")
        rm_ = _STABLEHLO_RESULT_RE.search(ln)
        payload = 0
        if rm_:
            dims, dtype = rm_.group(1), rm_.group(2)
            payload = _STABLEHLO_DTYPE_BYTES.get(dtype, 0)
            for d in dims.split("x"):
                payload *= int(d)
        pm = _STABLEHLO_PAIRS_RE.search(ln)
        fanout = int(pm.group(1)) if pm else 1
        count, nbytes = out.get(kind, (0, 0))
        out[kind] = (count + 1, nbytes + payload * max(1, fanout))
    return out


def census_per_quantity(census: Dict[str, Tuple[int, int]],
                        quantities: int) -> Dict[str, Tuple[int, int]]:
    """Attribute a quantity-batched census back to logical per-quantity
    bytes: ``{kind: (count, bytes // Q)}``.

    With quantity batching (parallel/exchange.py) one collective carries a
    packed ``(Q, ...)`` carrier of every same-dtype quantity's slab, so a
    raw census reports Q quantities' bytes on each op. Dividing by the
    quantity count restores the per-quantity figure the reference's
    Allreduced per-method byte counters speak (src/stencil.cu:139-161) —
    what one quantity's halos cost on the wire — while the COUNT column
    stays the batched truth (the whole point: Q-independent). For an
    unbatched program the two accountings coincide at Q = 1 and differ by
    exactly the op-count factor otherwise."""
    q = max(1, int(quantities))
    return {k: (c, b // q) for k, (c, b) in census.items()}


def assert_overlap_independent(mlir_text: str, expect_permutes: int = None) -> dict:
    """Raise AssertionError unless the permutes and the kernel are mutually
    independent (the overlap-enabling dataflow)."""
    rep = overlap_report(mlir_text)
    assert rep["n_kernels"] >= 1, f"no stencil kernel found: {rep}"
    assert rep["n_permutes"] >= 1, f"no collective_permute found: {rep}"
    if expect_permutes is not None:
        assert rep["n_permutes"] == expect_permutes, rep
    assert not rep["permutes_consume_kernel"], (
        f"collective_permute depends on a stencil kernel: {rep}"
    )
    assert rep["n_kernels_independent_of_permutes"] >= 1, (
        f"every stencil kernel depends on collective_permute results: {rep}"
    )
    return rep
