"""Static DMA-traffic accounting from compiled Mosaic kernels.

The outage-proof way to keep the performance story honest (VERDICT r4
item 6): instead of quoting roofline prose, lower the Pallas kernels for
the TPU platform (``jax.export`` runs the full Mosaic pipeline without
hardware), capture the TPU-dialect module each ``pallas_call`` dumps, and
read the ``tpu.enqueue_dma`` ops back — every DMA's direction, extent and
conditionality is statically visible. Tests then assert the per-grid-step
byte movement of the production kernels (the input-amplification and
1/k-traffic claims in BASELINE.md) the same way ``hlo_check.py`` pins the
overlap dataflow.

This is the analogue of the reference's Allreduced per-method byte
counters (reference: src/stencil.cu:139-161,620-627) — except derived
from the compiled artifact rather than incremented at runtime.
"""

from __future__ import annotations

import contextlib
import io
import re
from dataclasses import dataclass
from math import prod
from typing import Callable, List, Sequence, Tuple

_MARKER = "The Mosaic module for pallas_call kernel at "
# a kernel built with name= prints that name in the place of "kernel"
_MARKER_RE = re.compile(r"The Mosaic module for pallas_call \S+ at ")

# string literals must not contribute to region-brace counting (MLIR
# sym_name / location attributes may contain braces)
_STRLIT = re.compile(r'"(?:[^"\\]|\\.)*"')

_ITEMSIZE = {"f32": 4, "f64": 8, "i32": 4, "bf16": 2, "f16": 2, "i8": 1, "i64": 8}

_MEMREF = re.compile(
    r"memref<((?:\d+x)+)(\w+), #tpu\.memory_space<(\w+)>>"
)
_DMA = re.compile(
    r"tpu\.enqueue_dma\s+source\((.*?)\)\s+target\((.*?)\)\s+target_semaphore"
)
# older Mosaic prints the GENERIC MLIR form instead:
#   "tpu.enqueue_dma"(%a, %b, %sem) <{...}> : (memref<src>, memref<dst>, ...)
# operand order is the same (source, then target); types carry the spaces
_DMA_GENERIC = re.compile(r'"tpu\.enqueue_dma"\(.*?\).*?:\s*\((.*)\)')
_WAIT = re.compile(
    r"tpu\.wait_dma2\s+semaphore\(.*?\)\s+src\((.*?)\)\s+dst\((.*?)\)\s*$"
)
_BOUNDS = re.compile(r"iteration_bounds = array<i64: ([0-9, ]+)>")


@dataclass(frozen=True)
class DmaOp:
    """One ``tpu.enqueue_dma`` in a kernel body."""

    src_space: str  # 'any' == HBM operand, 'vmem'/'smem' == on-chip
    dst_space: str
    shape: Tuple[int, ...]
    itemsize: int
    if_depth: int  # enclosing scf.if regions; 0 = issued every grid step
    loop_depth: int  # enclosing scf.for/while regions (0 in these kernels)

    @property
    def nbytes(self) -> int:
        return prod(self.shape) * self.itemsize

    @property
    def is_input(self) -> bool:
        """HBM -> VMEM."""
        return self.src_space == "any" and self.dst_space != "any"

    @property
    def is_output(self) -> bool:
        """VMEM -> HBM."""
        return self.dst_space == "any" and self.src_space != "any"


@dataclass(frozen=True)
class DmaEvent:
    """One ``tpu.enqueue_dma`` ("start") or ``tpu.wait_dma2`` ("wait") in
    body order. ``branch``: the ordinals (in module order) of the
    ``scf.if`` ops that enclose it, outermost first; ``()`` runs every
    grid step."""

    kind: str
    op: DmaOp
    branch: Tuple[int, ...]


@dataclass
class KernelTraffic:
    """DMA inventory of one compiled Pallas kernel."""

    name: str  # "<basename>:<line>" of the pallas_call site
    grid: Tuple[int, ...]  # iteration_bounds
    dmas: List[DmaOp]
    events: Tuple[DmaEvent, ...] = ()  # the schedule: starts and waits in order
    vmem_bytes: int = 0  # the VMEM operands of @main: blocks and scratch

    def schedule(self, branches: Sequence[int]) -> List[DmaEvent]:
        """The events a grid step executes when exactly the ``scf.if`` ops
        with these ordinals are taken."""
        return [e for e in self.events if set(e.branch) <= set(branches)]

    @property
    def steps(self) -> int:
        return prod(self.grid) if self.grid else 1

    def input_bytes(self, unconditional_only: bool = False) -> int:
        """Sum of HBM->VMEM bytes enqueued in one kernel-body pass."""
        return sum(
            d.nbytes
            for d in self.dmas
            if d.is_input and (d.if_depth == 0 or not unconditional_only)
        )

    def output_bytes(self, unconditional_only: bool = False) -> int:
        return sum(
            d.nbytes
            for d in self.dmas
            if d.is_output and (d.if_depth == 0 or not unconditional_only)
        )

    def inputs(self) -> List[DmaOp]:
        return [d for d in self.dmas if d.is_input]

    def outputs(self) -> List[DmaOp]:
        return [d for d in self.dmas if d.is_output]

    def report(self) -> dict:
        """JSON-friendly summary (what scripts/export_traffic.py prints)."""
        return {
            "name": self.name,
            "grid": list(self.grid),
            "dmas": [
                {
                    "dir": "in" if d.is_input else ("out" if d.is_output else "local"),
                    "shape": list(d.shape),
                    "bytes": d.nbytes,
                    "if_depth": d.if_depth,
                    "loop_depth": d.loop_depth,
                }
                for d in self.dmas
            ],
        }


def _parse_ref(txt: str):
    m = _MEMREF.search(txt)
    if not m:
        return None
    dims = tuple(int(t) for t in m.group(1).split("x") if t)
    dtype = m.group(2)
    return dims, _ITEMSIZE.get(dtype, 4), m.group(3)


def _parse_module(name: str, lines: Sequence[str]) -> KernelTraffic:
    grid: Tuple[int, ...] = ()
    dmas: List[DmaOp] = []
    # region stack: 'if' (scf.if/else region) or 'op' (anything else).
    # Attribute dicts open and close braces on the same line, so only the
    # NET brace delta of a line changes the stack. Braces are counted on
    # the line with its string literals stripped — braces inside MLIR
    # string attrs (sym_name, location strings) would otherwise silently
    # skew the if/loop DMA attribution (ADVICE r5 #1).
    stack: List[str] = []
    ifs: List[int] = []  # ordinal of each 'if' frame of the stack
    n_ifs = 0
    events: List[DmaEvent] = []
    vmem_bytes = 0
    opened = False  # the module op's own region has been entered
    for ln in lines:
        if "func.func @main(" in ln:
            vmem_bytes = sum(
                prod(int(t) for t in dims.split("x") if t) * _ITEMSIZE.get(dt, 4)
                for dims, dt, space in _MEMREF.findall(ln.split(" attributes ")[0])
                if space == "vmem")
        b = _BOUNDS.search(ln)
        if b:
            grid = tuple(int(t) for t in b.group(1).replace(" ", "").split(","))
        if "tpu.enqueue_dma" in ln:
            m = _DMA.search(ln)
            if m:
                src = _parse_ref(m.group(1))
                dst = _parse_ref(m.group(2))
            else:
                # generic-form printer (older Mosaic): operand memrefs live
                # in the trailing type signature, source first, target next
                g = _DMA_GENERIC.search(ln)
                refs = _MEMREF.findall(g.group(1)) if g else []
                src = dst = None
                if len(refs) >= 2:
                    src, dst = (
                        (
                            tuple(int(t) for t in r[0].split("x") if t),
                            _ITEMSIZE.get(r[1], 4),
                            r[2],
                        )
                        for r in refs[:2]
                    )
            if src is None or dst is None:
                # an uncounted DMA would make the byte assertions pass
                # vacuously — fail loudly instead (e.g. a future Mosaic
                # printing strided/dynamic memref layouts)
                raise ValueError(f"unparseable enqueue_dma operands: {ln.strip()}")
            dmas.append(
                DmaOp(
                    src_space=src[2],
                    dst_space=dst[2],
                    shape=dst[0],
                    itemsize=dst[1],
                    if_depth=sum(1 for f in stack if f == "if"),
                    loop_depth=sum(1 for f in stack if f == "loop"),
                )
            )
            events.append(DmaEvent("start", dmas[-1], tuple(ifs)))
        w = _WAIT.search(ln)
        if w:
            src, dst = _parse_ref(w.group(1)), _parse_ref(w.group(2))
            if src is None or dst is None:
                raise ValueError(f"unparseable wait_dma2 operands: {ln.strip()}")
            events.append(DmaEvent("wait", DmaOp(
                src[2], dst[2], dst[0], dst[1], len(ifs), stack.count("loop")),
                tuple(ifs)))
        bare = _STRLIT.sub('""', ln)
        net = bare.count("{") - bare.count("}")
        if net > 0:
            if "scf.if" in bare or "} else {" in bare:
                kind = "if"
            elif "scf.for" in bare or "scf.while" in bare:
                kind = "loop"
            else:
                kind = "op"
            stack.extend([kind] * net)
            if kind == "if":
                if "scf.if" in bare:
                    n_ifs += 1
                ifs.extend([n_ifs - 1] * net)
            opened = True
        elif net < 0:
            if -net > len(stack):
                raise ValueError(
                    f"unbalanced region braces in Mosaic dump of {name}: "
                    f"{-net} closes against a {len(stack)}-deep stack"
                )
            closed = sum(1 for f in stack[net:] if f == "if")
            del stack[net:]
            del ifs[len(ifs) - closed:]
        # '} else {' with net == 0: the closed and opened regions are both
        # arms of the same scf.if — the stack is already correct.
        if opened and not stack:
            break  # top-level 'module {' closed; trailing text is not ours
    if not opened or stack:
        # a drifted stack would mis-attribute every subsequent DMA's
        # conditionality — refuse instead of returning skewed counts
        raise ValueError(
            f"Mosaic dump of {name} ended with an unbalanced region stack "
            f"(opened={opened}, depth={len(stack)})"
        )
    return KernelTraffic(name=name, grid=grid, dmas=dmas, events=tuple(events),
                         vmem_bytes=vmem_bytes)


def parse_mosaic_dumps(text: str) -> List[KernelTraffic]:
    """Split a captured debug stream into per-kernel traffic records."""
    out: List[KernelTraffic] = []
    chunks = _MARKER_RE.split(text)[1:]
    for chunk in chunks:
        lines = chunk.splitlines()
        # first line: "<path>:<line>:"
        loc = lines[0].rstrip(":")
        name = "/".join(loc.split("/")[-1:])
        # module body ends when the top-level 'module @kernel {' closes;
        # passing trailing text is harmless (no enqueue_dma outside).
        out.append(_parse_module(name, lines[1:]))
    return out


_capture_active = False


def capture_traffic(build: Callable[[], tuple]) -> List[KernelTraffic]:
    """Lower a Pallas-using function for the TPU platform and return the
    DMA inventory of every kernel it contains.

    ``build()`` must CONSTRUCT the kernels (pallas_call must run under the
    patch so the debug dump is enabled) and return ``(fn, args)``; the
    function is then jitted and exported for ``platforms=["tpu"]``.

    Process-global side effects: for the duration of build() + export this
    patches ``pl.pallas_call`` (forcing ``debug=True`` on every kernel
    constructed anywhere in the process) and redirects ALL of stdout into
    the capture buffer. Nested or concurrent use in one process would
    force debug onto foreign kernels and swallow their output, so reentry
    raises ``RuntimeError`` — run concurrent captures in subprocesses (the
    pattern scripts/export_traffic.py uses).
    """
    import jax
    from jax.experimental import pallas as pl

    global _capture_active
    if _capture_active:
        raise RuntimeError(
            "capture_traffic is not reentrant: it patches the process-global "
            "pl.pallas_call and redirects stdout; run concurrent captures in "
            "subprocesses"
        )
    _capture_active = True
    orig = pl.pallas_call

    def patched(*a, **k):
        k["debug"] = True
        return orig(*a, **k)

    buf = io.StringIO()
    pl.pallas_call = patched
    try:
        with contextlib.redirect_stdout(buf):
            fn, args = build()
            jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    finally:
        pl.pallas_call = orig
        _capture_active = False
    return parse_mosaic_dumps(buf.getvalue())
