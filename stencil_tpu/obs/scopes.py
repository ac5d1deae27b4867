"""The program's names for its own device work.

Three things live here, all metadata: they change no compiled arithmetic
and cost nothing on the call path.

- The VOCABULARY: every ``jax.named_scope`` literal the package opens
  (:data:`SCOPES`), every Pallas kernel name (:data:`KERNELS`) and every
  jitted chunk loop's module name (:data:`MODULES`), each scope and kernel
  mapped to the ``PERF.md`` layer it belongs to. A profiler trace then says
  which module of ``stencil_tpu`` asked for an operation, or that the
  program asked for none of it (no ``stencil.*`` scope: the compiler's).
- :func:`kernel_call`: the one ``pl.pallas_call`` site of the package. A
  kernel gets its ``name=`` and every invocation is traced under
  ``stencil.kernel.<name>``, whatever its shapes.
- The REGISTRY: a loop builder hands :func:`jit_loop` the abstract
  arguments it built for (shape, dtype, sharding: known at build time), and
  :func:`op_map` can later lower and compile that very loop again (a
  persistent-cache hit) and read, per optimized-HLO instruction name, the
  scope and layer. Nothing is lowered unless a reader asks, and only after
  its measurement is over.

Nothing here imports jax at module level (the ``obs`` package contract).
"""

from __future__ import annotations

import contextlib
import re
import time
from typing import Dict, List, Optional

LAYER_HALO = "Halo exchange"
LAYER_KERNELS = "Stencil kernels"
LAYER_GLUE = "XLA glue"

PREFIX = "stencil."
KERNEL_PREFIX = "stencil.kernel."

HALO_SELF_FILL = "stencil.halo.self_fill"   # in-place periodic fills
HALO_PACK = "stencil.halo.pack"             # boundary slabs -> carrier
HALO_WIRE = "stencil.halo.wire"             # the permute, narrow/widen
HALO_UNPACK = "stencil.halo.unpack"         # carrier -> halo cells
SWEEP_SHELL = "stencil.sweep.shell"         # exterior slabs, dynamic shells
MASK = "stencil.mask"                       # the sel == 1 / sel == 2 masks
CARRY = "stencil.carry"                     # reshapes, swaps, stacking
# a multigrid level's tag, ``stencil.mg.level<k>`` (k = 1 the coarsest):
# OUTSIDE the kernel, halo and glue scopes of what runs on the level, and of
# no layer itself, so an op keeps the layer of its innermost scope
MG_LEVEL = "stencil.mg.level"
# a Krylov solver's reductions (dot products, norms: each a device scalar
# that the next kernel of the same program reads) and its vector updates
SOLVER_DOT = "stencil.solver.dot"
SOLVER_AXPY = "stencil.solver.axpy"

SCOPES: Dict[str, str] = {
    HALO_SELF_FILL: LAYER_HALO,
    HALO_PACK: LAYER_HALO,
    HALO_WIRE: LAYER_HALO,
    HALO_UNPACK: LAYER_HALO,
    SWEEP_SHELL: LAYER_GLUE,
    MASK: LAYER_GLUE,
    CARRY: LAYER_GLUE,
    SOLVER_DOT: LAYER_GLUE,
    SOLVER_AXPY: LAYER_GLUE,
}

# pallas_call name -> layer. The self-fills and the split-x pack and unpack
# are the halo layer's kernels.
KERNELS: Dict[str, str] = {
    "jacobi_sweep": LAYER_KERNELS,
    "jacobi_multistep": LAYER_KERNELS,
    "jacobi_multistep_rows": LAYER_KERNELS,
    "astaroth_substep": LAYER_KERNELS,
    "iso3dfd_step": LAYER_KERNELS,
    # MG's four operators, Pallas or plain XLA (:func:`kernel_scope`)
    "mg_resid": LAYER_KERNELS,
    "mg_psinv": LAYER_KERNELS,
    "mg_rprj3": LAYER_KERNELS,
    "mg_interp": LAYER_KERNELS,
    # the V-cycle's coarse half in one call: every level below the coarsest
    # tight-x level, its 21 operators, their fills and both transfers
    "mg_coarse": LAYER_KERNELS,
    # D3Q19's stream-collide pass, Pallas or plain XLA (:func:`kernel_scope`)
    "lbm_d3q19": LAYER_KERNELS,
    # HPCG: half an eight-colour Gauss-Seidel sweep (the planes of one z
    # parity), the operator alone and in the residual (the MG box builder
    # at HPCG's weights), injection and its transpose; Pallas or plain XLA
    "hpcg_symgs": LAYER_KERNELS,
    "hpcg_spmv": LAYER_KERNELS,
    "hpcg_resid": LAYER_KERNELS,
    "hpcg_restrict": LAYER_KERNELS,
    "hpcg_prolong": LAYER_KERNELS,
    "self_fill_x": LAYER_HALO,
    "self_fill_y": LAYER_HALO,
    "self_fill_z": LAYER_HALO,
    "split_x_pack": LAYER_HALO,
    "split_x_unpack": LAYER_HALO,
}

# module names of the jitted chunk loops (the trace's ``XLA Modules`` line
# reads ``jit_<name>(<fingerprint>)``)
JACOBI_LOOP = "stencil_jacobi_loop"
JACOBI_STEP = "stencil_jacobi_step"
ASTAROTH_ITER = "stencil_astaroth_iter"
EXCHANGE_LOOP = "stencil_exchange_loop"
ISO3DFD_LOOP = "stencil_iso3dfd_loop"
MG_ITER = "stencil_mg_iter"
LBM_STEP = "stencil_lbm_step"
HPCG_ITER = "stencil_hpcg_iter"
MODULES = (JACOBI_LOOP, JACOBI_STEP, ASTAROTH_ITER, EXCHANGE_LOOP,
           ISO3DFD_LOOP, MG_ITER, LBM_STEP, HPCG_ITER)


def layer_of(scope: Optional[str]) -> Optional[str]:
    """The layer of a ``stencil.*`` scope name, ``None`` for none."""
    if scope is None:
        return None
    if scope.startswith(KERNEL_PREFIX):
        return KERNELS.get(scope[len(KERNEL_PREFIX):])
    return SCOPES.get(scope)


def scope(name: str):
    """``jax.named_scope(name)`` for a name of the vocabulary."""
    if name not in SCOPES:
        raise KeyError(f"{name!r} is not in the scope vocabulary")
    import jax

    return jax.named_scope(name)


def kernel_scope(name: str):
    """``jax.named_scope("stencil.kernel.<name>")`` for an operator of the
    kernel vocabulary that plain XLA computes: its ops carry the name a
    Pallas build of it would."""
    if name not in KERNELS:
        raise KeyError(f"{name!r} is not in the kernel vocabulary")
    import jax

    return jax.named_scope(KERNEL_PREFIX + name)


def level_scope(level: int):
    """``jax.named_scope("stencil.mg.level<level>")``: the tag of a
    multigrid level, opened outside everything that runs on it."""
    import jax

    return jax.named_scope(f"{MG_LEVEL}{int(level)}")


def level_of(op_name: str) -> Optional[int]:
    """The multigrid level an ``op_name`` path is tagged with, ``None``
    for none."""
    for part in scopes_in(op_name):
        if part.startswith(MG_LEVEL) and part[len(MG_LEVEL):].isdigit():
            return int(part[len(MG_LEVEL):])
    return None


def kernel_call(name: str, kernel, **kwargs):
    """``pl.pallas_call(kernel, name=name, **kwargs)`` whose every call is
    traced under ``stencil.kernel.<name>``. ``call`` runs only while jax
    traces the caller (never on a compiled loop's call path), and
    ``fn(*args)`` is where ``pallas_call`` traces the kernel's body: each
    invocation leaves one ``kernel.trace`` span, a child of the span open
    then."""
    if name not in KERNELS:
        raise KeyError(f"{name!r} is not in the kernel vocabulary")
    import jax
    from jax.experimental import pallas as pl

    from . import telemetry

    fn = pl.pallas_call(kernel, name=name, **kwargs)
    scope_name = KERNEL_PREFIX + name

    def call(*args):
        t0_ns, t0 = time.time_ns(), time.perf_counter()
        try:
            with jax.named_scope(scope_name):
                return fn(*args)
        finally:
            telemetry.get().child_span(
                "kernel.trace", t0_ns, time.perf_counter() - t0,
                phase="compile", kernel=name)

    return call


# ------------------------------------------------------------ registry

_registry: Dict[str, List[dict]] = {}
_MAX_PER_MODULE = 8


def abstract(tree, sharding=None):
    """``jax.ShapeDtypeStruct`` leaves for a pytree of arrays (or of
    structs); ``sharding`` overrides the leaves' own."""
    import jax

    def leaf(a):
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=sharding if sharding is not None
            else getattr(a, "sharding", None))

    return jax.tree.map(leaf, tree)


def jit_loop(module: str, fn, args=None, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)`` under the stable module name
    ``module``, registered with the abstract ``args`` it was built for
    (``None``: named, not registered). Returns the jitted object itself:
    this function puts no wrapper on the call path (a ping-pong builder
    hands the jitted object to ``ops/double_buffer.InPlaceLoop``, which
    swaps two handles after the call)."""
    if module not in MODULES:
        raise KeyError(f"{module!r} is not a module name of the vocabulary")
    import jax

    fn.__name__ = fn.__qualname__ = module
    jitted = jax.jit(fn, **jit_kwargs)
    if args is not None:
        entries = _registry.setdefault(module, [])
        entries.append({"fn": jitted, "args": tuple(args)})
        del entries[:-_MAX_PER_MODULE]
    return jitted


def registered(module: str) -> int:
    """How many loops are registered under ``module``."""
    return len(_registry.get(module, ()))


def clear() -> None:
    _registry.clear()


@contextlib.contextmanager
def _compile_cache_off():
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def hlo_text(module: str, entry: int = -1) -> str:
    """The optimized HLO text of a registered loop. An executable loaded
    from the persistent cache may come without its text: that one loop is
    then compiled once more with the cache off."""
    rec = _registry[module][entry]
    text = rec.get("text")
    if text is None:
        lowered = rec["fn"].lower(*rec["args"])
        text = lowered.compile().as_text() or ""
        if "HloModule" not in text:
            with _compile_cache_off():
                text = lowered.compile().as_text() or ""
        rec["text"] = text
    return text


def op_map(module: str, entry: int = -1) -> Dict[str, dict]:
    """Per optimized-HLO instruction name of a registered loop: ``opcode``,
    ``op_name`` (the metadata path), ``scope`` (the innermost ``stencil.*``
    name or ``None``), ``layer``, ``layers`` (every layer on the path: more
    than one is a vocabulary fault) and ``source`` (``file:line`` where the
    metadata has it). ``copy`` instructions also carry ``producer`` and
    ``consumers`` (each ``{"instr", "opcode", "scope", "operand"}``:
    ``operand`` is the position the value takes in a consumer; both are
    looked up through bitcasts and tuple plumbing)."""
    rec = _registry[module][entry]
    if "op_map" not in rec:
        t0 = time.perf_counter()
        rec["op_map"] = parse_hlo_text(hlo_text(module, entry))
        rec["seconds"] = time.perf_counter() - t0
    return rec["op_map"]


def op_map_seconds(module: str, entry: int = -1) -> Optional[float]:
    """What the last :func:`op_map` of this loop cost (lower, compile or
    cache load, parse)."""
    return _registry[module][entry].get("seconds")


# ------------------------------------------------------------ HLO text

_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SOURCE = re.compile(r'source_file="([^"]*)"(?:\s+source_line=(\d+))?')
_OPERAND = re.compile(r"%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
# what a value passes through unchanged on its way to the op that uses it
_TRANSPARENT = {"bitcast", "get-tuple-element", "copy-start", "copy-done",
                "optimization-barrier"}


def _closing(text: str, start: int) -> int:
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def scopes_in(op_name: str) -> List[str]:
    """The ``stencil.*`` components of an ``op_name`` path, outermost
    first."""
    return [p for p in op_name.split("/") if p.startswith(PREFIX)]


def _parse_line(line: str):
    m = _INSTR.match(line)
    if not m or " = " not in line:
        return None
    rest = m.group(3).lstrip()
    end = _closing(rest, 0) if rest.startswith("(") else rest.find(" ")
    if end <= 0:
        return None
    call = rest[end:].lstrip()
    par = call.find("(")
    if par <= 0:
        return None
    close = _closing(call, par)
    attrs = call[close:]
    name = _OP_NAME.search(attrs)
    op_name = name.group(1) if name else ""
    src = _SOURCE.search(attrs)
    path = scopes_in(op_name)
    layers = sorted({lay for lay in map(layer_of, path) if lay})
    scope_name = path[-1] if path else None
    return {
        "instr": m.group(2), "root": bool(m.group(1)),
        "opcode": call[:par].strip(),
        "operands": _OPERAND.findall(call[par:close]),
        "op_name": op_name, "scope": scope_name,
        "layer": layer_of(scope_name), "layers": layers,
        "source": (f"{src.group(1)}:{src.group(2)}" if src and src.group(2)
                   else src.group(1) if src else None),
    }


def _brief(ins: dict, operand=None) -> dict:
    return {"instr": ins["instr"], "opcode": ins["opcode"],
            "scope": ins["scope"], "operand": operand}


def parse_hlo_text(text: str) -> Dict[str, dict]:
    """:func:`op_map` of one module's text (instruction names are unique
    across a module's computations)."""
    instrs: Dict[str, dict] = {}
    users: Dict[str, list] = {}
    held: Dict[str, set] = {}       # computation -> scopes of its instructions
    calls: Dict[str, str] = {}      # fusion -> the computation it calls
    comp = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        ins = _parse_line(line)
        if ins is None:
            continue
        instrs[ins["instr"]] = ins
        if ins["scope"]:
            held.setdefault(comp, set()).add(ins["scope"])
        called = _CALLS.search(line) if ins["opcode"] == "fusion" else None
        if called:
            calls[ins["instr"]] = called.group(1)
        for pos, operand in enumerate(ins["operands"]):
            users.setdefault(operand, []).append((ins["instr"], pos))
    # a fusion the compiler rebuilt without metadata (a concatenate turned
    # into in-place updates of a fresh buffer) is the program's where every
    # scoped instruction it fused carries one and the same scope
    for name, called in calls.items():
        inner = held.get(called, ())
        if not instrs[name]["scope"] and len(inner) == 1:
            (scope_name,) = inner
            layer = layer_of(scope_name)
            instrs[name].update(scope=scope_name, layer=layer,
                                layers=[layer] if layer else [])

    def producer(ins):
        seen = set()
        while ins["operands"] and ins["instr"] not in seen:
            seen.add(ins["instr"])
            src = instrs.get(ins["operands"][0])
            if src is None:
                return None
            if src["opcode"] not in _TRANSPARENT:
                return _brief(src)
            ins = src
        return None

    def consumers(name, seen):
        out = []
        for user, pos in users.get(name, ()):
            if user in seen:
                continue
            seen.add(user)
            ins = instrs[user]
            if ins["opcode"] in _TRANSPARENT:
                out.extend(consumers(user, seen))
            else:
                out.append(_brief(ins, pos))
        return out

    out = {}
    for name, ins in instrs.items():
        rec = {k: ins[k] for k in ("opcode", "op_name", "scope", "layer",
                                   "layers", "source")}
        if ins["opcode"] in ("copy", "copy-start"):
            rec["producer"] = producer(ins)
            rec["consumers"] = consumers(name, {name})
        out[name] = rec
    return out
