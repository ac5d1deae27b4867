"""The step the cell ``astaroth256x4.weak`` dispatches (what
``apps/astaroth.run(nx=256, dtype="float32")`` builds on the four chips of a
host: (1,2,2), tight-x blocks of 262 x 272 x 256, one iteration a dispatch),
compiled at its real size for a described ``v5e:2x2``, in both schedules
the fused path has. ``default`` is what the cell runs since PR 34
(``overlap=None`` resolves to exchange-first on the fused path): four
permutes of the 8-quantity carriers all done BEFORE substep 0's kernel,
then the three fused substep kernels with nothing between them but
``stencil.carry``, no shell. ``overlap`` is what ``overlap=True`` still
builds (the cell's step until PR 34): substep 0's kernel on pre-exchange
data, at least one permute in flight across it, the four shells
re-integrated in XLA. Both: no whole-block ``copy``, every fusion and
in-place update under a ``stencil.*`` scope, sixteen donated buffers.
Nothing runs; a compile that passes is not a chip result.

PRINTED, not asserted: how many permutes fly beside the kernel, how many of
XLA's async copies are of a whole block, and the temporaries.

The topology is described inside a module-scoped fixture (the
on-chip-measurement guide, section 2): only the worker that gets this file
loads libtpu.
"""

import os
import re
from collections import Counter

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

NX = 256
QUANTITIES = 8
_SUBSTEP = re.compile(r"astaroth_substep[.\d]*$")
# name, result shape (a tuple's in brackets) and the rest of one instruction
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(.*?\)|\S+)\s+"
                    r"[\w\-]+\((.*)$")
_OPERAND = re.compile(r"%([\w.\-]+)")


# the two schedules: what the builder is asked, and what it then records in
# ``astaroth.step_plan`` (mode, shells, shell cells, exchanges an iteration).
# ``default`` asks nothing, as ``run()`` on TPU devices does
SCHEDULES = {"default": ({}, ("serial", 0, 0, 1)),
             "overlap": ({"overlap": True}, ("overlap", 4, 777_216, 1))}


@pytest.fixture(scope="module", params=sorted(SCHEDULES))
def step(request, topo, as_on_the_chip):
    """The compiled step: its ENTRY computation in schedule order (one
    parsed instruction a line, ``shape`` beside it), the memory analysis,
    the padded block, the step plan the build recorded and the schedule's
    name."""
    asked, _ = SCHEDULES[request.param]
    from stencil_tpu.apps.astaroth import DEFAULT_CONF
    from stencil_tpu.astaroth.config import load_config
    from stencil_tpu.astaroth.integrate import make_astaroth_step
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3, Radius, decompose_zy
    from stencil_tpu.obs import scopes, telemetry
    from stencil_tpu.parallel import HaloExchange, grid_mesh

    info, _ = load_config(DEFAULT_CONF)
    assert info.int_params["AC_nx"] == NX     # astaroth.conf:8-10 as it is
    dim = decompose_zy(4)
    assert (dim.x, dim.y, dim.z) == (1, 2, 2)
    spec = GridSpec(Dim3(NX * dim.x, NX * dim.y, NX * dim.z), dim,
                    Radius.constant(3).without_x())
    ex = HaloExchange(spec, grid_mesh(dim, list(topo.devices)[:4]))
    scopes.clear()
    # as run() calls it on TPU devices: nothing pinned but dt and the dtype
    make_astaroth_step(ex, info, dt=1e-8, dtype="float32", iters=1, **asked)
    rec = scopes._registry[scopes.ASTAROTH_ITER][-1]
    compiled = rec["fn"].lower(*rec["args"]).compile()
    text = compiled.as_text()
    omap = scopes.parse_hlo_text(text)      # scopes as the benchmark reads them
    lines = []
    for line in text[text.index("\nENTRY "):].splitlines()[1:]:
        m = _INSTR.match(line)
        if m and m.group(1) in omap:
            ins = omap[m.group(1)]
            lines.append({"instr": m.group(1), "opcode": ins["opcode"],
                          "scope": ins["scope"], "shape": m.group(2),
                          "operands": _OPERAND.findall(m.group(3))})
    plan = telemetry.get().records(kind="counter",
                                   name="astaroth.step_plan")[-1]
    return {"entry": lines, "mem": compiled.memory_analysis(),
            "padded": spec.padded(), "plan": plan,
            "schedule": request.param}


def _kernels(entry):
    return [i for i, ins in enumerate(entry)
            if ins["opcode"] == "custom-call" and _SUBSTEP.match(ins["instr"])]


def _permutes(entry):
    """[(index of the start, index of its done, the carrier's shape)]."""
    where = {ins["instr"]: i for i, ins in enumerate(entry)}
    return [(where[ins["operands"][0]], i, entry[where[ins["operands"][0]]]
             ["shape"]) for i, ins in enumerate(entry)
            if ins["opcode"] == "collective-permute-done"]


def test_three_fused_substeps_and_four_permutes_of_the_batched_carriers(step):
    entry, p = step["entry"], step["padded"]
    assert (p.z, p.y, p.x) == (262, 272, 256)
    assert len(_kernels(entry)) == 3
    for i in _kernels(entry):
        assert entry[i]["scope"] == "stencil.kernel.astaroth_substep"
    permutes = _permutes(entry)
    assert len(permutes) == 4
    # one carrier a direction holds all 8 quantities: y slabs, then z slabs
    y = f"f32[{QUANTITIES},1,1,1,{p.z},3,{p.x}]"
    z = f"f32[{QUANTITIES},1,1,1,3,{p.y},{p.x}]"
    firsts = [shape.lstrip("(").split("{")[0] for _, _, shape in permutes]
    assert sorted(firsts) == sorted([y, y, z, z]), firsts
    for _, done, _ in permutes:
        assert entry[done]["scope"] == "stencil.halo.wire"
    # the plan counts the same bytes: 26.2 MB a chip an exchange
    assert step["plan"]["halo_bytes_sent"] == 4 * QUANTITIES * 2 * 3 * (
        p.z + p.y) * p.x
    plan = step["plan"]
    assert (plan["mode"], plan["shells"], plan["shell_cells"],
            plan["exchanges_per_iter"]) == SCHEDULES[step["schedule"]][1]


def test_where_the_exchange_lies_against_substep_0(step):
    """``default``: exchange-first. All four permutes are done before
    substep 0's kernel starts, the three kernels follow with nothing
    between them but ``stencil.carry``, and no op anywhere is a shell's.
    ``overlap``: at least one permute starts before substep 0's kernel and
    is done after it (how many: printed), and the shells are there."""
    entry = step["entry"]
    kernels = _kernels(entry)
    k0, k2 = kernels[0], kernels[-1]
    permutes = _permutes(entry)
    beside = [(s, d) for s, d, _ in permutes if s < k0 < d]
    print(f"permutes in flight across substep 0's kernel: {len(beside)} of "
          f"{len(permutes)}; instructions in the entry: {len(entry)}")
    shells = [ins["instr"] for ins in entry
              if ins["scope"] == "stencil.sweep.shell"]
    if step["schedule"] == "overlap":
        assert beside
        assert len(shells) > 100
        return
    assert all(d < k0 for _, d, _ in permutes), (permutes, k0)
    assert not shells, shells
    between = [ins for i, ins in enumerate(entry[k0:k2], k0)
               if i not in kernels]
    kinds = Counter((ins["opcode"], ins["scope"]) for ins in between)
    print(f"instructions between the three kernels: {len(between)}: "
          f"{dict(kinds)}")
    # a kernel's eight results come off its tuple and are re-viewed: no
    # device time; anything that takes some is the carry's
    other = [(ins["instr"], ins["opcode"], ins["scope"]) for ins in between
             if ins["scope"] != "stencil.carry"
             and ins["opcode"] not in ("get-tuple-element", "bitcast")]
    assert not other, other


def test_no_synchronous_copy_of_a_whole_block(step):
    entry, p = step["entry"], step["padded"]
    block = f"{p.z},{p.y},{p.x}]"
    whole = [ins["instr"] for ins in entry
             if ins["opcode"] == "copy" and block in ins["shape"]]
    assert not whole, f"whole-block copies: {whole}"
    starts = [ins for ins in entry if ins["opcode"] == "copy-start"]
    print(f"async copies: {len(starts)}, of a whole block: "
          f"{sum(block in ins['shape'] for ins in starts)}")


def test_every_fusion_and_update_carries_a_scope(step):
    """Every fusion and in-place update of the step is the program's own:
    pack, wire, unpack, carry and, where asked for, shells: with the
    shells, the hundreds between substep 0's kernel and substep 1's;
    exchange-first, the few dozen before substep 0's. (The async copies XLA
    adds to move operands between memory spaces carry none and read as
    ``glue_compiler_ms_per_iter``.)"""
    entry = step["entry"]
    k0, k1, _ = _kernels(entry)
    shells = step["schedule"] == "overlap"
    glue = [ins for ins in (entry[k0 + 1:k1] if shells else entry[:k0])
            if ins["opcode"] in ("fusion", "dynamic-update-slice")]
    bare = [ins["instr"] for ins in glue if not ins["scope"]]
    assert not bare, f"no stencil.* scope on: {bare}"
    by_scope = Counter(ins["scope"] for ins in glue)
    print(f"fusions and updates {'between' if shells else 'before'} the "
          f"kernels, by scope: {dict(by_scope)}")
    halo = {"stencil.halo.pack", "stencil.halo.unpack", "stencil.halo.wire",
            "stencil.carry"}
    if shells:
        assert len(glue) > 100
        assert by_scope["stencil.sweep.shell"] > 100
        assert set(by_scope) <= halo | {"stencil.sweep.shell"}
    else:
        assert 0 < len(glue) < 100
        assert set(by_scope) <= halo
        assert not [ins["instr"] for ins in entry[k0:]
                    if ins["opcode"] in ("fusion", "dynamic-update-slice")
                    and ins["scope"] != "stencil.carry"]


def test_sixteen_buffers_all_aliased(step):
    mem, p = step["mem"], step["padded"]
    buffer = 4 * p.z * p.y * p.x
    assert buffer == 72_974_336
    assert mem.argument_size_in_bytes == 2 * QUANTITIES * buffer
    assert mem.alias_size_in_bytes == 2 * QUANTITIES * buffer
    print(f"temp_size_in_bytes {mem.temp_size_in_bytes} "
          f"({mem.temp_size_in_bytes / buffer:.2f} field buffers)")
    # the program fits a chip's 16 GB with room: 1.17 GB of arguments
    assert mem.temp_size_in_bytes < 8 * buffer
