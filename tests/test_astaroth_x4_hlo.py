"""The step the cell ``astaroth256x4.weak`` dispatches (what
``apps/astaroth.run(nx=256, dtype="float32")`` builds on the four chips of a
host: (1,2,2), tight-x blocks of 262 x 272 x 256, one iteration a dispatch),
compiled at its real size for a described ``v5e:2x2``: three fused substep
kernels, four permutes of the 8-quantity carriers, at least one of them in
flight across substep 0's kernel, no whole-block ``copy``, every fusion and
in-place update between the kernels under a ``stencil.*`` scope, sixteen
donated buffers. Nothing runs; a compile that passes is not a chip result.

What the next issue is to move is PRINTED, not asserted: how many permutes
fly beside the kernel, how many of XLA's async copies are of a whole block,
and the temporaries.

The topology is described inside a module-scoped fixture (the
on-chip-measurement guide, section 2): only the worker that gets this file
loads libtpu.
"""

import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

NX = 256
QUANTITIES = 8
_SUBSTEP = re.compile(r"astaroth_substep[.\d]*$")
# name, result shape (a tuple's in brackets) and the rest of one instruction
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(.*?\)|\S+)\s+"
                    r"[\w\-]+\((.*)$")
_OPERAND = re.compile(r"%([\w.\-]+)")


@pytest.fixture(scope="module")
def step(topo, as_on_the_chip):
    """The compiled step: its ENTRY computation in schedule order (one
    parsed instruction a line, ``shape`` beside it), the memory analysis,
    the padded block and the step plan the build recorded."""
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
    make_astaroth_step(ex, info, dt=1e-8, dtype="float32", iters=1)
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
            "padded": spec.padded(), "plan": plan}


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
    assert (step["plan"]["mode"], step["plan"]["shells"],
            step["plan"]["exchanges_per_iter"]) == ("overlap", 4, 1)


def test_the_exchange_overlaps_substep_0_as_dataflow(step):
    """At least one permute starts before substep 0's kernel and is done
    after it. How many do is the next issue's to move: printed."""
    entry = step["entry"]
    first = _kernels(entry)[0]
    beside = [(s, d) for s, d, _ in _permutes(entry) if s < first < d]
    print(f"permutes in flight across substep 0's kernel: {len(beside)} of "
          f"{len(_permutes(entry))}")
    assert beside


def test_no_synchronous_copy_of_a_whole_block(step):
    entry, p = step["entry"], step["padded"]
    block = f"{p.z},{p.y},{p.x}]"
    whole = [ins["instr"] for ins in entry
             if ins["opcode"] == "copy" and block in ins["shape"]]
    assert not whole, f"whole-block copies: {whole}"
    starts = [ins for ins in entry if ins["opcode"] == "copy-start"]
    print(f"async copies: {len(starts)}, of a whole block: "
          f"{sum(block in ins['shape'] for ins in starts)}")


def test_everything_between_the_kernels_carries_a_scope(step):
    """Every fusion and in-place update between substep 0's kernel and
    substep 1's is the program's own: shells, pack, wire, unpack, carry.
    (The async copies XLA adds to move operands between memory spaces carry
    none and read as ``glue_compiler_ms_per_iter``.)"""
    entry = step["entry"]
    k0, k1, _ = _kernels(entry)
    between = [ins for ins in entry[k0 + 1:k1]
               if ins["opcode"] in ("fusion", "dynamic-update-slice")]
    assert len(between) > 100
    bare = [ins["instr"] for ins in between if not ins["scope"]]
    assert not bare, f"no stencil.* scope on: {bare}"
    by_scope = {}
    for ins in between:
        by_scope[ins["scope"]] = by_scope.get(ins["scope"], 0) + 1
    print(f"fusions and updates between the kernels, by scope: {by_scope}")
    assert by_scope.get("stencil.sweep.shell", 0) > 100
    assert set(by_scope) <= {"stencil.sweep.shell", "stencil.halo.pack",
                             "stencil.halo.unpack", "stencil.halo.wire",
                             "stencil.carry"}


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
