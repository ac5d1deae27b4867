"""The three application cells' real loops, compiled at their real sizes
for a described ``v5e:2x2`` topology: the optimized HLO holds no ``copy``
of a whole block. A ``while`` trip or a program that ends with its
ping-pong pair exchanged costs three such copies a step on the chip (24 for
Astaroth's eight fields); before ops/double_buffer.py these counted 3, 3
and 24 (PERF.md, PR 26). Nothing runs; a compile that passes is not a chip
result.

The topology is described inside a module-scoped fixture (the
on-chip-measurement guide, section 2): only the worker that gets this file
loads libtpu.
"""

import math
import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

_COPY = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\S+)\s+copy\(", re.M)


def _big_copies(text):
    """``(instruction, shape)`` of every ``copy`` of a megabyte or more (of
    four-byte elements) in a compiled module's text."""
    return [(instr, shape) for instr, shape in _COPY.findall(text)
            if 4 * math.prod(int(n) for n in re.findall(
                r"\d+", shape.split("[", 1)[1].split("]")[0]) or [1])
            >= 1 << 20]


def _exchange(topo, n, radius, dim):
    """The tight-x exchange the applications realize on TPU devices."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.parallel import HaloExchange, grid_mesh

    d = Dim3(*dim)
    spec = GridSpec(Dim3(n * d.x, n * d.y, n * d.z), d,
                    Radius.constant(radius).without_x())
    return HaloExchange(spec, grid_mesh(d, list(topo.devices)[:d.flatten()]))


def _jacobi(dim, iters, deep_halo=None):
    """``jacobi3d.run``'s loop of ``iters`` steps a dispatch at 512^3 a
    chip: halos and depth as the application picks them for that dispatch,
    or as ``--deep-halo`` pins them."""
    def build(topo):
        from stencil_tpu.geometry import Dim3
        from stencil_tpu.obs import scopes
        from stencil_tpu.ops.jacobi import make_jacobi_loop
        from stencil_tpu.ops.pallas_stencil import pick_temporal_depth

        d = Dim3(*dim)
        k = deep_halo or pick_temporal_depth(
            Dim3(512 * d.x, 512 * d.y, 512 * d.z), d, iters)[0]
        ex = _exchange(topo, 512, k, dim)
        make_jacobi_loop(ex, iters, temporal_k=k if k >= 2 else None)
        return scopes.JACOBI_LOOP, ex.spec

    return build


def _astaroth(topo):
    from stencil_tpu.apps.astaroth import DEFAULT_CONF
    from stencil_tpu.astaroth.config import load_config
    from stencil_tpu.astaroth.integrate import make_astaroth_step
    from stencil_tpu.obs import scopes

    info, _ = load_config(DEFAULT_CONF)
    ex = _exchange(topo, 256, 3, (1, 1, 1))
    make_astaroth_step(ex, info, iters=1)
    return scopes.ASTAROTH_ITER, ex.spec


LOOPS = {
    # the application's default 10 iterations a dispatch (since PR 31 one
    # radius-10 exchange and one k = 10 pass), an odd count (k = 11), and a
    # checkpoint-clamped dispatch on the halos of ten (one k = 4 pass)
    "jacobi512x4.weak.iters10": _jacobi((1, 2, 2), 10),
    "jacobi512x4.weak.iters11": _jacobi((1, 2, 2), 11),
    "jacobi512x4.weak.iters4.halo10": _jacobi((1, 2, 2), 4, deep_halo=10),
    # --deep-halo 1: the per-step sweep, its mask and its shells
    "jacobi512x4.step.iters10": _jacobi((1, 2, 2), 10, deep_halo=1),
    "jacobi512x4.step.iters11": _jacobi((1, 2, 2), 11, deep_halo=1),
    "jacobi512.steady.iters10": _jacobi((1, 1, 1), 10),
    "astaroth256.steady.iters1": _astaroth,
}


@pytest.mark.parametrize("name", sorted(LOOPS))
def test_no_whole_block_copy_in_the_compiled_loop(name, topo, as_on_the_chip):
    from stencil_tpu.obs import scopes

    scopes.clear()
    module, spec = LOOPS[name](topo)
    text = scopes.hlo_text(module)
    assert "tpu_custom_call" in text, "the Pallas path did not engage"
    p = spec.padded()
    block = f"{p.z},{p.y},{p.x}]"
    whole = [(instr, shape) for instr, shape in _COPY.findall(text)
             if block in shape]
    assert not whole, (
        f"{len(whole)} whole-block copies in {module}: {whole}")


_CALL = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*custom-call\(.*"
                   r'custom_call_target="tpu_custom_call"', re.M)


def test_the_four_chip_dispatch_is_one_exchange_and_one_pass(
        topo, as_on_the_chip):
    """``jacobi512x4.weak``'s own loop (512^3 a chip on (1,2,2), ten steps a
    dispatch) at the depth ``jacobi3d.run`` picks: ONE radius-10 exchange
    and ONE ``jacobi_multistep`` call, no loop, nothing under
    ``stencil.mask`` or ``stencil.sweep.shell`` (0.62 and 0.29 ms an
    iteration of the per-step path, PERF.md section 5), no ``copy`` of a
    block, and two buffers are all it holds. The copies that are there are
    XLA's own relayout of the 10-row y slabs round the permutes."""
    from stencil_tpu.geometry import Dim3
    from stencil_tpu.obs import scopes, telemetry
    from stencil_tpu.ops.pallas_stencil import pick_temporal_depth

    d = Dim3(1, 2, 2)
    k, bound = pick_temporal_depth(Dim3(512, 1024, 1024), d, 10)
    assert (k, bound) == (10, "chunk")
    scopes.clear()
    module, spec = _jacobi((1, 2, 2), 10)(topo)
    staged = telemetry.get().records(
        kind="counter", name="kernel.multistep.staging")[-1]
    assert staged["k"] == k and staged["rows"] == 0
    assert staged["rows_computed"] == k * 512 + k * (k - 1)
    assert staged["vmem_bytes"] <= 46 * 1024 * 1024
    p = spec.padded()
    assert (p.z, p.y, p.x) == (532, 544, 512)

    rec = scopes._registry[module][-1]
    compiled = rec["fn"].lower(*rec["args"]).compile()
    text = compiled.as_text()
    assert _CALL.findall(text) == ["jacobi_multistep.1"]
    assert "while(" not in text, "one k = 10 pass needs no loop"
    # y and z, both directions
    assert len(re.findall(r" collective-permute-start\(", text)) == 4
    used = {v["scope"] for v in scopes.op_map(module).values()}
    assert scopes.KERNEL_PREFIX + "jacobi_multistep" in used
    assert not used & {scopes.MASK, scopes.SWEEP_SHELL}, used
    slab = 4 * p.z * k * p.x
    for instr, shape in _COPY.findall(text):
        dims = [int(n) for n in re.search(r"\[([\d,]*)\]", shape).group(1)
                .split(",") if n]
        assert 4 * math.prod(dims) <= slab, (instr, shape)

    mem = compiled.memory_analysis()
    buffer = 4 * p.z * p.y * p.x
    assert buffer == 592_707_584
    # curr and nxt, donated and aliased to the results; sel is not read
    assert mem.argument_size_in_bytes == 2 * buffer
    assert mem.alias_size_in_bytes == 2 * buffer
    assert mem.temp_size_in_bytes == 0


_RESULT = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\S+)\s+(copy|dynamic-update-slice)\(",
    re.M)


def test_split_x_exchange_loop_touches_no_whole_field_on_the_lane_axis(
        topo, as_on_the_chip):
    """``exchange512x4.r3q4``'s own loop (``make_loop(10)``, 4 x 512^3 r3 a
    chip on (2,2,1)): the x phase is the two edge-tile kernels and two
    permutes of lane-dense carriers. Before them this loop held four
    whole-field ``copy`` and eight whole-field ``dynamic-update-slice`` on
    the lane axis, 28 of its 34 ms (PERF.md, PR 29). The y phase's eight
    in-place ``dynamic-update-slice`` (rows, 0.27 ms each) are still XLA's."""
    import jax
    import jax.numpy as jnp

    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.obs import scopes
    from stencil_tpu.parallel import HaloExchange, grid_mesh

    d = Dim3(2, 2, 1)
    spec = GridSpec(Dim3(1024, 1024, 512), d, Radius.constant(3))
    ex = HaloExchange(spec, grid_mesh(d, list(topo.devices)[:4]))
    like = {q: jax.ShapeDtypeStruct(spec.stacked_shape_zyx(), jnp.float32,
                                    sharding=ex.sharding()) for q in range(4)}
    scopes.clear()
    ex.make_loop(10, like=like)
    text = scopes.hlo_text(scopes.EXCHANGE_LOOP)
    for kernel in ("split_x_pack", "split_x_unpack", "self_fill_z"):
        assert re.search(rf"%{kernel}[.\d]* = .*tpu_custom_call", text), kernel
    p = spec.padded()
    field = f"{p.z},{p.y},{p.x}]"
    whole = [(name, op, shape) for name, shape, op in _RESULT.findall(text)
             if field in shape]
    assert [w for w in whole if w[1] == "copy"] == []
    # what is left are the y phase's: two sides of four fields
    assert len(whole) == 8, whole
    omap = scopes.op_map(scopes.EXCHANGE_LOOP)
    for name, _op, _shape in whole:
        assert omap[name]["scope"] == scopes.HALO_UNPACK
    # and no 3-column slab of a field exists anywhere in the program
    assert f"{p.z},{p.y},3]" not in text
    # the carriers on the wire are lane-dense: 13 groups of 42 planes
    assert "f32[13,4,528,128]" in text


@pytest.mark.parametrize("part", [(1, 1, 1), (1, 2, 2)], ids=str)
def test_the_mg_iteration_copies_no_level_and_holds_nothing_of_its_own(
        part, topo, as_on_the_chip):
    """``mg512.steady``'s own program (class C: nine levels, 34 operators
    and their fills, one iteration) and the same on the application's
    four-chip mesh: every level's u and r is donated and comes back where
    it lay, the program allocates nothing beside them, and no ``copy`` has
    the shape of a block of a megabyte or more (the tight-x levels and the first below them). The copies
    that are there on the split partition are XLA's interleaves of the
    prolongation below the tight-x levels, the largest the owned cells of
    128^3, and relayouts of the 2^3 and 4^3 levels' few rows; on one block
    those levels are the coarse call's and XLA computes nothing."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3
    from stencil_tpu.obs import scopes, telemetry
    from stencil_tpu.ops import mg
    from stencil_tpu.parallel import HaloExchange, grid_mesh

    d = Dim3(*part)
    mesh = grid_mesh(d, list(topo.devices)[:d.flatten()])
    exs = [HaloExchange(GridSpec(Dim3(m, m, m), d, mg.level_radius(m, d)),
                        mesh) for m in mg.level_sizes(512)]
    scopes.clear()
    mg.make_mg_iter(exs)
    rec = scopes._registry[scopes.MG_ITER][-1]
    compiled = rec["fn"].lower(*rec["args"]).compile()
    text = compiled.as_text()
    plan = telemetry.get().records(kind="counter", name="mg.cycle_plan")[-1]
    pallas = sum(op["calls_per_iter"] for lv in plan["levels"]
                 for op in lv["operators"].values() if op["impl"] == "pallas")
    assert pallas == 11, plan
    for kernel in mg.OPERATORS:
        assert re.search(rf"%{kernel}[.\d]* = .*tpu_custom_call", text), kernel
    # on one block the six levels under 128^3 are ONE more call, compiled
    # here at its real size with its 18 MB of VMEM; a split partition has
    # none, and its coarse levels stay XLA
    coarse = re.findall(r"%mg_coarse[.\d]* = .*tpu_custom_call", text)
    assert len(coarse) == (1 if part == (1, 1, 1) else 0)
    assert plan["resident_calls"] == (23 if part == (1, 1, 1) else 0)
    blocks = []
    for ex in exs:
        p = ex.spec.padded()
        if 4 * p.z * p.y * p.x >= 1 << 20:
            blocks.append(f"{p.z},{p.y},{p.x}]")
    assert len(blocks) >= 3
    whole = [(instr, shape) for instr, shape in _COPY.findall(text)
             if any(shape.startswith("f32[" + b) or ",1," + b in shape
                    for b in blocks)]
    assert not whole, whole
    mem = compiled.memory_analysis()
    levels = sum(2 * 4 * math.prod(ex.spec.block_shape_zyx()) for ex in exs)
    v = 4 * math.prod(exs[0].spec.block_shape_zyx())
    assert mem.argument_size_in_bytes == levels + v
    assert mem.alias_size_in_bytes == levels
    assert mem.temp_size_in_bytes == 0


def test_the_lbm_step_holds_two_lattices_and_nothing_else(topo,
                                                          as_on_the_chip):
    """``lbm384.steady``'s own program (384^3, 19 populations, five steps a
    dispatch): both lattices are donated and come back where they lay, the
    program allocates nothing beside their 9.0 GB, and no ``copy`` of a
    megabyte or more exists (two lattices leave 7 GB of the chip: one stray
    copy of a lattice is an out-of-memory a user would meet). A step is
    five Pallas calls: the one-sided y and z fills, two each (the low and
    the high halo's five populations), and the stream-collide pass."""
    from stencil_tpu.apps.lbm import DEFAULT_CHUNK
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3
    from stencil_tpu.obs import scopes, telemetry
    from stencil_tpu.ops import lbm
    from stencil_tpu.parallel import HaloExchange, grid_mesh

    d = Dim3(1, 1, 1)
    spec = GridSpec(Dim3(384, 384, 384), d, lbm.domain_radius(True))
    ex = HaloExchange(
        spec, grid_mesh(d, list(topo.devices)[:1]),
        quantity_radius={i: lbm.population_radius(i, True)
                         for i in range(lbm.Q)})
    scopes.clear()
    lbm.make_lbm_step(ex, lbm.omega_of(1.0), iters=DEFAULT_CHUNK)
    rec = scopes._registry[scopes.LBM_STEP][-1]
    compiled = rec["fn"].lower(*rec["args"]).compile()
    text = compiled.as_text()
    plan = telemetry.get().records(kind="counter", name="lbm.step_plan")[-1]
    assert (plan["kernel"], plan["layout"]) == ("pallas", "tight_x")
    # a while of two steps a trip and the odd fifth: three steps' calls
    for kernel, count in (("lbm_d3q19", 3), ("self_fill_y", 6),
                          ("self_fill_z", 6)):
        calls = re.findall(rf"%{kernel}[.\d]* = .*tpu_custom_call", text)
        assert len(calls) == count, (kernel, len(calls))
    big = _big_copies(text)
    assert not big, big
    mem = compiled.memory_analysis()
    lattices = 2 * lbm.Q * 4 * math.prod(spec.block_shape_zyx())
    assert lattices == 9_012_019_200
    assert mem.argument_size_in_bytes == lattices
    assert mem.alias_size_in_bytes == lattices
    assert mem.temp_size_in_bytes == 0


def test_the_hpcg_iteration_transfers_in_place_and_holds_nothing_of_its_own(
        topo, as_on_the_chip):
    """``hpcg512.steady``'s own program (512^3, four levels, one CG
    iteration with its V-cycle): the transfers between the tight-x levels
    (512 <-> 256, 256 <-> 128) are Pallas calls, the prolongation's aliased
    operand is the sweep's result itself (a ``copy`` there is a pass over
    0.55 GB a call), the ONE ``copy`` of a block is the restart's ``r <-
    b`` under its ``conditional``, and the program allocates nothing of its
    own (the XLA transfers held 0.69 GB of temporaries)."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3
    from stencil_tpu.obs import scopes, telemetry
    from stencil_tpu.ops import hpcg
    from stencil_tpu.parallel import HaloExchange, grid_mesh

    d = Dim3(1, 1, 1)
    mesh = grid_mesh(d, list(topo.devices)[:1])
    exs = [HaloExchange(GridSpec(Dim3(*s), d, hpcg.level_radius(s)), mesh,
                        periodic=(False,) * 3)
           for s in hpcg.level_sizes((512,) * 3)]
    scopes.clear()
    hpcg.make_hpcg_iter(exs)
    rec = scopes._registry[scopes.HPCG_ITER][-1]
    compiled = rec["fn"].lower(*rec["args"]).compile()
    text = compiled.as_text()
    plan = telemetry.get().records(kind="counter", name="hpcg.iter_plan")[-1]
    impl = [[lv["operators"].get(n, {}).get("impl")
             for n in ("hpcg_restrict", "hpcg_prolong")]
            for lv in plan["levels"]]
    assert impl == [["pallas"] * 2, ["pallas"] * 2, ["xla"] * 2, [None] * 2]
    for kernel, count in (("hpcg_restrict", 2), ("hpcg_prolong", 2),
                          ("hpcg_resid", 3), ("hpcg_spmv", 1),
                          ("hpcg_symgs", 24)):
        calls = re.findall(rf"%{kernel}[.\d]* = .*tpu_custom_call", text)
        assert len(calls) == count, (kernel, len(calls))
    # what a prolongation updates in place is a sweep's result, uncopied
    fed = re.findall(r"%hpcg_prolong[.\d]* = \S+ custom-call\(([^)]*)\)", text)
    assert len(fed) == 2 and all(
        ops.split(", ")[2].startswith("%hpcg_symgs") for ops in fed), fed
    big = _big_copies(text)
    assert [shape.split("{")[0] for _, shape in big] == [
        "f32[1,1,1,514,528,512]"], big
    assert compiled.memory_analysis().temp_size_in_bytes == 0
