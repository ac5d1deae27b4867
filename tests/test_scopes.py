"""The program's names for its own device work (``obs/scopes.py``): the
vocabulary is the only source of scope, kernel and module names; every
default loop can be mapped back from optimized-HLO instruction names to the
scope and layer that asked for the work; the self-fill kernels count the
bytes their DMAs move; the recorder keeps its records in memory."""

import ast
import os

import jax
import jax.numpy as jnp
import pytest

from stencil_tpu.obs import scopes, telemetry

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "stencil_tpu")
PLUMBING = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
            "while", "call", "conditional"}


def _trees():
    for base, _, files in os.walk(PKG):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(base, f)
                with open(path) as fh:
                    yield os.path.relpath(path, PKG), ast.parse(fh.read())


def _calls(tree, attr):
    return [n for n in ast.walk(tree) if isinstance(n, ast.Call)
            and isinstance(n.func, ast.Attribute) and n.func.attr == attr]


# ------------------------------------------------------------ (a) vocabulary


def test_every_pallas_call_passes_a_name_and_every_kernel_is_in_the_vocabulary():
    sites, kernels = [], []
    for rel, tree in _trees():
        for call in _calls(tree, "pallas_call"):
            sites.append(rel)
            assert any(k.arg == "name" for k in call.keywords), rel
        for call in _calls(tree, "kernel_call"):
            first = call.args[0]
            assert isinstance(first, ast.Constant), (rel, "literal name")
            kernels.append(first.value)
    assert sites == [os.path.join("obs", "scopes.py")], sites
    assert len(kernels) == 23
    # every kernel of the vocabulary has its pallas_call (HPCG's injection
    # and its transpose since PR 52; where a level pair is not tight-x they
    # are plain XLA under the same names, through kernel_scope)
    assert set(kernels) == set(scopes.KERNELS)
    # an operator that plain XLA may compute carries its kernel's name all
    # the same (kernel_scope); a name outside the vocabulary is refused
    assert scopes.layer_of("stencil.kernel.mg_rprj3") == scopes.LAYER_KERNELS
    with pytest.raises(KeyError):
        scopes.kernel_scope("nameless")
    # a multigrid level's tag is of no layer: an op keeps its innermost's
    assert scopes.layer_of(scopes.MG_LEVEL + "3") is None
    assert scopes.level_of("jit(f)/stencil.mg.level7/stencil.carry/mul") == 7
    assert scopes.level_of("jit(f)/stencil.carry/mul") is None


def test_every_named_scope_is_in_the_vocabulary():
    constants = {v for k, v in vars(scopes).items()
                 if k.isupper() and isinstance(v, str)}
    seen = set()
    for rel, tree in _trees():
        for call in _calls(tree, "named_scope"):
            # only obs/scopes.py opens jax.named_scope, from vocabulary names
            assert rel == os.path.join("obs", "scopes.py"), rel
        for call in _calls(tree, "scope"):
            arg = call.args[0]
            if isinstance(arg, ast.Constant):
                assert arg.value in scopes.SCOPES, (rel, arg.value)
                seen.add(arg.value)
            elif (isinstance(arg, ast.Attribute)
                  and isinstance(arg.value, ast.Name)
                  and arg.value.id == "scopes"):
                name = getattr(scopes, arg.attr)
                assert name in scopes.SCOPES and name in constants, rel
                seen.add(name)
    assert seen == set(scopes.SCOPES)
    assert all(s.startswith(scopes.PREFIX) for s in scopes.SCOPES)
    assert scopes.layer_of("stencil.kernel.self_fill_x") == scopes.LAYER_HALO
    assert scopes.layer_of("stencil.kernel.jacobi_multistep") == scopes.LAYER_KERNELS
    for name in ("split_x_pack", "split_x_unpack"):
        assert scopes.KERNELS[name] == scopes.LAYER_HALO
        assert scopes.layer_of(scopes.KERNEL_PREFIX + name) == scopes.LAYER_HALO
    assert scopes.layer_of(None) is None
    with pytest.raises(KeyError):
        scopes.scope("stencil.typo")
    with pytest.raises(KeyError):
        scopes.kernel_call("nameless", lambda: None)
    with pytest.raises(KeyError):
        scopes.jit_loop("entry_fn", lambda x: x)


# ------------------------------------------------------------ (b) op maps


def _build(case):
    """Build one default loop on the CPU mesh; the module it registers
    under."""
    from stencil_tpu.apps import astaroth, exchange_weak, jacobi3d

    scopes.clear()
    app, n = case
    devices = jax.devices()[:n]
    if app == "jacobi":
        jacobi3d.run(32, 16, 16, iters=20, devices=devices)
        return scopes.JACOBI_LOOP
    if app == "exchange":
        exchange_weak.run(16, 16, 16, iters=20, devices=devices)
        return scopes.EXCHANGE_LOOP
    astaroth.run(nx=16, iters=2, devices=devices, dtype="float32")
    return scopes.ASTAROTH_ITER


@pytest.mark.parametrize("case", [("jacobi", 1), ("jacobi", 4),
                                  ("astaroth", 1), ("exchange", 1),
                                  ("exchange", 4)], ids=lambda c: f"{c[0]}{c[1]}")
def test_default_loop_maps_back_to_scopes(case):
    module = _build(case)
    assert scopes.registered(module) >= 1
    text = scopes.hlo_text(module)
    assert text.startswith(f"HloModule jit_{module}")
    omap = scopes.op_map(module)
    assert len(omap) > 10 and scopes.op_map_seconds(module) > 0
    work = {k: v for k, v in omap.items() if v["opcode"] not in PLUMBING}
    # no instruction lies under scopes of two layers
    assert [k for k, v in omap.items() if len(v["layers"]) > 1] == []
    for k, v in work.items():
        assert v["layer"] == scopes.layer_of(v["scope"]), k
        # every collective is the halo layer's wire
        if v["opcode"].startswith("collective-permute"):
            assert v["scope"] == scopes.HALO_WIRE, k
    used = {v["scope"] for v in work.values()}
    if case[0] == "exchange":
        # an exchange loop is pack, wire, unpack and nothing else: every
        # instruction that slices, stacks or updates carries a halo scope
        for k, v in work.items():
            if any(w in v["opcode"] for w in ("slice", "concatenate", "fusion")
                   ) and "slice" in (v["op_name"] + v["opcode"]):
                assert (v["scope"] or "").startswith("stencil.halo."), (k, v)
        assert {scopes.HALO_PACK, scopes.HALO_UNPACK} <= used
    if case == ("jacobi", 4):
        assert {scopes.HALO_PACK, scopes.HALO_WIRE, scopes.HALO_UNPACK,
                scopes.SWEEP_SHELL, scopes.MASK} <= used
    if case[1] == 4:
        assert scopes.HALO_WIRE in used


def test_op_map_reads_tpu_style_text_copies_and_kernels():
    text = '''HloModule jit_stencil_jacobi_loop, is_scheduled=true
%body (p: (f32[8,128], f32[8,128])) -> (f32[8,128], f32[8,128]) {
  %p = (f32[8,128]{1,0:T(8,128)}, f32[8,128]{1,0:T(8,128)}) parameter(0)
  %get-tuple-element.1 = f32[8,128]{1,0:T(8,128)} get-tuple-element(%p), index=0
  %copy.7 = f32[8,128]{1,0:T(8,128)} copy(%get-tuple-element.1)
  %bitcast.3 = f32[1,8,128]{2,1,0:T(8,128)} bitcast(%copy.7)
  %jacobi_multistep.2 = f32[1,8,128]{2,1,0:T(8,128)} custom-call(%bitcast.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(stencil_jacobi_loop)/while/body/stencil.kernel.jacobi_multistep/jacobi_multistep/pallas_call" source_file="/x/pallas_stencil.py" source_line=709}
  %fusion.4 = f32[8,128]{1,0:T(8,128)} fusion(%jacobi_multistep.2), kind=kLoop, calls=%f, metadata={op_name="jit(stencil_jacobi_loop)/while/body/stencil.mask/eq"}
  ROOT %tuple.9 = (f32[8,128]{1,0:T(8,128)}, f32[8,128]{1,0:T(8,128)}) tuple(%fusion.4, %copy.7)
}
'''
    omap = scopes.parse_hlo_text(text)
    k = omap["jacobi_multistep.2"]
    assert k["scope"] == "stencil.kernel.jacobi_multistep"
    assert k["layer"] == scopes.LAYER_KERNELS
    assert k["source"] == "/x/pallas_stencil.py:709"
    assert omap["fusion.4"]["layer"] == scopes.LAYER_GLUE
    copy = omap["copy.7"]
    assert copy["scope"] is None and copy["layer"] is None
    assert copy["producer"] == {"instr": "p", "opcode": "parameter",
                                "scope": None, "operand": None}
    assert {"instr": "jacobi_multistep.2", "opcode": "custom-call",
            "scope": "stencil.kernel.jacobi_multistep",
            "operand": 0} in copy["consumers"]
    assert any(c["opcode"] == "tuple" for c in copy["consumers"])


def test_a_fusion_without_metadata_takes_the_scope_its_ops_agree_on():
    """XLA rebuilds the carriers' concatenate as in-place updates of a fresh
    buffer; the fusion it makes has no metadata, the slices it fused have
    (the four-chip Astaroth step, PR 33). One scope inside: the fusion's.
    Two, or none: the compiler's, as before."""
    text = '''HloModule jit_stencil_astaroth_iter, is_scheduled=true
%fused_computation.1 (param_0: f32[8,128]) -> f32[2,8,128] {
  %custom-call.3 = f32[2,8,128]{2,1,0} custom-call(), custom_call_target="AllocateBuffer"
  %param_0 = f32[8,128]{1,0} parameter(0)
  %slice.1 = f32[3,128]{1,0} slice(%param_0), slice={[0:3], [0:128]}, metadata={op_name="jit(stencil_astaroth_iter)/shard_map/stencil.halo.pack/dynamic_slice"}
  ROOT %dynamic-update-slice.1 = f32[2,8,128]{2,1,0} dynamic-update-slice(%custom-call.3, %slice.1)
}
%fused_computation.2 (param_0.1: f32[8,128]) -> f32[8,128] {
  %param_0.1 = f32[8,128]{1,0} parameter(0)
  %slice.2 = f32[8,128]{1,0} slice(%param_0.1), slice={[0:8], [0:128]}, metadata={op_name="jit(stencil_astaroth_iter)/shard_map/stencil.halo.pack/dynamic_slice"}
  ROOT %add.2 = f32[8,128]{1,0} add(%slice.2, %slice.2), metadata={op_name="jit(stencil_astaroth_iter)/shard_map/stencil.sweep.shell/add"}
}
%fused_computation.3 (param_0.2: f32[8,128]) -> f32[8,128] {
  %param_0.2 = f32[8,128]{1,0} parameter(0)
  ROOT %copy.3 = f32[8,128]{1,0} copy(%param_0.2)
}
ENTRY %main (p: f32[8,128]) -> f32[8,128] {
  %p = f32[8,128]{1,0} parameter(0)
  %fusion.1 = f32[2,8,128]{2,1,0} fusion(%p), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8,128]{1,0} fusion(%p), kind=kLoop, calls=%fused_computation.2
  %fusion.3 = f32[8,128]{1,0} fusion(%p), kind=kLoop, calls=%fused_computation.3
  ROOT %fusion.4 = f32[8,128]{1,0} fusion(%p), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(stencil_astaroth_iter)/shard_map/stencil.carry/reshape"}
}
'''
    omap = scopes.parse_hlo_text(text)
    assert omap["fusion.1"]["scope"] == scopes.HALO_PACK
    assert omap["fusion.1"]["layer"] == scopes.LAYER_HALO
    assert omap["fusion.1"]["layers"] == [scopes.LAYER_HALO]
    assert omap["fusion.2"]["scope"] is None        # two scopes inside
    assert omap["fusion.3"]["scope"] is None        # none inside
    assert omap["fusion.4"]["scope"] == scopes.CARRY    # its own stands


# ------------------------------------------------------------ (d) bytes moved


def _fill_bytes(n, axis, nq, radius=3, build=None):
    """(spec, what ``build(make)`` returned, the one counter record) of a
    self-fill built while a recorder of its own is installed."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.ops.halo_fill import make_self_fill

    spec = GridSpec(Dim3(n, n, n), Dim3(1, 1, 1), Radius.constant(radius))
    rec = telemetry.Recorder()
    old, telemetry._recorder = telemetry._recorder, rec

    def make():
        return make_self_fill(spec, axis, nq=nq)

    try:
        built = make() if build is None else build(spec, make)
    finally:
        telemetry._recorder = old
    (r,) = rec.records(kind="counter", name="halo.self_fill.bytes_dma")
    assert r["axis"] == axis and r["quantities"] == nq
    assert r["bytes"] == r["bytes_read"] + r["bytes_written"]
    p = spec.padded()
    assert r["shape"] == [p.z, p.y, p.x]
    return spec, built, r


def test_self_fill_byte_count_matches_the_hand_count_at_512_r3():
    """ops/halo_fill.py's docstring, per quantity: z 6 plane copies, y two
    8-row tiles rewritten from two more read, x both 128-lane edge tiles
    of every row rewritten."""
    total = 0
    for axis in "xyz":
        spec, _, r = _fill_bytes(512, axis, 4)
        total += r["bytes"] / 4
        p = spec.padded()
        # y: 512 rows are a multiple of 8, so neither destination window
        # holds an owned row and neither is read: two source windows read,
        # two destination windows written
        hand = {"z": 2 * 6 * p.y * p.x * 4,
                "y": (2 + 2) * 8 * p.z * p.x * 4,
                "x": 2 * 2 * 128 * p.z * p.y * 4}[axis]
        assert abs(r["bytes"] / 4 - hand) <= 0.02 * hand, (axis, r, hand)
        if axis == "y":
            assert r["bytes_read"] == r["bytes_written"]
            assert r["dst_read_skipped"] == 2
    # 0.56 + 0.043 + 0.016 GB a quantity as the docstring has it
    assert abs(total - 0.62e9) <= 0.02 * 0.62e9


@pytest.mark.parametrize("axis", ["x", "y", "z"])
def test_self_fill_byte_count_equals_the_lowered_mosaic_modules(axis):
    """The cross-check: the DMAs of the lowered Mosaic module
    (``utils/mosaic_traffic``), counted per grid step as they execute."""
    from stencil_tpu.utils.mosaic_traffic import capture_traffic

    nq = 2

    def lowered(spec, make):
        # the kernel is built under the capture's patch, which turns on the
        # Mosaic dump
        p = spec.padded()
        arg = jax.ShapeDtypeStruct((p.z, p.y, p.x), jnp.float32)
        return capture_traffic(lambda: (make(), (arg,) * nq))

    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        spec, (kt,), r = _fill_bytes(128, axis, nq, build=lowered)
    finally:
        jax.config.update("jax_enable_x64", x64)
    p = spec.padded()
    written = kt.steps * kt.output_bytes()
    # the x and y kernels read each batch once: at step 0, or prefetched a
    # step ahead; their bodies spell both, and x's a third for a clamped tail
    spelled = {"x": 3 if p.z % 16 else 2, "y": 2, "z": 1}[axis]
    read = kt.steps * kt.input_bytes() // spelled
    assert (r["bytes_read"], r["bytes_written"]) == (read, written)


@pytest.mark.parametrize("rows,skipped", [(128, 2), (140, 0)])
def test_self_fill_y_schedule_in_the_lowered_mosaic_module(rows, skipped):
    """The y kernel's DMA schedule, read off the lowered module in body
    order at nq 4 (``KernelTraffic.events``). In a middle grid step every
    read of the NEXT batch (a source window a quantity and side, and the
    destination window where it holds owned rows: 140 rows) is started
    before anything is waited for; then this batch's reads are waited for,
    every write of it is started before any is waited for, and the step
    ends on their wait, which is what frees the slot the next step loads.
    The counter's ``dmas``, ``in_flight`` and ``dst_read_skipped`` and the
    scratch ``_y_scratch_bytes`` counts are that module's."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.ops import halo_fill as HF
    from stencil_tpu.utils.mosaic_traffic import capture_traffic

    nq = 4
    spec = GridSpec(Dim3(128, rows, 128), Dim3(1, 1, 1), Radius.constant(3))
    p = spec.padded()
    arg = jax.ShapeDtypeStruct((p.z, p.y, p.x), jnp.float32)
    rec = telemetry.Recorder()
    old, telemetry._recorder = telemetry._recorder, rec
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        (kt,) = capture_traffic(
            lambda: (HF.make_self_fill(spec, "y", nq=nq), (arg,) * nq))
    finally:
        jax.config.update("jax_enable_x64", x64)
        telemetry._recorder = old
    (r,) = rec.records(kind="counter", name="halo.self_fill.bytes_dma")
    n_rd = (4 - skipped) * nq     # reads of a batch; 2 * nq writes
    assert kt.steps > 2

    # the scf.if ops in module order: 0 the first step's own reads,
    # 1 the prefetch of the next batch; a middle step takes 1 alone
    first = [e for e in kt.events if e.branch == (0,)]
    assert [e.kind for e in first] == ["start"] * n_rd
    middle = kt.schedule([1])
    kinds = [(e.kind, "rd" if e.op.is_input else "wr") for e in middle]
    assert kinds == ([("start", "rd")] * n_rd + [("wait", "rd")] * n_rd
                     + [("start", "wr")] * 2 * nq + [("wait", "wr")] * 2 * nq)
    assert all(e.branch == ((1,) if n < n_rd else ()) for n, e in enumerate(middle))

    # outstanding DMAs through a middle step, which begins with this
    # batch's reads in flight (started a step ago) and ends the same way
    out = most = n_rd
    for kind, _ in kinds:
        out += 1 if kind == "start" else -1
        most = max(most, out)
    assert out == n_rd
    assert r["in_flight"] == most == 2 * n_rd
    assert r["dmas"] == kt.steps * (n_rd + 2 * nq)
    assert r["dst_read_skipped"] == skipped
    tzb = HF._y_tzb(spec, nq)
    assert kt.vmem_bytes == HF._y_scratch_bytes(spec, nq, tzb)
    assert kt.steps == -(-p.z // tzb)
    assert {e.op.shape[0] for e in kt.events} == {tzb}


def test_split_x_byte_count_at_the_four_chip_cells_size_and_in_the_lowered_modules():
    """``halo.split_x.bytes_dma``, once a build: pack reads both edge
    lane-tiles of a field (0.280 GB at 518 x 528 x 640) and writes two
    carriers, unpack reads the tiles and the carriers and writes the tiles:
    0.84 GB a quantity an exchange within 5 %. The lowered Mosaic modules
    (``utils/mosaic_traffic``) move the same bytes."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.ops import halo_fill as HF
    from stencil_tpu.utils.mosaic_traffic import capture_traffic

    def counted(spec, nq, lowered):
        rec = telemetry.Recorder()
        old, telemetry._recorder = telemetry._recorder, rec
        p = spec.padded()
        field = jax.ShapeDtypeStruct((p.z, p.y, p.x), jnp.float32)
        carriers = [jax.ShapeDtypeStruct(s, jnp.float32)
                    for s in HF.split_x_carrier_shapes(spec, nq)]
        try:
            if not lowered:
                HF.make_split_x_pack(spec, nq)
                HF.make_split_x_unpack(spec, nq)
                kts = None
            else:
                kts = capture_traffic(lambda: (
                    lambda *f: HF.make_split_x_unpack(spec, nq)(
                        *f, *HF.make_split_x_pack(spec, nq)(*f)),
                    (field,) * nq))
        finally:
            telemetry._recorder = old
        pack, unpack = sorted(
            rec.records(kind="counter", name="halo.split_x.bytes_dma"),
            key=lambda r: r["part"])
        assert (pack["part"], unpack["part"]) == ("pack", "unpack")
        for r in (pack, unpack):
            assert r["quantities"] == nq and r["shape"] == [p.z, p.y, p.x]
            assert r["bytes"] == r["bytes_read"] + r["bytes_written"]
        return pack, unpack, kts, carriers

    cell = GridSpec(Dim3(1024, 1024, 512), Dim3(2, 2, 1), Radius.constant(3))
    pack, unpack, _, _ = counted(cell, 4, lowered=False)
    tile = 518 * 528 * 128 * 4
    assert pack["bytes_read"] == unpack["bytes_written"] == 4 * 2 * tile
    assert pack["bytes_written"] == unpack["bytes_read"] - 4 * 2 * tile \
        == 2 * 13 * 4 * 528 * 128 * 4
    a_quantity = (pack["bytes"] + unpack["bytes"]) / 4
    assert abs(a_quantity - 0.84e9) <= 0.05 * 0.84e9

    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        small = GridSpec(Dim3(256, 128, 128), Dim3(2, 1, 1), Radius.constant(3))
        pack, unpack, kts, carriers = counted(small, 2, lowered=True)
    finally:
        jax.config.update("jax_enable_x64", x64)
    kp, ku = kts
    groups = carriers[0].shape[0]
    tzb = HF._split_x_geom(small, 2).tzb        # 16: a carrier group is (2, py, 128)
    is_tile = lambda d: d.shape[0] == tzb
    tiles_in = lambda kt: sum(d.nbytes for d in kt.inputs() if is_tile(d))
    carr_in = lambda kt: sum(d.nbytes for d in kt.inputs() if not is_tile(d))
    # a batch's tiles are read once: at step 0, or prefetched a step ahead
    # (the body spells both); a carrier group is written once, and read
    # once: at step 0 or a group ahead
    assert pack["bytes_read"] == kp.steps * tiles_in(kp) // 2
    assert pack["bytes_written"] == groups * kp.output_bytes()
    assert unpack["bytes_read"] == (ku.steps * tiles_in(ku) // 2
                                    + groups * carr_in(ku) // 2)
    assert unpack["bytes_written"] == ku.steps * ku.output_bytes()


# ------------------------------------------------------------ (e) recorder


def test_recorder_keeps_records_in_memory_without_a_sink():
    rec = telemetry.Recorder()
    assert not rec.enabled
    with rec.span("jacobi.warmup", phase="compile"):
        with rec.span("jacobi.init"):
            rec.counter("halo.self_fill.bytes_dma", bytes=7, axis="x")
    close = rec.open_span("jacobi.steps")
    close()
    inner, outer, steps = rec.records(kind="span")
    assert (inner["name"], outer["name"]) == ("jacobi.init", "jacobi.warmup")
    assert inner["parent"] == "jacobi.warmup" and "parent" not in outer
    assert steps["name"] == "jacobi.steps" and "parent" not in steps
    for r in (inner, outer, steps):
        assert r["t0_ns"] <= r["t1_ns"]
        assert r["t1_ns"] - r["t0_ns"] == int(r["seconds"] * 1e9)
        assert telemetry.validate_record(r) == []
    assert outer["t0_ns"] <= inner["t0_ns"] and inner["t1_ns"] <= outer["t1_ns"]
    assert abs(outer["t0_ns"] / 1e9 - outer["t"]) < 60      # the unix clock
    assert [r["bytes"] for r in rec.records(name="halo.self_fill.bytes_dma")] == [7]
    assert rec.records(kind="gauge") == []


def test_recorder_memory_is_bounded():
    rec = telemetry.Recorder()
    for i in range(telemetry.KEEP_RECORDS + 50):
        rec.gauge("exchange.gb_per_s", float(i))
    kept = rec.records()
    assert len(kept) == telemetry.KEEP_RECORDS
    assert kept[0]["value"] == 50.0 and kept[-1]["value"] == telemetry.KEEP_RECORDS + 49.0


def test_new_names_are_in_the_telemetry_vocabulary():
    for app in ("jacobi", "astaroth", "exchange"):
        for part in ("realize", "warmup", "steps"):
            assert f"{app}.{part}" in telemetry.KNOWN_NAMES
    assert "halo.self_fill.bytes_dma" in telemetry.KNOWN_NAMES
    assert "halo.split_x.bytes_dma" in telemetry.KNOWN_NAMES
