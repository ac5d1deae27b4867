"""The layers read from inside (``benchmark/scope_lib.py`` and the readers
over it), on a small hand-made trace and op map: the four classes are a
partition of the op self time, an unscoped copy is the compiler's, a kernel
is found by its name whatever its shapes, and every new reader leaves its
metric out where there is nothing to read."""

import pytest

from _bench_util import bench
from benchmark import scope_lib
from benchmark import trace_reduce as tr
from benchmark.harness import load_module

NEW_READERS = [
    "kernel_scope_ms_per_iter", "halo_scope_ms.app", "halo_scope_ms.exch",
    "glue_program_ms_per_iter", "glue_compiler_ms_per_iter",
    "self_fill_moved_roofline", "app_run_host_init_s", "app_run_compile_s",
    "app_run_steps_s"]
MODULE = "stencil_jacobi_loop"

# the optimized module the hand-made trace "ran": names as on the chip
HLO = '''HloModule jit_stencil_jacobi_loop, is_scheduled=true
%body (p: (f32[64,128], f32[64,128])) -> (f32[64,128], f32[64,128]) {
  %p = (f32[64,128]{1,0:T(8,128)}, f32[64,128]{1,0:T(8,128)}) parameter(0)
  %get-tuple-element.1 = f32[64,128]{1,0:T(8,128)} get-tuple-element(%p), index=0
  %copy.7 = f32[64,128]{1,0:T(8,128)} copy(%get-tuple-element.1)
  %jacobi_sweep.2 = f32[64,128]{1,0:T(8,128)} custom-call(%copy.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(stencil_jacobi_loop)/while/body/stencil.kernel.jacobi_sweep/jacobi_sweep/pallas_call"}
  %self_fill_x.3 = (f32[64,128]{1,0:T(8,128)}, f32[64,128]{1,0:T(8,128)}) custom-call(%jacobi_sweep.2, %copy.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(stencil_jacobi_loop)/while/body/stencil.halo.self_fill/stencil.kernel.self_fill_x/self_fill_x/pallas_call"}
  %slice_fusion.4 = f32[3,128]{1,0:T(8,128)} fusion(%jacobi_sweep.2), kind=kLoop, calls=%f, metadata={op_name="jit(stencil_jacobi_loop)/while/body/stencil.halo.pack/dynamic_slice"}
  %collective-permute.5 = f32[3,128]{1,0:T(8,128)} collective-permute(%slice_fusion.4), metadata={op_name="jit(stencil_jacobi_loop)/while/body/stencil.halo.wire/ppermute"}
  %compare_fusion.6 = pred[64,128]{1,0:T(8,128)} fusion(%jacobi_sweep.2), kind=kLoop, calls=%g, metadata={op_name="jit(stencil_jacobi_loop)/while/body/stencil.mask/eq"}
  ROOT %tuple.9 = (f32[64,128]{1,0:T(8,128)}, f32[64,128]{1,0:T(8,128)}) tuple(%jacobi_sweep.2, %copy.7)
}
ENTRY %main (a: f32[64,128]) -> f32[64,128] {
  %while.1 = (f32[64,128]{1,0:T(8,128)}, f32[64,128]{1,0:T(8,128)}) while(%t), condition=%c, body=%body
}
'''


def _op(instr, opcode, start, dur, results=((64, 128),), operands=1,
        target=None, cls="glue", **more):
    return dict({"instr": instr, "opcode": opcode, "target": target,
                 "results": [tuple(r) for r in results],
                 "operands": [(64, 128)] * operands, "start": float(start),
                 "dur": float(dur), "self": float(dur), "cls": cls}, **more)


def _trace(kernel_shape=(64, 128), kernel_cls="stencil"):
    """One chip, one 1 ms dispatch of 2 iterations: a while holding a copy,
    the sweep kernel, an x self-fill of two quantities, a pack fusion, a
    permute and the mask fusion."""
    pallas = "tpu_custom_call"
    ops = [
        _op("while.1", "while", 0, 1_000_000, cls="container"),
        _op("copy.7", "copy", 0, 100_000),
        _op("jacobi_sweep.2", "custom-call", 100_000, 500_000,
            results=(kernel_shape,), target=pallas, cls=kernel_cls,
            kernel="jacobi_sweep"),
        _op("self_fill_x.3", "custom-call", 600_000, 200_000,
            results=((64, 128), (64, 128)), operands=2, target=pallas,
            cls="halo", kernel="self_fill"),
        _op("slice_fusion.4", "fusion", 800_000, 50_000),
        _op("collective-permute.5", "collective-permute", 850_000, 30_000,
            cls="collective"),
        _op("compare_fusion.6", "fusion", 880_000, 70_000),
        _op("mystery.8", "fusion", 950_000, 20_000),
    ]
    ops[0]["self"] = 1_000_000.0 - sum(o["dur"] for o in ops[1:])
    chip = {"id": 0, "modules": [("jit_stencil_jacobi_loop(42)", 0.0, 1e6)],
            "ops": ops, "async": []}
    return {"chips": [chip], "host": []}


class _Kernel:
    @staticmethod
    def work(build, facts):
        return {"per": "iteration", "bytes": 1000, "flops": 0, "note": "x"}


@pytest.fixture
def program(monkeypatch):
    """The program's registry holding the hand-made module's text, and a
    recorder of its own holding what ``run()`` would have recorded."""
    from stencil_tpu.obs import scopes, telemetry

    monkeypatch.setattr(scopes, "_registry",
                        {MODULE: [{"fn": None, "args": (), "text": HLO}]})
    rec = telemetry.Recorder()
    monkeypatch.setattr(telemetry, "_recorder", rec)
    return scopes, rec


def _ctx(trace, lines, phases=None):
    return {"trace": trace, "say": lines.append,
            "window": {"iterations": 2, "seconds": 0.001, "dispatches": 1,
                       "iters_per_dispatch": 2},
            "facts": {"quantities": 2}, "phases": phases or {},
            "peak": {"hbm_bytes_per_s": 1e9, "flops_per_s_bf16": 1e12},
            "kernels": {"halo": {"self_fill": _Kernel}}}


def test_the_module_is_read_off_the_trace():
    assert scope_lib.module_name(_trace()) == MODULE
    assert scope_lib.module_name({"chips": [], "host": []}) is None
    two = _trace()
    two["chips"][0]["modules"].append(("jit_many(7)", 2e6, 5.0))
    assert scope_lib.module_name(two) == MODULE     # the one that fills it


def test_the_four_classes_are_a_partition_of_the_op_self_time(program):
    lines = []
    ctx = _ctx(_trace(), lines)
    got = {c: scope_lib.class_ms(ctx, c) for c in scope_lib.CLASSES}
    # ns per dispatch / 2 iterations / 1e6
    assert got["kernel"] == pytest.approx(0.25)
    assert got["halo"] == pytest.approx((200_000 + 50_000 + 30_000) / 2e6)
    assert got["glue_program"] == pytest.approx(70_000 / 2e6)
    # the copy and the op the map does not know are the compiler's, and
    # so are the 30 us of the while that its body does not fill
    assert got["glue_compiler"] == pytest.approx(
        (100_000 + 20_000 + 30_000) / 2e6)
    total = sum(op["self"] for op in ctx["trace"]["chips"][0]["ops"]) / 2e6
    assert sum(got.values()) == pytest.approx(total) == pytest.approx(0.5)
    text = "\n".join(lines)
    assert "partition (mean over chips)" in text and "0.5000 ms" in text
    assert "0.0100 ms in ops the map does not know" in text
    assert "without a stencil.kernel.* name: none" in text
    assert "disagree" not in text          # by name and by shape agree here
    # the copy is over 0.1 ms / 2 = 0.05 ms an iteration: not in the table
    assert "scopes: copy copy.7" not in text


def test_an_unscoped_copy_is_compiler_glue_and_is_named_with_what_it_feeds(
        program, monkeypatch):
    monkeypatch.setattr(scope_lib, "COPY_TABLE_MS", 0.01)
    lines = []
    ctx = _ctx(_trace(), lines)
    scope_lib.scoped(ctx)
    op = next(o for o in ctx["trace"]["chips"][0]["ops"]
              if o["instr"] == "copy.7")
    assert op["scope"] is None and op["scoped"] == "glue_compiler"
    row = next(l for l in lines if l.startswith("scopes: copy copy.7"))
    assert "0.0500 ms/iter, compiler" in row
    assert "from p (parameter" in row
    assert "jacobi_sweep.2 (custom-call, stencil.kernel.jacobi_sweep) " \
           "operand 0" in row


def test_a_changed_shape_no_longer_loses_a_kernel(program):
    """The shape match turned this call into glue (no recorded build has
    its shapes); by name it is still the sweep kernel, and the table says
    which call the two disagree on."""
    lines = []
    ctx = _ctx(_trace(kernel_shape=(72, 128), kernel_cls="glue"), lines)
    assert tr.class_ns(ctx["trace"]["chips"][0], "stencil") == 0
    assert scope_lib.class_ms(ctx, "kernel") == pytest.approx(0.25)
    row = next(l for l in lines if "disagree" in l)
    assert "jacobi_sweep.2:custom-call:tpu_custom_call" in row
    assert "stencil.kernel.jacobi_sweep" in row and "0.2500 ms/iter" in row


def test_bytes_moved_come_from_the_programs_own_counter(program):
    _, rec = program
    lines = []
    ctx = _ctx(_trace(), lines)
    assert scope_lib.self_fill_moved(ctx) is None       # nothing counted
    assert any("no halo.self_fill.bytes_dma" in l for l in lines)
    rec.counter("halo.self_fill.bytes_dma", bytes=100_000, axis="x",
                quantities=2, shape=[64, 128], bytes_read=50_000,
                bytes_written=50_000)
    rec.counter("halo.self_fill.bytes_dma", bytes=7, axis="x", quantities=1,
                shape=[64, 128], bytes_read=4, bytes_written=3)
    lines.clear()
    # 100 kB at 1 GB/s is 100 us of the call's 200 us
    assert scope_lib.self_fill_moved(ctx) == pytest.approx(50.0)
    assert any("useful share 2.0 %" in l for l in lines)   # 2 x 1000 logical


def test_app_run_readers_add_up_to_the_phase_less_the_printed_remainder(
        program):
    _, rec = program
    for name, seconds, parent in [
            ("jacobi.realize", 1.5, None), ("jacobi.init", 6.0, None),
            ("jacobi.warmup", 2.25, None), ("jacobi.build", 0.5, "jacobi.warmup"),
            ("jacobi.steps", 0.75, None)]:
        rec.emit("span", name, seconds=seconds, t0_ns=1, t1_ns=2,
                 parent=parent)
    rec.emit("span", "jacobi.iter", seconds=99.0)       # a sample, no span
    lines = []
    ctx = _ctx(_trace(), lines, phases={"app_run": 11.0})
    got = [load_module("layer_metrics", f"app_run_{p}_s").read(ctx)
           for p in ("host_init", "compile", "steps")]
    assert got == [7.5, 2.25, 0.75]
    assert any("no span covers 0.500 s" in l for l in lines)
    assert 11.0 - sum(got) == pytest.approx(0.5)


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_returns_none_without_a_tpu_plane(name, program):
    _, rec = program
    rec.emit("span", "jacobi.init", seconds=1.0, t0_ns=1, t1_ns=2)
    ctx = _ctx({"chips": [], "host": []}, [], phases={"app_run": 2.0})
    assert load_module("layer_metrics", name).read(ctx) is None


def test_an_older_program_gives_every_reader_none(monkeypatch):
    monkeypatch.setattr(scope_lib, "program", lambda: None)
    for name in NEW_READERS:
        ctx = _ctx(_trace(), [], phases={"app_run": 2.0})
        assert load_module("layer_metrics", name).read(ctx) is None


def test_the_new_entries_are_declared_with_their_cells():
    per_layer = {m["name"]: m for m in bench()["per_layer"]}
    assert set(NEW_READERS) <= set(per_layer)
    assert per_layer["self_fill_moved_roofline"]["workloads"] == [
        "exchange512.r3q4"]
    assert per_layer["halo_scope_ms.exch"]["workloads"] == [
        "exchange512.r3q4"]
    cells = [w["name"] for w in bench()["workloads"]]
    for part in ("host_init", "compile", "steps"):
        assert per_layer[f"app_run_{part}_s"]["workloads"] == cells
        assert per_layer[f"app_run_{part}_s"]["moves"] == "setup_s"
