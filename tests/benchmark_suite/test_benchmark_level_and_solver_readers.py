"""The two readers that waited for a ``benchmark`` PR (PR 53), on small
hand-made traces and op maps: ``mg_coarse_ms_per_iter`` sums every op under
the tag of a level that the program's newest ``mg.cycle_plan`` lays out
``inline``, ``solver_reduce_ms_per_iter`` every op whose innermost scope is
``stencil.solver.dot``; each prints its table and leaves its metric out
(never an exception) where there is nothing to read."""

import pytest

from benchmark import scope_lib
from benchmark.harness import load_module

PALLAS = "tpu_custom_call"
MG, HPCG = "stencil_mg_iter", "stencil_hpcg_iter"


def _line(instr, opcode, path, target=None):
    call = f', custom_call_target="{target}"' if target else ""
    meta = f', metadata={{op_name="jit(loop)/{path}"}}' if path else ""
    return (f"  %{instr} = f32[64,128]{{1,0:T(8,128)}} {opcode}(%p){call}"
            f"{meta}\n")


def _hlo(module, rows):
    return (f"HloModule jit_{module}, is_scheduled=true\n"
            "ENTRY %main (p: f32[64,128]) -> f32[64,128] {\n"
            "  %p = f32[64,128]{1,0:T(8,128)} parameter(0)\n"
            + "".join(_line(*row) for row in rows) + "}\n")


# (instruction, opcode, op_name path under the loop, custom-call target, ns)
MG_OPS = [
    ("mg_resid.1", "custom-call",
     "stencil.mg.level9/stencil.kernel.mg_resid/mg_resid/pallas_call",
     PALLAS, 400_000),
    ("self_fill_y.2", "custom-call",
     "stencil.mg.level9/stencil.halo.self_fill/stencil.kernel.self_fill_y/"
     "self_fill_y/pallas_call", PALLAS, 60_000),
    ("mg_psinv.3", "custom-call",
     "stencil.mg.level7/stencil.kernel.mg_psinv/mg_psinv/pallas_call",
     PALLAS, 70_000),
    # the coarse half of the V-cycle: ONE call under the first inline level
    ("mg_coarse.4", "custom-call",
     "stencil.mg.level6/stencil.kernel.mg_coarse/mg_coarse/pallas_call",
     PALLAS, 120_000),
    # an operator and a fill that plain XLA computes on an inline level
    ("add_fusion.5", "fusion",
     "stencil.mg.level5/stencil.kernel.mg_psinv/add", None, 30_000),
    ("slice_fusion.6", "fusion",
     "stencil.mg.level5/stencil.halo.self_fill/dynamic_update_slice", None,
     10_000),
    ("copy.7", "copy", "", None, 5_000),            # the compiler's: no tag
]
HPCG_OPS = [
    ("hpcg_spmv.1", "custom-call",
     "stencil.kernel.hpcg_spmv/hpcg_spmv/pallas_call", PALLAS, 1_800_000),
    ("multiply_reduce_fusion.1", "fusion", "stencil.solver.dot/reduce_sum",
     None, 2_700_000),
    ("multiply_reduce_fusion.2", "fusion",
     "stencil.mg.level4/stencil.solver.dot/reduce_sum", None, 2_800_000),
    ("multiply_add_fusion", "fusion", "stencil.solver.axpy/add", None,
     4_850_000),
    ("select_fusion.3", "fusion", "stencil.carry/select_n", None, 900_000),
]
PLAN = [{"level": 9, "layout": "tight_x"}, {"level": 7, "layout": "tight_x"},
        {"level": 6, "layout": "inline"}, {"level": 5, "layout": "inline"}]
ITERS = 2


def _trace(module, rows, chips=2):
    """``chips`` chips that ran the same one dispatch of 2 iterations."""
    out = []
    for c in range(chips):
        ops, t = [], 0.0
        for instr, opcode, _path, target, ns in rows:
            ops.append({"instr": instr, "opcode": opcode, "target": target,
                        "results": [(64, 128)], "operands": [(64, 128)],
                        "start": t, "dur": float(ns), "self": float(ns),
                        "cls": "glue"})
            t += ns
        out.append({"id": c, "ops": ops, "async": [],
                    "modules": [(f"jit_{module}(42)", 0.0, t)]})
    return {"chips": out, "host": []}


@pytest.fixture
def program(monkeypatch):
    from stencil_tpu.obs import scopes, telemetry

    monkeypatch.setattr(scopes, "_registry", {
        MG: [{"fn": None, "args": (),
              "text": _hlo(MG, [r[:4] for r in MG_OPS])}],
        HPCG: [{"fn": None, "args": (),
                "text": _hlo(HPCG, [r[:4] for r in HPCG_OPS])}]})
    rec = telemetry.Recorder()
    monkeypatch.setattr(telemetry, "_recorder", rec)
    return rec


def _ctx(trace, lines=None):
    return {"trace": trace, "say": (lines if lines is not None else []).append,
            "window": {"iterations": ITERS, "seconds": 0.001}}


def _read(name, trace, lines=None):
    return load_module("layer_metrics", name).read(_ctx(trace, lines))


# ------------------------------------------------------------ mg_coarse


def test_the_inline_levels_ops_are_summed_whatever_computes_them(program):
    program.counter("mg.cycle_plan", value=1, module=MG, levels=PLAN)
    lines = []
    got = _read("mg_coarse_ms_per_iter", _trace(MG, MG_OPS), lines)
    # the coarse call, the XLA operator and the XLA fill; mean over chips
    assert got == pytest.approx((120_000 + 30_000 + 10_000) / ITERS / 1e6)
    text = "\n".join(l for l in lines if l.startswith("mg levels:"))
    assert "0.0600 ms  level 6 (inline)  stencil.kernel.mg_coarse" in text
    assert "0.2000 ms  level 9 (tight_x)  stencil.kernel.mg_resid" in text
    assert "0.0050 ms  level 5 (inline)  stencil.halo.self_fill" in text
    assert "copy.7" not in text and text.count("\n") == 5    # six lines


def test_the_newest_plan_of_the_traced_module_is_the_one_read(program):
    program.counter("mg.cycle_plan", value=1, module=MG, levels=[
        dict(lv, layout="inline") for lv in PLAN])
    program.counter("mg.cycle_plan", value=1, module="another_loop",
                    levels=[dict(lv, layout="inline") for lv in PLAN])
    program.counter("mg.cycle_plan", value=1, module=MG, levels=PLAN)
    assert _read("mg_coarse_ms_per_iter", _trace(MG, MG_OPS, chips=1)) == \
        pytest.approx(0.08)


@pytest.mark.parametrize("why", ["no counter", "another module's plan",
                                 "no inline level", "no op under its tag",
                                 "no TPU plane", "an older program"])
def test_mg_coarse_is_left_out_where_there_is_nothing_to_read(
        why, program, monkeypatch):
    trace = _trace(MG, MG_OPS)
    if why == "another module's plan":
        program.counter("mg.cycle_plan", value=1, module=HPCG, levels=PLAN)
    elif why == "no inline level":
        program.counter("mg.cycle_plan", value=1, module=MG, levels=[
            dict(lv, layout="tight_x") for lv in PLAN])
    elif why == "no op under its tag":
        program.counter("mg.cycle_plan", value=1, module=MG, levels=[
            {"level": 1, "layout": "inline"}])
    elif why == "no TPU plane":
        program.counter("mg.cycle_plan", value=1, module=MG, levels=PLAN)
        trace = {"chips": [], "host": []}
    elif why == "an older program":
        program.counter("mg.cycle_plan", value=1, module=MG, levels=PLAN)
        monkeypatch.setattr(scope_lib, "program", lambda: None)
    assert _read("mg_coarse_ms_per_iter", trace) is None


# ------------------------------------------------------------ solver_reduce


def test_the_dots_are_summed_by_their_innermost_scope(program):
    lines = []
    got = _read("solver_reduce_ms_per_iter", _trace(HPCG, HPCG_OPS), lines)
    # both dots, the one on a multigrid level too; not the updates
    assert got == pytest.approx((2_700_000 + 2_800_000) / ITERS / 1e6)
    assert ("solver: 2.7500 ms an iteration under stencil.solver.dot, "
            "2.4250 ms under stencil.solver.axpy") in lines
    # both are part of glue_program_ms_per_iter
    assert load_module("layer_metrics", "glue_program_ms_per_iter").read(
        _ctx(_trace(HPCG, HPCG_OPS))) == pytest.approx(
            (2_700_000 + 2_800_000 + 4_850_000 + 900_000) / ITERS / 1e6)


def test_a_solver_without_updates_says_so(program):
    lines = []
    rows = [r for r in HPCG_OPS if "axpy" not in r[2]]
    assert _read("solver_reduce_ms_per_iter", _trace(HPCG, rows),
                 lines) == pytest.approx(2.75)
    assert any("no op under stencil.solver.axpy" in l for l in lines)


@pytest.mark.parametrize("why", ["no op under the scope", "no TPU plane",
                                 "an older program"])
def test_solver_reduce_is_left_out_where_there_is_nothing_to_read(
        why, program, monkeypatch):
    trace = _trace(HPCG, HPCG_OPS)
    if why == "no op under the scope":      # a loop with no reduction
        trace = _trace(MG, MG_OPS)
    elif why == "no TPU plane":
        trace = {"chips": [], "host": []}
    else:
        monkeypatch.setattr(scope_lib, "program", lambda: None)
    assert _read("solver_reduce_ms_per_iter", trace) is None
