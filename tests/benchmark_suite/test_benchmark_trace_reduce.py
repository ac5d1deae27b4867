"""``trace_reduce.py`` on small traces recorded on the v5e in PR 24 (cut to
their first program launches; ``data/``; the four-chip exchange cell's in PR
27, a traced run of 0.4 s), and its arithmetic on hand-made intervals. The
fixtures are keyed by the traces there are: a cell without a recorded trace
has no case here."""

import os

import pytest

from _bench_util import DATA, ROOT, bench  # noqa: F401
from benchmark import layer_lib, trace_reduce as tr
from benchmark.harness import load_json, load_module

MULTISTEP = ("%closed_call.1 = f32[514,528,512]{2,1,0:T(8,128)} custom-call("
             "f32[514,528,512]{2,1,0:T(8,128)} %bitcast, f32[514,528,512]"
             "{2,1,0:T(8,128)} %bitcast.1), custom_call_target=\"tpu_custom_call\""
             ", operand_layout_constraints={f32[514,528,512]{2,1,0}}")
WHILE = ("%while.6 = (s32[]{:T(128)}, f32[1,1,1,514,528,512]{5,4,3,2,1,0:T(8,128)})"
         " while((s32[]{:T(128)}, f32[1,1,1,514,528,512]{5,4,3,2,1,0:T(8,128)})"
         " %tuple.7), condition=%region_1.3, body=%region_0.2")


def test_parse_hlo_custom_call():
    op = tr.parse_hlo(MULTISTEP)
    assert op["instr"] == "closed_call.1" and op["opcode"] == "custom-call"
    assert op["target"] == "tpu_custom_call"
    assert op["results"] == [(514, 528, 512)]
    assert op["operands"] == [(514, 528, 512)] * 2
    assert tr.label(op) == "closed_call.1:custom-call:tpu_custom_call"
    assert tr.is_pallas(op)


def test_parse_hlo_tuple_result_and_bare_name():
    op = tr.parse_hlo(WHILE)
    assert op["opcode"] == "while" and op["instr"] == "while.6"
    assert op["results"] == [(), (1, 1, 1, 514, 528, 512)]
    bare = tr.parse_hlo("collective-permute-start.3")
    assert bare["opcode"] == "collective-permute-start"
    assert tr.COLLECTIVE.match(bare["opcode"])
    assert not tr.COLLECTIVE.match("copy-start")


def test_interval_arithmetic():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)] and tr.measure(u) == 6
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]


def test_self_time_is_duration_less_nested_events():
    ev = [("%a = f32[2]{0} while(f32[2]{0} %p)", 0.0, 100.0, None),
          ("%b = f32[2]{0} copy(f32[2]{0} %p)", 10.0, 30.0, None),
          ("%c = f32[2]{0} copy(f32[2]{0} %p)", 50.0, 40.0, None)]
    ops = {o["instr"]: o for o in tr._ops(ev)}
    assert ops["a"]["self"] == 30.0 and ops["b"]["self"] == 30.0
    assert ops["b"]["parent"] == "a"


def test_in_flight_pairs_start_with_done():
    chip = {"async": [], "ops": [
        dict(tr.parse_hlo("collective-permute-start.1"), start=0.0, dur=1.0),
        dict(tr.parse_hlo("fusion.1"), start=1.0, dur=4.0, cls="glue"),
        dict(tr.parse_hlo("collective-permute-done.1"), start=5.0, dur=3.0)]}
    assert tr.in_flight(chip) == [(0.0, 8.0)]
    # in flight for 8, another op runs for 4 of them
    assert tr.exposed_collective_ns(chip) == 4.0


def _ctx(cell):
    b = bench()
    w = next(c for c in b["workloads"] if c["name"] == cell)
    entry = next(c for c in b["configs"] if c["name"] == w["config"])
    config = load_json(ROOT, entry["file"])
    kernels = {role: {n: load_module("kernels", n) for n in names}
               for role, names in config["kernels"].items()}
    trace = tr.load(os.path.join(DATA, f"{cell}.xplane.pb"))
    return trace, kernels


# the pallas_call builds of each cell as its run recorded them (chip, PR 24)
BUILDS = {
    "jacobi512.steady": [{"kernel": "make_pallas_jacobi_multistep",
                          "grid": (532,), "out_shapes": [(514, 528, 512)],
                          "n_operands": 2}],
    "jacobi512x4.weak": [{"kernel": "make_pallas_jacobi_sweep", "grid": (512,),
                          "out_shapes": [(514, 528, 512)], "n_operands": 3}],
    "astaroth256.steady": [
        {"kernel": "make_pallas_substep", "grid": (2, 128),
         "out_shapes": [(262, 272, 256)] * 8, "n_operands": 16},
        {"kernel": "make_self_fill", "grid": (33,),
         "out_shapes": [(262, 272, 256)] * 8, "n_operands": 8}],
    "exchange512.r3q4": [{"kernel": "make_self_fill", "grid": (259,),
                          "out_shapes": [(518, 528, 640)] * 4,
                          "n_operands": 4}],
    # x and y are split: only the z fill is a kernel (chip, PR 27)
    "exchange512x4.r3q4": [{"kernel": "make_self_fill", "grid": (1,),
                            "out_shapes": [(518, 528, 640)] * 4,
                            "n_operands": 4}],
}
FACTS = {
    "jacobi512.steady": {"block_zyx": [512] * 3, "itemsize": 4, "quantities": 1,
                         "radius_zyx": [[1, 1], [1, 1], [0, 0]],
                         "padded_zyx": [514, 528, 512]},
    "jacobi512x4.weak": {"block_zyx": [512] * 3, "itemsize": 4, "quantities": 1,
                         "radius_zyx": [[1, 1], [1, 1], [0, 0]],
                         "padded_zyx": [514, 528, 512]},
    "astaroth256.steady": {"block_zyx": [256] * 3, "itemsize": 4,
                           "quantities": 8,
                           "radius_zyx": [[3, 3], [3, 3], [0, 0]],
                           "padded_zyx": [262, 272, 256]},
    "exchange512.r3q4": {"block_zyx": [512] * 3, "itemsize": 4, "quantities": 4,
                         "radius_zyx": [[3, 3], [3, 3], [3, 3]],
                         "padded_zyx": [518, 528, 640]},
}
FACTS["exchange512x4.r3q4"] = FACTS["exchange512.r3q4"]
# (program launches on chip 0, iterations per launch, chips, classes present)
EXPECT = {
    "jacobi512.steady": (6, 10, 1, {"stencil", "glue", "container"}),
    "jacobi512x4.weak": (2, 10, 4, {"stencil", "glue", "container",
                                    "collective"}),
    "astaroth256.steady": (4, 1, 1, {"stencil", "halo", "glue"}),
    "exchange512.r3q4": (4, 10, 1, {"halo", "container"}),
    "exchange512x4.r3q4": (2, 10, 4, {"halo", "collective", "glue",
                                      "container"}),
}


@pytest.mark.parametrize("cell", sorted(EXPECT))
def test_recorded_trace_reduces_to_sane_layer_numbers(cell):
    launches, k, chips, classes = EXPECT[cell]
    trace, kernels = _ctx(cell)
    assert len(trace["chips"]) == chips
    chip = trace["chips"][0]
    assert len(chip["modules"]) == launches
    assert {n for n, _, _ in trace["host"]} >= {"bench.dispatch", "bench.sync"}
    tr.classify(trace, kernels, BUILDS[cell])
    assert {op["cls"] for op in chip["ops"]} == classes
    # self times of all ops add up to the busy time of the chip
    busy = tr.busy_ns(chip)
    assert sum(op["self"] for op in chip["ops"]) == pytest.approx(busy, rel=1e-6)
    span = (max(m[1] + m[2] for m in chip["modules"])
            - min(m[1] for m in chip["modules"]))
    assert 0.5 * span < busy <= span
    gaps = tr.launch_gaps_ns(chip)
    assert len(gaps) == launches - 1 and all(0 < g < 5e6 for g in gaps)
    ctx = {"trace": trace, "kernels": kernels, "facts": FACTS[cell],
           "window": {"iterations": launches * k, "seconds": span / 1e9},
           "peak": {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12},
           "say": lambda _: None}
    for role in ("stencil", "halo"):
        share = layer_lib.roofline_share(ctx, role)
        if role not in classes:
            assert share is None
        elif role == "halo" and "collective" in classes:
            # part of the halo cells travel by the wire, and the kernel's
            # description counts every halo cell: its share means nothing
            # here (194 %), which is why BENCHMARK.json lists
            # self_fill_roofline in no cell on more than one chip
            assert share > 100, (role, share)
        else:
            assert 0 < share < 100, (role, share)
    bd = tr.breakdown(trace)
    assert 1 <= len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][1] > 0
    assert all(name.startswith("bench.") or name == "outside bench spans"
               for name, _ in bd["idle_gaps"])


def test_recorded_numbers_of_the_flagship_cell():
    """The 512^3 multistep call is 9.8 ms, and three 1.68 ms copies ride
    with it in every dispatch (PERF.md section 5)."""
    trace, kernels = _ctx("jacobi512.steady")
    tr.classify(trace, kernels, BUILDS["jacobi512.steady"])
    chip = trace["chips"][0]
    calls = [op for op in chip["ops"] if op["cls"] == "stencil"]
    assert len(calls) == 6
    assert all(9.7e6 < op["dur"] < 9.9e6 for op in calls)
    copies = [op for op in chip["ops"] if op["opcode"] == "copy"]
    assert len(copies) == 18 and all(1.6e6 < c["dur"] < 1.8e6 for c in copies)
    ctx = {"trace": trace, "window": {"iterations": 60, "seconds": 0.1}}
    assert layer_lib.class_ms_per_iter(ctx, ("stencil",)) == pytest.approx(
        0.98, abs=0.01)
    assert layer_lib.class_ms_per_iter(ctx, ("halo", "collective")) is None


def test_a_pallas_call_no_recorded_build_matches_is_glue():
    """No guess by elimination: a custom-call whose shapes no recorded build
    has is not credited to the one family built (its bytes would be another
    call's), it counts as glue."""
    trace, kernels = _ctx("jacobi512.steady")
    other = [dict(BUILDS["jacobi512.steady"][0], out_shapes=[(258, 528, 512)])]
    tr.classify(trace, kernels, other)
    calls = [op for op in trace["chips"][0]["ops"] if tr.is_pallas(op)]
    assert len(calls) == 6 and {op["cls"] for op in calls} == {"glue"}
    ctx = {"trace": trace, "window": {"iterations": 60, "seconds": 0.1}}
    assert layer_lib.class_ms_per_iter(ctx, ("stencil",)) is None


# a level's block: the box operator on it and the prolongation onto it have
# the same result shape and three operands each (chip, PR 52)
BLOCK = (514, 528, 512)
BOX = {"kernel": "make_pallas_mg_box", "name": "hpcg_resid",
       "out_shapes": [BLOCK], "n_operands": 3}
PROLONG = {"kernel": "make_pallas_hpcg_prolong", "name": "hpcg_prolong",
           "out_shapes": [BLOCK], "n_operands": 3}


def _call(instr, operands=3, shape=BLOCK):
    return {"instr": instr, "opcode": "custom-call", "target":
            "tpu_custom_call", "results": [shape],
            "operands": [shape] * operands}


@pytest.mark.parametrize("builds", [[BOX, PROLONG], [PROLONG, BOX]],
                         ids=["box built first", "transfer built first"])
def test_equal_shapes_and_operand_counts_are_told_apart_by_name(builds):
    """Never by build order: the build whose ``name=`` is the stem of the
    instruction's name."""
    assert tr.match_build(_call("hpcg_prolong.1"), builds) is PROLONG
    assert tr.match_build(_call("hpcg_resid.3"), builds) is BOX
    assert tr.match_build(_call("hpcg_resid"), builds) is BOX


def test_without_a_name_the_shapes_decide_as_before():
    builds = [BOX, PROLONG]
    # an older trace names no kernel; an older record holds no name
    assert tr.match_build(_call("closed_call.7"), builds) is BOX
    bare = [{k: v for k, v in b.items() if k != "name"} for b in builds]
    assert tr.match_build(_call("hpcg_prolong.1"), bare) is bare[0]
    # the operand count still comes first, a name never overrides a shape
    four = dict(PROLONG, n_operands=4)
    assert tr.match_build(_call("hpcg_prolong.1"), [BOX, four]) is BOX
    assert tr.match_build(_call("hpcg_prolong.1", shape=(258, 272, 256)),
                          builds) is None
    assert tr.match_build(_call("hpcg_prolong.1", operands=5),
                          [four]) is four       # by shape alone, as before


def test_hpcgs_transfers_are_classed_under_their_own_descriptions():
    """The four transfer calls of ``hpcg512.steady`` and the box calls on
    the levels they write, through ``classify`` with the configuration's
    own kernel lists."""
    config = load_json(ROOT, "benchmark", "configs", "hpcg-512-f32.json")
    kernels = {role: {n: load_module("kernels", n) for n in names}
               for role, names in config["kernels"].items()}
    mid, low = (258, 272, 256), (130, 144, 128)
    builds = [dict(BOX), dict(BOX, out_shapes=[mid]),
              dict(PROLONG), dict(PROLONG, out_shapes=[mid]),
              {"kernel": "make_pallas_hpcg_restrict", "name": "hpcg_restrict",
               "out_shapes": [mid], "n_operands": 3},
              {"kernel": "make_pallas_hpcg_restrict", "name": "hpcg_restrict",
               "out_shapes": [low], "n_operands": 3},
              # no description: never a candidate
              {"kernel": "make_something_else", "name": "hpcg_prolong",
               "out_shapes": [BLOCK], "n_operands": 3}]
    calls = {"hpcg_resid.3": (BLOCK, "mg_box27"),
             "hpcg_resid.2": (mid, "mg_box27"),
             "hpcg_prolong.1": (BLOCK, "hpcg_prolong"),
             "hpcg_prolong": (mid, "hpcg_prolong"),
             "hpcg_restrict.1": (mid, "hpcg_restrict"),
             "hpcg_restrict": (low, "hpcg_restrict")}
    ops = [dict(_call(instr, shape=shape), start=0.0, dur=1.0, self=1.0)
           for instr, (shape, _) in calls.items()]
    trace = {"chips": [{"ops": ops}]}
    tr.classify(trace, kernels, builds)
    assert {op["instr"]: (op["cls"], op["kernel"]) for op in ops} == {
        instr: ("stencil", kernel) for instr, (_, kernel) in calls.items()}
    assert all(op["build"]["out_shapes"] == op["results"] for op in ops)


def test_four_chip_trace_exposes_its_collectives():
    trace, kernels = _ctx("jacobi512x4.weak")
    tr.classify(trace, kernels, BUILDS["jacobi512x4.weak"])
    ctx = {"trace": trace, "window": {"iterations": 20, "seconds": 0.2}}
    exposed = layer_lib.collective_exposed_ms(ctx)
    assert 0 <= exposed < 1.0
    for chip in trace["chips"]:
        assert tr.in_flight(chip), "no collective found on a chip"


def test_four_chip_exchange_waits_over_a_millisecond_for_its_collectives():
    """1.63 ms an exchange with a ``ppermute`` in flight and nothing else
    running, on the worst chip (PERF.md section 5): the wire is not hidden,
    where the four-chip jacobi cell hides all but 0.07 ms of it."""
    cell = "exchange512x4.r3q4"
    trace, kernels = _ctx(cell)
    tr.classify(trace, kernels, BUILDS[cell])
    ctx = {"trace": trace, "window": {"iterations": 20, "seconds": 0.7}}
    exposed = load_module("layer_metrics",
                          "collective_exposed_ms.exch").read(ctx)
    assert 1.0 < exposed < 3.0
    halo = layer_lib.class_ms_per_iter(ctx, ("halo", "collective"))
    assert exposed < halo
    for chip in trace["chips"]:
        assert tr.in_flight(chip), "no collective found on a chip"


def test_a_cpu_trace_has_nothing_to_read(tmp_path):
    trace = {"chips": [], "host": []}
    ctx = {"trace": trace, "window": {"iterations": 10, "seconds": 1.0}}
    assert layer_lib.launch_gap_ms(ctx) is None
    assert layer_lib.idle_share(ctx) is None
    assert tr.breakdown(trace) == {"device_ops": [], "idle_gaps": []}
