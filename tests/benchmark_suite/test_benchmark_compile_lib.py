"""Set-up read from inside (``benchmark/compile_lib.py`` and the four
readers over it), on hand-made records: a stage counts under ``run()`` by
its ancestor, whatever lies outside is printed and not counted, each
``*.warmup`` prints what no stage covers, and a program without the records
or a trace without a TPU plane leaves the metrics out."""

import os

import pytest

from _bench_util import BENCH_DIR, bench, one_more
from benchmark import compile_lib, scope_lib
from benchmark.harness import load_module

READERS = {"app_run_trace_s": "s", "app_run_lower_s": "s",
           "app_run_backend_s": "s", "app_run_cache_misses": "count"}
S = 1_000_000_000           # ns
T0 = 1_700_000_000 * S      # run() starts here


@pytest.fixture
def program(monkeypatch):
    """A recorder of its own holding what ``run()`` would have recorded."""
    from stencil_tpu.obs import telemetry

    rec = telemetry.Recorder()
    monkeypatch.setattr(telemetry, "_recorder", rec)
    monkeypatch.setattr(telemetry, "_watcher", None)
    return rec


def _span(rec, name, start_s, seconds, parent=None, **tags):
    t0 = T0 + int(start_s * S)
    return rec.emit("span", name, seconds=seconds, t0_ns=t0,
                    t1_ns=t0 + int(seconds * S), parent=parent, **tags)


def _run(rec, missed=False):
    """An Astaroth run(): realize 1 s, init 4 s, a 20 s warmup holding the
    step's three stages (a build of 0.5 s inside it holds a fold), a second
    warmup of 1 s, steps; then the benchmark's own programs after run()."""
    loop = "stencil_astaroth_iter"
    _span(rec, "compile.other", 0.2, 0.25, "astaroth.realize", stage="backend",
          count=2, funs={"<lambda>": 0.25}, hits=1, misses=1 if missed else 0,
          missed=["<lambda>"] if missed else [])
    _span(rec, "astaroth.realize", 0, 1.0, mem_bytes_in_use=10,
          mem_peak_bytes=20)
    _span(rec, "astaroth.init", 1, 4.0, mem_bytes_in_use=1_100_000_000,
          mem_peak_bytes=1_200_000_000)
    _span(rec, "compile.other", 5.1, 0.125, "astaroth.build", stage="trace",
          count=3, funs={"arange": 0.125})
    _span(rec, "astaroth.build", 5.0, 0.5, "astaroth.warmup")
    for i in range(3):
        _span(rec, "kernel.trace", 6.0 + i, 0.75, "astaroth.warmup",
              kernel="astaroth_substep")
    _span(rec, "compile.trace", 5.5, 8.0, "astaroth.warmup", fun=loop,
          module=loop)
    _span(rec, "compile.lower", 13.5, 6.0, "astaroth.warmup", fun=loop,
          module=loop)
    _span(rec, "compile.backend", 19.5, 4.0, "astaroth.warmup", fun=loop,
          module=loop, cache="miss" if missed else "hit",
          retrieval_s=None if missed else 0.5)
    _span(rec, "astaroth.warmup", 5, 20.0)
    _span(rec, "compile.backend", 25.2, 0.5, "astaroth.warmup",
          fun="stencil_exchange_loop", module="stencil_exchange_loop",
          cache="hit", retrieval_s=0.25)
    _span(rec, "astaroth.warmup", 25, 1.0)
    _span(rec, "astaroth.iter", 26.0, 0.03, "astaroth.steps", iters=1)
    _span(rec, "astaroth.steps", 26, 0.1, mem_bytes_in_use=1_250_000_000,
          mem_peak_bytes=1_300_000_000)
    # the benchmark's own: seeding after run(), op_map after the window
    _span(rec, "compile.other", 27.0, 0.375, stage="backend", count=4,
          funs={"fill": 0.375}, hits=4, misses=0, missed=[])
    _span(rec, "compile.lower", 60.0, 2.0, fun=loop, module=loop)


def _ctx(lines, chips=True, window_s=4.0):
    """The window opens 30 s after run() started, on the spans' clock."""
    return {"trace": {"chips": [{"ops": []}] if chips else [], "host": []},
            "say": lines.append, "phases": {"app_run": 27.0},
            "window": {"iterations": 2, "seconds": window_s,
                       "t0_ns": T0 + 30 * S}}


def _read(ctx):
    return {name: load_module("layer_metrics", name).read(ctx)
            for name in READERS}


def test_the_readers_sum_by_ancestor_and_leave_out_what_is_outside(program):
    _run(program)
    lines = []
    got = _read(_ctx(lines))
    # the fold under astaroth.build counts through its ancestor; the
    # seeding fold and op_map's lowering, with no parent, do not
    assert got == {"app_run_trace_s": 8.125, "app_run_lower_s": 6.0,
                   "app_run_backend_s": 4.75, "app_run_cache_misses": 0}
    text = "\n".join(lines)
    assert "run() spent trace 8.125 + lower 6.000 + backend 4.750 s" in text
    assert ("astaroth.warmup 20.000 s; stages cover 18.125, no stage covers "
            "1.875") in text
    assert ("astaroth.warmup 1.000 s; stages cover 0.500, no stage covers "
            "0.500") in text
    assert ("stencil_astaroth_iter under astaroth.warmup: trace 8.000 (x1) "
            "[of it kernel.trace astaroth_substep x3 2.250], lower 6.000 "
            "(x1), backend 4.000 (x1; hit, read 0.500)") in text
    assert "(other) under astaroth.build: trace 0.125 (x3)" in text
    assert "device memory at its end 1.250 GB in use, peak so far 1.300 GB" \
        in text
    assert ("outside run(), after run(), before the window (seed, "
            "first_chunk_check, warmup): trace 0.000, lower 0.000, backend "
            "0.375") in text
    assert ("outside run(), after the window (checks, readers, op_map): "
            "trace 0.000, lower 2.000, backend 0.000") in text
    # the window's own clock places it: run() ended at 26.1 s, seeding at
    # 27.375 s, the window ran from 30 to 34 s, op_map lowered from 60 s on
    assert ("the measured window opened 3.900 s after run() and lasted "
            "4.000 s: no stage record lies inside it") in text
    # four readers, one table
    assert sum("run() spent" in line for line in lines) == 1


def test_a_cold_run_counts_its_misses_named_and_folded(program):
    _run(program, missed=True)
    lines = []
    got = _read(_ctx(lines))
    assert got["app_run_cache_misses"] == 2
    text = "\n".join(lines)
    assert "backend 4.000 (x1; miss)" in text
    assert "backend 0.250 (x2; 1 hit, 1 miss)" in text
    assert "missed: <lambda>" in text


def test_a_stage_inside_the_window_is_called_out(program):
    """``harness.run_window`` reads ``time.time_ns()`` as the window opens,
    the clock the program's spans carry: of a stage every 8 s after run(),
    the two that start inside a window from 30 to 50 s are named, and one
    that straddles its opening is inside it too."""
    _run(program)
    for at in (29.75, 35.0, 43.0, 51.0):
        _span(program, "compile.backend", at, 0.5,
              fun="stencil_astaroth_iter", module="stencil_astaroth_iter",
              cache="miss")
    lines = []
    _read(_ctx(lines, window_s=20.0))
    text = "\n".join(lines)
    assert ("outside run(), INSIDE THE MEASURED WINDOW: trace 0.000, lower "
            "0.000, backend 1.500") in text
    assert ("lasted 20.000 s: 3 STAGE RECORD(S) LIE INSIDE THE WINDOW"
            ) in text
    assert ("outside run(), after the window (checks, readers, op_map): "
            "trace 0.000, lower 2.000, backend 0.500") in text


def test_the_window_carries_the_clock_the_programs_spans_carry():
    """``run_window`` reads ``time.time_ns()`` once, as the window opens and
    before the clock it times with: the window lies after it, whole."""
    import contextlib
    import time

    from benchmark import harness

    class Session:
        facts = {"iters_per_dispatch": 1}

        def dispatch(self):
            return 0

    before = time.time_ns()
    window = harness.run_window(Session(), 0.02,
                                lambda name: contextlib.nullcontext())
    after = time.time_ns()
    assert window["dispatches"] > 0 and window["seconds"] >= 0.02
    assert before <= window["t0_ns"]
    assert window["t0_ns"] + int(window["seconds"] * 1e9) <= after + 5_000_000


def test_a_recompile_in_the_steps_is_a_child_of_the_steps(program):
    _run(program)
    _span(program, "compile.trace", 26.02, 0.0625, "astaroth.steps",
          fun="stencil_astaroth_iter", module="stencil_astaroth_iter")
    lines = []
    assert _read(_ctx(lines))["app_run_trace_s"] == 8.1875
    assert any("stencil_astaroth_iter under astaroth.steps: trace 0.062"
               in line for line in lines)


def test_the_old_app_run_readers_read_what_they_read(program):
    """The new records have a parent or another name: the three metrics
    that were there sum the same spans."""
    _run(program)
    lines = []
    ctx = _ctx(lines)
    got = [load_module("layer_metrics", f"app_run_{p}_s").read(ctx)
           for p in ("host_init", "compile", "steps")]
    assert got == [5.0, 21.0, 0.1]


@pytest.mark.parametrize("name", READERS)
def test_a_reader_returns_none_without_a_tpu_plane(name, program):
    _run(program)
    assert load_module("layer_metrics", name).read(
        _ctx([], chips=False)) is None


@pytest.mark.parametrize("name", READERS)
def test_an_older_program_gives_the_reader_none(name, program, monkeypatch):
    from stencil_tpu.obs import telemetry

    _run(program)
    monkeypatch.delattr(telemetry, "flush_compile_stages")
    assert load_module("layer_metrics", name).read(_ctx([])) is None
    monkeypatch.setattr(scope_lib, "program", lambda: None)
    assert load_module("layer_metrics", name).read(_ctx([])) is None


@pytest.mark.parametrize("name", READERS)
def test_a_program_that_recorded_no_stage_gives_the_reader_none(name, program):
    _span(program, "astaroth.warmup", 5, 20.0)
    lines = []
    assert load_module("layer_metrics", name).read(_ctx(lines)) is None
    assert lines == []


def test_a_parent_whose_record_is_gone_counts_by_its_name(program):
    loop = "stencil_jacobi_loop"
    _span(program, "compile.trace", 5.5, 1.5, "jacobi.warmup", fun=loop,
          module=loop)
    _span(program, "compile.trace", 6.5, 0.5, "jacobi.exchange_warmup",
          fun=loop, module=loop)
    lines = []
    assert _read(_ctx(lines))["app_run_trace_s"] == 1.5
    assert any("jacobi.warmup (its own record is not kept)" in line
               for line in lines)


def test_an_untraced_reading_places_no_window(program):
    _run(program)
    lines = []
    out = compile_lib.split(program.records(kind="span"))
    compile_lib.table(out, None, lines.append)
    text = "\n".join(lines)
    assert "the measured window" not in text
    assert "outside run(), after run(): " in text


@pytest.mark.parametrize("name", READERS)
def test_every_new_entry_has_its_reader_file_and_the_cells_of_the_split(name):
    per_layer = {m["name"]: m for m in bench()["per_layer"]}
    entry = per_layer[name]
    assert os.path.isfile(os.path.join(BENCH_DIR, "layer_metrics",
                                       f"{name}.py"))
    assert entry == dict(per_layer["app_run_compile_s"], name=name,
                         unit=READERS[name])
    assert entry["workloads"] == [w["name"] for w in bench()["workloads"]]
    assert in_their_place(bench())


def in_their_place(b: dict) -> bool:
    """The four entries one after the other, in their order, after
    ``halo_sent_share``, the last entry that was there before them. (Not
    "the end of the list": whatever a later PR appends comes after.)"""
    names = [m["name"] for m in b["per_layer"]]
    at = names.index("halo_sent_share") + 1
    return names[at:at + len(READERS)] == list(READERS)


def _moved(b, name, before=None):
    """``one_more(b)`` with one entry moved in front of another, or to the
    end."""
    b = one_more(b)
    entries = b["per_layer"]
    names = [m["name"] for m in entries]
    entry = entries.pop(names.index(name))
    names.remove(name)
    entries.insert(names.index(before) if before else len(entries), entry)
    return b


@pytest.mark.parametrize("case, make, held", [
    ("as committed", lambda b: b, True),
    ("a cell and a metric appended", one_more, True),
    ("two of the four swapped",
     lambda b: _moved(b, "app_run_lower_s", "app_run_trace_s"), False),
    ("one of the four moved to the end",
     lambda b: _moved(b, "app_run_trace_s"), False),
    ("an entry put between them and what was there",
     lambda b: _moved(b, "next_metric", "app_run_trace_s"), False)])
def test_the_four_are_held_to_their_order_not_to_the_end(case, make, held):
    assert in_their_place(make(bench())) is held, case
