"""The orchestration layer's readers (``benchmark/chunk_lib.py``) on
hand-made records: the chunk spans under the run's ``*.steps`` span give
what a chunk of the program's own loop costs over the window's dispatch and
what the host does between two; an exchange-only chunk counts between and
not over; nothing to read gives ``None`` and never an exception; the four
entries stand where they were appended, with the lists ISSUE 54 gave."""

import os

import pytest

from _bench_util import BENCH_DIR, bench, one_more
from benchmark import chunk_lib, scope_lib
from benchmark.harness import load_module

MS = 1_000_000
T0 = 1_700_000_000_000_000_000
APP = ["jacobi512.steady", "astaroth256.steady", "jacobi512x4.weak",
       "jacobi768.steady", "astaroth256x4.weak", "iso3dfd1024x4.steady"]
EXCH = ["exchange512.r3q4", "exchange512x4.r3q4"]
ENTRIES = {"chunk_over_window_ms.app": ("mcells_per_s_per_chip", APP),
           "chunk_over_window_ms.exch": ("exchange_ms", EXCH),
           "chunk_between_ms.app": ("mcells_per_s_per_chip", APP),
           "chunk_between_ms.exch": ("exchange_ms", EXCH)}
TRACE = {"chips": [{"id": 0, "ops": [], "async": [], "modules": []}],
         "host": []}


@pytest.fixture
def program(monkeypatch):
    """A recorder of its own holding what ``run()`` would have recorded."""
    from stencil_tpu.obs import telemetry

    rec = telemetry.Recorder()
    monkeypatch.setattr(telemetry, "_recorder", rec)
    return rec


def _chunk(rec, name, parent, at_ms, wall_ms, iters, fields=True):
    """One chunk span that started ``at_ms`` after ``T0``."""
    tags = dict(enqueue_s=0.2e-3, wait_s=wall_ms / 1e3 - 0.2e-3,
                sync="hard_sync", module="stencil_jacobi_loop"
                ) if fields else {}
    rec.emit("span", name, phase="step", seconds=wall_ms / 1e3 / iters,
             t0_ns=T0 + int(at_ms * MS),
             t1_ns=T0 + int((at_ms + wall_ms) * MS), parent=parent,
             iters=iters, **tags)


def _steps(rec, name, ms):
    rec.emit("span", name, phase="step", seconds=ms / 1e3, t0_ns=T0,
             t1_ns=T0 + int(ms * MS))


def _jacobi(rec, fields=True):
    """Four chunks of ten, 7.8 / 7.6 / 8.0 / 7.7 ms, 0.05 / 0.03 / 0.10 ms
    apart, then a tail chunk of three; another span under the steps."""
    at = 0.01
    for wall, gap in ((7.8, 0.05), (7.6, 0.03), (8.0, 0.10), (7.7, 0.04)):
        _chunk(rec, "jacobi.iter", "jacobi.steps", at, wall, 10, fields)
        at += wall + gap
    _chunk(rec, "jacobi.iter", "jacobi.steps", at, 2.5, 3, fields)
    rec.emit("span", "health.check", seconds=0.001, t0_ns=T0 + 40 * MS,
             t1_ns=T0 + 41 * MS, parent="jacobi.steps")
    _steps(rec, "jacobi.steps", 34.0)


def _ctx(lines=None, trace=TRACE, dispatch_ms=(6.9, 6.8, 6.7, 50.0, 6.8),
         k=10):
    return {"trace": trace, "say": (lines if lines is not None else []).append,
            "window": {"dispatch_s": [t / 1e3 for t in dispatch_ms],
                       "enqueue_s": [0.15e-3] * len(dispatch_ms),
                       "iters_per_dispatch": k, "iterations": k * 5,
                       "seconds": 0.08}}


def _read(name, ctx):
    return load_module("layer_metrics", name).read(ctx)


@pytest.mark.parametrize("kind", ["app", "exch"])
def test_synthetic_chunks_give_the_four_values_and_one_table(program, kind):
    _jacobi(program)
    lines = []
    ctx = _ctx(lines)
    # the median of the four chunks of TEN (7.75), less the window's 6.8
    assert _read(f"chunk_over_window_ms.{kind}", ctx) == pytest.approx(0.95)
    # the gaps between consecutive step chunks, the tail chunk's too
    assert _read(f"chunk_between_ms.{kind}", ctx) == pytest.approx(0.045)
    text = "\n".join(lines)
    assert all(l.startswith("chunks: ") for l in lines) and len(lines) == 6
    assert "jacobi.steps 0.0340 s holds 5 chunk(s)" in lines[0]
    assert "before the first 0.010 ms, after the last 0.170 ms" in lines[0]
    assert "0.045 ms (median of 4)" in lines[0]
    row = next(l for l in lines if "jacobi.iter" in l and " 10 " in l).split()
    assert row[1:7] == ["jacobi.iter", "4", "10", "7.750", "0.200", "7.550"]
    assert "hard_sync (stencil_jacobi_loop)" in text
    window = next(l for l in lines if "bench.*" in l)
    assert window.split()[4:9] == ["5", "10", "6.800", "0.150", "6.650"]
    assert "+0.950 ms against the window's dispatch" in lines[-1]
    # a second reader of the same run prints nothing more
    _read(f"chunk_between_ms.{kind}", ctx)
    assert len(lines) == 6


def test_one_chunk_gives_no_between(program):
    _chunk(program, "mg.iter", "mg.steps", 0.5, 14.0, 1)
    _steps(program, "mg.steps", 300.0)
    ctx = _ctx(dispatch_ms=(13.1, 13.0, 13.2), k=1)
    assert _read("chunk_between_ms.app", ctx) is None
    assert _read("chunk_over_window_ms.app", ctx) == pytest.approx(0.9)


def test_an_exchange_only_chunk_counts_between_and_not_over(program):
    """Astaroth's loop times an exchange-only program after each iteration:
    it is what the loop does between two step chunks."""
    for i in range(3):
        _chunk(program, "astaroth.iter", "astaroth.steps", 26.0 * i, 16.0, 1)
        _chunk(program, "astaroth.exchange", "astaroth.steps",
               26.0 * i + 16.02, 9.9, 1)
    _steps(program, "astaroth.steps", 78.0)
    lines = []
    ctx = _ctx(lines, dispatch_ms=(15.7, 15.6, 15.8), k=1)
    assert _read("chunk_over_window_ms.app", ctx) == pytest.approx(0.3)
    assert _read("chunk_between_ms.app", ctx) == pytest.approx(10.0)
    assert sum("astaroth.exchange" in l for l in lines) == 1
    # a run of exchange-only chunks alone (--no-compute) has no step chunk
    other = program.__class__()
    for i in range(3):
        _chunk(other, "astaroth.exchange", "astaroth.steps", 10.0 * i, 9.9, 3)
    _steps(other, "astaroth.steps", 30.0)
    out = chunk_lib.split(other.records(kind="span"))
    assert out["step_chunks"] == [] and out["between_s"] == []


def test_chunks_of_another_length_than_the_windows_give_no_over(program):
    _jacobi(program)
    ctx = _ctx(k=12)
    assert _read("chunk_over_window_ms.app", ctx) is None
    assert _read("chunk_between_ms.app", ctx) == pytest.approx(0.045)


def test_the_newest_steps_span_is_the_runs(program):
    _chunk(program, "jacobi.iter", "jacobi.steps", -90.0, 20.0, 10)
    program.emit("span", "jacobi.steps", seconds=0.05, t0_ns=T0 - 100 * MS,
                 t1_ns=T0 - 50 * MS)
    _jacobi(program)
    out = chunk_lib.split(program.records(kind="span"))
    assert len(out["chunks"]) == 5 and out["steps"]["t0_ns"] == T0


@pytest.mark.parametrize("why", ["spans without enqueue_s", "no chunk span",
                                 "no steps span", "no TPU plane",
                                 "an older program"])
@pytest.mark.parametrize("name", ENTRIES)
def test_a_reader_gives_none_where_there_is_nothing_to_read(
        name, why, program, monkeypatch):
    trace = TRACE
    if why == "spans without enqueue_s":
        _jacobi(program, fields=False)
    elif why == "no chunk span":
        _steps(program, "jacobi.steps", 34.0)
    elif why == "no steps span":
        _chunk(program, "jacobi.iter", "jacobi.steps", 0.0, 7.8, 10)
    elif why == "no TPU plane":
        _jacobi(program)
        trace = {"chips": [], "host": []}
    else:
        _jacobi(program)
        monkeypatch.setattr(scope_lib, "program", lambda: None)
    assert _read(name, _ctx(trace=trace)) is None


def test_the_reader_reads_what_a_real_run_records():
    """The program's own loop, tiny, on the CPU: its spans are the reader's
    input as they are (the host's times are no device metric: only their
    shape is held)."""
    import jax
    from stencil_tpu.apps._bench_common import time_exchange
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.obs import telemetry

    rec = telemetry.configure(heartbeat_thread=False)
    try:
        time_exchange(Dim3(16, 16, 16), Radius.constant(1), iters=5,
                      devices=jax.devices()[:1], quantities=1, chunk=2)
        lines = []
        ctx = _ctx(lines, dispatch_ms=(0.01,), k=2)
        out = chunk_lib.read(ctx)
    finally:
        telemetry.configure(heartbeat_thread=False)
    assert out["steps"]["name"] == "exchange.steps"
    assert [r["iters"] for r in out["step_chunks"]] == [2, 2, 1]
    assert len(out["between_s"]) == 2 and min(out["between_s"]) >= 0
    assert out["head_s"] >= 0 and out["tail_s"] >= 0
    assert out["over_ms"] > 0 and out["between_ms"] >= 0
    assert sum("exchange.iter" in l for l in lines) == 2    # by length
    assert "hard_sync (stencil_exchange_loop)" in "\n".join(lines)


def in_their_place(b: dict) -> bool:
    """The four entries one after the other, in their order, after
    ``solver_reduce_ms_per_iter``, the last entry that was there before
    them (not "the end of the list": a later PR appends after them)."""
    names = [m["name"] for m in b["per_layer"]]
    at = names.index("solver_reduce_ms_per_iter") + 1
    return names[at:at + len(ENTRIES)] == list(ENTRIES)


def _swapped(b):
    b = one_more(b)
    names = [m["name"] for m in b["per_layer"]]
    i, j = (names.index(n) for n in list(ENTRIES)[:2])
    b["per_layer"][i], b["per_layer"][j] = b["per_layer"][j], b["per_layer"][i]
    return b


@pytest.mark.parametrize("case, make, held", [
    ("as committed", lambda b: b, True),
    ("a cell and a metric appended", one_more, True),
    ("two of the four swapped", _swapped, False)])
def test_the_four_entries_are_held_to_their_order(case, make, held):
    assert in_their_place(make(bench())) is held, case


@pytest.mark.parametrize("name", ENTRIES)
def test_every_entry_has_its_file_and_exactly_its_cells(name):
    b = bench()
    entry = next(m for m in b["per_layer"] if m["name"] == name)
    moves, cells = ENTRIES[name]
    assert entry == {"name": name, "unit": "ms", "better": "lower",
                     "source": "program_span",
                     "layer": "Domain / orchestration", "moves": moves,
                     "workloads": cells}
    assert os.path.isfile(os.path.join(BENCH_DIR, "layer_metrics",
                                       f"{name}.py"))
    config_of = {w["name"]: w["config"] for w in b["workloads"]}
    exchange = [c for c in config_of if config_of[c].startswith("exchange")]
    if name.endswith(".exch"):
        assert cells == exchange        # only exchange cells, all of them
    else:
        assert not set(cells) & set(exchange)
    # in BENCHMARK.json's own order of cells
    assert cells == [c for c in config_of if c in cells]
