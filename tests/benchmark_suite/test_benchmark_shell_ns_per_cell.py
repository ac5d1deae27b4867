"""The reader of ``shell_ns_per_cell``: device self time under
``stencil.sweep.shell`` an iteration, mean over chips, over ``shell_cells``
of the program's ``astaroth.step_plan`` record for the loop the trace ran;
nothing (never an exception) where the program recorded no plan, a plan
without shells, knows no such counter, or the trace holds no TPU plane."""

import pytest

from _bench_util import bench
from benchmark import scope_lib
from benchmark.harness import load_module

NAME = "shell_ns_per_cell"
MODULE = "stencil_astaroth_iter"
SHELL = "stencil.sweep.shell"
# the four-chip cell's record: (1,2,2), tight-x blocks of 256^3
PLAN_X4 = dict(module=MODULE, mode="overlap", pallas=True, tight_x=True,
               blocks=4, quantities=8, exchanges_per_iter=1, shells=4,
               shell_cells=777_216, block_cells=16_777_216,
               halo_bytes_sent=26_247_168)
PLAN_ONE = dict(PLAN_X4, mode="serial", blocks=1, shells=0, shell_cells=0,
                halo_bytes_sent=0)


def _op(scope, self_ns):
    return {"scope": scope, "self": self_ns}


def _ctx(lines=None, iterations=10):
    """Two chips, ten iterations; chip 1's shells cost twice chip 0's."""
    chips = [{"ops": [_op(SHELL, 30e6), _op(SHELL, 10e6),
                      _op("stencil.halo.unpack", 5e6), _op(None, 1e6)]},
             {"ops": [_op(SHELL, 80e6), _op("stencil.carry", 2e6)]}]
    ctx = {"trace": {"chips": chips}, "window": {"iterations": iterations},
           "say": (lines if lines is not None else []).append}
    # what scope_lib.scoped() leaves behind once it has joined the ops
    ctx["scoped"] = {"ms": {"kernel": 23.27, "halo": 2.0, "glue_program": 6.0,
                            "glue_compiler": 0.5},
                     "total_ms": 31.77, "omap": {}, "module": MODULE}
    return ctx


@pytest.fixture
def recorder(monkeypatch):
    from stencil_tpu.obs import telemetry

    rec = telemetry.Recorder()
    monkeypatch.setattr(telemetry, "_recorder", rec)
    return rec


def _read(ctx):
    return load_module("layer_metrics", NAME).read(ctx)


def test_shell_time_over_the_plans_shell_cells(recorder):
    recorder.counter("astaroth.step_plan", value=1, **PLAN_X4)
    lines = []
    # (30 + 10 + 80) ms over 2 chips and 10 iterations = 6 ms an iteration
    got = _read(_ctx(lines))
    assert got == pytest.approx(6e6 / 777_216)
    assert got * PLAN_X4["shell_cells"] == pytest.approx(6e6)
    assert any("shells=4" in l and "mode=overlap" in l
               and "shell_cells=777216" in l for l in lines)
    # the kernel's cost beside it: 23.27 ms over 3 x 16.8 M cells
    assert any("0.4623 ns a cell and substep" in l for l in lines)


def test_the_newest_plan_with_shells_of_the_traced_module(recorder):
    recorder.counter("astaroth.step_plan", value=1,
                     **dict(PLAN_X4, shell_cells=6_000_000))
    recorder.counter("astaroth.step_plan", value=1, **PLAN_X4)
    recorder.counter("astaroth.step_plan", value=1,
                     **dict(PLAN_X4, module="another_loop", shell_cells=1))
    assert _read(_ctx()) == pytest.approx(6e6 / 777_216)


def test_without_shells_or_without_the_counter_the_metric_is_left_out(
        recorder, monkeypatch):
    recorder.counter("loop.pingpong", value=1, module=MODULE)
    assert _read(_ctx()) is None                # the parent: no such record
    recorder.counter("astaroth.step_plan", value=1, **PLAN_ONE)
    assert _read(_ctx()) is None                # one block: no shells
    empty = _ctx()
    empty["scoped"] = None                      # a CPU rehearsal: no plane
    assert _read(empty) is None
    monkeypatch.setattr(scope_lib, "program", lambda: None)
    assert _read(_ctx()) is None                # a program without records()


def test_no_entry_lists_the_reader_while_no_cell_runs_shells():
    """PR 34 took the shells out of ``astaroth256x4.weak``'s plan and the
    metric read nothing there: PR 53 took the entry out. The reader stays,
    unlisted, for the day a cell's plan holds shells again (ROADMAP C14
    deletes it with the shells' path)."""
    assert NAME not in [m["name"] for m in bench()["per_layer"]]
