"""The reader of ``multistep_recompute_share``: 1 - rows_kept /
rows_computed of the program's ``kernel.multistep.staging`` record for the
loop the trace ran, and nothing (never an exception) where the program
recorded none, knows no such counter, or the trace holds no TPU plane."""

import pytest

from _bench_util import bench
from benchmark import scope_lib
from benchmark.harness import load_module

NAME = "multistep_recompute_share"
MODULE = "stencil_jacobi_loop"
TRACE = {"chips": [{"id": 0, "ops": [], "async": [],
                    "modules": [(f"jit_{MODULE}(42)", 0.0, 1e6)]}],
         "host": []}
# the 768^3 cell's record: k = 10 on 2 strips of 384 rows
ROWS_768 = dict(module=MODULE, k=10, rows=384, strips=2, halo_rows=32,
                rows_computed=7860, rows_kept=7680, vmem_bytes=41_975_808)
PLANES_512 = dict(module=MODULE, k=10, rows=0, strips=1, halo_rows=16,
                  rows_computed=5120, rows_kept=5120, vmem_bytes=35_684_352)


@pytest.fixture
def recorder(monkeypatch):
    from stencil_tpu.obs import telemetry

    rec = telemetry.Recorder()
    monkeypatch.setattr(telemetry, "_recorder", rec)
    return rec


def _read(trace=TRACE, lines=None):
    ctx = {"trace": trace, "say": (lines if lines is not None else []).append}
    return load_module("layer_metrics", NAME).read(ctx)


def test_row_strips_read_their_recompute_and_full_planes_zero(recorder):
    recorder.counter("kernel.multistep.staging", value=10, **ROWS_768)
    lines = []
    assert _read(lines=lines) == pytest.approx(100 * (1 - 7680 / 7860))
    assert _read() == pytest.approx(2.29, abs=0.005)
    assert any("rows=384" in l and "strips=2" in l and "k=10" in l
               for l in lines)
    # the newest build of the module is the one the window dispatched
    recorder.counter("kernel.multistep.staging", value=10, **PLANES_512)
    assert _read() == 0.0


def test_a_record_of_another_module_is_not_this_loops(recorder):
    recorder.counter("kernel.multistep.staging", value=10,
                     **dict(ROWS_768, module="stencil_jacobi_step"))
    assert _read() is None


def test_without_the_counter_the_metric_is_left_out(recorder, monkeypatch):
    recorder.counter("loop.pingpong", value=1, module=MODULE)
    assert _read() is None                      # the parent: no such record
    assert _read(trace={"chips": [], "host": []}) is None   # a CPU rehearsal
    monkeypatch.setattr(scope_lib, "program", lambda: None)
    assert _read() is None                      # a program without records()


def test_the_entry_names_the_cells_that_build_a_multistep():
    """Later cells may join the list; these three are where the reader has
    something to read today (the four-chip cell runs the same multistep on
    a deep halo: 1.7 % of its rows are recomputed)."""
    (m,) = [m for m in bench()["per_layer"] if m["name"] == NAME]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        "%", "lower", "program_counter", "Stencil kernels",
        "mcells_per_s_per_chip")
    assert {"jacobi512.steady", "jacobi768.steady",
            "jacobi512x4.weak"} <= set(m["workloads"])
