"""Every cell walked on the CPU at its rehearsal size: the shape of the
result line, ``correct`` true on sound code, and never a result line or a
zero exit code off the chip."""

import json

import pytest

from _bench_util import CELLS, bench, rehearse, run_py


def _expected_metrics(cell, group):
    return {m["name"] for m in bench()[group]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_result_has_the_contracts_shape(cell):
    result, rc = rehearse(cell)
    assert rc == 3, "a rehearsal never exits 0"
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "stalls"}
    assert result["stalls"]["over_twice_the_median"] >= 0
    assert result["stalls"]["host"]["cpu_s"] > 0
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == _expected_metrics(cell, "end_to_end")
    units = {m["name"]: m["unit"] for m in bench()["end_to_end"]}
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == units[name]
        assert m["value"] > 0
    dev = result["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    chips = next(w["chips"] for w in bench()["workloads"] if w["name"] == cell)
    assert dev["count"] == chips and dev["platform"] == "cpu"
    json.dumps(result)


def test_rehearsal_walks_interpret_mode_kernels_and_reads_their_depth():
    """On the CPU the builders are told to interpret, so the recorded
    builds are the kernels' own: the multistep's depth is read off its
    grid, as on the chip."""
    from _bench_util import open_session
    from benchmark.harness import load_module

    session = open_session(CELLS[0])
    builds = {b["kernel"]: b for b in session.builds}
    assert all(b["interpret"] for b in session.builds)
    multi = builds["make_pallas_jacobi_multistep"]
    mod = load_module("kernels", "jacobi_multistep")
    nz = session.facts["block_zyx"][0]
    k = mod.depth(multi, session.facts)
    assert k == min(12, (nz - 1) // 2, session.facts["iters_per_dispatch"])
    assert multi["n_operands"] == 2 and len(multi["out_shapes"]) == 1


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_window_and_no_device_number(cell):
    """A CPU trace holds no TPU plane: every reader finds nothing to read
    and its metric is left out, never filled from the CPU."""
    result, rc = rehearse(cell, trace=1)
    assert rc == 3 and result["correct"] is True
    assert result["metrics"] == {}
    assert result["device"]["window_s"] > 0
    assert "busy_s" not in result["device"]
    assert result["breakdown"] == {"device_ops": [], "idle_gaps": []}


def test_rehearsal_process_prints_no_result_line_and_exits_3():
    p = run_py(["--workload", CELLS[0], "--seed", "2147483777", "--seconds",
                "0.3", "--trace", "0", "--rehearsal"])
    assert p.returncode == 3, p.stderr[-2000:]
    last = p.stdout.strip().splitlines()[-1]
    assert last.startswith("[bench] rehearsal complete")
    assert not last.lstrip().startswith("{")
    phases = [l for l in p.stdout.splitlines() if "setup phase" in l]
    assert [l.split()[3].rstrip(":") for l in phases] == [
        "import", "app_run", "seed", "first_chunk_check", "warmup"]
    assert any("compile cache before the window" in l
               for l in p.stdout.splitlines())


def test_without_a_chip_nothing_is_printed_and_the_exit_code_is_not_zero():
    p = run_py(["--workload", CELLS[0], "--seed", "1", "--seconds", "0.3",
                "--trace", "0"], env={"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr
