"""A later PR adds a configuration, a mix, a cell, a per-layer metric, an
end-to-end metric, a kernel and the cell's recorded sets (the seventh kind
of file a cell brings: ``tests/benchmark_suite/data/sets/<cell>.json``) as
new files and entries only: shown on a temporary copy of the benchmark and
of its tests, in which no file that was there is edited, the dummy cell runs
and the copy's own contract, bounds and declared-cells tests pass."""

import filecmp
import json
import os
import shutil
import subprocess
import sys

from _bench_util import ROOT, run_py

SUITE = os.path.join("tests", "benchmark_suite")
# the tests that read the names of cells out of BENCHMARK.json: the ones a
# new cell used to fail until a file that was there was edited, and (since
# PR 53) the ones that hold an older entry to its place in a list, which an
# appended cell or metric failed while they held it to the END
RULES = ["test_benchmark_contract.py", "test_benchmark_bounds.py",
         "test_benchmark_scope_lib.py"
         "::test_the_new_entries_are_declared_with_their_cells",
         "test_benchmark_compile_lib.py"
         "::test_every_new_entry_has_its_reader_file_and_the_cells_of_the_split",
         "test_benchmark_lbm.py"
         "::test_the_cell_joins_mg512s_metrics_and_brings_none_of_its_own",
         "test_benchmark_mg.py"
         "::test_the_cell_joins_the_shared_metrics_and_brings_none_of_its_own",
         "test_benchmark_hpcg.py"
         "::test_the_cell_joins_the_shared_metrics_and_brings_none_of_its_own"]


def _copy(tmp_path):
    root = tmp_path / "checkout"
    for rel in ("benchmark", SUITE):
        shutil.copytree(os.path.join(ROOT, rel), root / rel,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    os.symlink(os.path.join(ROOT, "stencil_tpu"), root / "stencil_tpu")
    return root


def _files(root):
    return {os.path.relpath(os.path.join(d, f), root)
            for rel in ("benchmark", SUITE)
            for d, _, fs in os.walk(root / rel) for f in fs
            if "__pycache__" not in d}


def _rules_pass(root):
    """The copy's own tests, in a process of their own: ``_bench_util``
    finds its root from where it lies, so they read the copy."""
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly",
         *(os.path.join(SUITE, r) for r in RULES)],
        cwd=root, capture_output=True, text=True, timeout=600)
    return p.returncode == 0, p.stdout[-3000:] + p.stderr[-1500:]


def test_dummy_cell_config_mix_metric_and_kernel_are_data_only(tmp_path):
    root = _copy(tmp_path)
    bdir = root / "benchmark"
    before = _files(root)

    src = json.loads((bdir / "configs" / "exchange-512-r3-q4.json").read_text())
    src.update(source="a dummy deployment for the data-only test",
               rehearsal_args={"x": 8, "y": 16, "z": 24, "iters": 6},
               kernels={"stencil": [], "halo": ["dummy_kernel"]})
    (bdir / "configs" / "dummy-config.json").write_text(json.dumps(src))
    mix = json.loads((bdir / "traffic" / "r3q4.json").read_text())
    mix.update(iters_per_dispatch=3, why="three exchanges a dispatch")
    (bdir / "traffic" / "dummy_mix.json").write_text(json.dumps(mix))
    (bdir / "kernels" / "dummy_kernel.py").write_text(
        'FAMILIES = ("make_self_fill",)\n\n\n'
        'def work(build, facts):\n'
        '    return {"per": "iteration", "bytes": 1, "flops": 0, "note": "x"}\n')
    (bdir / "layer_metrics" / "dummy.layer-metric.py").write_text(
        'def read(ctx):\n'
        '    return float(ctx["window"]["iters_per_dispatch"]) '
        '+ len(ctx["kernels"]["halo"])\n')
    (bdir / "end_to_end" / "dummy_e2e.py").write_text(
        'def read(ctx):\n    return float(ctx["window"]["dispatches"])\n')

    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "dummy-config", "source": "none",
                             "file": "benchmark/configs/dummy-config.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy.cell", "config": "dummy-config",
                               "traffic": "dummy_mix", "chips": 1,
                               "why": "test"})
    # its adapter is the exchange's: it joins what every exchange cell reads
    joins = {"exchange_ms", "halo_scope_ms.exch", "launch_gap_ms.exch",
             "device_idle_share.exch", "app_run_host_init_s",
             "app_run_compile_s", "app_run_steps_s", "app_run_trace_s",
             "app_run_lower_s", "app_run_backend_s", "app_run_cache_misses"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in joins:
            m["workloads"].append("dummy.cell")
    bench["end_to_end"].append({"name": "dummy_e2e", "unit": "n",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["dummy.cell"]})
    bench["per_layer"].append({"name": "dummy.layer-metric", "unit": "n",
                               "better": "higher", "source": "program_counter",
                               "layer": "Dummy", "moves": "dummy_e2e",
                               "workloads": ["dummy.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    steady = [100.0, 100.1, 99.9, 100.05, 99.95, 100.02]
    (root / SUITE / "data" / "sets" / "dummy.cell.json").write_text(json.dumps(
        {"recorded": "PR 0, commit none: made up for the data-only test",
         "sets": {k: {"exchange_ms": steady, "dummy_e2e": steady,
                      "setup_s": steady} for k in ("1", "2")}}))

    def rehearsal_line(trace):
        p = run_py(["--workload", "dummy.cell", "--seed", "9", "--seconds",
                    "0.2", "--trace", str(trace), "--rehearsal"], cwd=str(root))
        assert p.returncode == 3, p.stdout[-1500:] + p.stderr[-1500:]
        line = next(l for l in p.stdout.splitlines()
                    if "rehearsal line (not a result): " in l)
        return json.loads(line.split("(not a result): ", 1)[1]), p.stdout

    result, out = rehearsal_line(0)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"exchange_ms", "dummy_e2e", "setup_s"}
    assert result["metrics"]["dummy_e2e"]["value"] == result["attempted"]
    assert "global_xyz = Dim3(8,16,24)" in out
    assert "exchanges_per_dispatch = 3" in out

    traced, _ = rehearsal_line(1)
    # 3 iterations a dispatch + 1 halo kernel description
    assert traced["metrics"] == {"dummy.layer-metric": {"value": 4.0,
                                                        "unit": "n"}}

    # the rules the tests hold every cell to hold the dummy cell too
    ok, out = _rules_pass(root)
    assert ok, out
    # and they do look: without its recorded sets the new cell alone fails
    os.remove(root / SUITE / "data" / "sets" / "dummy.cell.json")
    ok, out = _rules_pass(root)
    assert not ok, out
    missing = [l for l in out.splitlines() if l.startswith("FAILED")
               and "test_cell_brings_its_two_recorded_sets" in l]
    assert len(missing) == 1 and missing[0].endswith("[dummy.cell]"), out

    # nothing that was there was edited
    assert before <= _files(root)
    for rel in before:
        assert filecmp.cmp(root / rel, os.path.join(ROOT, rel),
                           shallow=False), rel
