"""A later PR adds a configuration, a mix, a cell, a per-layer metric, an
end-to-end metric and a kernel as new files and entries only: shown on a
temporary copy of the benchmark, in which no file that was there is edited."""

import filecmp
import json
import os
import shutil

from _bench_util import BENCH_DIR, ROOT, run_py


def _copy(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    os.symlink(os.path.join(ROOT, "stencil_tpu"), root / "stencil_tpu")
    return root


def test_dummy_cell_config_mix_metric_and_kernel_are_data_only(tmp_path):
    root = _copy(tmp_path)
    bdir = root / "benchmark"
    before = {os.path.relpath(os.path.join(d, f), bdir)
              for d, _, fs in os.walk(bdir) for f in fs}

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
    for m in bench["end_to_end"]:
        if m["name"] == "exchange_ms":
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

    # nothing that was there was edited
    for rel in before:
        assert filecmp.cmp(bdir / rel, os.path.join(BENCH_DIR, rel),
                           shallow=False), rel
