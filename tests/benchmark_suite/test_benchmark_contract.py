"""BENCHMARK.json against the builder's contract, and the rule that a
configuration states what a user passes and nothing the application
decides for itself."""

import json
import os
import re

import pytest

from _bench_util import BENCH_DIR, ROOT, bench

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# what a user can say on the command line of apps/jacobi3d.py,
# apps/astaroth.py or apps/exchange_weak.py
USER_ARGS = {"x", "y", "z", "nx", "iters", "weak", "dtype"}
EXPECTS = {"dtype", "radius", "quantities", "chips"}
CONFIG_KEYS = {"source", "reduced", "assumed", "why", "app", "args", "expects",
               "rehearsal_args", "kernels"}
APP_DECIDES = {"deep_halo", "partition", "method", "overlap", "multistep_rows",
               "temporal_k", "chunk", "use_pallas", "kernel_variant",
               "batch_quantities", "placement", "tight_x", "layout"}


def _config(entry):
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def test_top_level_keys_and_limits():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert 1 <= len(b["command"]) <= 32
    for word in b["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/")
        assert ".." not in word
    assert b["paths"] == ["benchmark", "tests/benchmark_suite"]
    # the full check of 24 cells fits the driver's day
    runs = 2 + 14 * 24
    assert runs * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_entry_keys():
    b = bench()
    names = []
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
        names.append(c["name"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        names.append(w["name"])
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        names.append(m["name"])
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and 1 <= len(m["layer"]) <= 200
        names.append(m["name"])
    for n in names:
        assert NAME.match(n), n
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads"):
        got = [e["name"] for e in b[group]]
        assert len(got) == len(set(got))
    metrics = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(metrics) == len(set(metrics))
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25
               for m in b["end_to_end"])


def test_cells_configs_and_metrics_fit_together():
    b = bench()
    configs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"]: w for w in b["workloads"]}
    assert {w["config"] for w in cells.values()} == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    assert len(pairs) == len(set(pairs))
    four = [w for w in cells.values() if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 2)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    for m in b["end_to_end"] + b["per_layer"]:
        for w in m.get("workloads", []):
            assert w in cells, (m["name"], w)
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", list(cells))
        for w in m.get("workloads", list(cells)):
            assert w in moved, (m["name"], w)
    for name in cells:
        reported = [m["name"] for m in b["end_to_end"]
                    if name in m.get("workloads", [name])]
        assert "setup_s" in reported and len(reported) >= 2, name
        assert any(name in m.get("workloads", [name]) for m in b["per_layer"])


@pytest.mark.parametrize("entry", bench()["configs"], ids=lambda c: c["name"])
def test_configuration_states_only_what_a_user_passes(entry):
    """No configuration can pin deep_halo, partition, method, overlap,
    multistep_rows or any other choice of the application's: the keys are
    the user-level list and no more."""
    assert entry["file"].startswith("benchmark/configs/")
    cfg = _config(entry)
    assert set(cfg) <= CONFIG_KEYS, set(cfg) - CONFIG_KEYS
    for group in ("args", "rehearsal_args"):
        assert set(cfg[group]) <= USER_ARGS, (group, set(cfg[group]) - USER_ARGS)
        assert not set(cfg[group]) & APP_DECIDES
    assert set(cfg["expects"]) <= EXPECTS
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert set(cfg["kernels"]) <= {"stencil", "halo"}
    for names in cfg["kernels"].values():
        for k in names:
            assert os.path.isfile(os.path.join(BENCH_DIR, "kernels", f"{k}.py"))
    assert os.path.isfile(os.path.join(BENCH_DIR, "apps", f"{cfg['app']}.py"))
    assert os.path.isfile(os.path.join(BENCH_DIR, "reference",
                                       f"{cfg['app']}.py"))


def test_every_named_file_exists_and_peaks_name_their_source():
    b = bench()
    for w in b["workloads"]:
        assert os.path.isfile(os.path.join(BENCH_DIR, "traffic",
                                           f"{w['traffic']}.json"))
    for m in b["end_to_end"]:
        assert os.path.isfile(os.path.join(BENCH_DIR, "end_to_end",
                                           f"{m['name']}.py"))
    for m in b["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH_DIR, "layer_metrics",
                                           f"{m['name']}.py"))
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["flops_per_s_bf16"] == 197e12
    assert "TPU v5e" in v5e["source"]


def test_reference_and_trace_reduction_import_nothing_of_the_program():
    for rel in ("trace_reduce.py", "fields.py", "bounds_check.py",
                "reference/jacobi3d.py", "reference/astaroth.py",
                "reference/exchange.py"):
        with open(os.path.join(BENCH_DIR, rel)) as f:
            text = f.read()
        assert "import stencil_tpu" not in text, rel
        assert "from stencil_tpu" not in text, rel


def test_harness_names_no_application_cell_metric_or_kernel():
    b = bench()
    with open(os.path.join(BENCH_DIR, "harness.py")) as f:
        text = f.read()
    words = [w["name"] for w in b["workloads"]]
    words += [m["name"] for m in b["end_to_end"] + b["per_layer"]
              if m["name"] != "setup_s"]
    words += ["jacobi", "astaroth", "exchange_weak", "multistep", "self_fill"]
    for w in words:
        assert w not in text, w
