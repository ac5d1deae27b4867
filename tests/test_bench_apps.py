"""Smoke tests for the benchmark apps (small sizes, virtual CPU mesh) —
ensures each produces the reference-format CSV and sane numbers."""

import jax
import numpy as np
import pytest

from stencil_tpu.apps import (
    bench_alltoall,
    bench_exchange,
    bench_link,
    bench_pack,
    bench_qap,
    exchange_strong,
    exchange_weak,
    machine_info,
    measure_overlap,
    pingpong,
)


def test_exchange_weak_csv():
    r = exchange_weak.run(8, 8, 8, iters=4, devices=jax.devices()[:8])
    row = exchange_weak.csv_row(r)
    parts = row.split(",")
    assert parts[0] == "exchange"
    assert len(parts) == 16
    assert r["trimean_s"] > 0
    assert r["bytes_logical"] > 0
    # weak scaling grew the domain for 8 devices
    assert r["x"] * r["y"] * r["z"] == 8 * 8 * 8 * 8


def test_exchange_strong_fixed_domain():
    r = exchange_strong.run(16, 16, 16, iters=2, devices=jax.devices()[:8])
    assert (r["x"], r["y"], r["z"]) == (16, 16, 16)


def test_exchange_weak_placement_flags():
    r = exchange_weak.run(8, 8, 8, iters=2, naive=True, devices=jax.devices()[:8])
    assert r["naive"] == 1


def test_bench_exchange_sweep():
    rows = bench_exchange.run(16, 16, 16, iters=2, devices=jax.devices()[:8])
    assert len(rows) == 5
    names = [r["config"].split("/")[1] for r in rows]
    assert names == ["px", "x", "faces", "face&edge", "uniform"]
    for r in rows:
        assert r["bytes"] > 0 and r["trimean_s"] > 0
    # faces-only moves more halo bytes than x-only
    assert rows[2]["bytes"] > rows[1]["bytes"]


def test_bench_exchange_method_ablation():
    rows, agree = bench_exchange.ablate(16, 16, 16, iters=2, devices=jax.devices()[:8])
    assert [r["config"].split("method=")[1] for r in rows] == [
        "axis-composed", "direct26", "auto-spmd",
    ]
    # identical logical bytes — only the movement strategy differs
    assert len({r["bytes"] for r in rows}) == 1 and rows[0]["bytes"] > 0
    # the CI gate: all three strategies deliver bit-identical halos
    assert agree
    # census columns: with quantity batching (the default) the manual
    # methods' counts are Q-independent — the harness's 4 quantities ride
    # packed carriers: composed 6 total, direct26 one per direction —
    # auto >= 1 synthesized permute and nothing else (the partitioner
    # still emits per-quantity permutes; its schedule is its own).
    by = {r["config"].split("method=")[1]: r for r in rows}
    assert by["axis-composed"]["cp_count"] == 6
    assert by["direct26"]["cp_count"] == 26
    assert by["auto-spmd"]["cp_count"] >= 1
    assert all(r["other_collectives"] == 0 for r in rows)
    assert all(r["cp_bytes"] > 0 for r in rows)
    # the ablation CSV has the census columns
    assert bench_exchange.ablate_row(rows[0]).count(",") == \
        bench_exchange.ablate_header().count(",")


def test_bench_pack_rows():
    rows = bench_pack.run(16, 16, 16, radius=2, iters=3)
    assert len(rows) == 26
    face = next(r for r in rows if r["dir"] == (1, 0, 0))
    corner = next(r for r in rows if r["dir"] == (1, 1, 1))
    assert face["bytes"] == 2 * 16 * 16 * 4
    assert corner["bytes"] == 2 * 2 * 2 * 4


def test_bench_qap_rows():
    rows = bench_qap.run(sizes=(4,), catch_sizes=(8,), timeout_s=1.0)
    assert any(r["solver"] == "exact-native" for r in rows) or any(
        r["solver"] == "exact-py" for r in rows
    )
    for r in rows:
        assert np.isfinite(r["cost"]) and r["s"] >= 0


def test_machine_info_report():
    r = machine_info.run(devices=jax.devices()[:8], size=64)
    text = machine_info.report(r)
    assert "8 device(s)" in text
    assert r["dist"].shape == (8, 8)
    assert r["partition"].flatten() == 8
    # distance diagonal is self-distance, off-diagonal same-process
    assert np.allclose(np.diag(r["dist"]), 0.1)


def test_bench_link_rows():
    rows = bench_link.run(sizes_kb=(16,), devices=jax.devices()[:8], iters=3, rounds=2)
    # 2x2x2 partition: all three axes measured
    assert {r["axis"] for r in rows} == {"x", "y", "z"}
    for r in rows:
        assert r["gb_per_s"] > 0 and r["devices_on_axis"] == 2
        assert csv_ok(bench_link.csv_row(r), "bench_link")


def test_bench_alltoall_rows():
    rows = bench_alltoall.run(sizes_kb=(16,), devices=jax.devices()[:4], iters=2, rounds=2)
    assert {r["strategy"] for r in rows} == {"all_to_all", "ring"}
    for r in rows:
        assert r["gb_per_s"] > 0
        assert csv_ok(bench_alltoall.csv_row(r), "bench_alltoall")


def test_alltoall_strategies_agree():
    # both strategies must implement the same transpose: seed distinct
    # payloads and check all_to_all vs ring deliver identical results
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()[:4]
    n = len(devs)
    mesh = Mesh(np.asarray(devs), ("i",))
    x = jnp.arange(n * n * 8, dtype=jnp.float32).reshape(n, n, 8)
    xs = jax.device_put(x, NamedSharding(mesh, P("i", None, None)))
    outs = {}
    for name, make in (("a2a", bench_alltoall._alltoall_body),
                       ("ring", bench_alltoall._ring_body)):
        fn = jax.jit(
            jax.shard_map(make(n), mesh=mesh, in_specs=P("i", None, None),
                          out_specs=P("i", None, None))
        )
        outs[name] = np.asarray(jax.device_get(fn(xs)))
    np.testing.assert_array_equal(outs["a2a"], outs["ring"])
    # and it is the blockwise transpose of the input
    want = np.asarray(x).reshape(n, n, 8).transpose(1, 0, 2)
    np.testing.assert_array_equal(outs["a2a"], want)


def csv_ok(row: str, prefix: str) -> bool:
    return row.startswith(prefix + ",") and len(row.split(",")) >= 5


def test_measure_overlap_row(tmp_path):
    r = measure_overlap.run(
        8, 8, 8, iters=2, rounds=2, devices=jax.devices()[:8],
        trace_dir=str(tmp_path / "trace"),
    )
    row = measure_overlap.csv_row(r)
    assert row.startswith("measure_overlap,8,")
    for k in ("compute_s", "exchange_s", "serial_s", "overlap_s"):
        assert r[k] > 0
    # serial = exchange + full sweep, so it cannot beat the compute floor
    assert r["serial_s"] > r["compute_s"] * 0.5
    # the profiler trace artifact was written
    assert any((tmp_path / "trace").rglob("*")), "no trace files written"


def test_pingpong_rows():
    rows = pingpong.run(min_bytes=8, max_bytes=128, iters=3, devices=jax.devices()[:2])
    assert len(rows) >= 2
    for r in rows:
        assert r["latency_us"] > 0 and r["gb_per_s"] > 0


def test_weak_scaling_harness_smoke():
    from stencil_tpu.apps import weak_scaling

    res = weak_scaling.run(
        devices=jax.devices()[:8],
        iters=2, jacobi_iters=2, overlap_rounds=1,
        per_chip=weak_scaling.Dim3(16, 16, 16),
        exw_per_chip=weak_scaling.Dim3(16, 16, 16),
        config2_global=weak_scaling.Dim3(16, 16, 16),
    )
    lines = weak_scaling.csv_rows(res)
    assert lines[0] == weak_scaling.CSV_HEADER
    assert len(lines) == 5
    names = [l.split(",")[0] for l in lines[1:]]
    assert names == [
        "config2_exchange", "config3_exchange_weak",
        "config5_jacobi_overlap", "config5_hidden_frac",
    ]
    for line in lines[1:]:
        parts = line.split(",")
        assert int(parts[4]) == 8
        assert float(parts[5]) > 0  # seconds
