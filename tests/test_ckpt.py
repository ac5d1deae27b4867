"""Elastic checkpoint/restart (stencil_tpu/ckpt/) tests.

Pins the subsystem's acceptance contract (ISSUE 4):

- round-trip bit-exactness: save at step k, restore, continue to step n
  equals an uninterrupted n-step run — fp32 and fp64, uniform and uneven
  partitions, and an oversubscribed (resident-block) config;
- elastic restore parity: a (2,2,2)x8-device snapshot restores
  bit-identically onto (1,2,4)x8, onto 4 devices (oversubscribed), and
  onto 1 device — and CONTINUES identically there;
- crash-safety: truncated/missing payloads are rejected by validation
  and skipped by auto-resume (fallback to the previous good snapshot);
  LATEST never names a partial snapshot; retention keeps the newest N;
- the async double-buffered writer produces the same durable snapshots
  as the synchronous path;
- ckpt_tool inspect/validate/diff exit codes.

The filesystem-protocol tests build snapshots from a bare GridSpec +
numpy state (no domain, no compile) so they stay fast.
"""

import json
import os

import jax
import numpy as np
import pytest

from stencil_tpu.api import DistributedDomain
from stencil_tpu.ckpt import (
    AsyncCheckpointer,
    find_resume,
    list_snapshots,
    load_manifest,
    read_latest,
    snapshot_name,
    step_of,
    validate_snapshot,
    write_snapshot,
)
from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.ops.jacobi import INIT_TEMP, make_jacobi_step, sphere_sel
from stencil_tpu.parallel.exchange import shard_blocks


def coord_field(g: Dim3, dtype) -> np.ndarray:
    z, y, x = np.meshgrid(
        np.arange(g.z), np.arange(g.y), np.arange(g.x), indexing="ij"
    )
    return (x + y * 1_000 + z * 1_000_000).astype(dtype)


def make_domain(size, dtype, partition=None, ndev=8, radius=1):
    dd = DistributedDomain(*size)
    dd.set_radius(radius)
    dd.set_devices(jax.devices()[:ndev])
    if partition is not None:
        dd.set_partition(partition)
    h = dd.add_data("temperature", dtype)
    dd.realize()
    return dd, h


def run_steps(dd, h, n: int):
    """Advance the domain's curr state by n jacobi steps (fused per-call,
    like the apps: exchange + sweep + swap inside one jit)."""
    step = make_jacobi_step(dd.halo_exchange, overlap=True)
    sel = shard_blocks(sphere_sel(dd.size), dd.spec, dd.mesh)
    curr, nxt = dd.get_curr(h), dd.get_next(h)
    for _ in range(n):
        curr, nxt = step(curr, nxt, sel)
    dd.set_curr(h, curr)
    dd.set_next(h, nxt)


# -- round-trip bit-exactness (save at k, restore, continue to n) ------------


@pytest.mark.parametrize(
    "dtype,size,partition,ndev",
    [
        ("float32", (12, 12, 8), (2, 2, 2), 8),   # uniform
        ("float64", (13, 11, 9), (2, 2, 2), 8),   # uneven (remainder rule)
        ("float32", (12, 12, 8), (2, 2, 2), 4),   # oversubscribed residents
    ],
    ids=["fp32-uniform", "fp64-uneven", "fp32-oversubscribed"],
)
def test_continue_matches_uninterrupted(tmp_path, dtype, size, partition, ndev):
    k, n = 2, 4
    init = np.full((size[2], size[1], size[0]), INIT_TEMP, dtype)

    dd, h = make_domain(size, dtype, partition, ndev)
    dd.set_curr_global(h, init)
    run_steps(dd, h, n)
    want = dd.get_curr_global(h)

    dd1, h1 = make_domain(size, dtype, partition, ndev)
    dd1.set_curr_global(h1, init)
    run_steps(dd1, h1, k)
    dd1.save_checkpoint(str(tmp_path), k, asynchronous=False)

    dd2, h2 = make_domain(size, dtype, partition, ndev)
    assert dd2.restore_checkpoint(str(tmp_path)) == k
    run_steps(dd2, h2, n - k)
    got = dd2.get_curr_global(h2)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


# -- elastic restore parity ---------------------------------------------------


def test_elastic_restore_across_partitions(tmp_path):
    """A (2,2,2)/8-device snapshot restores bit-identically onto (1,2,4),
    onto 4 devices (oversubscribed), and onto 1 device — and the (1,2,4)
    target CONTINUES bit-identically to the saver's own continuation."""
    size, dtype, k, n = (12, 12, 8), "float32", 2, 4
    init = np.full((size[2], size[1], size[0]), INIT_TEMP, dtype)

    dd, h = make_domain(size, dtype, (2, 2, 2), 8)
    dd.set_curr_global(h, init)
    run_steps(dd, h, k)
    dd.save_checkpoint(str(tmp_path), k, asynchronous=False)
    saved_global = dd.get_curr_global(h)
    run_steps(dd, h, n - k)
    want_final = dd.get_curr_global(h)

    for partition, ndev in [((1, 2, 4), 8), ((2, 2, 2), 4), ((1, 1, 1), 1)]:
        dd2, h2 = make_domain(size, dtype, partition, ndev)
        assert dd2.restore_checkpoint(str(tmp_path)) == k, (partition, ndev)
        np.testing.assert_array_equal(
            dd2.get_curr_global(h2), saved_global
        ), (partition, ndev)

    dd3, h3 = make_domain(size, dtype, (1, 2, 4), 8)
    assert dd3.restore_checkpoint(str(tmp_path)) == k
    run_steps(dd3, h3, n - k)
    np.testing.assert_array_equal(dd3.get_curr_global(h3), want_final)


def test_restore_falls_back_past_incompatible_newer_snapshot(tmp_path):
    """A newer VALID snapshot from a different domain shape (the bench
    CPU-fallback scenario) must not shadow an older compatible one: the
    compatibility check joins the fallback chain."""
    g = coord_field(Dim3(12, 12, 8), "float32")
    dd, h = make_domain((12, 12, 8), "float32", (2, 2, 2), 8)
    dd.set_curr_global(h, g)
    dd.save_checkpoint(str(tmp_path), 5, asynchronous=False)
    # a different campaign writes a newer snapshot into the same dir
    other, _ = make_domain((16, 12, 8), "float32", (2, 2, 2), 8)
    other.save_checkpoint(str(tmp_path), 9, asynchronous=False)
    assert read_latest(str(tmp_path)) == snapshot_name(9)

    dd2, h2 = make_domain((12, 12, 8), "float32", (1, 2, 4), 8)
    assert dd2.restore_checkpoint(str(tmp_path)) == 5
    np.testing.assert_array_equal(dd2.get_curr_global(h2), g)


def test_restore_incompatible_returns_none(tmp_path):
    dd, h = make_domain((12, 12, 8), "float32", (2, 2, 2), 8)
    dd.save_checkpoint(str(tmp_path), 1, asynchronous=False)
    # different global size -> no compatible snapshot, never an exception
    dd2, _ = make_domain((16, 12, 8), "float32", (2, 2, 2), 8)
    assert dd2.restore_checkpoint(str(tmp_path)) is None
    # different dtype -> bit-exact restore impossible, refused
    dd3 = DistributedDomain(12, 12, 8)
    dd3.set_radius(1)
    dd3.set_devices(jax.devices()[:8])
    dd3.set_partition((2, 2, 2))
    dd3.add_data("temperature", "float64")
    dd3.realize()
    assert dd3.restore_checkpoint(str(tmp_path)) is None
    # empty/missing dir -> None
    assert dd2.restore_checkpoint(str(tmp_path / "nope")) is None


# -- filesystem protocol (bare GridSpec + numpy, no compile) ------------------


def small_spec():
    return GridSpec(Dim3(8, 6, 4), Dim3(2, 1, 1), Radius.constant(1))


def host_state(spec, seed=0):
    rng = np.random.RandomState(seed)
    return {"q": rng.rand(*spec.stacked_shape_zyx()).astype(np.float32)}


def test_write_protocol_latest_and_retention(tmp_path):
    spec = small_spec()
    d = str(tmp_path)
    for step in (1, 2, 3, 4, 5):
        write_snapshot(d, step, spec, host_state(spec, step), keep=3)
    assert list_snapshots(d) == [snapshot_name(s) for s in (3, 4, 5)]
    assert read_latest(d) == snapshot_name(5)
    assert step_of(snapshot_name(5)) == 5
    for s in (3, 4, 5):
        assert validate_snapshot(os.path.join(d, snapshot_name(s))) == []


def test_rewrite_same_step_never_deletes_before_publish(tmp_path):
    """Overwriting an existing step moves the old snapshot aside (rename)
    rather than rmtree'ing it first — a crash between the renames leaves
    the old state on disk instead of losing the newest durable step. The
    completed rewrite replaces the content and leaves no leftovers."""
    spec = small_spec()
    d = str(tmp_path)
    write_snapshot(d, 2, spec, host_state(spec, 1), keep=3)
    old = np.load(os.path.join(d, snapshot_name(2), "block_0_0_0.npz"))["q"]
    write_snapshot(d, 2, spec, host_state(spec, 9), keep=3)
    new = np.load(os.path.join(d, snapshot_name(2), "block_0_0_0.npz"))["q"]
    assert not np.array_equal(old, new)
    assert validate_snapshot(os.path.join(d, snapshot_name(2))) == []
    assert list_snapshots(d) == [snapshot_name(2)]
    assert not [e for e in os.listdir(d) if e.startswith(".tmp-")]


def test_resume_past_target_never_relabels(tmp_path):
    """jacobi3d resumed with --iters BELOW the checkpointed step runs
    nothing and must NOT re-label the further-along snapshot as the
    smaller step (campaign step accounting stays truthful)."""
    from stencil_tpu.apps.jacobi3d import run

    d = str(tmp_path)
    run(8, 8, 8, iters=2, weak=False, devices=jax.devices()[:1],
        warmup=0, ckpt_dir=d)
    assert list_snapshots(d) == [snapshot_name(2)]
    r = run(8, 8, 8, iters=1, weak=False, devices=jax.devices()[:1],
            warmup=0, ckpt_dir=d, resume=True)
    assert list_snapshots(d) == [snapshot_name(2)]  # untouched
    assert not np.isfinite(r["iter_trimean_s"])  # nothing was timed


def test_truncated_payload_rejected_and_skipped(tmp_path):
    spec = small_spec()
    d = str(tmp_path)
    write_snapshot(d, 1, spec, host_state(spec, 1), keep=5)
    write_snapshot(d, 2, spec, host_state(spec, 2), keep=5)
    victim = os.path.join(d, snapshot_name(2), "block_0_0_0.npz")
    with open(victim, "r+b") as f:
        f.truncate(10)
    errs = validate_snapshot(os.path.join(d, snapshot_name(2)))
    assert errs and "truncated" in errs[0]
    # auto-resume skips the bad snapshot, falls back to the good one
    snap, manifest = find_resume(d)
    assert manifest["step"] == 1
    # LATEST itself still names the (now bad) newest — the pointer is only
    # ever moved AFTER a complete snapshot landed, so it cannot name a
    # .tmp partial; corruption-after-the-fact is find_resume's job
    assert read_latest(d) == snapshot_name(2)


def test_missing_payload_and_hash_mismatch(tmp_path):
    spec = small_spec()
    d = str(tmp_path)
    snap = write_snapshot(d, 3, spec, host_state(spec), keep=2)
    os.remove(os.path.join(snap, "block_0_0_1.npz"))
    errs = validate_snapshot(snap)
    assert any("missing payload" in e for e in errs)

    snap2 = write_snapshot(d, 4, spec, host_state(spec), keep=2)
    path = os.path.join(snap2, "block_0_0_0.npz")
    size = os.path.getsize(path)
    with open(path, "r+b") as f:  # same size, flipped bytes
        f.seek(size // 2)
        f.write(b"\xff\xff\xff\xff")
    errs = validate_snapshot(snap2)
    assert any("SHA-256 mismatch" in e for e in errs)
    assert validate_snapshot(snap2, deep=False) == []  # shallow skips hashes


def test_partial_tmp_dir_is_invisible(tmp_path):
    spec = small_spec()
    d = str(tmp_path)
    write_snapshot(d, 1, spec, host_state(spec), keep=3)
    # a crashed writer leaves a .tmp- dir: never listed, never resumed
    os.makedirs(os.path.join(d, ".tmp-step-00000099-123"))
    assert list_snapshots(d) == [snapshot_name(1)]
    snap, manifest = find_resume(d)
    assert manifest["step"] == 1


def test_resume_prefers_newest_even_when_latest_lags(tmp_path):
    """A crash between publishing a snapshot and moving LATEST leaves an
    intact step newer than the pointer; resume must take the newest valid
    snapshot, not the pointer's (LATEST is the floor, not the ceiling)."""
    from stencil_tpu.ckpt.snapshot import _write_latest

    spec = small_spec()
    d = str(tmp_path)
    write_snapshot(d, 1, spec, host_state(spec, 1), keep=5)
    write_snapshot(d, 2, spec, host_state(spec, 2), keep=5)
    _write_latest(d, snapshot_name(1))  # simulate the crash window
    snap, manifest = find_resume(d)
    assert manifest["step"] == 2


def test_latest_pointing_at_removed_snapshot_falls_back(tmp_path):
    spec = small_spec()
    d = str(tmp_path)
    write_snapshot(d, 1, spec, host_state(spec, 1), keep=5)
    write_snapshot(d, 2, spec, host_state(spec, 2), keep=5)
    import shutil

    shutil.rmtree(os.path.join(d, snapshot_name(2)))
    snap, manifest = find_resume(d)
    assert manifest["step"] == 1


def test_manifest_contents(tmp_path):
    spec = small_spec()
    snap = write_snapshot(str(tmp_path), 7, spec, host_state(spec), keep=1)
    m = load_manifest(snap)
    assert m["v"] == 1 and m["kind"] == "stencil-ckpt" and m["step"] == 7
    assert m["global"] == {"x": 8, "y": 6, "z": 4}
    assert m["partition"] == {"x": 2, "y": 1, "z": 1}
    assert [q["name"] for q in m["quantities"]] == ["q"]
    assert len(m["files"]) == spec.num_blocks()
    for fe in m["files"]:
        assert fe["bytes"] > 0 and len(fe["sha256"]) == 64
        # interiors only: recorded size is the logical block size
        ix, iy, iz = fe["block"]
        s = spec.block_size((ix, iy, iz))
        assert fe["size"] == [s.x, s.y, s.z]


def test_async_checkpointer_matches_sync(tmp_path):
    spec = small_spec()
    state = host_state(spec, 42)
    sync_dir, async_dir = str(tmp_path / "s"), str(tmp_path / "a")
    write_snapshot(sync_dir, 5, spec, state, keep=2)

    import jax.numpy as jnp

    cp = AsyncCheckpointer(async_dir, keep=2)
    arrays = {"q": jnp.asarray(state["q"])}
    cp.save(spec, arrays, 5)
    cp.save(spec, arrays, 6)  # second save drains the first (double buffer)
    cp.close()
    assert cp.last_step == 6
    assert list_snapshots(async_dir) == [snapshot_name(5), snapshot_name(6)]
    for sdir in list_snapshots(async_dir):
        assert validate_snapshot(os.path.join(async_dir, sdir)) == []
    # payload equality with the synchronous write (npz bytes differ by zip
    # metadata; the arrays must not)
    a = np.load(os.path.join(async_dir, snapshot_name(5), "block_0_0_0.npz"))
    b = np.load(os.path.join(sync_dir, snapshot_name(5), "block_0_0_0.npz"))
    np.testing.assert_array_equal(a["q"], b["q"])


# -- ckpt_tool ----------------------------------------------------------------


def test_ckpt_tool_cli(tmp_path, capsys):
    from stencil_tpu.apps.ckpt_tool import main as tool

    spec = small_spec()
    d = str(tmp_path)
    write_snapshot(d, 1, spec, host_state(spec, 1), keep=5)
    write_snapshot(d, 2, spec, host_state(spec, 1), keep=5)  # same data
    write_snapshot(d, 3, spec, host_state(spec, 3), keep=5)

    assert tool(["inspect", d]) == 0
    out = capsys.readouterr().out
    assert "step      3" in out and "q:float32" in out
    assert tool(["inspect", d, "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["step"] == 3

    assert tool(["validate", d, "--all"]) == 0
    capsys.readouterr()

    # metadata diff: steps differ
    s1 = os.path.join(d, snapshot_name(1))
    s2 = os.path.join(d, snapshot_name(2))
    s3 = os.path.join(d, snapshot_name(3))
    assert tool(["diff", s1, s2]) == 1  # step differs
    assert tool(["diff", s1, s2, "--data"]) == 1  # ... even if data equal
    assert tool(["diff", s1, s1, "--data"]) == 0
    assert tool(["diff", s2, s3, "--data"]) == 1
    out = capsys.readouterr().out
    assert "differing cells" in out

    # corrupt one payload: validate CLI must exit nonzero
    with open(os.path.join(s3, "block_0_0_0.npz"), "r+b") as f:
        f.truncate(10)
    assert tool(["validate", d, "--all"]) == 1


def test_quarantine_invalid_snapshot(tmp_path):
    """ckpt_tool validate --quarantine / quarantine_snapshot: an invalid
    (truncated) snapshot is renamed aside so find_resume stops rescanning
    it on every restart; LATEST is repointed at the newest survivor."""
    from stencil_tpu.apps import ckpt_tool
    from stencil_tpu.ckpt import QUARANTINE_PREFIX, quarantine_snapshot

    spec = small_spec()
    d = str(tmp_path)
    write_snapshot(d, 1, spec, host_state(spec, 1), keep=5)
    write_snapshot(d, 2, spec, host_state(spec, 2), keep=5)
    victim = os.path.join(d, snapshot_name(2), "block_0_0_0.npz")
    with open(victim, "r+b") as f:
        f.truncate(10)
    # the CLI path: validate --all --quarantine renames the bad one
    rc = ckpt_tool.main(["validate", d, "--all", "--quarantine"])
    assert rc == 1  # the invalid snapshot still fails THIS run
    assert list_snapshots(d) == [snapshot_name(1)]
    qdirs = [e for e in os.listdir(d) if e.startswith(QUARANTINE_PREFIX)]
    assert len(qdirs) == 1 and snapshot_name(2) in qdirs[0]
    # evidence breadcrumb + LATEST repointed at the survivor
    assert os.path.isfile(os.path.join(d, qdirs[0], "QUARANTINED.txt"))
    assert read_latest(d) == snapshot_name(1)
    # a fresh validate now passes, and resume lands on the survivor
    assert ckpt_tool.main(["validate", d, "--all"]) == 0
    snap, manifest = find_resume(d)
    assert manifest["step"] == 1
    # quarantining the last snapshot removes the dangling LATEST
    assert quarantine_snapshot(d, snapshot_name(1), reason="test") is not None
    assert read_latest(d) is None
    assert find_resume(d) is None
    # and a nonexistent name is a no-op
    assert quarantine_snapshot(d, snapshot_name(9)) is None


# -- manifests written by PRs 17 to 29 carry the retired two-level keys ------


def _rewrite_manifest(ck, edit):
    snaps = [e for e in os.listdir(ck) if e.startswith("step-")]
    mpath = os.path.join(ck, snaps[0], "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    edit(manifest["meta"]["plan"])
    with open(mpath, "w") as f:
        json.dump(manifest, f)


@pytest.mark.parametrize("key,value", [
    ("hierarchy", ["z", 2]),
    ("host_placement", [1, 0]),
    ("host_blocks", [0, 0, 0, 0, 1, 1, 1, 1]),
    ("method", "remote-dma"),
    ("kernel_variant", "fused"),
    ("kernel_variant", "persistent"),
], ids=["hierarchy", "host_placement", "host_blocks", "remote-dma", "fused",
        "persistent"])
def test_restore_ignores_retired_plan_keys(tmp_path, capfd, key, value):
    """The block geometry never depended on the outer split, its host
    assignment or the host index per block: a manifest that carries them
    (null in a one-level run, set in a two-level one) restores bit-exact,
    and says nothing. Nor did it depend on what carried the halos: a
    manifest written under the kernel-initiated transport of PRs 10 to 45
    or one of its kernel variants restores bit-exact under the composed
    plan, with ONE warning that names what is retired."""
    ck = str(tmp_path / "ck")
    dd, h = make_domain((12, 12, 8), "float32")
    field = coord_field(dd.size, np.float32)
    dd.set_curr_global(h, field)
    dd.save_checkpoint(ck, 3, asynchronous=False)
    transport = key in ("method", "kernel_variant")

    def edit(plan):
        # as the parent tree wrote a one-level run, then the key under test
        plan["choice"].update(hierarchy=None, host_placement=None)
        plan["host_blocks"] = [0] * 8
        (plan if key == "host_blocks" else plan["choice"])[key] = value
        if key == "kernel_variant":
            plan["choice"]["method"] = "remote-dma"
            plan["choice"]["multistep_k"] = 1 + (value == "persistent")

    _rewrite_manifest(ck, edit)
    dd2, h2 = make_domain((12, 12, 8), "float32")
    capfd.readouterr()
    assert dd2.restore_checkpoint(ck) == 3
    err = capfd.readouterr().err
    assert err.count("[WARN]") == (1 if transport else 0), err
    if transport:
        assert "'remote-dma' is retired" in err and "axis-composed" in err
    np.testing.assert_array_equal(dd2.get_curr_global(h2), field)


def test_ckpt_restore_survives_unknown_future_method(tmp_path, capfd):
    ck = str(tmp_path / "ck")
    dd, h = make_domain((16, 16, 16), "float32")
    field = coord_field(dd.size, np.float32)
    dd.set_curr_global(h, field)
    dd.save_checkpoint(ck, 2, asynchronous=False)
    # a method neither this build nor any before it knew
    _rewrite_manifest(
        ck, lambda plan: plan["choice"].update(method="quantum-teleport"))
    capfd.readouterr()
    dd2, h2 = make_domain((16, 16, 16), "float32")
    assert dd2.restore_checkpoint(ck) == 2   # warns, never crashes
    assert "unknown to this build" in capfd.readouterr().err
    np.testing.assert_array_equal(dd2.get_curr_global(h2), field)


def test_ckpt_restore_warns_on_wire_dtype_delta(tmp_path, capfd):
    ck = str(tmp_path / "ck")
    dd = DistributedDomain(16, 16, 16)
    dd.set_radius(1)
    dd.set_devices(jax.devices()[:8])
    dd.set_wire_dtype("bfloat16")
    h = dd.add_data("temperature", "float32")
    dd.realize()
    field = coord_field(dd.size, np.float32)
    dd.set_curr_global(h, field)
    dd.save_checkpoint(ck, 2, asynchronous=False)
    capfd.readouterr()
    dd2, h2 = make_domain((16, 16, 16), "float32")
    assert dd2.restore_checkpoint(ck) == 2
    assert "wire_dtype" in capfd.readouterr().err
    np.testing.assert_array_equal(dd2.get_curr_global(h2), field)
