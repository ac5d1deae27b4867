"""Method.REMOTE_DMA — kernel-initiated halo exchange, pinned on the CPU
emulation (ISSUE 10 / ROADMAP #2).

The claims under test:

- **0 ppermutes**: a lowered REMOTE_DMA exchange contains ZERO
  collective-permutes — ``collective_census`` over EVERY compiled piece
  of the emulation comes back permute-free, and the recorded
  ``exchange.permutes_per_quantity`` gauge reads 0.
- **bit parity**: the emulation (host-initiated per-neighbor
  device-to-device copies of the composed-phase slabs) is bit-identical
  to AXIS_COMPOSED on uniform, uneven, and oversubscribed partitions,
  fp32/fp64/mixed dicts, and the full jacobi step.
- **Q-independent DMA count**: the per-dtype packed carrier keeps the
  emulated transfer count independent of the quantity count (PR-5
  geometry).
- **bf16 on the wire**: the compression knob halves the lowered-module
  wire bytes at an unchanged permute count, within the wire dtype's
  rounding bound, and never touches local/self-wrap movement.

Runs on the virtual 8-device CPU mesh from conftest.py.
"""

import os

import jax
import numpy as np
import pytest

from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.parallel import HaloExchange, Method, grid_mesh
from stencil_tpu.parallel.exchange import shard_blocks
from stencil_tpu.plan.ir import REMOTE_DMA, PlanChoice, PlanConfig, build_plan


def _state(spec, mesh, nq, dtypes=None):
    g = spec.global_size
    base = (
        np.arange(g.z)[:, None, None] * 1_000_000.0
        + np.arange(g.y)[None, :, None] * 1_000.0
        + np.arange(g.x)[None, None, :]
    )
    out = {}
    for i in range(nq):
        dt = dtypes[i] if dtypes else np.float32
        out[i] = shard_blocks((base + i).astype(dt), spec, mesh)
    return out


def _gather(state):
    return np.stack(
        [np.asarray(jax.device_get(state[i])) for i in sorted(state)]
    )


# -- plan IR -------------------------------------------------------------------


def test_remote_plan_predicts_zero_permutes_and_dma_count():
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    plan = build_plan(spec, Dim3(2, 2, 2), REMOTE_DMA)
    assert plan.collectives_per_exchange(1, 1) == 0
    assert plan.collectives_per_exchange(8, 1) == 0
    # 2 async copies per axis phase, Q-independent per dtype group
    assert plan.dmas_per_exchange(1, 1) == 6
    assert plan.dmas_per_exchange(8, 1) == 6
    assert plan.dmas_per_exchange(8, 2) == 12   # two dtype groups
    # the wire model is literally the composed one
    composed = build_plan(spec, Dim3(2, 2, 2), Method.AXIS_COMPOSED)
    assert plan.wire_bytes([4, 4]) == composed.wire_bytes([4, 4])
    assert "dmas=2" in plan.describe()
    assert "0 ppermutes" in plan.describe()


def test_remote_plan_self_wrap_has_no_dmas():
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 1, 1), Radius.constant(1))
    plan = build_plan(spec, Dim3(2, 1, 1), REMOTE_DMA)
    x, y, z = plan.remote_phases
    assert x.dmas() == 2 and y.dmas() == 0 and z.dmas() == 0
    assert plan.dmas_per_exchange(4, 1) == 2


def test_wire_dtype_byte_model():
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    native = build_plan(spec, Dim3(2, 2, 2), Method.AXIS_COMPOSED)
    bf16 = build_plan(spec, Dim3(2, 2, 2), Method.AXIS_COMPOSED,
                      wire_dtype="bfloat16")
    assert native.wire_bytes([4, 4]) == 2 * bf16.wire_bytes([4, 4])
    # fp64 narrows to 2 bytes on the wire too (4x)
    assert native.wire_bytes([8]) == 4 * bf16.wire_bytes([8])
    # local bytes never compress
    assert native.local_bytes([4]) == bf16.local_bytes([4])
    # integer quantities never narrow (the lowering keeps them native,
    # so the byte model must too): an int32 + fp32 pair compresses only
    # the float half
    assert bf16.wire_bytes([4, 4], floating=[False, True]) == \
        native.wire_bytes([4]) + bf16.wire_bytes([4])
    cfg = PlanConfig.make(Dim3(16, 16, 16), Radius.constant(1),
                          ["int32", "float32"], 8)
    assert cfg.floating_flags() == (True, False) or \
        cfg.floating_flags() == (False, True)
    # aligned with itemsizes(): sorted dtype order puts float32 first
    assert list(zip(cfg.itemsizes(), cfg.floating_flags())) == \
        [(4, True), (4, False)]


def test_wire_narrow_dtype_policy():
    import jax.numpy as jnp

    from stencil_tpu.ops.halo_fill import wire_narrow_dtype

    assert wire_narrow_dtype(jnp.float32, "bfloat16") == jnp.dtype("bfloat16")
    assert wire_narrow_dtype(jnp.float64, "bfloat16") == jnp.dtype("bfloat16")
    assert wire_narrow_dtype(jnp.float32, None) is None
    # never widens, never touches ints
    assert wire_narrow_dtype(jnp.bfloat16, "float32") is None
    assert wire_narrow_dtype(jnp.float32, "float32") is None
    assert wire_narrow_dtype(jnp.int32, "bfloat16") is None


# -- census + parity -----------------------------------------------------------


def test_remote_census_has_zero_ppermutes():
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    ex = HaloExchange(spec, mesh, Method.REMOTE_DMA)
    census = ex.collective_census(_state(spec, mesh, 2))
    assert census.get("collective-permute", (0, 0))[0] == 0
    # nothing else snuck onto the collective path either
    assert sum(c for c, _b in census.values()) == 0, census


def test_remote_permutes_per_quantity_gauge_reads_zero(tmp_path):
    from stencil_tpu.obs import telemetry

    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    ex = HaloExchange(spec, mesh, Method.REMOTE_DMA)
    state = _state(spec, mesh, 2)
    sink = str(tmp_path / "m.jsonl")
    rec = telemetry.Recorder(sink=sink, run_id="r", app="test")
    telemetry.record_exchange_truth(ex, state, [4, 4], rec=rec)
    rec.close()
    import json

    recs = [json.loads(ln) for ln in open(sink) if ln.strip()]
    gauges = {r["name"]: r for r in recs if r["kind"] == "gauge"}
    assert gauges["exchange.permutes_per_quantity"]["value"] == 0.0
    on_wire = [r for r in recs if r["name"] == "exchange.bytes_on_wire"]
    assert on_wire and on_wire[0]["bytes"] == 0  # nothing on the XLA path


def test_remote_transfer_count_q_independent():
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    counts = {}
    for nq in (1, 4):
        ex = HaloExchange(spec, mesh, Method.REMOTE_DMA)
        ex(_state(spec, mesh, nq))
        counts[nq] = ex._remote.last_transfer_count
    # 8 devices x (2 copies per active ring phase) — independent of Q
    assert counts[1] == counts[4] == 8 * 6
    # per-quantity mode scales with Q, like the ppermute baseline
    ex = HaloExchange(spec, mesh, Method.REMOTE_DMA, batch_quantities=False)
    ex(_state(spec, mesh, 4))
    assert ex._remote.last_transfer_count == 4 * 8 * 6


@pytest.mark.parametrize("name,size,dim,mesh_dim,ndev,dtypes,radius,batch", [
    ("uniform", (16, 16, 16), (2, 2, 2), (2, 2, 2), 8, None, 1, True),
    ("uneven", (17, 19, 16), (2, 2, 2), (2, 2, 2), 8, None, 1, True),
    ("oversubscribed", (16, 16, 16), (2, 2, 2), (2, 2, 1), 4, None, 1, True),
    ("mixed-dtype", (16, 16, 16), (2, 2, 2), (2, 2, 2), 8,
     [np.float32, np.float64, np.float32], 1, True),
    ("uneven-oversub-f64", (17, 16, 16), (2, 2, 2), (2, 1, 2), 4,
     [np.float64, np.float64], 1, True),
    # x self-wraps, four uneven blocks on z (composed is held to DIRECT26
    # on the same split in test_exchange.py)
    ("uneven-1x2x4-r2", (14, 18, 20), (1, 2, 4), (1, 2, 4), 8, None, 2, True),
    ("batch-off", (16, 16, 16), (2, 2, 2), (2, 2, 2), 8, None, 2, False),
])
def test_remote_bit_parity_vs_composed(name, size, dim, mesh_dim, ndev,
                                       dtypes, radius, batch):
    spec = GridSpec(Dim3(*size), Dim3(*dim), Radius.constant(radius))
    mesh = grid_mesh(Dim3(*mesh_dim), jax.devices()[:ndev])
    nq = len(dtypes) if dtypes else 2
    outs = {}
    for method in (Method.AXIS_COMPOSED, Method.REMOTE_DMA):
        ex = HaloExchange(spec, mesh, method, batch_quantities=batch)
        out = ex(_state(spec, mesh, nq, dtypes))
        outs[method] = [np.asarray(jax.device_get(out[i]))
                        for i in sorted(out)]
    for a, b in zip(outs[Method.AXIS_COMPOSED], outs[Method.REMOTE_DMA]):
        np.testing.assert_array_equal(a, b, err_msg=name)


def test_remote_make_loop_matches_repeated_composed():
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(2))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    exr = HaloExchange(spec, mesh, Method.REMOTE_DMA)
    exc = HaloExchange(spec, mesh, Method.AXIS_COMPOSED)
    sr = exr.make_loop(3)(_state(spec, mesh, 2))
    sc = exc.make_loop(3)(_state(spec, mesh, 2))
    np.testing.assert_array_equal(_gather(sr), _gather(sc))


def test_remote_full_jacobi_step_parity():
    import jax.numpy as jnp

    from stencil_tpu.api import DistributedDomain
    from stencil_tpu.ops.jacobi import INIT_TEMP, make_jacobi_loop, sphere_sel

    def run(method):
        dd = DistributedDomain(16, 16, 16)
        dd.set_radius(1)
        dd.set_methods(method)
        dd.set_devices(jax.devices()[:8])
        h = dd.add_data("t", "float32")
        dd.realize()
        dd.set_curr_global(h, np.full((16, 16, 16), INIT_TEMP, np.float32))
        sel = shard_blocks(sphere_sel((16, 16, 16)), dd.spec, dd.mesh)
        loop = make_jacobi_loop(dd.halo_exchange, 4)
        c = dd.get_curr(h)
        n = jax.device_put(jnp.zeros_like(c), dd.sharding())
        c, _n = loop(c, n, sel)
        dd.set_curr(h, c)
        return dd.get_curr_global(h)

    np.testing.assert_array_equal(
        run(Method.AXIS_COMPOSED), run(Method.REMOTE_DMA))


def test_remote_has_no_per_block_body():
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    ex = HaloExchange(spec, mesh, Method.REMOTE_DMA)
    with pytest.raises(RuntimeError, match="REMOTE_DMA"):
        ex.exchange_blocks({0: None})


# -- bf16 on the wire ----------------------------------------------------------


def test_wire_compression_halves_lowered_wire_bytes():
    from stencil_tpu.utils.hlo_check import stablehlo_wire_census

    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    st = _state(spec, mesh, 2)
    cens = {}
    for wd in (None, "bfloat16"):
        ex = HaloExchange(spec, mesh, Method.AXIS_COMPOSED, wire_dtype=wd)
        cens[wd] = stablehlo_wire_census(
            ex._compiled.lower(st).as_text())
    cp_n = cens[None]["collective-permute"]
    cp_w = cens["bfloat16"]["collective-permute"]
    assert cp_n[0] == cp_w[0] == 6      # count unchanged (Q=2, batched)
    assert cp_n[1] == 2 * cp_w[1]       # bytes halved
    # and the plan model predicts the same ratio
    exw = HaloExchange(spec, mesh, Method.AXIS_COMPOSED,
                      wire_dtype="bfloat16")
    exn = HaloExchange(spec, mesh, Method.AXIS_COMPOSED)
    assert exn.plan.wire_bytes([4, 4]) == 2 * exw.plan.wire_bytes([4, 4])


def test_wire_compression_error_bounded_and_lossless_locally():
    # one multi-block axis (wire) + two self-wrap axes (local): the wire
    # halos round to bf16, the self-wrap halos stay bit-exact
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 1, 1), Radius.constant(1))
    mesh = grid_mesh(Dim3(2, 1, 1), jax.devices()[:2])
    outs = {}
    for wd in (None, "bfloat16"):
        ex = HaloExchange(spec, mesh, Method.AXIS_COMPOSED, wire_dtype=wd)
        outs[wd] = _gather(ex(_state(spec, mesh, 1)))
    a, b = outs[None], outs["bfloat16"]
    rel = np.abs(a - b) / np.maximum(np.abs(a), 1.0)
    assert 0 < rel.max() <= 2 ** -8    # rounded, within bf16 half-ulp
    # self-wrap y halo rows are pure local copies: bit-identical over the
    # compute-x columns (the x-halo columns they carry crossed the wire
    # in the earlier x phase and legitimately rounded)
    off = spec.compute_offset()
    xs = slice(off.x, off.x + spec.base.x)
    np.testing.assert_array_equal(a[..., off.y - 1, xs],
                                  b[..., off.y - 1, xs])
    np.testing.assert_array_equal(a[..., off.y + spec.base.y, xs],
                                  b[..., off.y + spec.base.y, xs])


def test_wire_compression_parity_remote_vs_composed():
    # the lossy knob must stay CONSISTENT across transports: remote-dma
    # with bf16 wire equals composed with bf16 wire bit-for-bit
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    outs = {}
    for method in (Method.AXIS_COMPOSED, Method.REMOTE_DMA):
        ex = HaloExchange(spec, mesh, method, wire_dtype="bfloat16")
        outs[method] = _gather(ex(_state(spec, mesh, 2)))
    np.testing.assert_array_equal(outs[Method.AXIS_COMPOSED],
                                  outs[Method.REMOTE_DMA])


def test_wire_dtype_ignored_for_auto_spmd(capfd):
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    ex = HaloExchange(spec, mesh, Method.AUTO_SPMD, wire_dtype="bfloat16")
    assert ex.wire_dtype is None
    assert "ignored" in capfd.readouterr().err


# -- cost model + autotuner + DB ----------------------------------------------


def test_remote_dma_cost_entry_and_platform_split():
    from stencil_tpu.plan.cost import (DEFAULT_CALIBRATION,
                                       enumerate_candidates, rank, score)

    assert "remote_dma" in DEFAULT_CALIBRATION
    assert "modeled" in DEFAULT_CALIBRATION["remote_dma"]["provenance"]
    mk = lambda platform: PlanConfig.make(
        Dim3(24, 24, 24), Radius.constant(2), ["float32"] * 4, 8, platform)
    # cpu: the emulation penalty keeps remote-dma BELOW the recorded
    # composed winner (static-only rankings must not change on this mesh)
    ranked_cpu = rank(mk("cpu"), enumerate_candidates(mk("cpu")))
    assert ranked_cpu[0][1].method == "axis-composed"
    # tpu: the modeled kernel-initiated transport competes (and its cost
    # carries the 0-permute / dma split for plan_tool explain)
    ranked_tpu = rank(mk("tpu"), enumerate_candidates(mk("tpu")))
    best_remote = next(
        (c, ch) for c, ch in ranked_tpu if ch.method == REMOTE_DMA)
    assert best_remote[0].collectives == 0
    assert best_remote[0].dmas > 0
    # remote-dma candidates are scored for every config
    sc = score(mk("cpu"), PlanChoice(partition=(2, 2, 2), method=REMOTE_DMA))
    assert sc is not None and sc.collectives == 0 and sc.dmas == 6


def test_autotune_persists_remote_dma_keyed_entry(tmp_path):
    from stencil_tpu.plan import db as plandb
    from stencil_tpu.plan.autotune import autotune

    db_path = str(tmp_path / "plans.json")
    res = autotune(
        Dim3(16, 16, 16), Radius.constant(1), ["float32"],
        ndev=8, platform="cpu", db_path=db_path, probe=False,
        methods=("remote-dma",),
    )
    assert res.choice.method == "remote-dma"
    db = plandb.load_db(db_path)   # validates: remote-dma is a known method
    entry = plandb.lookup(db, res.config)
    assert entry is not None
    assert PlanChoice.from_json(entry["choice"]).method == "remote-dma"
    # and a second run replays it as a pure DB hit
    res2 = autotune(
        Dim3(16, 16, 16), Radius.constant(1), ["float32"],
        ndev=8, platform="cpu", db_path=db_path, probe=False,
        methods=("remote-dma",),
    )
    assert res2.cache_hit and res2.choice.method == "remote-dma"


# -- ckpt plan-mismatch satellite ---------------------------------------------


def _make_domain(method, wire_dtype=None):
    from stencil_tpu.api import DistributedDomain

    dd = DistributedDomain(16, 16, 16)
    dd.set_radius(1)
    dd.set_methods(method)
    if wire_dtype:
        dd.set_wire_dtype(wire_dtype)
    dd.set_devices(jax.devices()[:8])
    h = dd.add_data("t", "float32")
    dd.realize()
    return dd, h


def test_ckpt_restore_warns_on_remote_dma_plan_mismatch(tmp_path, capfd):
    ck = str(tmp_path / "ck")
    dd, h = _make_domain(Method.REMOTE_DMA)
    field = np.arange(16 ** 3, dtype=np.float32).reshape(16, 16, 16)
    dd.set_curr_global(h, field)
    dd.save_checkpoint(ck, 2, asynchronous=False)
    capfd.readouterr()
    # a snapshot written under REMOTE_DMA restoring under COMPOSED warns
    # (names both methods) and restores bit-exactly — never crashes
    dd2, h2 = _make_domain(Method.AXIS_COMPOSED)
    assert dd2.restore_checkpoint(ck) == 2
    err = capfd.readouterr().err
    assert "exchange plan" in err and "remote-dma" in err
    np.testing.assert_array_equal(dd2.get_curr_global(h2), field)


def test_ckpt_restore_survives_unknown_future_method(tmp_path, capfd):
    import json

    ck = str(tmp_path / "ck")
    dd, h = _make_domain(Method.AXIS_COMPOSED)
    field = np.arange(16 ** 3, dtype=np.float32).reshape(16, 16, 16)
    dd.set_curr_global(h, field)
    dd.save_checkpoint(ck, 2, asynchronous=False)
    # rewrite the manifest's plan with a method this build does not know
    snaps = [e for e in os.listdir(ck) if e.startswith("step-")]
    mpath = os.path.join(ck, snaps[0], "manifest.json")
    manifest = json.load(open(mpath))
    manifest["meta"]["plan"]["choice"]["method"] = "quantum-teleport"
    json.dump(manifest, open(mpath, "w"))
    capfd.readouterr()
    dd2, h2 = _make_domain(Method.AXIS_COMPOSED)
    assert dd2.restore_checkpoint(ck) == 2   # warns, never crashes
    err = capfd.readouterr().err
    assert "unknown to this build" in err
    np.testing.assert_array_equal(dd2.get_curr_global(h2), field)


def test_ckpt_restore_warns_on_wire_dtype_delta(tmp_path, capfd):
    ck = str(tmp_path / "ck")
    dd, h = _make_domain(Method.AXIS_COMPOSED, wire_dtype="bfloat16")
    field = np.arange(16 ** 3, dtype=np.float32).reshape(16, 16, 16)
    dd.set_curr_global(h, field)
    dd.save_checkpoint(ck, 2, asynchronous=False)
    capfd.readouterr()
    dd2, h2 = _make_domain(Method.AXIS_COMPOSED)
    assert dd2.restore_checkpoint(ck) == 2
    err = capfd.readouterr().err
    assert "wire_dtype" in err
