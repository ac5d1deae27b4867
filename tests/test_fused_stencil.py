"""The fused compute+exchange mega-kernel stack (ISSUE 14 / ROADMAP #5),
pinned on the CPU emulation.

The claims under test:

- **fused plan IR**: the per-direction FusedPhaseIR set predicts 0
  collectives, the exact direct-geometry wire bytes, and the concurrent
  DMA count; fused is REMOTE_DMA-only and single-resident-only (loud).
- **bit parity**: the emulated fused schedule (pack → start every
  per-direction copy → wait → unpack) is bit-identical to AXIS_COMPOSED
  across uniform/uneven/fp64/mixed-dict configs, INCLUDING under bf16
  and fp8 wire compression — a carrier rounds exactly once either way.
- **overlap step parity**: the full fused jacobi loop (interior compute
  slotted between start and wait) and the fused astaroth loop (8-field
  MHD, diagonal pencils) land bit-identical to composed programs.
- **interpret-mode kernel**: the all-self-wrap form of the jacobi
  mega-kernel (in-kernel wrap fills + interior/boundary sweep) equals
  the XLA step on any host.
- **fp8 wire tier**: float8_e4m3fn quarters on-wire bytes at an
  unchanged permute/DMA count within the e4m3 half-ulp bound.
- **plan plumbing**: the autotuner searches the fused variant, persists
  it, replays it probe-free; verify_plan audits the fused lowering's
  census/byte/DMA predictions like the other four methods.

Runs on the virtual 8-device CPU mesh from conftest.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.parallel import HaloExchange, Method, grid_mesh
from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks
from stencil_tpu.plan.ir import (FUSED_VARIANT, REMOTE_DMA, PlanChoice,
                                 PlanConfig, build_plan, wire_itemsize)


def _state(spec, mesh, nq, dtypes=None, scale=1.0):
    g = spec.global_size
    base = (
        np.arange(g.z)[:, None, None] * 1_000_000.0
        + np.arange(g.y)[None, :, None] * 1_000.0
        + np.arange(g.x)[None, None, :]
    ) * scale
    out = {}
    for i in range(nq):
        dt = dtypes[i] if dtypes else np.float32
        out[i] = shard_blocks((base + i * scale).astype(dt), spec, mesh)
    return out


def _gather(state):
    return [np.asarray(jax.device_get(state[i])) for i in sorted(state)]


# -- plan IR -------------------------------------------------------------------


def test_fused_plan_predicts_zero_permutes_and_concurrent_dmas():
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    plan = build_plan(spec, Dim3(2, 2, 2), REMOTE_DMA, fused=True)
    assert plan.collectives_per_exchange(1, 1) == 0
    assert plan.collectives_per_exchange(8, 2) == 0
    # one concurrent copy per active direction (constant radius: all 26),
    # Q-independent per dtype group
    assert plan.dmas_per_exchange(1, 1) == 26
    assert plan.dmas_per_exchange(8, 1) == 26
    assert plan.dmas_per_exchange(8, 2) == 52
    # exact direct-geometry wire model (not the composed full-extent one)
    direct = build_plan(spec, Dim3(2, 2, 2), "direct26")
    assert plan.wire_bytes([4, 4]) == direct.wire_bytes([4, 4])
    assert "(fused compute+exchange kernel)" in plan.describe()
    assert "dmas=1" in plan.describe()


def test_fused_plan_self_wrap_directions_are_local():
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 1, 1), Radius.constant(1))
    plan = build_plan(spec, Dim3(2, 1, 1), REMOTE_DMA, fused=True)
    # only x-crossing directions pay a DMA (2 x 9 of the 26)
    assert plan.dmas_per_exchange(1, 1) == 18
    local = [p for p in plan.fused_phases if not p.crossing]
    assert len(local) == 8 and all(p.wire_cells == 0 for p in local)


def test_fused_plan_validation_is_loud():
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    with pytest.raises(ValueError, match="REMOTE_DMA"):
        build_plan(spec, Dim3(2, 2, 2), "axis-composed", fused=True)
    with pytest.raises(ValueError, match="single-resident"):
        build_plan(spec, Dim3(2, 2, 1), REMOTE_DMA, fused=True)


def test_fp8_wire_itemsize_in_byte_model():
    assert wire_itemsize("float8_e4m3fn") == 1
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    native = build_plan(spec, Dim3(2, 2, 2), REMOTE_DMA, fused=True)
    fp8 = build_plan(spec, Dim3(2, 2, 2), REMOTE_DMA, fused=True,
                     wire_dtype="float8_e4m3fn")
    assert native.wire_bytes([4]) == 4 * fp8.wire_bytes([4])
    # local hand-offs never compress
    assert native.local_bytes([4]) == fp8.local_bytes([4])


# -- cost model + search space -------------------------------------------------


def test_fused_cost_overlap_aware_and_platform_split():
    from stencil_tpu.plan.cost import enumerate_candidates, rank, score

    mk = lambda platform: PlanConfig.make(
        Dim3(24, 24, 24), Radius.constant(2), ["float32"] * 4, 8, platform)
    # the search space carries fused candidates for remote-dma
    cands = enumerate_candidates(mk("cpu"))
    assert any(c.is_fused for c in cands)
    assert all(c.method == REMOTE_DMA for c in cands if c.is_fused)
    # tpu: hiding wire behind interior compute can only help — the fused
    # exchange cost never exceeds the serialized remote-dma cost
    part = (2, 2, 2)
    plain = score(mk("tpu"), PlanChoice(partition=part, method=REMOTE_DMA))
    fused = score(mk("tpu"), PlanChoice(partition=part, method=REMOTE_DMA,
                                        kernel_variant=FUSED_VARIANT))
    assert fused is not None and plain is not None
    assert fused.collectives == 0 and fused.dmas > 0
    assert fused.exchange_s <= plain.exchange_s
    # cpu: the emulation penalty keeps the composed winner on top
    ranked_cpu = rank(mk("cpu"), enumerate_candidates(mk("cpu")))
    assert ranked_cpu[0][1].method == "axis-composed"


def test_fused_choice_infeasible_outside_its_scope():
    from stencil_tpu.plan.cost import score

    cfg = PlanConfig.make(Dim3(24, 24, 24), Radius.constant(2),
                          ["float32"], 8, "cpu")
    # fused is a REMOTE_DMA lowering
    assert score(cfg, PlanChoice(partition=(2, 2, 2),
                                 method="axis-composed",
                                 kernel_variant=FUSED_VARIANT)) is None
    # and single-resident only (16 blocks on 8 devices oversubscribes)
    assert score(cfg, PlanChoice(partition=(2, 2, 4), method=REMOTE_DMA,
                                 kernel_variant=FUSED_VARIANT)) is None


# -- emulated fused schedule: census + parity ---------------------------------


def test_fused_census_has_zero_ppermutes():
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    ex = HaloExchange(spec, mesh, Method.REMOTE_DMA, fused=True)
    census = ex.collective_census(_state(spec, mesh, 2))
    assert census.get("collective-permute", (0, 0))[0] == 0
    assert sum(c for c, _b in census.values()) == 0, census


def test_fused_transfer_count_q_independent_and_predicted():
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    counts = {}
    for nq in (1, 4):
        ex = HaloExchange(spec, mesh, Method.REMOTE_DMA, fused=True)
        ex(_state(spec, mesh, nq))
        counts[nq] = ex._remote.last_transfer_count
    # 8 devices x 26 concurrent copies — independent of Q, and exactly
    # what the plan predicts
    assert counts[1] == counts[4] == 8 * 26
    ex = HaloExchange(spec, mesh, Method.REMOTE_DMA, fused=True)
    assert counts[1] == ex.plan.dmas_per_exchange(1, 1) * 8


@pytest.mark.parametrize("name,size,dim,ndev,dtypes,wire,batch", [
    ("uniform", (16, 16, 16), (2, 2, 2), 8, None, None, True),
    ("uneven", (17, 19, 16), (2, 2, 2), 8, None, None, True),
    ("fp64", (16, 16, 16), (2, 2, 2), 8, [np.float64, np.float64], None,
     True),
    ("mixed-dtype", (16, 16, 16), (2, 2, 2), 8,
     [np.float32, np.float64, np.float32], None, True),
    ("bf16-wire", (16, 16, 16), (2, 2, 2), 8, None, "bfloat16", True),
    ("fp8-wire", (16, 16, 16), (2, 2, 2), 8, None, "float8_e4m3fn", True),
    ("uneven-bf16", (17, 16, 16), (2, 2, 2), 8, None, "bfloat16", True),
    ("anisotropic", (16, 16, 16), (1, 2, 4), 8, None, None, True),
    # x self-wraps, four uneven blocks on z (composed is held to DIRECT26
    # on the same split in test_exchange.py)
    ("uneven-1x2x4", (14, 18, 20), (1, 2, 4), 8, None, None, True),
    ("batch-off", (16, 16, 16), (2, 2, 2), 8, None, None, False),
])
def test_fused_bit_parity_vs_composed(name, size, dim, ndev, dtypes, wire,
                                      batch):
    spec = GridSpec(Dim3(*size), Dim3(*dim), Radius.constant(2))
    mesh = grid_mesh(Dim3(*dim), jax.devices()[:ndev])
    nq = len(dtypes) if dtypes else 2
    # fp8's finite range tops out at 448: scale the coordinate fixture
    # into range (out-of-range values map to NaN — the policy user data
    # must follow)
    scale = 2e-5 if wire == "float8_e4m3fn" else 1.0
    outs = {}
    for method, fused in ((Method.AXIS_COMPOSED, False),
                          (Method.REMOTE_DMA, True)):
        ex = HaloExchange(spec, mesh, method, wire_dtype=wire, fused=fused,
                          batch_quantities=batch)
        out = ex(_state(spec, mesh, nq, dtypes, scale=scale))
        outs[fused] = _gather(out)
    for a, b in zip(outs[False], outs[True]):
        np.testing.assert_array_equal(a, b, err_msg=name)


def test_fused_make_loop_matches_repeated_composed():
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(2))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    exf = HaloExchange(spec, mesh, Method.REMOTE_DMA, fused=True)
    exc = HaloExchange(spec, mesh, Method.AXIS_COMPOSED)
    sf = exf.make_loop(3)(_state(spec, mesh, 2))
    sc = exc.make_loop(3)(_state(spec, mesh, 2))
    for a, b in zip(_gather(sc), _gather(sf)):
        np.testing.assert_array_equal(a, b)


def test_fused_ctor_validation_is_loud():
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    with pytest.raises(ValueError, match="REMOTE_DMA"):
        HaloExchange(spec, mesh, Method.AXIS_COMPOSED, fused=True)
    spec2 = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    mesh2 = grid_mesh(Dim3(2, 2, 1), jax.devices()[:4])  # oversubscribed
    with pytest.raises(ValueError, match="single-resident"):
        HaloExchange(spec2, mesh2, Method.REMOTE_DMA, fused=True)


# -- the fused jacobi step loop ------------------------------------------------


def _run_jacobi(method, fused, size, iters=4):
    from stencil_tpu.api import DistributedDomain
    from stencil_tpu.ops.jacobi import (INIT_TEMP, make_jacobi_loop,
                                        sphere_sel)

    dd = DistributedDomain(*size)
    dd.set_radius(1)
    dd.set_methods(method)
    if fused:
        dd.set_fused_exchange(True)
    dd.set_devices(jax.devices()[:8])
    h = dd.add_data("t", "float32")
    dd.realize()
    dd.set_curr_global(h, np.full(size[::-1], INIT_TEMP, np.float32))
    sel = shard_blocks(sphere_sel(size), dd.spec, dd.mesh)
    loop = make_jacobi_loop(dd.halo_exchange, iters)
    c = dd.get_curr(h)
    n = jax.device_put(jnp.zeros_like(c), dd.sharding())
    c, _n = loop(c, n, sel)
    dd.set_curr(h, c)
    return dd.get_curr_global(h)


@pytest.mark.parametrize("size", [(16, 16, 16), (17, 19, 16)])
def test_fused_jacobi_step_parity(size):
    a = _run_jacobi(Method.AXIS_COMPOSED, False, size)
    b = _run_jacobi(Method.REMOTE_DMA, True, size)
    np.testing.assert_array_equal(a, b)


def test_fused_jacobi_emits_overlap_telemetry(tmp_path):
    from stencil_tpu.obs import telemetry

    sink = str(tmp_path / "m.jsonl")
    rec = telemetry.configure(metrics_out=sink, app="test",
                              heartbeat_thread=False)
    try:
        _run_jacobi(Method.REMOTE_DMA, True, (16, 16, 16), iters=2)
    finally:
        rec.close()
        telemetry._recorder = None
    import json

    recs = [json.loads(ln) for ln in open(sink) if ln.strip()]
    assert not any(telemetry.validate_record(r) for r in recs)
    spans = {r["name"] for r in recs if r["kind"] == "span"}
    for want in ("fused.pack", "fused.interior", "fused.dma_wait",
                 "fused.boundary"):
        assert want in spans, (want, sorted(spans))
    fracs = [r["value"] for r in recs if r["kind"] == "gauge"
             and r["name"] == "fused.overlap_fraction"]
    assert fracs and all(0.0 <= v <= 1.0 for v in fracs)
    # the variant tag splits aggregation (report._agg_key)
    from stencil_tpu.apps.report import _agg_key

    span_rec = next(r for r in recs if r["name"] == "fused.interior")
    assert _agg_key(span_rec) == "fused.interior[fused]"


# -- the interpret-mode mega-kernel --------------------------------------------


def test_fused_kernel_interpret_parity_vs_xla_step():
    """The all-self-wrap (single device) form of the mega-kernel — wrap
    fills + interior/boundary sweep — is bit-identical to the XLA jacobi
    step over two substeps of the double buffer."""
    from stencil_tpu.ops.fused_stencil import make_fused_jacobi_kernel
    from stencil_tpu.ops.jacobi import INIT_TEMP, sphere_sel

    size = (16, 16, 16)
    spec = GridSpec(Dim3(*size), Dim3(1, 1, 1), Radius.constant(1))
    plan = build_plan(spec, Dim3(1, 1, 1), REMOTE_DMA, fused=True)
    kern = make_fused_jacobi_kernel(spec, plan, interpret=True)
    p = spec.padded()
    off = spec.compute_offset()
    sl = (slice(off.z, off.z + 16), slice(off.y, off.y + 16),
          slice(off.x, off.x + 16))
    curr = np.zeros((p.z, p.y, p.x), np.float32)
    curr[sl] = INIT_TEMP
    sel = np.zeros((p.z, p.y, p.x), np.int32)
    sel[sl] = sphere_sel(size)
    nxt = np.zeros_like(curr)
    c, n = jnp.asarray(curr), jnp.asarray(nxt)
    for _ in range(2):  # two substeps through the double buffer
        c2, out = kern(c, n, jnp.asarray(sel))
        c, n = out, c2
    # the XLA step on the same single-device domain (fp32 throughout —
    # the fixture the other kernels' parity is pinned against)
    ref = _run_jacobi_single_device(size, iters=2)
    np.testing.assert_array_equal(np.asarray(c)[sl], ref)


def _run_jacobi_single_device(size, iters):
    from stencil_tpu.api import DistributedDomain
    from stencil_tpu.ops.jacobi import (INIT_TEMP, make_jacobi_loop,
                                        sphere_sel)

    dd = DistributedDomain(*size)
    dd.set_radius(1)
    dd.set_devices(jax.devices()[:1])
    h = dd.add_data("t", "float32")
    dd.realize()
    dd.set_curr_global(h, np.full(size[::-1], INIT_TEMP, np.float32))
    sel = shard_blocks(sphere_sel(size), dd.spec, dd.mesh)
    loop = make_jacobi_loop(dd.halo_exchange, iters)
    c = dd.get_curr(h)
    n = jax.device_put(jnp.zeros_like(c), dd.sharding())
    c, _n = loop(c, n, sel)
    dd.set_curr(h, c)
    return dd.get_curr_global(h)


def test_fused_kernel_interpret_rejects_multi_device_form():
    from stencil_tpu.ops.fused_stencil import make_fused_jacobi_kernel

    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    plan = build_plan(spec, Dim3(2, 2, 2), REMOTE_DMA, fused=True)
    with pytest.raises(ValueError, match="interpret"):
        make_fused_jacobi_kernel(spec, plan, interpret=True)


# -- the fused astaroth loop (8-field MHD fold-in) ----------------------------


def _astaroth_fixture(n=16):
    from stencil_tpu.apps.astaroth import DEFAULT_CONF
    from stencil_tpu.astaroth import config as ac_config
    from stencil_tpu.astaroth.integrate import FIELDS

    info = ac_config.AcMeshInfo()
    with open(DEFAULT_CONF) as f:
        ac_config.parse_config(f.read(), info)
    info.int_params["AC_nx"] = n
    info.int_params["AC_ny"] = n
    info.int_params["AC_nz"] = n
    info.update_builtin_params()
    rng = np.random.RandomState(7)
    fields = {k: rng.randn(n, n, n) * 0.05 for k in FIELDS}
    fields["lnrho"] = fields["lnrho"] + 0.5
    return info, fields


def test_fused_astaroth_loop_matches_composed():
    """8-field MHD through the fused schedule: diagonal cross-derivative
    pencils ride the concurrent per-direction copies. Bit-identical to
    an AXIS_COMPOSED program with the same compute split; within float
    ulps of the monolithic composed step (whose single XLA program fuses
    across the pieces' boundaries)."""
    from stencil_tpu.astaroth.integrate import (FIELDS, make_astaroth_step,
                                                make_fused_astaroth_loop)

    n = 16
    info, fields = _astaroth_fixture(n)
    dt = 1e-3
    spec = GridSpec(Dim3(n, n, n), Dim3(2, 2, 2), Radius.constant(3))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])

    def start():
        curr = {k: shard_blocks(fields[k], spec, mesh) for k in FIELDS}
        out = {k: shard_blocks(np.zeros((n, n, n)), spec, mesh)
               for k in FIELDS}
        return curr, out

    exf = HaloExchange(spec, mesh, Method.REMOTE_DMA, fused=True)
    loop = make_fused_astaroth_loop(exf, info, iters=2, dt=dt)
    curr, out = loop(*start())
    got = {k: unshard_blocks(curr[k], spec) for k in FIELDS}

    exc = HaloExchange(spec, mesh)
    step = make_astaroth_step(exc, info, dt=dt, overlap=True, iters=2)
    curr, out = step(*start())
    ref = {k: unshard_blocks(curr[k], spec) for k in FIELDS}
    for k in FIELDS:
        np.testing.assert_allclose(got[k], ref[k], rtol=1e-12, atol=1e-14,
                                   err_msg=k)


def test_fused_astaroth_rejects_unsupported_configs():
    from stencil_tpu.astaroth.integrate import make_fused_astaroth_loop

    info, _ = _astaroth_fixture(16)
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(3))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    ex = HaloExchange(spec, mesh, Method.AXIS_COMPOSED)
    with pytest.raises(ValueError, match="fused=True"):
        make_fused_astaroth_loop(ex, info)


# -- fp8 wire tier -------------------------------------------------------------


def test_fp8_wire_ab_gates_bytes_and_e4m3_bound():
    from stencil_tpu.apps.bench_exchange import wire_ab, wire_gate

    ratio_thr, rel_bound = wire_gate("float8_e4m3fn")
    assert ratio_thr == pytest.approx(3.8)
    assert rel_bound == pytest.approx(2.0 ** -4)
    rows, ratio, err = wire_ab(
        16, 16, 16, iters=2, quantities=2, radius=2,
        wire="float8_e4m3fn", partition=(2, 2, 2),
        devices=jax.devices()[:8],
    )
    assert ratio >= ratio_thr            # >= 3.8x vs fp32
    assert err["max_rel_err"] <= rel_bound   # inside the e4m3 half-ulp
    assert err["max_rel_err"] > 0            # actually rounded
    # unchanged permute count between the native and compressed legs
    assert len({row["cp_count"] for row in rows}) == 1


def test_fp8_wire_ab_fused_transport():
    from stencil_tpu.apps.bench_exchange import wire_ab, wire_gate

    ratio_thr, rel_bound = wire_gate("float8_e4m3fn")
    rows, ratio, err = wire_ab(
        16, 16, 16, iters=2, quantities=2, radius=2,
        wire="float8_e4m3fn", partition=(2, 2, 2),
        devices=jax.devices()[:8], method=Method.REMOTE_DMA, fused=True,
    )
    assert ratio >= ratio_thr
    assert err["max_rel_err"] <= rel_bound
    assert all(row["cp_count"] == 0 for row in rows)  # 0 ppermutes


# -- conformance auditor + autotune round-trip --------------------------------


def test_verify_plan_audits_fused_lowering():
    from stencil_tpu.analysis import verify_plan as vp

    configs = vp.sweep_configs(size=16, radius=2, partitions=[(2, 2, 2)],
                               methods=[vp.FUSED_METHOD_LABEL],
                               qsets=[("float32", "float32")])
    res = vp.run_sweep(configs)
    assert res["checked"] == 1 and res["failed"] == 0
    checks = {c["name"]: c for c in res["verdicts"][0].checks}
    assert checks["collectives_per_exchange"]["actual"] == 0
    assert checks["census_bytes"]["actual"] == 0
    assert checks["dma_transfers"]["ok"]
    # the auditor actually trips when the DMA prediction drifts
    res = vp.run_sweep(configs, perturb_dmas=1)
    assert res["failed"] == 1


def test_verify_plan_default_sweep_includes_fused():
    from stencil_tpu.analysis import verify_plan as vp

    methods = {c["method"] for c in vp.sweep_configs()}
    assert vp.FUSED_METHOD_LABEL in methods


def test_autotune_persists_fused_variant_entry(tmp_path):
    from stencil_tpu.plan import db as plandb
    from stencil_tpu.plan.autotune import autotune

    db_path = str(tmp_path / "plans.json")
    kwargs = dict(ndev=8, platform="cpu", db_path=db_path, probe=False,
                  methods=("remote-dma",), variants=(FUSED_VARIANT,))
    res = autotune(Dim3(16, 16, 16), Radius.constant(1), ["float32"],
                   **kwargs)
    assert res.choice.is_fused and res.choice.method == "remote-dma"
    db = plandb.load_db(db_path)
    entry = plandb.lookup(db, res.config)
    assert PlanChoice.from_json(entry["choice"]).is_fused
    res2 = autotune(Dim3(16, 16, 16), Radius.constant(1), ["float32"],
                    **kwargs)
    assert res2.cache_hit and res2.choice.is_fused


def test_domain_realizes_tuned_fused_plan():
    from stencil_tpu.api import DistributedDomain

    dd = DistributedDomain(16, 16, 16, plan={
        "partition": [2, 2, 2], "method": "remote-dma",
        "batch_quantities": True, "multistep_k": 1,
        "kernel_variant": "fused",
    })
    dd.set_radius(1)
    dd.set_devices(jax.devices()[:8])
    dd.add_data("t", "float32")
    dd.realize()
    assert dd.halo_exchange.fused
    assert dd.plan_meta()["choice"]["kernel_variant"] == "fused"
