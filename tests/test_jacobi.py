"""jacobi3d correctness: distributed overlap step vs numpy periodic
reference (BASELINE.json config 1 idiom: vs CPU reference)."""

import jax
import numpy as np
import pytest

from stencil_tpu.apps.jacobi3d import run, weak_scale, csv_row
from stencil_tpu.geometry import Dim3
from stencil_tpu.ops.jacobi import INIT_TEMP, jacobi_reference, sphere_masks
from stencil_tpu.parallel import Method


def test_weak_scale_matches_reference_rule():
    # prime factors of 8 = [2,2,2] multiplied into smallest axis each time
    assert weak_scale(4, 4, 4, 8) == Dim3(8, 8, 8)
    assert weak_scale(2, 3, 5, 6) == Dim3(6, 6, 5)  # pf [3,2]: x*3=6 then y*2=6
    assert weak_scale(5, 5, 5, 1) == Dim3(5, 5, 5)


@pytest.mark.parametrize("overlap", [True, False])
def test_jacobi_matches_numpy(overlap):
    iters = 4
    r = run(20, 16, 12, iters=iters, overlap=overlap, weak=False,
            devices=jax.devices()[:8], warmup=0)
    size = Dim3(r["x"], r["y"], r["z"])
    dd, h = r["domain"], r["handle"]
    got = dd.get_curr_global(h)

    masks = sphere_masks(size)
    field = np.full((size.z, size.y, size.x), INIT_TEMP, dtype=np.float32)
    want = jacobi_reference(field, masks, iters)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_overlap_equals_no_overlap():
    ra = run(20, 16, 12, iters=3, overlap=True, weak=False,
             devices=jax.devices()[:8], warmup=0)
    rb = run(20, 16, 12, iters=3, overlap=False, weak=False,
             devices=jax.devices()[:8], warmup=0)
    a = ra["domain"].get_curr_global(ra["handle"])
    b = rb["domain"].get_curr_global(rb["handle"])
    np.testing.assert_array_equal(a, b)


def test_direct26_method_agrees():
    ra = run(16, 16, 16, iters=2, weak=False, devices=jax.devices()[:8], warmup=0)
    rb = run(16, 16, 16, iters=2, weak=False, devices=jax.devices()[:8],
             method=Method.DIRECT26, warmup=0)
    a = ra["domain"].get_curr_global(ra["handle"])
    b = rb["domain"].get_curr_global(rb["handle"])
    np.testing.assert_array_equal(a, b)


def test_uneven_distributed_jacobi():
    """Uneven partition falls back to non-overlap but must stay correct."""
    iters = 3
    r = run(18, 14, 10, iters=iters, weak=False, devices=jax.devices()[:8], warmup=0)
    size = Dim3(r["x"], r["y"], r["z"])
    masks = sphere_masks(size)
    field = np.full((size.z, size.y, size.x), INIT_TEMP, dtype=np.float32)
    want = jacobi_reference(field, masks, iters)
    got = r["domain"].get_curr_global(r["handle"])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_csv_row_format():
    r = run(8, 8, 8, iters=1, weak=False, devices=jax.devices()[:1], warmup=0)
    row = csv_row(r)
    assert row.startswith("jacobi3d,axis-composed,1,1,8,8,8,")
    assert len(row.split(",")) == 10


def test_run_executes_exact_iteration_count():
    """iters not a multiple of the fused chunk must not overshoot."""
    iters = 7
    r = run(16, 12, 10, iters=iters, weak=False, devices=jax.devices()[:8],
            warmup=0, chunk=5)
    size = Dim3(r["x"], r["y"], r["z"])
    masks = sphere_masks(size)
    field = np.full((size.z, size.y, size.x), INIT_TEMP, dtype=np.float32)
    want = jacobi_reference(field, masks, iters)
    got = r["domain"].get_curr_global(r["handle"])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_distributed_pallas_step_matches_xla_path():
    """Full distributed jacobi step (wrap/exchange + pallas sweep inside
    shard_map) on a 2x2x1 mesh in interpret mode vs the XLA path — pins
    the integration wiring (axis subsetting, in-kernel wrap on the
    single-block axis), not just the standalone kernel."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.jacobi import make_jacobi_step, sphere_sel
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    size = Dim3(16, 16, 16)
    spec = GridSpec(size, Dim3(2, 2, 1), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:4])
    ex = HaloExchange(spec, mesh)
    rng = np.random.RandomState(4)
    field = rng.rand(size.z, size.y, size.x).astype(np.float32)
    sel = shard_blocks(sphere_sel(size), spec, mesh)

    outs = {}
    for label, kwargs in (
        ("pallas", dict(use_pallas=True, interpret=True)),
        ("xla", dict(use_pallas=False)),
    ):
        step = make_jacobi_step(ex, **kwargs)
        curr = shard_blocks(field, spec, mesh)
        nxt = shard_blocks(np.zeros_like(field), spec, mesh)
        curr, nxt = step(curr, nxt, sel)
        outs[label] = unshard_blocks(curr, spec)
    np.testing.assert_allclose(outs["pallas"], outs["xla"], rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("k", [1, 2, 3, 5, 12])
def test_pallas_multistep_matches_reference(k):
    """Temporal-blocked kernel (interpret mode): k fused steps must equal
    k applications of the numpy periodic reference, spheres included.
    k=12 pins the default cap depth (re-measured round 5;
    STENCIL_TEMPORAL_K_CAP probes others; pipeline needs nz >= 2k+1)."""
    import jax.numpy as jnp
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.pallas_stencil import make_pallas_jacobi_multistep

    size = Dim3(20, 16, 12) if k <= 5 else Dim3(20, 16, 28)
    spec = GridSpec(size, Dim3(1, 1, 1), Radius.constant(1))
    p = spec.padded()
    off = spec.compute_offset()
    fn = make_pallas_jacobi_multistep(spec, k, interpret=True)
    rng = np.random.RandomState(0)
    curr = np.zeros((p.z, p.y, p.x), np.float32)
    sl = (
        slice(off.z, off.z + size.z),
        slice(off.y, off.y + size.y),
        slice(off.x, off.x + size.x),
    )
    field = rng.rand(size.z, size.y, size.x).astype(np.float32)
    curr[sl] = field
    got = np.asarray(
        fn(jnp.asarray(curr), jnp.zeros((p.z, p.y, p.x), jnp.float32))
    )
    want = jacobi_reference(field, sphere_masks(size), k)
    # fp32 rounding accumulates ~linearly in fused steps (the reference
    # runs in float64)
    np.testing.assert_allclose(
        got[sl], want, rtol=1e-7 * (2 + k), atol=5e-8 * (1 + k)
    )


@pytest.mark.parametrize(
    "k,size,ty",
    [
        # ny=40 NOT divisible by ty=16: the final strip re-anchors to
        # yo + ny - ty and recomputes its overlap with the previous strip
        (3, Dim3(20, 40, 12), 16),
        # the target depth regime the row tiling exists for (k >= 8)
        (8, Dim3(20, 32, 18), 16),
    ],
)
def test_pallas_multistep_row_tiled_matches_reference(k, size, ty):
    """Row-tiled staging (strips instead of full (py, px) planes): k fused
    wavefront steps must equal k applications of the numpy periodic
    reference, spheres included, edge strips' periodic y rows delivered by
    the wrap-row DMAs (VERDICT r5 weak #2 — 768^3 depth regime)."""
    import jax.numpy as jnp
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.pallas_stencil import make_pallas_jacobi_multistep

    spec = GridSpec(size, Dim3(1, 1, 1), Radius.constant(1))
    p = spec.padded()
    off = spec.compute_offset()
    fn = make_pallas_jacobi_multistep(spec, k, interpret=True, rows=ty)
    rng = np.random.RandomState(0)
    curr = np.zeros((p.z, p.y, p.x), np.float32)
    sl = (
        slice(off.z, off.z + size.z),
        slice(off.y, off.y + size.y),
        slice(off.x, off.x + size.x),
    )
    field = rng.rand(size.z, size.y, size.x).astype(np.float32)
    curr[sl] = field
    got = np.asarray(
        fn(jnp.asarray(curr), jnp.zeros((p.z, p.y, p.x), jnp.float32))
    )
    want = jacobi_reference(field, sphere_masks(size), k)
    np.testing.assert_allclose(
        got[sl], want, rtol=1e-7 * (2 + k), atol=5e-8 * (1 + k)
    )


def test_pallas_multistep_row_tiled_tight_x():
    """Row strips compose with the zero-x-radius tight layout (the 768^3
    production combination: x wrap by lane rolls, y wrap by strip DMAs)."""
    import jax.numpy as jnp
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.pallas_stencil import make_pallas_jacobi_multistep

    k, ty = 4, 16
    size = Dim3(128, 32, 14)
    spec = GridSpec(size, Dim3(1, 1, 1), Radius.constant(1).without_x())
    assert spec.padded().x == 128 and spec.compute_offset().x == 0
    p = spec.padded()
    off = spec.compute_offset()
    fn = make_pallas_jacobi_multistep(spec, k, interpret=True, rows=ty)
    rng = np.random.RandomState(3)
    curr = np.zeros((p.z, p.y, p.x), np.float32)
    sl = (
        slice(off.z, off.z + size.z),
        slice(off.y, off.y + size.y),
        slice(off.x, off.x + size.x),
    )
    curr[sl] = rng.rand(size.z, size.y, size.x)
    got = np.asarray(fn(jnp.asarray(curr), jnp.zeros_like(curr)))[sl]
    want = jacobi_reference(curr[sl], sphere_masks(size), k).astype(np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_deep_halo_multistep_row_tiled_z_split_matches_xla():
    """Row-tiled staging under a deep-halo z split (dim 1x1x2, radius 2):
    strips stage the y wrap while z rides the radius-k exchange — the
    768^3-per-chip-on-a-z-mesh configuration. Forced via multistep_rows;
    must match the XLA loop bit-for-bit."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.jacobi import make_jacobi_loop, sphere_sel
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    size = Dim3(16, 32, 20)
    iters = 4
    spec = GridSpec(size, Dim3(1, 1, 2), Radius.constant(2))  # k caps at 2
    mesh = grid_mesh(spec.dim, jax.devices()[:2])
    ex = HaloExchange(spec, mesh)
    rng = np.random.RandomState(31)
    field = rng.rand(size.z, size.y, size.x).astype(np.float32)
    sel = shard_blocks(sphere_sel(size), spec, mesh)

    outs = {}
    for label, kwargs in (
        ("pallas-rows", dict(use_pallas=True, interpret=True,
                             multistep_rows=16)),
        ("xla", dict(use_pallas=False)),
    ):
        loop = make_jacobi_loop(ex, iters, **kwargs)
        curr = shard_blocks(field, spec, mesh)
        nxt = shard_blocks(np.zeros_like(field), spec, mesh)
        curr, nxt = loop(curr, nxt, sel)
        outs[label] = unshard_blocks(curr, spec)
    np.testing.assert_array_equal(outs["pallas-rows"], outs["xla"])


def test_plan_multistep_staging_regimes():
    """The staging planner: full planes while they reach the cap (512^3
    regime — byte-identical to the round-5 layout), row strips when the
    plane size would self-cap the depth (the 768^3 regime that measured
    k=4 / 55.3 Gcells/s on full planes), and a graceful full-plane
    fallback for multi-block y."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.pallas_stencil import (
        plan_multistep_staging, valid_strip_rows,
    )

    budget = 46 * 1024 * 1024
    tight = Radius.constant(1).without_x()
    s512 = GridSpec(Dim3(512, 512, 512), Dim3(1, 1, 1), tight)
    k, rows = plan_multistep_staging(s512, 12, budget)
    assert (k, rows) == (12, None)  # full planes still reach the cap

    s768 = GridSpec(Dim3(768, 768, 768), Dim3(1, 1, 1), tight)
    k, rows = plan_multistep_staging(s768, 12, budget)
    assert k >= 8 and rows is not None  # the depth the full planes lost
    assert valid_strip_rows(s768, k, rows)

    # multi-block y: strips are unsupported — depth degrades, never crashes
    my = GridSpec(Dim3(768, 768, 768), Dim3(1, 2, 1), Radius.constant(12))
    k, rows = plan_multistep_staging(my, 12, budget)
    assert rows is None and k >= 2


def test_temporal_k_cap_env(monkeypatch):
    """STENCIL_TEMPORAL_K_CAP overrides the default depth cap (the probe
    knob that re-measures the diminishing-returns point on hardware —
    k=12 won at 512^3 round 5); the requested depth must reach the
    multistep builder."""
    import stencil_tpu.ops.pallas_stencil as ps
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.jacobi import make_jacobi_loop
    from stencil_tpu.parallel import HaloExchange, grid_mesh

    recorded = []
    orig = ps.make_pallas_jacobi_multistep

    def rec(spec, k, **kw):
        recorded.append(k)
        return orig(spec, k, **kw)

    monkeypatch.setattr(ps, "make_pallas_jacobi_multistep", rec)
    size = Dim3(20, 16, 28)  # nz >= 2k+1 for k=12
    spec = GridSpec(size, Dim3(1, 1, 1), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:1])
    ex = HaloExchange(spec, mesh)
    for env, want in ((None, 12), ("10", 10)):
        recorded.clear()
        if env is None:
            monkeypatch.delenv("STENCIL_TEMPORAL_K_CAP", raising=False)
        else:
            monkeypatch.setenv("STENCIL_TEMPORAL_K_CAP", env)
        make_jacobi_loop(ex, iters=24, use_pallas=True, interpret=True)
        assert recorded == [want], (env, recorded)


@pytest.mark.parametrize("tiles", [None, (5, 16)])
def test_pallas_wrap_matches_periodic_reference(tiles, monkeypatch):
    """Self-wrap mode (kernel fills periodic halos itself) vs np.roll
    reference; tiles=(5,16) forces the row-tiled slab path with the
    staged y-wrap DMA."""
    import jax.numpy as jnp
    import stencil_tpu.ops.pallas_stencil as ps
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius

    size = Dim3(24, 64, 10)
    spec = GridSpec(size, Dim3(1, 1, 1), Radius.constant(1))
    if tiles is not None:
        monkeypatch.setattr(ps, "_pick_tiles", lambda *a: tiles)
    sweep = ps.make_pallas_jacobi_sweep(
        spec, (0, 0), interpret=True, wrap=(True, True, True)
    )
    p = spec.padded()
    off = spec.compute_offset()
    rng = np.random.RandomState(1)
    curr = jnp.asarray(rng.rand(p.z, p.y, p.x).astype(np.float32))
    got = np.asarray(
        sweep(curr, jnp.zeros((p.z, p.y, p.x), jnp.float32),
              jnp.zeros((p.z, p.y, p.x), np.int32))
    )
    sl = (
        slice(off.z, off.z + size.z),
        slice(off.y, off.y + size.y),
        slice(off.x, off.x + size.x),
    )
    f = np.asarray(curr)[sl].astype(np.float64)
    want = (
        np.roll(f, 1, 2) + np.roll(f, -1, 2) + np.roll(f, 1, 1)
        + np.roll(f, -1, 1) + np.roll(f, 1, 0) + np.roll(f, -1, 0)
    ) / 6
    np.testing.assert_allclose(got[sl], want, rtol=3e-7, atol=1e-7)


def test_pallas_sweep_matches_xla_interpret():
    """Pallas kernel (interpret mode) computes exactly what the XLA path
    computes over the compute region, including sphere overrides."""
    import jax.numpy as jnp
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius, Rect3
    from stencil_tpu.ops.jacobi import jacobi_sweep, sphere_sel
    from stencil_tpu.ops.pallas_stencil import make_pallas_jacobi_sweep, sel_z_range

    size = Dim3(40, 16, 8)
    spec = GridSpec(size, Dim3(1, 1, 1), Radius.constant(1))
    sweep = make_pallas_jacobi_sweep(spec, sel_z_range(spec), interpret=True)
    p = spec.padded()
    off = spec.compute_offset()
    rng = np.random.RandomState(0)
    curr = jnp.asarray(rng.rand(p.z, p.y, p.x).astype(np.float32))
    nxt = jnp.zeros((p.z, p.y, p.x), jnp.float32)
    selg = sphere_sel(size)
    sel = np.zeros((p.z, p.y, p.x), np.int32)
    cz = slice(off.z, off.z + size.z)
    cy = slice(off.y, off.y + size.y)
    cx = slice(off.x, off.x + size.x)
    sel[cz, cy, cx] = selg
    got = np.asarray(sweep(curr, nxt, jnp.asarray(sel)))

    rect = Rect3(off, off + spec.base)
    sel_j = jnp.asarray(sel)
    want = np.asarray(
        jacobi_sweep(curr, jnp.zeros_like(nxt), rect, (sel_j == 1, sel_j == 2))
    )
    # the two lowerings may reassociate differently -> ULP-level tolerance
    np.testing.assert_allclose(got[cz, cy, cx], want[cz, cy, cx], rtol=3e-7, atol=1e-7)
    assert (sel[cz, cy, cx] == 1).any()  # spheres actually exercised


def test_distributed_pallas_overlap_2x2x2_matches_xla():
    """Overlapped Pallas fast path on a full 2x2x2 mesh (every axis
    multi-block, interpret mode), three fused iterations: the full-region
    sweep reads pre-exchange data and the multi-block-axis shells are
    re-swept from exchanged halos — must equal the XLA overlap path
    (VERDICT r2 item 2a)."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.jacobi import make_jacobi_loop, sphere_sel
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    size = Dim3(16, 16, 16)
    spec = GridSpec(size, Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    ex = HaloExchange(spec, mesh)
    rng = np.random.RandomState(11)
    field = rng.rand(size.z, size.y, size.x).astype(np.float32)
    sel = shard_blocks(sphere_sel(size), spec, mesh)

    outs = {}
    for label, kwargs in (
        ("pallas", dict(use_pallas=True, interpret=True)),
        ("xla", dict(use_pallas=False)),
    ):
        loop = make_jacobi_loop(ex, iters=3, overlap=True, **kwargs)
        curr = shard_blocks(field, spec, mesh)
        nxt = shard_blocks(np.zeros_like(field), spec, mesh)
        curr, nxt = loop(curr, nxt, sel)
        outs[label] = unshard_blocks(curr, spec)
    np.testing.assert_allclose(outs["pallas"], outs["xla"], rtol=1e-6, atol=1e-7)


def test_uneven_overlap_equals_no_overlap():
    """Uneven partitions keep the interior/exterior overlap via dynamic
    shells (ops/shells.py, VERDICT r2 item 8): the overlapped step must be
    bit-exact vs the serialized step on a genuinely uneven 2x2x2 split
    (x blocks 10 and 9) and match the global reference."""
    iters = 3
    kw = dict(iters=iters, weak=False, devices=jax.devices()[:8], warmup=0,
              partition=(2, 2, 2))
    ra = run(19, 14, 10, overlap=True, **kw)
    rb = run(19, 14, 10, overlap=False, **kw)
    a = ra["domain"].get_curr_global(ra["handle"])
    b = rb["domain"].get_curr_global(rb["handle"])
    np.testing.assert_array_equal(a, b)
    size = Dim3(ra["x"], ra["y"], ra["z"])
    masks = sphere_masks(size)
    field = np.full((size.z, size.y, size.x), INIT_TEMP, dtype=np.float32)
    want = jacobi_reference(field, masks, iters)
    np.testing.assert_allclose(a, want, rtol=1e-5, atol=1e-6)


def test_distributed_pallas_uneven_overlap_matches_xla():
    """Pallas fast path with dynamic-shell overlap on an uneven 2x2x1 mesh
    (x blocks 10 and 9; z self-wraps in-kernel), interpret mode, vs the
    serialized XLA step."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.jacobi import make_jacobi_step, sphere_sel
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    size = Dim3(19, 16, 12)
    spec = GridSpec(size, Dim3(2, 2, 1), Radius.constant(1))
    assert not spec.is_uniform()
    mesh = grid_mesh(spec.dim, jax.devices()[:4])
    ex = HaloExchange(spec, mesh)
    rng = np.random.RandomState(11)
    field = rng.rand(size.z, size.y, size.x).astype(np.float32)
    sel = shard_blocks(sphere_sel(size), spec, mesh)

    outs = {}
    for label, kwargs in (
        ("pallas-overlap", dict(use_pallas=True, interpret=True, overlap=True)),
        ("xla-overlap", dict(use_pallas=False, overlap=True)),
        ("xla-serial", dict(use_pallas=False, overlap=False)),
    ):
        step = make_jacobi_step(ex, **kwargs)
        curr = shard_blocks(field, spec, mesh)
        nxt = shard_blocks(np.zeros_like(field), spec, mesh)
        for _ in range(2):
            curr, nxt = step(curr, nxt, sel)
        outs[label] = unshard_blocks(curr, spec)
    np.testing.assert_array_equal(outs["xla-overlap"], outs["xla-serial"])
    np.testing.assert_allclose(
        outs["pallas-overlap"], outs["xla-serial"], rtol=1e-6, atol=1e-7
    )


def test_deep_halo_multistep_2x2x2_matches_xla():
    """Multi-chip temporal blocking (VERDICT r2 item 7): with radius-2
    halos on a full 2x2x2 mesh, the fused loop takes the deep-halo
    multistep path — ONE radius-2 exchange feeding k=2 fused wavefront
    steps — and must match the per-step XLA overlap loop bit-for-bit on
    the gathered field (integer sphere math, same operand order)."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.jacobi import make_jacobi_loop, sphere_sel
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    size = Dim3(24, 24, 24)
    iters = 4
    spec = GridSpec(size, Dim3(2, 2, 2), Radius.constant(2))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    ex = HaloExchange(spec, mesh)
    rng = np.random.RandomState(6)
    field = rng.rand(size.z, size.y, size.x).astype(np.float32)
    sel = shard_blocks(sphere_sel(size), spec, mesh)

    outs = {}
    for label, kwargs in (
        ("pallas-deep", dict(use_pallas=True, interpret=True)),
        ("xla", dict(use_pallas=False)),
    ):
        loop = make_jacobi_loop(ex, iters, **kwargs)
        curr = shard_blocks(field, spec, mesh)
        nxt = shard_blocks(np.zeros_like(field), spec, mesh)
        curr, nxt = loop(curr, nxt, sel)
        outs[label] = unshard_blocks(curr, spec)
    np.testing.assert_array_equal(outs["pallas-deep"], outs["xla"])


def test_deep_halo_multistep_mixed_mesh_matches_xla():
    """Deep-halo multistep on a mesh mixing a multi-block z axis with
    self-wrap y/x axes (2x1x1): z halos exchanged at depth k, y/x wrapped
    in-kernel per stage."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.jacobi import make_jacobi_loop, sphere_sel
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    size = Dim3(20, 16, 24)
    iters = 6
    spec = GridSpec(size, Dim3(1, 1, 2), Radius.constant(3))  # k caps at 3
    mesh = grid_mesh(spec.dim, jax.devices()[:2])
    ex = HaloExchange(spec, mesh)
    rng = np.random.RandomState(8)
    field = rng.rand(size.z, size.y, size.x).astype(np.float32)
    sel = shard_blocks(sphere_sel(size), spec, mesh)

    outs = {}
    for label, kwargs in (
        ("pallas-deep", dict(use_pallas=True, interpret=True)),
        ("xla", dict(use_pallas=False)),
    ):
        loop = make_jacobi_loop(ex, iters, **kwargs)
        curr = shard_blocks(field, spec, mesh)
        nxt = shard_blocks(np.zeros_like(field), spec, mesh)
        curr, nxt = loop(curr, nxt, sel)
        outs[label] = unshard_blocks(curr, spec)
    np.testing.assert_array_equal(outs["pallas-deep"], outs["xla"])


def test_deep_halo_app_flag_stays_correct():
    """--deep-halo K realizes radius-K halos (XLA path on the CPU mesh);
    results must be unchanged."""
    iters = 3
    r = run(16, 16, 16, iters=iters, weak=False, devices=jax.devices()[:8],
            warmup=0, deep_halo=2)
    size = Dim3(r["x"], r["y"], r["z"])
    masks = sphere_masks(size)
    field = np.full((size.z, size.y, size.x), INIT_TEMP, dtype=np.float32)
    want = jacobi_reference(field, masks, iters)
    got = r["domain"].get_curr_global(r["handle"])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_deep_halo_sphere_crossing_periodic_boundary():
    """Non-cubic domain where the hot/cold spheres (radius g.x//10) cross
    the periodic z boundary of a z-split mesh: the deep-halo multistep must
    clamp halo-extended cells at their WRAPPED global coordinates, exactly
    as the owning block does (review r3 finding)."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.jacobi import make_jacobi_loop, sphere_sel
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    size = Dim3(128, 16, 20)  # R = 12 > g.z/2 - ... : spheres wrap in z
    iters = 4
    spec = GridSpec(size, Dim3(1, 1, 2), Radius.constant(2))
    mesh = grid_mesh(spec.dim, jax.devices()[:2])
    ex = HaloExchange(spec, mesh)
    rng = np.random.RandomState(9)
    field = rng.rand(size.z, size.y, size.x).astype(np.float32)
    sel = shard_blocks(sphere_sel(size), spec, mesh)

    outs = {}
    for label, kwargs in (
        ("pallas-deep", dict(use_pallas=True, interpret=True)),
        ("xla", dict(use_pallas=False)),
    ):
        loop = make_jacobi_loop(ex, iters, **kwargs)
        curr = shard_blocks(field, spec, mesh)
        nxt = shard_blocks(np.zeros_like(field), spec, mesh)
        curr, nxt = loop(curr, nxt, sel)
        outs[label] = unshard_blocks(curr, spec)
    np.testing.assert_array_equal(outs["pallas-deep"], outs["xla"])


def test_oversubscribed_jacobi_matches_reference():
    """2x2x2 partition on 4 devices (2 z-blocks resident per device,
    reference: dd.set_gpus({0,0})): the full distributed iteration must
    match the global reference and the 8-device run bit-for-bit."""
    iters = 3
    ra = run(16, 16, 16, iters=iters, weak=False, devices=jax.devices()[:4],
             warmup=0, partition=(2, 2, 2))
    rb = run(16, 16, 16, iters=iters, weak=False, devices=jax.devices()[:8],
             warmup=0, partition=(2, 2, 2))
    a = ra["domain"].get_curr_global(ra["handle"])
    b = rb["domain"].get_curr_global(rb["handle"])
    np.testing.assert_array_equal(a, b)
    size = Dim3(16, 16, 16)
    masks = sphere_masks(size)
    field = np.full((size.z, size.y, size.x), INIT_TEMP, dtype=np.float32)
    want = jacobi_reference(field, masks, iters)
    np.testing.assert_allclose(a, want, rtol=1e-5, atol=1e-6)


def test_oversubscribed_jacobi_two_devices_matches_reference():
    """2x2x2 partition on TWO devices — mixed (cz, cy) = (2, 2) stacking
    (VERDICT r3 item 4 'done' bar): must match the 8-device run bit-for-bit
    and the global reference."""
    iters = 3
    ra = run(16, 16, 16, iters=iters, weak=False, devices=jax.devices()[:2],
             warmup=0, partition=(2, 2, 2))
    rb = run(16, 16, 16, iters=iters, weak=False, devices=jax.devices()[:8],
             warmup=0, partition=(2, 2, 2))
    assert ra["domain"].halo_exchange.oversubscribed
    a = ra["domain"].get_curr_global(ra["handle"])
    b = rb["domain"].get_curr_global(rb["handle"])
    np.testing.assert_array_equal(a, b)
    size = Dim3(16, 16, 16)
    masks = sphere_masks(size)
    field = np.full((size.z, size.y, size.x), INIT_TEMP, dtype=np.float32)
    want = jacobi_reference(field, masks, iters)
    np.testing.assert_allclose(a, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("overlap", [True, False])
def test_resident_pallas_step_matches_xla(overlap):
    """Resident z-stack (2x2x2 partition on 4 devices) on the Pallas fast
    path (interpret): the per-block kernel loops over the stacked residents
    and must match the XLA slab path bit-for-bit (VERDICT r4 item 7 —
    oversubscription no longer forfeits the Pallas sweep)."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.jacobi import make_jacobi_step, sphere_sel
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    size = Dim3(16, 16, 16)
    spec = GridSpec(size, Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(Dim3(2, 2, 1), jax.devices()[:4])
    ex = HaloExchange(spec, mesh)
    assert ex.oversubscribed and ex.resident.z == 2
    rng = np.random.RandomState(21)
    field = rng.rand(size.z, size.y, size.x).astype(np.float32)
    sel = shard_blocks(sphere_sel(size), spec, mesh)

    outs = {}
    for label, kwargs in (
        ("pallas", dict(use_pallas=True, interpret=True)),
        ("xla", dict(use_pallas=False)),
    ):
        step = make_jacobi_step(ex, overlap=overlap, **kwargs)
        curr = shard_blocks(field, spec, mesh)
        nxt = shard_blocks(np.zeros_like(field), spec, mesh)
        curr, nxt = step(curr, nxt, sel)
        outs[label] = unshard_blocks(curr, spec)
    np.testing.assert_array_equal(outs["pallas"], outs["xla"])


def test_resident_mixed_pallas_step_matches_xla():
    """Mixed (cy, cx) residency (2x2x2 on 2 devices, mesh z=2): the sweep
    loop flattens ALL leading block dims, not just z-stacks."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.jacobi import make_jacobi_step, sphere_sel
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    size = Dim3(16, 16, 16)
    spec = GridSpec(size, Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(Dim3(1, 1, 2), jax.devices()[:2])
    ex = HaloExchange(spec, mesh)
    assert ex.resident.x == 2 and ex.resident.y == 2 and ex.resident.z == 1
    rng = np.random.RandomState(22)
    field = rng.rand(size.z, size.y, size.x).astype(np.float32)
    sel = shard_blocks(sphere_sel(size), spec, mesh)

    outs = {}
    for label, kwargs in (
        ("pallas", dict(use_pallas=True, interpret=True)),
        ("xla", dict(use_pallas=False)),
    ):
        step = make_jacobi_step(ex, **kwargs)
        curr = shard_blocks(field, spec, mesh)
        nxt = shard_blocks(np.zeros_like(field), spec, mesh)
        curr, nxt = step(curr, nxt, sel)
        outs[label] = unshard_blocks(curr, spec)
    np.testing.assert_array_equal(outs["pallas"], outs["xla"])


@pytest.mark.parametrize("mesh_z,ndev", [(1, 1), (2, 2)])
def test_resident_deep_halo_multistep_matches_xla(mesh_z, ndev):
    """Deep-halo temporal multistep under z residency: each resident block
    gets its own multistep call at its own global origin (the config-2
    fully-resident-on-one-chip geometry, and its 2-device split)."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.jacobi import make_jacobi_loop, sphere_sel
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    size = Dim3(20, 16, 24)
    iters = 4
    nz = 2 * mesh_z
    spec = GridSpec(size, Dim3(1, 1, nz), Radius.constant(2))
    mesh = grid_mesh(Dim3(1, 1, mesh_z), jax.devices()[:ndev])
    ex = HaloExchange(spec, mesh)
    assert ex.resident.z == 2
    rng = np.random.RandomState(23)
    field = rng.rand(size.z, size.y, size.x).astype(np.float32)
    sel = shard_blocks(sphere_sel(size), spec, mesh)

    outs = {}
    for label, kwargs in (
        ("pallas-deep", dict(use_pallas=True, interpret=True)),
        ("xla", dict(use_pallas=False)),
    ):
        loop = make_jacobi_loop(ex, iters, **kwargs)
        curr = shard_blocks(field, spec, mesh)
        nxt = shard_blocks(np.zeros_like(field), spec, mesh)
        curr, nxt = loop(curr, nxt, sel)
        outs[label] = unshard_blocks(curr, spec)
    np.testing.assert_array_equal(outs["pallas-deep"], outs["xla"])


def test_oversubscribed_uneven_xy_overlap_falls_back():
    """Resident z-stacking + an uneven x/y split + overlap=True used to
    crash at trace time in _patch_shells_dyn's (pz,py,px) reshape (ADVICE
    r3); it must fall back to the serialized exchange-then-sweep path and
    still match the global reference."""
    iters = 2
    # x = 10+9 (uneven), y = 9+9, z = 8+8 (uniform, required for residency)
    ra = run(19, 18, 16, iters=iters, weak=False, devices=jax.devices()[:4],
             warmup=0, partition=(2, 2, 2), overlap=True)
    assert ra["domain"].halo_exchange.resident_z == 2
    a = ra["domain"].get_curr_global(ra["handle"])
    size = Dim3(19, 18, 16)
    masks = sphere_masks(size)
    field = np.full((size.z, size.y, size.x), INIT_TEMP, dtype=np.float32)
    want = jacobi_reference(field, masks, iters)
    np.testing.assert_allclose(a, want, rtol=1e-5, atol=1e-6)


def test_pallas_sweep_lane_aligned_inline_matches_xla():
    """Lane-aligned nx (128) with INLINE halos (radius 1, xo == 1): the
    tight-x gate must stay off (DMA slice offsets must be 128-divisible,
    ops/pallas_stencil._tight_x_layout) and the inline path must match the
    XLA step bit-for-bit. The engaged tight path is pinned separately by
    test_zero_x_radius_tight_layout_matches_reference."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.jacobi import make_jacobi_step, sphere_sel
    from stencil_tpu.ops.pallas_stencil import make_pallas_jacobi_sweep
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    size = Dim3(128, 16, 12)  # x self-wraps and is lane-aligned
    spec = GridSpec(size, Dim3(1, 2, 1), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:2])
    ex = HaloExchange(spec, mesh)
    rng = np.random.RandomState(12)
    field = rng.rand(size.z, size.y, size.x).astype(np.float32)
    sel = shard_blocks(sphere_sel(size), spec, mesh)

    outs = {}
    for label, kwargs in (
        ("pallas", dict(use_pallas=True, interpret=True)),
        ("xla", dict(use_pallas=False)),
    ):
        step = make_jacobi_step(ex, **kwargs)
        curr = shard_blocks(field, spec, mesh)
        nxt = shard_blocks(np.zeros_like(field), spec, mesh)
        for _ in range(2):
            curr, nxt = step(curr, nxt, sel)
        outs[label] = unshard_blocks(curr, spec)
    np.testing.assert_array_equal(outs["pallas"], outs["xla"])


def test_pallas_multistep_lane_aligned_inline_matches_reference():
    """Lane-aligned x (nx % 128 == 0) with INLINE halos (radius 1,
    xo == 1): the multistep's tight-x gate stays off and the inline path
    must equal k applications of the numpy periodic reference. The
    engaged tight multistep (zero-x-radius layout) is pinned by
    test_zero_x_radius_tight_layout_matches_reference (k=4) and
    test_zero_x_radius_tight_multistep_deep_k below."""
    import jax.numpy as jnp
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.pallas_stencil import make_pallas_jacobi_multistep

    k = 3
    size = Dim3(128, 16, 12)
    spec = GridSpec(size, Dim3(1, 1, 1), Radius.constant(1))
    p = spec.padded()
    off = spec.compute_offset()
    fn = make_pallas_jacobi_multistep(spec, k, interpret=True)
    rng = np.random.RandomState(0)
    curr = np.zeros((p.z, p.y, p.x), np.float32)
    sl = (
        slice(off.z, off.z + size.z),
        slice(off.y, off.y + size.y),
        slice(off.x, off.x + size.x),
    )
    curr[sl] = rng.rand(size.z, size.y, size.x)
    got = np.asarray(fn(jnp.asarray(curr), jnp.zeros_like(curr)))[sl]
    want = jacobi_reference(curr[sl], sphere_masks(size), k).astype(np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_zero_x_radius_tight_layout_matches_reference():
    """Radius.without_x on a single block (no x halo columns allocated,
    px == nx): both the one-step sweep and the fused multistep must match
    the periodic numpy reference in interpret mode."""
    import jax.numpy as jnp
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.jacobi import (
        make_jacobi_loop, make_jacobi_step, sphere_sel,
    )
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    size = Dim3(128, 16, 12)
    spec = GridSpec(size, Dim3(1, 1, 1), Radius.constant(1).without_x())
    assert spec.padded().x == 128 and spec.compute_offset().x == 0
    mesh = grid_mesh(spec.dim, jax.devices()[:1])
    ex = HaloExchange(spec, mesh)
    rng = np.random.RandomState(13)
    field = rng.rand(size.z, size.y, size.x).astype(np.float32)
    sel = shard_blocks(sphere_sel(size), spec, mesh)
    masks = sphere_masks(size)

    for iters, maker in ((1, lambda: make_jacobi_step(
            ex, use_pallas=True, interpret=True)),
                         (4, lambda: make_jacobi_loop(
            ex, 4, use_pallas=True, interpret=True))):
        step = maker()
        curr = shard_blocks(field, spec, mesh)
        nxt = shard_blocks(np.zeros_like(field), spec, mesh)
        curr, nxt = step(curr, nxt, sel)
        got = unshard_blocks(curr, spec)
        want = jacobi_reference(field, masks, iters).astype(np.float32)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7,
                                   err_msg=f"iters={iters}")


def test_tight_x_multiblock_yz_matches_reference():
    """Tight-x with MULTI-BLOCK y/z axes (dim 1x2x2, radius-2 inline y/z
    halos, zero x radius): the kernel wraps x by lane rolls while y/z ride
    the exchange; the overlap step (roll-aware shells) and the deep-halo
    fused loop must match the periodic reference in interpret mode
    (VERDICT r3 item 5: tight-x beyond the all-single-block case)."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.jacobi import make_jacobi_loop, make_jacobi_step, sphere_sel
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    size = Dim3(128, 16, 12)
    spec = GridSpec(size, Dim3(1, 2, 2), Radius.constant(2).without_x())
    assert spec.padded().x == 128 and spec.compute_offset().x == 0
    mesh = grid_mesh(spec.dim, jax.devices()[:4])
    ex = HaloExchange(spec, mesh)
    rng = np.random.RandomState(17)
    field = rng.rand(size.z, size.y, size.x).astype(np.float32)
    sel = shard_blocks(sphere_sel(size), spec, mesh)
    masks = sphere_masks(size)

    for iters, maker in (
        (1, lambda: make_jacobi_step(ex, use_pallas=True, interpret=True)),
        # radius 2 on the multi-block axes engages the deep-halo multistep
        # at k=2 (one exchange per 2 fused steps)
        (4, lambda: make_jacobi_loop(ex, 4, use_pallas=True, interpret=True)),
    ):
        step = maker()
        curr = shard_blocks(field, spec, mesh)
        nxt = shard_blocks(np.zeros_like(field), spec, mesh)
        curr, nxt = step(curr, nxt, sel)
        got = unshard_blocks(curr, spec)
        want = jacobi_reference(field, masks, iters).astype(np.float32)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7,
                                   err_msg=f"iters={iters}")


def test_tight_x_sidebuf_multiblock_x_matches_reference():
    """Tight-x on a MULTI-BLOCK x axis (out-of-line halo side buffers,
    VERDICT r3 item 5): the kernel rolls x block-locally, the exchange
    delivers neighbor columns as side buffers, and the x-edge columns are
    patched from them. dim 2x1x1 (pure x split) and 2x2x1 (x+y split),
    overlap and serialized, must match the periodic reference."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.jacobi import make_jacobi_step, sphere_sel
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    size = Dim3(256, 16, 12)  # x blocks of 128 (lane-aligned per block)
    rng = np.random.RandomState(29)
    field = rng.rand(size.z, size.y, size.x).astype(np.float32)
    masks = sphere_masks(size)
    want = jacobi_reference(field, masks, 1).astype(np.float32)

    for dim, ndev in ((Dim3(2, 1, 1), 2), (Dim3(2, 2, 1), 4)):
        spec = GridSpec(size, dim, Radius.constant(1).without_x())
        assert spec.padded().x == 128 and spec.compute_offset().x == 0
        mesh = grid_mesh(spec.dim, jax.devices()[:ndev])
        ex = HaloExchange(spec, mesh)
        sel = shard_blocks(sphere_sel(size), spec, mesh)
        for overlap in (True, False):
            step = make_jacobi_step(ex, overlap=overlap, use_pallas=True,
                                    interpret=True)
            curr = shard_blocks(field, spec, mesh)
            nxt = shard_blocks(np.zeros_like(field), spec, mesh)
            curr, nxt = step(curr, nxt, sel)
            got = unshard_blocks(curr, spec)
            np.testing.assert_allclose(
                got, want, rtol=1e-6, atol=1e-7,
                err_msg=f"dim={tuple(dim)} overlap={overlap}",
            )


def test_zero_x_radius_tight_multistep_deep_k():
    """The engaged tight-x multistep at k=5, called directly: k fused
    wavefront steps over a zero-x-radius block (x wrap via lane rolls)
    must equal k applications of the numpy periodic reference."""
    import jax.numpy as jnp
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.pallas_stencil import make_pallas_jacobi_multistep

    k = 5
    size = Dim3(128, 16, 12)
    spec = GridSpec(size, Dim3(1, 1, 1), Radius.constant(1).without_x())
    assert spec.padded().x == 128 and spec.compute_offset().x == 0
    p = spec.padded()
    off = spec.compute_offset()
    fn = make_pallas_jacobi_multistep(spec, k, interpret=True)
    rng = np.random.RandomState(0)
    curr = np.zeros((p.z, p.y, p.x), np.float32)
    sl = (
        slice(off.z, off.z + size.z),
        slice(off.y, off.y + size.y),
        slice(off.x, off.x + size.x),
    )
    curr[sl] = rng.rand(size.z, size.y, size.x)
    got = np.asarray(fn(jnp.asarray(curr), jnp.zeros_like(curr)))[sl]
    want = jacobi_reference(curr[sl], sphere_masks(size), k).astype(np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_uneven_overlap_asymmetric_radius():
    """Dynamic shells honor per-side radii: asymmetric halos (x-: 2, x+: 1,
    y: 1, z-: 1, z+: 2) on an uneven 2x2x2 split, overlap vs serialized
    bit-exact."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.jacobi import make_jacobi_step, sphere_sel
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    r = Radius.constant(1)
    r.set_dir((-1, 0, 0), 2)
    r.set_dir((0, 0, 1), 2)
    size = Dim3(19, 14, 10)  # x blocks (10, 9): uneven
    spec = GridSpec(size, Dim3(2, 2, 2), r)
    assert not spec.is_uniform()
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    ex = HaloExchange(spec, mesh)
    rng = np.random.RandomState(21)
    field = rng.rand(size.z, size.y, size.x).astype(np.float32)
    sel = shard_blocks(sphere_sel(size), spec, mesh)

    outs = {}
    for label, ov in (("overlap", True), ("serial", False)):
        step = make_jacobi_step(ex, overlap=ov, use_pallas=False)
        curr = shard_blocks(field, spec, mesh)
        nxt = shard_blocks(np.zeros_like(field), spec, mesh)
        for _ in range(2):
            curr, nxt = step(curr, nxt, sel)
        outs[label] = unshard_blocks(curr, spec)
    np.testing.assert_array_equal(outs["overlap"], outs["serial"])


# The forks of the step builder, in one table: which builder
# ``_compile_jacobi`` hands each method to. ``None``
# is the inline composed-geometry build (the one the benchmark's cells
# run), which returns an in-place ping-pong loop.
_BUILDERS = [
    ("composed", Method.AXIS_COMPOSED, {}, None),
    ("direct26", Method.DIRECT26, {}, None),
    ("auto-spmd", Method.AUTO_SPMD, {}, "_compile_jacobi_auto"),
]


@pytest.mark.parametrize("name,method,kw,builder", _BUILDERS,
                         ids=[b[0] for b in _BUILDERS])
def test_step_builder_dispatch(monkeypatch, name, method, kw, builder):
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops import jacobi
    from stencil_tpu.ops.double_buffer import InPlaceLoop
    from stencil_tpu.parallel import HaloExchange, grid_mesh

    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(2))
    ex = HaloExchange(spec, grid_mesh(spec.dim, jax.devices()[:8]), method,
                      **kw)
    called = []
    for b in sorted({b[3] for b in _BUILDERS if b[3]}):
        monkeypatch.setattr(
            jacobi, b,
            lambda *a, _b=b, **k: called.append(_b) or _b)
    loop = jacobi.make_jacobi_loop(ex, 2)
    if builder is None:
        assert called == [] and isinstance(loop, InPlaceLoop)
    else:
        assert called == [builder] and loop == builder
