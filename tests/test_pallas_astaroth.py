"""Fused Pallas RK3 substep vs the XLA path (interpret mode).

Both paths call the same fd/equations math, so parity is structural; these
tests pin the kernel's tiling, DMA pipeline, and RK3 combine against
_integrate_region over the full compute region. Halo contents are random
but identical for both paths, so results must match regardless of
exchange state (reference idiom: test_cuda_mpi_exchange.cu uses
position-determined values the same way)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu.astaroth.config import load_config
from stencil_tpu.astaroth.equations import Constants
from stencil_tpu.astaroth.integrate import FIELDS, _integrate_region
from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius, Rect3
from stencil_tpu.ops.pallas_astaroth import (
    make_pallas_substep,
    pick_tiles,
    substep_supported,
)

CONF = "stencil_tpu/astaroth/astaroth.conf"
DT = 0.1  # large enough that updates are visible in fp32


def _setup(size=(16, 16, 16)):
    spec = GridSpec(Dim3(*size), Dim3(1, 1, 1), Radius.constant(3))
    info, _ = load_config(CONF)
    c = Constants.from_info(info)
    inv_ds = (
        info.real_params["AC_inv_dsx"],
        info.real_params["AC_inv_dsy"],
        info.real_params["AC_inv_dsz"],
    )
    p = spec.padded()
    rng = np.random.RandomState(7)
    curr = {k: jnp.asarray(rng.rand(p.z, p.y, p.x) * 0.1, jnp.float32) for k in FIELDS}
    out = {k: jnp.asarray(rng.rand(p.z, p.y, p.x) * 0.1, jnp.float32) for k in FIELDS}
    return spec, c, inv_ds, curr, out


@pytest.mark.parametrize("substep", [0, 1, 2])
@pytest.mark.parametrize("tiles", [None, (4, 8)])
def test_substep_parity(substep, tiles):
    spec, c, inv_ds, curr, out = _setup()
    assert substep_supported(spec, jnp.float32)

    fn = make_pallas_substep(spec, c, inv_ds, substep, DT, interpret=True, tiles=tiles)
    got = fn(tuple(curr[k] for k in FIELDS), tuple(out[k] for k in FIELDS))
    got = {k: np.asarray(v) for k, v in zip(FIELDS, got)}

    off = spec.compute_offset()
    compute = Rect3(off, off + spec.base)
    want = _integrate_region(substep, compute, inv_ds, c, DT, curr, out)
    want = {k: np.asarray(v) for k, v in want.items()}

    sl = (
        slice(off.z, off.z + spec.base.z),
        slice(off.y, off.y + spec.base.y),
        slice(off.x, off.x + spec.base.x),
    )
    for k in FIELDS:
        # few-ulp fp32 reassociation between XLA fusion and interpret mode;
        # absolute error stays <1e-5 on fields of magnitude up to ~20
        np.testing.assert_allclose(
            got[k][sl], want[k][sl], rtol=1e-4, atol=1e-5, err_msg=f"field {k}"
        )
        # the update must actually be visible (guards against a dt so small
        # the test would pass vacuously)
        assert not np.array_equal(got[k][sl], np.asarray(curr[k])[sl])


@pytest.mark.slow
@pytest.mark.parametrize(
    "substep,tiles",
    [
        # tz=2: ring offsets cycle 0,2,4,6 over W=8 slots (4 z-tiles)
        (0, (2, 8)),
        (2, (2, 8)),
        # tz=4: W=10 — tz does NOT divide W, so the offset walks 0,4,8,2
        # and the fresh-plane slots wrap mid-window (the uneven z-tiling)
        (1, (4, 8)),
    ],
)
def test_substep_parity_ring(substep, tiles):
    """Ring-indexed (shift-free) window variant vs the XLA path, all 8
    fields at radius 3: the modular-slot rotation must be invisible in the
    results at every substep, including tilings whose ring offset cycles
    through every slot (VERDICT r5 "Next" #1). Slow tier: the per-plane
    dynamic-slot reads trace to a much larger interpret graph than the
    shift variant's static slices."""
    spec, c, inv_ds, curr, out = _setup()
    fn = make_pallas_substep(
        spec, c, inv_ds, substep, DT, interpret=True, tiles=tiles,
        variant="ring",
    )
    got = fn(tuple(curr[k] for k in FIELDS), tuple(out[k] for k in FIELDS))
    got = {k: np.asarray(v) for k, v in zip(FIELDS, got)}

    off = spec.compute_offset()
    compute = Rect3(off, off + spec.base)
    want = _integrate_region(substep, compute, inv_ds, c, DT, curr, out)
    sl = (
        slice(off.z, off.z + spec.base.z),
        slice(off.y, off.y + spec.base.y),
        slice(off.x, off.x + spec.base.x),
    )
    for k in FIELDS:
        np.testing.assert_allclose(
            got[k][sl], np.asarray(want[k])[sl], rtol=1e-4, atol=1e-5,
            err_msg=f"field {k}",
        )
        assert not np.array_equal(got[k][sl], np.asarray(curr[k])[sl])


def test_kernel_variant_plumbing(monkeypatch):
    """make_astaroth_step resolves kernel_variant (arg > env > 'shift')
    and passes it to every substep kernel builder."""
    import stencil_tpu.astaroth.integrate as integ
    import stencil_tpu.ops.pallas_astaroth as pa
    from stencil_tpu.parallel import HaloExchange, grid_mesh

    recorded = []
    orig = pa.make_pallas_substep

    def rec(*a, **kw):
        recorded.append(kw.get("variant"))
        return orig(*a, **kw)

    # integrate.py imports the builder inside make_astaroth_step, so patch
    # it at its defining module
    monkeypatch.setattr(pa, "make_pallas_substep", rec)
    from stencil_tpu.astaroth.config import load_config

    info, _ = load_config(CONF)
    n = 16
    info.int_params["AC_nx"] = info.int_params["AC_ny"] = info.int_params["AC_nz"] = n
    info.update_builtin_params()
    spec = GridSpec(Dim3(n, n, n), Dim3(1, 1, 1), Radius.constant(3))
    mesh = grid_mesh(spec.dim, jax.devices()[:1])
    ex = HaloExchange(spec, mesh)
    for env, arg, want in (
        (None, None, "shift"),
        ("ring", None, "ring"),
        ("ring", "shift", "shift"),
        (None, "ring", "ring"),
    ):
        recorded.clear()
        if env is None:
            monkeypatch.delenv("STENCIL_ASTAROTH_VARIANT", raising=False)
        else:
            monkeypatch.setenv("STENCIL_ASTAROTH_VARIANT", env)
        integ.make_astaroth_step(
            ex, info, use_pallas=True, interpret=True, kernel_variant=arg,
        )
        assert recorded == [want] * 3, (env, arg, recorded)


def test_astaroth_variant_checked_at_build_time(monkeypatch):
    from stencil_tpu.astaroth.integrate import _check_variant

    monkeypatch.delenv("STENCIL_ASTAROTH_VARIANT", raising=False)
    _check_variant(None)
    _check_variant("ring")
    with pytest.raises(ValueError, match="valid values"):
        _check_variant("bogus")
    # the exchange's retired kernel variants are not the window's
    with pytest.raises(ValueError, match="valid values"):
        _check_variant("fused")
    monkeypatch.setenv("STENCIL_ASTAROTH_VARIANT", "rnig")
    with pytest.raises(ValueError, match="STENCIL_ASTAROTH_VARIANT"):
        _check_variant(None)


@pytest.mark.slow
def test_distributed_pallas_step_matches_xla_path():
    """Full distributed step (exchange + fused substeps inside shard_map)
    on a 2x2x2 mesh in interpret mode vs the XLA path — pins the
    integration wiring, not just the standalone kernel."""
    from stencil_tpu.astaroth.config import load_config
    from stencil_tpu.astaroth.integrate import make_astaroth_step
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    n = 16
    info, _ = load_config(CONF)
    info.int_params["AC_nx"] = info.int_params["AC_ny"] = info.int_params["AC_nz"] = n
    info.update_builtin_params()

    spec = GridSpec(Dim3(n, n, n), Dim3(2, 2, 2), Radius.constant(3))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    ex = HaloExchange(spec, mesh)
    rng = np.random.RandomState(2)
    fields = {k: (rng.randn(n, n, n) * 0.05).astype(np.float32) for k in FIELDS}
    fields["lnrho"] = fields["lnrho"] + 0.5

    outs = {}
    for label, kwargs in (
        ("pallas", dict(use_pallas=True, interpret=True)),
        ("xla", dict(use_pallas=False)),
    ):
        step = make_astaroth_step(ex, info, dt=1e-3, **kwargs)
        curr = {k: shard_blocks(fields[k], spec, mesh) for k in FIELDS}
        nxt = {k: shard_blocks(np.zeros((n, n, n), np.float32), spec, mesh)
               for k in FIELDS}
        curr, nxt = step(curr, nxt)
        outs[label] = {k: unshard_blocks(curr[k], spec) for k in FIELDS}
    for k in FIELDS:
        np.testing.assert_allclose(
            outs["pallas"][k], outs["xla"][k], rtol=1e-4, atol=1e-5, err_msg=k
        )


def test_substep_gates():
    spec, *_ = _setup()
    assert substep_supported(spec, jnp.float32)
    assert not substep_supported(spec, jnp.float64)
    # unaligned layout
    u = GridSpec(Dim3(16, 16, 16), Dim3(1, 1, 1), Radius.constant(3), aligned=False)
    assert not substep_supported(u, jnp.float32)
    # radius < 3
    r2 = GridSpec(Dim3(16, 16, 16), Dim3(1, 1, 1), Radius.constant(2))
    assert not substep_supported(r2, jnp.float32)
    # ny not a multiple of 8
    odd = GridSpec(Dim3(16, 12, 16), Dim3(1, 1, 1), Radius.constant(3))
    assert not substep_supported(odd, jnp.float32)


@pytest.mark.slow
def test_pick_tiles_budget():
    spec, *_ = _setup((256, 256, 256))
    tz, ty = pick_tiles(spec)
    assert tz >= 1 and ty % 8 == 0
    assert 256 % tz == 0 and 256 % ty == 0
    from stencil_tpu.ops.pallas_astaroth import _SCRATCH_BUDGET, scratch_bytes

    assert scratch_bytes(spec, tz, ty) <= _SCRATCH_BUDGET


def _eqns(jaxpr, out):
    """Every equation of a jaxpr, the bodies of its calls and loops too."""
    for e in jaxpr.eqns:
        out.append(e)
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _eqns(sub, out)
    return out


@pytest.mark.parametrize("layout", ["tight", "inline"])
def test_substep_plan_counter_says_what_the_traced_body_asks(layout):
    """``astaroth.substep_plan``, once a build, against the traced body of
    the cells' layout (tight-x) and the inline one: a vreg position costs
    at most 84 whole-row lane rolls (8 x 6 of the x pencils, 2 x 18 of the
    mixed derivatives: the y and z differences shifted after they are
    summed) and 200 window reads, every read of the window is ONE plane's
    8-row group at its tile boundary, and the loop over the groups is
    traced once."""
    from stencil_tpu.obs import telemetry

    n = 128 if layout == "tight" else 16
    radius = Radius.constant(3)
    spec = GridSpec(Dim3(n, 16, 16), Dim3(1, 1, 1),
                    radius.without_x() if layout == "tight" else radius)
    assert substep_supported(spec, jnp.float32)
    info, _ = load_config(CONF)
    c = Constants.from_info(info)
    inv_ds = tuple(info.real_params[k]
                   for k in ("AC_inv_dsx", "AC_inv_dsy", "AC_inv_dsz"))
    rec = telemetry.get()
    before = len(rec.records(kind="counter", name="astaroth.substep_plan"))
    fn = make_pallas_substep(spec, c, inv_ds, 1, DT, interpret=True)
    p = spec.padded()
    like = tuple(jax.ShapeDtypeStruct((p.z, p.y, p.x), jnp.float32)
                 for _ in FIELDS)
    eqns = _eqns(jax.make_jaxpr(fn)(like, like).jaxpr, [])
    jax.make_jaxpr(fn)(like, like)  # a second trace records nothing
    plans = rec.records(kind="counter", name="astaroth.substep_plan")[before:]
    assert len(plans) == 1
    plan = plans[0]
    tz, ty = pick_tiles(spec)
    assert plan["value"] == 1 and plan["tiles"] == [tz, ty]
    assert plan["tight_x"] is (layout == "tight")
    assert plan["variant"] == "shift"
    assert plan["lane_rolls_per_position"] <= 84
    assert plan["window_reads_per_position"] <= 200

    rolls = [e for e in eqns if e.primitive.name == "roll"]
    lane = [e for e in rolls if e.params["axis"] == 2]
    assert len(lane) == plan["lane_rolls_per_position"] == 84
    # the y pencils and deryz, built in registers: one rotation a read
    assert len(rolls) - len(lane) == 8 * 6 + 4 * 12
    loops = [e for e in eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in loops] == [tz * ty // 8]
    window = (len(FIELDS), tz + 6, ty + 16, p.x)
    reads = [e for e in _eqns(loops[0].params["jaxpr"].jaxpr, [])
             if e.primitive.name == "get"
             and e.invars[0].aval.shape == window]
    assert len(reads) == plan["window_reads_per_position"] == 120
    for e in reads:
        _, planes, rows, lanes = jax.tree_util.tree_unflatten(
            e.params["tree"], e.invars[1:])[0].indices
        assert planes.size == 1 and rows.size == 8
        assert (lanes.start, lanes.size) == (0, p.x)
