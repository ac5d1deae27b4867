"""Quantity-batched halo exchange — bit parity and collective census.

The tentpole claim (ISSUE 5): with ``batch_quantities`` (the default) every
collective carries ONE packed ``(Q, ...slab)`` carrier of a same-dtype
group's boundary slabs, so the collective count per exchange is independent
of the quantity count — 6 composed permutes (or ≤26 direct ones) total, not
per quantity — while the result stays bit-identical to the per-quantity
program (the exchange is pure data movement). Parity is pinned for
fp32/fp64/mixed dicts on uniform, remainder, and oversubscribed partitions;
the census pin (batched Q=8 emits the Q=1 permute count) is what the CI
gate (`bench_exchange --batched-ab`) re-checks on every push.

Runs on the virtual 8-device CPU mesh from conftest.py.
"""

import jax
import numpy as np
import pytest

from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.parallel import HaloExchange, Method, grid_mesh
from stencil_tpu.parallel.exchange import shard_blocks

FP32 = ("float32",) * 3
FP64 = ("float64",) * 3
MIXED = ("float32", "float64", "float32", "float64")


def _coord(g: Dim3) -> np.ndarray:
    return (
        np.arange(g.z)[:, None, None] * 1_000_000.0
        + np.arange(g.y)[None, :, None] * 1_000.0
        + np.arange(g.x)[None, None, :]
    )


def _state(spec, mesh, dtypes):
    c = _coord(spec.global_size)
    return {
        i: shard_blocks((c + i).astype(dt), spec, mesh)
        for i, dt in enumerate(dtypes)
    }


def _ab_outputs(spec, mesh, dtypes, method=Method.AXIS_COMPOSED):
    """One exchange through the batched and the per-quantity program (fresh
    states each — the exchange donates its buffers); host-side results."""
    outs = {}
    for batched in (True, False):
        ex = HaloExchange(spec, mesh, method, batch_quantities=batched)
        out = ex(_state(spec, mesh, dtypes))
        outs[batched] = {
            k: np.asarray(jax.device_get(v)) for k, v in out.items()
        }
    return outs


def _assert_parity(outs, dtypes):
    for k in range(len(dtypes)):
        assert outs[True][k].dtype == outs[False][k].dtype == np.dtype(dtypes[k])
        np.testing.assert_array_equal(outs[True][k], outs[False][k])


@pytest.mark.parametrize("dtypes", [FP32, FP64, MIXED],
                         ids=["fp32", "fp64", "mixed"])
def test_batched_parity_uniform(dtypes):
    spec = GridSpec(Dim3(8, 8, 8), Dim3(2, 2, 2), Radius.constant(2))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    _assert_parity(_ab_outputs(spec, mesh, dtypes), dtypes)


@pytest.mark.parametrize("dtypes", [FP32, FP64, MIXED],
                         ids=["fp32", "fp64", "mixed"])
def test_batched_parity_remainder(dtypes):
    """Uneven split on every axis: the packed carrier's slab starts are
    traced size-table lookups, exactly like the per-quantity phases."""
    spec = GridSpec(Dim3(11, 9, 13), Dim3(2, 2, 2), Radius.constant(2))
    assert not spec.is_uniform()
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    _assert_parity(_ab_outputs(spec, mesh, dtypes), dtypes)


def test_batched_parity_oversubscribed_uneven():
    """Resident z-stacking with an uneven resident axis (z = 7+6 on 4
    devices, mixed dtypes): only the boundary slabs ride the (packed)
    permute; the resident-neighbor shifts stay per-quantity local copies."""
    spec = GridSpec(Dim3(12, 12, 13), Dim3(2, 2, 2), Radius.constant(2))
    mesh = grid_mesh(Dim3(2, 2, 1), jax.devices()[:4])
    _assert_parity(_ab_outputs(spec, mesh, MIXED), MIXED)


def test_batched_parity_direct26():
    """DIRECT26 batching: one packed carrier per active direction (uniform
    and remainder partitions, incl. the face→edge→corner layering of the
    uneven path)."""
    for size in (Dim3(8, 8, 8), Dim3(11, 9, 13)):
        spec = GridSpec(size, Dim3(2, 2, 2), Radius.constant(2))
        mesh = grid_mesh(spec.dim, jax.devices()[:8])
        _assert_parity(_ab_outputs(spec, mesh, MIXED, Method.DIRECT26), MIXED)


def test_batched_census_q_independent():
    """The tentpole pin: batched AXIS_COMPOSED at Q=8 emits the SAME
    ppermute count as Q=1 (6 on the 2x2x2 mesh) with Q× the carrier
    bytes; the per-quantity program emits 6·Q. census_per_quantity
    attributes the packed bytes back to the logical per-quantity figure."""
    spec = GridSpec(Dim3(8, 8, 8), Dim3(2, 2, 2), Radius.constant(2))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])

    def census(ex, q, dtypes=None):
        dtypes = dtypes or ("float32",) * q
        return ex.collective_census(_state(spec, mesh, dtypes))

    exb = HaloExchange(spec, mesh)
    assert exb.batch_quantities  # default on
    c1 = census(exb, 1)
    c8 = census(exb, 8)
    assert c1["collective-permute"][0] == c8["collective-permute"][0] == 6
    assert c8["collective-permute"][1] == 8 * c1["collective-permute"][1]

    exp = HaloExchange(spec, mesh, batch_quantities=False)
    assert census(exp, 8)["collective-permute"][0] == 6 * 8

    from stencil_tpu.utils.hlo_check import census_per_quantity

    per_q = census_per_quantity(c8, 8)
    assert per_q["collective-permute"] == c1["collective-permute"]

    # mixed dtypes never share a carrier (no bitcast): one packed pair per
    # phase per dtype group -> 12 permutes for a 2-group dict at any Q
    cm = census(exb, 4, MIXED)
    assert cm["collective-permute"][0] == 12


def test_batched_census_direct26_q_independent():
    spec = GridSpec(Dim3(8, 8, 8), Dim3(2, 2, 2), Radius.constant(2))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    exd = HaloExchange(spec, mesh, Method.DIRECT26)

    def census(q):
        return exd.collective_census(_state(spec, mesh, ("float32",) * q))

    c1, c4 = census(1), census(4)
    assert c1["collective-permute"][0] == c4["collective-permute"][0] == 26
    assert c4["collective-permute"][1] == 4 * c1["collective-permute"][1]


def test_domain_quantity_batching_knob():
    """api.py wiring: set_quantity_batching reaches the realized
    HaloExchange; default is on."""
    from stencil_tpu.api import DistributedDomain

    for enabled in (True, False):
        dd = DistributedDomain(8, 8, 8)
        dd.set_radius(1)
        dd.set_partition((2, 2, 2))
        dd.set_devices(jax.devices()[:8])
        if not enabled:
            dd.set_quantity_batching(False)
        dd.add_data("a")
        dd.add_data("b", "float64")
        dd.realize()
        assert dd.halo_exchange.batch_quantities is enabled


@pytest.mark.parametrize("wire", [None, "bfloat16"], ids=["native", "bf16wire"])
def test_split_x_kernels_match_the_slab_path(monkeypatch, wire):
    """A split lane axis packs and unpacks with the edge-tile kernels of
    ops/halo_fill.py: forced onto that path off-TPU by injecting the
    interpret-mode kernels on a 4-device mesh that splits x (as
    test_exchange_blocks_fused_dispatch injects the self-fills), with
    max_fill_group shrunk so a group of three is chunked 2 + 1. fp32 fields
    take the kernels and the fp64 one the slab path, in one exchange; the
    result is the XLA slab path's bit for bit, wire compression included."""
    import stencil_tpu.ops.halo_fill as HF
    from stencil_tpu.parallel.mesh import BLOCK_PSPEC

    spec = GridSpec(Dim3(256, 16, 12), Dim3(2, 2, 1), Radius.constant(2))
    mesh = grid_mesh(spec.dim, jax.devices()[:4])
    dtypes = ("float32", "float32", "float64", "float32")

    want = HaloExchange(spec, mesh, wire_dtype=wire)(_state(spec, mesh, dtypes))

    ex = HaloExchange(spec, mesh, wire_dtype=wire)
    assert HF.split_x_supported(spec, np.float32)
    monkeypatch.setattr(ex, "_on_tpu", lambda: True)
    monkeypatch.setattr(HF, "max_fill_group", lambda _spec: 2)
    calls = []

    def counted(nq):
        pack = HF.make_split_x_pack(spec, nq, interpret=True)
        unpack = HF.make_split_x_unpack(spec, nq, interpret=True)

        def packed(*a):
            calls.append(nq)
            return pack(*a)

        return packed, unpack

    ex.__dict__["_split_x_kernels"] = {n: counted(n) for n in (1, 2)}
    x_phase, y_phase = ex.plan.axis_phases[0], ex.plan.axis_phases[1]
    assert ex._split_x(x_phase, np.dtype("float32"))
    assert not ex._split_x(x_phase, np.dtype("float64"))
    assert not ex._split_x(y_phase, np.dtype("float32"))
    # the interpreter cannot be traced under shard_map's vma check
    got = jax.jit(jax.shard_map(
        ex.exchange_blocks, mesh=mesh, in_specs=BLOCK_PSPEC,
        out_specs=BLOCK_PSPEC, check_vma=False))(_state(spec, mesh, dtypes))
    assert calls == [2, 1]
    for k, dt in enumerate(dtypes):
        assert got[k].dtype == np.dtype(dt)
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(got[k])), np.asarray(jax.device_get(want[k])))


def test_split_x_needs_an_even_split_one_block_a_device_and_tpus():
    """Everything the predicate refuses keeps the XLA slab path."""
    spec = GridSpec(Dim3(256, 16, 12), Dim3(2, 2, 1), Radius.constant(2))
    mesh = grid_mesh(spec.dim, jax.devices()[:4])
    ex = HaloExchange(spec, mesh)
    x_phase = ex.plan.axis_phases[0]
    f32 = np.dtype("float32")
    assert not ex._split_x(x_phase, f32)          # CPU devices
    ex._on_tpu = lambda: True
    assert ex._split_x(x_phase, f32)
    # uneven x split
    uneven = GridSpec(Dim3(250, 16, 12), Dim3(2, 2, 1), Radius.constant(2))
    exu = HaloExchange(uneven, grid_mesh(uneven.dim, jax.devices()[:4]))
    exu._on_tpu = lambda: True
    assert not exu._split_x(exu.plan.axis_phases[0], f32)
    # two x blocks resident on one device
    res = GridSpec(Dim3(256, 16, 12), Dim3(4, 1, 1), Radius.constant(2))
    exr = HaloExchange(res, grid_mesh(Dim3(2, 1, 1), jax.devices()[:2]))
    exr._on_tpu = lambda: True
    assert not exr._split_x(exr.plan.axis_phases[0], f32)
    # x not split: the self-fill's phase
    one = GridSpec(Dim3(128, 16, 12), Dim3(1, 2, 2), Radius.constant(2))
    exo = HaloExchange(one, grid_mesh(one.dim, jax.devices()[:4]))
    exo._on_tpu = lambda: True
    assert not exo._split_x(exo.plan.axis_phases[0], f32)
