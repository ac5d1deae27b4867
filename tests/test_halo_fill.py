"""In-place self-wrap halo-fill kernels (interpret mode) vs direct numpy
slab placement — the pack/unpack-kernel correctness check (reference idiom:
test_cuda_pack.cu round-trips)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.ops.halo_fill import (
    _y_sides, _y_tzb, make_self_fill, self_fill_supported)


def _assert_y_fill(got, want, base, spec):
    """``got`` is ``want`` on every row but the dead rows (neither halo nor
    owned) of a destination window the y kernel rebuilds from its source
    window: each of those is finite and a row of the field, the source
    window's at the same place."""
    dead = {s.dst_t + j: s.src_t + j
            for s in _y_sides(spec) if s.rebuilt
            for j in range(s.dst_span) if not s.dst_at <= j < s.dst_at + s.r}
    keep = [y for y in range(got.shape[1]) if y not in dead]
    np.testing.assert_array_equal(got[:, keep], want[:, keep])
    for y, src in dead.items():
        assert np.isfinite(got[:, y]).all(), y
        np.testing.assert_array_equal(got[:, y], base[:, src])


def _garbage_outside_y(base, spec):
    """Every row of the y halos and of the dead pad starts as NaN."""
    o, sy = spec.compute_offset().y, spec.base.y
    base[:, :o] = np.nan
    base[:, o + sy:] = np.nan


# 136, 160, 144 rows: a multiple of 8, so neither y destination window holds
# an owned row and the kernel rebuilds both; 140: the high window holds rows
# 144 to 147 of the block and is read, modified and written; 142: it spans
# two row tiles. 21 and 37 planes leave a clamped last z batch.
@pytest.mark.parametrize("size,r", [((256, 136, 24), 1), ((140, 160, 40), 2), ((256, 144, 30), 3),
                                    ((128, 140, 21), 3), ((128, 142, 37), 3)])
@pytest.mark.parametrize("axis", ["x", "y", "z"])
def test_self_fill_matches_numpy(size, r, axis):
    sx, sy, sz = size
    spec = GridSpec(Dim3(sx, sy, sz), Dim3(1, 1, 1), Radius.constant(r))
    assert self_fill_supported(spec, axis, jnp.float32)
    p = spec.padded()
    o = spec.compute_offset()
    rng = np.random.RandomState(0)
    base = rng.rand(p.z, p.y, p.x).astype(np.float32)
    if axis == "y":
        _garbage_outside_y(base, spec)
        assert sz % 2 == 0 or p.z % _y_tzb(spec) != 0   # 21, 37: clamped
        assert [s.rebuilt for s in _y_sides(spec)] == [sy % 8 == 0] * 2
    fill = make_self_fill(spec, axis, interpret=True)
    got = np.asarray(fill(jnp.asarray(base)))
    want = base.copy()
    if axis == "z":
        want[o.z - r : o.z] = base[o.z + sz - r : o.z + sz]
        want[o.z + sz : o.z + sz + r] = base[o.z : o.z + r]
    elif axis == "y":
        want[:, o.y - r : o.y, :] = base[:, o.y + sy - r : o.y + sy, :]
        want[:, o.y + sy : o.y + sy + r, :] = base[:, o.y : o.y + r, :]
        assert np.isfinite(want[:, o.y - r : o.y + sy + r]).all()
        return _assert_y_fill(got, want, base, spec)
    else:
        want[:, :, o.x - r : o.x] = base[:, :, o.x + sx - r : o.x + sx]
        want[:, :, o.x + sx : o.x + sx + r] = base[:, :, o.x : o.x + r]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("axis,sy", [("x", 160), ("y", 160), ("z", 160), ("y", 140)])
def test_self_fill_asymmetric_radius(axis, sy):
    # rm != rp per side (the reference's per-direction Radius semantics)
    r = Radius.constant(0)
    lo = {"x": (-1, 0, 0), "y": (0, -1, 0), "z": (0, 0, -1)}[axis]
    hi = {"x": (1, 0, 0), "y": (0, 1, 0), "z": (0, 0, 1)}[axis]
    r.set_dir(lo, 1)
    r.set_dir(hi, 3)
    spec = GridSpec(Dim3(140, sy, 40), Dim3(1, 1, 1), r)
    assert self_fill_supported(spec, axis, jnp.float32)
    p = spec.padded()
    o = spec.compute_offset()
    rng = np.random.RandomState(3)
    base = rng.rand(p.z, p.y, p.x).astype(np.float32)
    if axis == "y":
        _garbage_outside_y(base, spec)
    got = np.asarray(make_self_fill(spec, axis, interpret=True)(jnp.asarray(base)))
    want = base.copy()
    # active send dir d fills the receiver's -d halo: radius.dir(-d) gates,
    # so lo-side halo width = r.dir(lo) = 1, hi-side = r.dir(hi) = 3
    sx, sz = 140, 40
    if axis == "z":
        want[o.z - 1 : o.z] = base[o.z + sz - 1 : o.z + sz]
        want[o.z + sz : o.z + sz + 3] = base[o.z : o.z + 3]
    elif axis == "y":
        want[:, o.y - 1 : o.y, :] = base[:, o.y + sy - 1 : o.y + sy, :]
        want[:, o.y + sy : o.y + sy + 3, :] = base[:, o.y : o.y + 3, :]
        return _assert_y_fill(got, want, base, spec)
    else:
        want[:, :, o.x - 1 : o.x] = base[:, :, o.x + sx - 1 : o.x + sx]
        want[:, :, o.x + sx : o.x + sx + 3] = base[:, :, o.x : o.x + 3]
    np.testing.assert_array_equal(got, want)


def test_y_fill_rebuilt_windows_hold_rows_of_the_field():
    """Where the kernel skips the read of a destination window it writes
    the source window there: from halos and dead rows that start as NaN,
    every row of both windows comes out finite and equal to the row of the
    field at the same place in the source window, and nothing else of the
    block changes."""
    spec = GridSpec(Dim3(128, 64, 19), Dim3(1, 1, 1), Radius.constant(3))
    p = spec.padded()
    rng = np.random.RandomState(11)
    base = rng.rand(p.z, p.y, p.x).astype(np.float32)
    _garbage_outside_y(base, spec)
    got = np.asarray(make_self_fill(spec, "y", interpret=True)(jnp.asarray(base)))
    sides = _y_sides(spec)
    assert [s.rebuilt for s in sides] == [True, True]
    touched = np.zeros(p.y, bool)
    for s in sides:
        win = got[:, s.dst_t : s.dst_t + s.dst_span]
        assert np.isfinite(win).all()
        np.testing.assert_array_equal(win, base[:, s.src_t : s.src_t + s.src_span])
        touched[s.dst_t : s.dst_t + s.dst_span] = True
    np.testing.assert_array_equal(got[:, ~touched], base[:, ~touched])


@pytest.mark.parametrize("axis,nq,sy", [
    ("x", 3, 160), ("y", 3, 160), ("z", 3, 160),
    # y: both branches of the destination read (160 rows: rebuilt; 140:
    # read) at the quantity counts of the cells (4, 8) and at 1
    ("y", 1, 160), ("y", 4, 160), ("y", 8, 160),
    ("y", 1, 140), ("y", 4, 140), ("y", 8, 140)])
def test_multi_quantity_fill_matches_per_quantity(axis, nq, sy):
    # fused kernel must equal nq independent single-quantity fills
    spec = GridSpec(Dim3(140, sy, 40), Dim3(1, 1, 1), Radius.constant(2))
    p = spec.padded()
    rng = np.random.RandomState(5)
    bases = [rng.rand(p.z, p.y, p.x).astype(np.float32) for _ in range(nq)]
    single = make_self_fill(spec, axis, interpret=True)
    multi = make_self_fill(spec, axis, interpret=True, nq=nq)
    got = multi(*[jnp.asarray(b) for b in bases])
    got = (got,) if nq == 1 else got
    for q in range(nq):
        want = np.asarray(single(jnp.asarray(bases[q])))
        np.testing.assert_array_equal(np.asarray(got[q]), want)


@pytest.mark.parametrize("axis,sy", [("x", 32), ("y", 32), ("y", 36)])
@pytest.mark.parametrize("nq", [1, 2])
def test_self_fill_z_stack_matches_per_block(axis, nq, sy):
    """``z_stack=c``: one fill over the (c*pz, py, px) view of a resident
    z-stack must equal the single-block fill applied to each stacked block
    (VERDICT r4 item 7 — the resident Pallas fast path). 36 rows: the y
    kernel reads its high destination window; 32: it rebuilds both."""
    c = 3
    spec = GridSpec(Dim3(140, sy, 16), Dim3(1, 1, c), Radius.constant(2))
    assert self_fill_supported(spec, axis, jnp.float32, z_stack=c)
    p = spec.padded()
    rng = np.random.RandomState(7)
    bases = [rng.rand(c, p.z, p.y, p.x).astype(np.float32) for _ in range(nq)]
    single = make_self_fill(spec, axis, interpret=True, nq=nq)
    stacked = make_self_fill(spec, axis, interpret=True, nq=nq, z_stack=c)
    got = stacked(*[jnp.asarray(b.reshape(c * p.z, p.y, p.x)) for b in bases])
    got = (got,) if nq == 1 else got
    want = [
        single(*[jnp.asarray(b[j]) for b in bases]) for j in range(c)
    ]
    want = [(w,) if nq == 1 else w for w in want]
    for q in range(nq):
        w = np.stack([np.asarray(want[j][q]) for j in range(c)])
        np.testing.assert_array_equal(
            np.asarray(got[q]).reshape(c, p.z, p.y, p.x), w
        )


def test_self_fill_z_stack_gates():
    # the z fill copies planes across the stack boundary — unsupported
    spec = GridSpec(Dim3(140, 32, 16), Dim3(1, 1, 2), Radius.constant(2))
    assert not self_fill_supported(spec, "z", jnp.float32, z_stack=2)
    # a stack of thin blocks clears the streamed-batch depth gate
    thin = GridSpec(Dim3(128, 64, 4), Dim3(1, 1, 4), Radius.constant(1))
    assert not self_fill_supported(thin, "y", jnp.float32)
    assert self_fill_supported(thin, "y", jnp.float32, z_stack=4)


def test_exchange_blocks_fused_dispatch(monkeypatch):
    """The fused/rest split, chunking, and reshape wiring of
    HaloExchange.exchange_blocks — forced onto the fused path off-TPU by
    injecting interpret-mode fill kernels, with max_fill_group shrunk to
    exercise chunk boundaries (including a trailing nq=1 chunk)."""
    import jax

    from stencil_tpu.parallel import HaloExchange, grid_mesh
    import stencil_tpu.ops.halo_fill as HF
    from stencil_tpu.parallel.exchange import shard_blocks

    g = Dim3(140, 16, 16)
    spec = GridSpec(g, Dim3(1, 1, 1), Radius.constant(2))
    mesh = grid_mesh(spec.dim, jax.devices()[:1])
    ex = HaloExchange(spec, mesh)
    # inject interpret-mode fills (the TPU gate would otherwise leave
    # _self_fills empty on CPU and the dispatch under test never runs)
    ex.__dict__["_self_fills"] = {
        a: HF.make_self_fill(spec, a, interpret=True) for a in ("x", "y", "z")
    }
    ex.__dict__["_multi_fills"] = {
        (a, n): HF.make_self_fill(spec, a, interpret=True, nq=n)
        for a in ("x", "y", "z")
        for n in (1, 2, 3, 5)
    }
    monkeypatch.setattr(HF, "max_fill_group", lambda _spec, _axis="x": 2)

    rng = np.random.RandomState(9)
    coords = (
        np.arange(g.z)[:, None, None] * 10000
        + np.arange(g.y)[None, :, None] * 100
        + np.arange(g.x)[None, None, :]
    )
    state = {i: shard_blocks(coords.astype(np.float32), spec, mesh) for i in range(5)}
    state["f64"] = shard_blocks(coords.astype(np.float64), spec, mesh)
    out = ex.exchange_blocks(state)

    off = spec.compute_offset()
    r = spec.radius
    for key, arr in out.items():
        blk = np.asarray(jax.device_get(arr))[0, 0, 0]
        bad = checked = 0
        for zz in range(-r.z(-1), g.z + r.z(1)):
            for yy in range(-r.y(-1), g.y + r.y(1)):
                for xx in range(-r.x(-1), g.x + r.x(1)):
                    if 0 <= zz < g.z and 0 <= yy < g.y and 0 <= xx < g.x:
                        continue
                    want = (zz % g.z) * 10000 + (yy % g.y) * 100 + (xx % g.x)
                    checked += 1
                    bad += blk[off.z + zz, off.y + yy, off.x + xx] != want
        assert checked > 0 and bad == 0, (key, bad)


def test_exchange_blocks_fused_dispatch_resident(monkeypatch):
    """The z-stacked fused dispatch (VERDICT r4 item 7): a (cz, 1, 1)
    resident shard must route the x/y self-wrap phases through z_stack
    fill kernels (folded (cz*pz, py, px) view) composed with the resident
    z-shift phase — forced on-path off-TPU by injecting interpret-mode
    z_stack fills, with max_fill_group shrunk to hit the nq chunking."""
    import jax

    from stencil_tpu.parallel import HaloExchange, grid_mesh
    import stencil_tpu.ops.halo_fill as HF
    from stencil_tpu.parallel.exchange import shard_blocks

    g = Dim3(140, 16, 16)
    cz = 2
    spec = GridSpec(g, Dim3(1, 1, cz), Radius.constant(2))
    mesh = grid_mesh(Dim3(1, 1, 1), jax.devices()[:1])
    ex = HaloExchange(spec, mesh)
    assert ex.oversubscribed and ex.resident.z == cz
    assert ex._fill_shape() == (cz * spec.padded().z, spec.padded().y,
                                spec.padded().x)
    # z is multi-block (resident shifts); only x/y self-wrap fills exist
    ex.__dict__["_self_fills"] = {
        a: HF.make_self_fill(spec, a, interpret=True, z_stack=cz)
        for a in ("x", "y")
    }
    ex.__dict__["_multi_fills"] = {
        (a, n): HF.make_self_fill(spec, a, interpret=True, nq=n, z_stack=cz)
        for a in ("x", "y")
        for n in (1, 2, 3, 5)
    }
    monkeypatch.setattr(HF, "max_fill_group", lambda _spec, _axis="x": 2)

    coords = (
        np.arange(g.z)[:, None, None] * 10000
        + np.arange(g.y)[None, :, None] * 100
        + np.arange(g.x)[None, None, :]
    )
    state = {i: shard_blocks(coords.astype(np.float32), spec, mesh) for i in range(5)}
    state["f64"] = shard_blocks(coords.astype(np.float64), spec, mesh)
    out = ex.exchange_blocks(state)

    off = spec.compute_offset()
    r = spec.radius
    bz = g.z // cz
    for key, arr in out.items():
        stacked = np.asarray(jax.device_get(arr))
        for j in range(cz):
            blk = stacked[j, 0, 0]
            z0 = j * bz
            bad = checked = 0
            for zz in range(-r.z(-1), bz + r.z(1)):
                for yy in range(-r.y(-1), g.y + r.y(1)):
                    for xx in range(-r.x(-1), g.x + r.x(1)):
                        if 0 <= zz < bz and 0 <= yy < g.y and 0 <= xx < g.x:
                            continue
                        want = (
                            ((z0 + zz) % g.z) * 10000
                            + (yy % g.y) * 100
                            + (xx % g.x)
                        )
                        checked += 1
                        bad += blk[off.z + zz, off.y + yy, off.x + xx] != want
            assert checked > 0 and bad == 0, (key, j, bad)


def test_y_fill_group_follows_the_vmem_budget(monkeypatch):
    """The y kernel's buffers scale with the quantity count (a slot pair a
    quantity and window), so ``max_fill_group(spec, "y")`` is what
    ``HaloExchange._self_fill_group`` chunks by: at a budget that carries
    two quantities a group of five goes as 2 + 2 + 1, ``make_self_fill``
    refuses a third, and every halo cell comes out right."""
    import stencil_tpu.ops.halo_fill as HF
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks

    g = Dim3(140, 16, 16)
    spec = GridSpec(g, Dim3(1, 1, 1), Radius.constant(2))
    assert HF.max_fill_group(spec, "y") == 16
    monkeypatch.setattr(HF, "_VMEM_BUDGET", 600_000)
    assert HF.max_fill_group(spec, "y") == 2 == HF.max_fill_group(spec)
    assert HF._y_tzb(spec, 2) <= 8
    assert HF._y_scratch_bytes(spec, 2, 8) <= 600_000 < HF._y_scratch_bytes(spec, 3, 8)
    with pytest.raises(ValueError, match="group size 3"):
        make_self_fill(spec, "y", interpret=True, nq=3)

    built = []
    real = HF.make_self_fill

    def interpreted(spec, axis, vma=None, nq=1, z_stack=1):
        built.append((axis, nq))
        return real(spec, axis, interpret=True, nq=nq, z_stack=z_stack)

    monkeypatch.setattr(HF, "make_self_fill", interpreted)
    mesh = grid_mesh(spec.dim, jax.devices()[:1])
    ex = HaloExchange(spec, mesh)
    ex.__dict__["_self_fills"] = {a: interpreted(spec, a) for a in "xyz"}
    del built[:]
    coords = (np.arange(g.z)[:, None, None] * 10000
              + np.arange(g.y)[None, :, None] * 100
              + np.arange(g.x)[None, None, :]).astype(np.float32)
    out = ex.exchange_blocks(
        {i: shard_blocks(coords, spec, mesh) for i in range(5)})
    assert sorted(built) == [("x", 2), ("y", 2), ("z", 5)]   # 1: the cached one
    off, r = spec.compute_offset(), 2
    wrap = [np.arange(-r, n + r) % n for n in (g.z, g.y, g.x)]
    for arr in out.values():
        blk = np.asarray(jax.device_get(arr))[0, 0, 0]
        held = blk[off.z - r : off.z + g.z + r, off.y - r : off.y + g.y + r,
                   off.x - r : off.x + g.x + r]
        np.testing.assert_array_equal(held, coords[np.ix_(*wrap)])

    # a plane too wide for even one quantity at the shallowest batch
    monkeypatch.setattr(HF, "_VMEM_BUDGET", 200_000)
    assert not self_fill_supported(spec, "y", jnp.float32)


def test_max_fill_group_positive():
    from stencil_tpu.ops.halo_fill import max_fill_group

    spec = GridSpec(Dim3(256, 256, 256), Dim3(1, 1, 1), Radius.constant(3))
    assert max_fill_group(spec) >= 4


def test_self_fill_gates():
    # float64 and unaligned layouts must fall back
    spec = GridSpec(Dim3(64, 64, 16), Dim3(1, 1, 1), Radius.constant(1))
    assert not self_fill_supported(spec, "x", jnp.float64)
    spec_u = GridSpec(Dim3(64, 64, 16), Dim3(1, 1, 1), Radius.constant(1), aligned=False)
    assert not self_fill_supported(spec_u, "x", jnp.float32)
    # zero radius on the axis: nothing to fill
    r = Radius.constant(0)
    r.set_dir((-1, 0, 0), 1)
    r.set_dir((1, 0, 0), 1)
    spec_x = GridSpec(Dim3(64, 64, 16), Dim3(1, 1, 1), r)
    assert not self_fill_supported(spec_x, "y", jnp.float32)


def test_self_fill_gates_thin_z():
    # x (TZB=4) and y (TZB=8) kernels stream fixed-depth z batches; blocks
    # thinner than one batch must fall back (z0 would go negative)
    spec = GridSpec(Dim3(128, 64, 4), Dim3(1, 1, 1), Radius.constant(1))
    assert spec.padded().z < 8
    assert not self_fill_supported(spec, "y", jnp.float32)
    thin = GridSpec(Dim3(128, 64, 2), Dim3(1, 1, 1), Radius.constant(1))
    if thin.padded().z < 4:
        assert not self_fill_supported(thin, "x", jnp.float32)
    # z kernel copies whole planes regardless of depth
    assert self_fill_supported(spec, "z", jnp.float32)


def test_self_fill_gates_vmem_budget():
    # huge planes exceed the VMEM scratch budget; must fall back instead of
    # failing Mosaic compilation inside HaloExchange
    spec = GridSpec(Dim3(2048, 2048, 64), Dim3(1, 1, 1), Radius.constant(3))
    assert not self_fill_supported(spec, "z", jnp.float32)  # r*py*px*4 ~ 50 MB
    # x shrinks its batch depth down to 2 and still fits here...
    assert self_fill_supported(spec, "x", jnp.float32)
    # ...but a 4096-row plane exceeds the budget even at depth 2
    huge = GridSpec(Dim3(4096, 4096, 64), Dim3(1, 1, 1), Radius.constant(3))
    assert not self_fill_supported(huge, "x", jnp.float32)


# -- split x axis: pack / unpack on the two edge lane-tiles -------------------


def _radius_x(rm, rp, rest=2):
    r = Radius.constant(rest)
    r.set_dir((-1, 0, 0), rm)
    r.set_dir((1, 0, 0), rp)
    return r


def _slab_path(mine, minus, plus, spec):
    """numpy: what the XLA slab path leaves in ``mine`` when ``minus`` and
    ``plus`` are its -x and +x neighbours."""
    o, sx = spec.compute_offset().x, spec.base.x
    rm, rp = spec.radius.x(-1), spec.radius.x(1)
    want = mine.copy()
    if rm:
        want[:, :, o - rm : o] = minus[:, :, o + sx - rm : o + sx]
    if rp:
        want[:, :, o + sx : o + sx + rp] = plus[:, :, o : o + rp]
    return want


SPLIT_X_CASES = {
    # size of one block, x radii (minus, plus), quantities
    "r1.q1": ((256, 136, 24), (1, 1), 1),
    "r2.q3": ((140, 160, 40), (2, 2), 3),
    "r3.q3.lane_shift": ((200, 24, 30), (3, 3), 3),
    "r3.q1.two_groups": ((256, 144, 30), (3, 3), 1),
    # pz = 41 on z batches of 16: the clamped last batch overlaps its
    # predecessor and sends planes 25 to 31 twice
    "r3.q2.tail_overlap": ((128, 16, 35), (3, 3), 2),
    "asymmetric.1.3": ((140, 160, 40), (1, 3), 2),
    "asymmetric.3.1": ((128, 32, 20), (3, 1), 1),
    "forward_only": ((140, 160, 40), (2, 0), 1),
    "backward_only": ((140, 160, 40), (0, 2), 2),
    # 9 columns: 14 planes a 128-lane group, so batches shrink to 8
    "r9.q1": ((256, 16, 40), (9, 9), 1),
}


@pytest.mark.parametrize("case", sorted(SPLIT_X_CASES))
def test_split_x_pack_unpack_match_the_slab_path(case):
    """Three blocks in a ring along x: each packs, the carriers go forward
    and backward, each unpacks: bit for bit what slicing 3 columns off the
    neighbours and writing them into the halos gives."""
    from stencil_tpu.ops.halo_fill import (
        make_split_x_pack, make_split_x_unpack, split_x_carrier_shapes,
        split_x_supported)

    size, (rm, rp), nq = SPLIT_X_CASES[case]
    spec = GridSpec(Dim3(3 * size[0], size[1], size[2]), Dim3(3, 1, 1),
                    _radius_x(rm, rp))
    assert split_x_supported(spec, jnp.float32)
    p = spec.padded()
    rng = np.random.RandomState(11)
    # garbage (NaN) in halos and padding: nothing but the source columns
    # may reach a carrier, nothing but the halo lanes may change
    off = spec.compute_offset()
    blocks = []
    for _ in range(3):
        fields = []
        for _q in range(nq):
            a = np.full((p.z, p.y, p.x), np.nan, np.float32)
            a[:, :, off.x : off.x + size[0]] = rng.rand(p.z, p.y, size[0])
            fields.append(a)
        blocks.append(fields)
    pack = make_split_x_pack(spec, nq, interpret=True)
    unpack = make_split_x_unpack(spec, nq, interpret=True)
    shapes = split_x_carrier_shapes(spec, nq)
    assert len(shapes) == (rm > 0) + (rp > 0)
    carriers = [pack(*[jnp.asarray(f) for f in b]) for b in blocks]
    for c in carriers:
        assert [tuple(x.shape) for x in c] == [tuple(s) for s in shapes]
        assert all(np.isfinite(np.asarray(x)).all() for x in c)
    for i, mine in enumerate(blocks):
        minus, plus = (i - 1) % 3, (i + 1) % 3
        arriving = []
        if rm:      # the -x neighbour's forward carrier
            arriving.append(carriers[minus][0])
        if rp:      # the +x neighbour's backward carrier
            arriving.append(carriers[plus][-1])
        got = unpack(*[jnp.asarray(f) for f in mine], *arriving)
        for q in range(nq):
            want = _slab_path(mine[q], blocks[minus][q], blocks[plus][q], spec)
            np.testing.assert_array_equal(np.asarray(got[q]), want)


def test_split_x_geometry_shares_the_x_fills_batches():
    from stencil_tpu.ops import halo_fill as HF

    # the four-chip exchange cell's block: 518 x 528 x 640, offset 3
    spec = GridSpec(Dim3(1024, 1024, 512), Dim3(2, 2, 1), Radius.constant(3))
    g = HF._split_x_geom(spec, 4)
    assert g.shape == (518, 528, 640)
    assert g.tzb == HF._x_tzb(spec, 4) == 2 and g.n_b == 259
    fwd, bwd = g.sides
    assert (fwd.src_tile, fwd.src, fwd.dst_tile, fwd.dst) == (512, 0, 0, 0)
    assert (bwd.src_tile, bwd.src, bwd.dst_tile, bwd.dst) == (0, 3, 512, 3)
    # 42 planes of 3 columns a 128-lane group: 126 lanes in use
    assert fwd.k * g.tzb == 42 and fwd.groups == 13
    assert HF.split_x_carrier_shapes(spec, 4) == [(13, 4, 528, 128)] * 2
    # a clamped tail (pz % TZB != 0) keeps the batch count of the x fill
    odd = GridSpec(Dim3(256, 16, 35), Dim3(2, 1, 1), Radius.constant(3))
    go = HF._split_x_geom(odd, 1)
    assert go.shape[0] % go.tzb and go.n_b == -(-go.shape[0] // go.tzb)


def test_split_x_gates():
    """The predicate is the x self-fill's: dtype, alignment, a radius on x,
    depth, VMEM, halo and source columns inside the two edge lane-tiles."""
    from stencil_tpu.ops.halo_fill import (
        make_split_x_pack, make_split_x_unpack, max_fill_group,
        split_x_supported)

    ok = GridSpec(Dim3(256, 64, 16), Dim3(2, 1, 1), Radius.constant(1))
    assert split_x_supported(ok, jnp.float32)
    assert not split_x_supported(ok, jnp.float64)
    unaligned = GridSpec(Dim3(256, 64, 16), Dim3(2, 1, 1), Radius.constant(1),
                         aligned=False)
    assert not split_x_supported(unaligned, jnp.float32)
    no_x = GridSpec(Dim3(256, 64, 16), Dim3(2, 1, 1),
                    Radius.constant(1).without_x())
    assert not split_x_supported(no_x, jnp.float32)
    thin = GridSpec(Dim3(256, 64, 1), Dim3(2, 1, 1), Radius.constant(1))
    if thin.padded().z < 4:
        assert not split_x_supported(thin, jnp.float32)
    huge = GridSpec(Dim3(8192, 4096, 64), Dim3(2, 1, 1), Radius.constant(3))
    assert not split_x_supported(huge, jnp.float32)
    for make in (make_split_x_pack, make_split_x_unpack):
        with pytest.raises(ValueError):
            make(no_x, 1, interpret=True)
        with pytest.raises(ValueError):
            make(ok, max_fill_group(ok) + 1, interpret=True)
