"""Halo-exchange correctness — the reference's verification idiom.

Each grid point is initialized with a value determined by its global
coordinate (bit-packed, reference: test_cuda_mpi_distributed_domain.cu:11-17;
ripple, reference: test_exchange.cu:12-33). After one exchange, every halo
cell must hold the value of its periodically-wrapped source coordinate
(reference: test_exchange.cu:126-191). This exercises the entire
partition/slab/ppermute/update pipeline with no reference simulation.

Runs on the virtual 8-device CPU mesh from conftest.py.
"""

import jax
import numpy as np
import pytest

from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import DIRECTIONS_26, Dim3, Radius
from stencil_tpu.parallel import HaloExchange, Method, grid_mesh
from stencil_tpu.parallel.exchange import BLOCK_PSPEC, shard_blocks, unshard_blocks


def coord_field(g: Dim3) -> np.ndarray:
    """value = x | y<<10 | z<<20 (valid for extents < 1024)."""
    z, y, x = np.meshgrid(
        np.arange(g.z), np.arange(g.y), np.arange(g.x), indexing="ij"
    )
    return (x | (y << 10) | (z << 20)).astype(np.int32)


def check_halos(stacked, spec: GridSpec, dirs=None):
    """Verify halo cells for every active direction on every block."""
    arr = np.asarray(jax.device_get(stacked))
    g = spec.global_size
    ref = coord_field(g)
    off = spec.compute_offset()
    checked = 0
    for iz in range(spec.dim.z):
        for iy in range(spec.dim.y):
            for ix in range(spec.dim.x):
                idx = (ix, iy, iz)
                size = spec.block_size(idx)
                origin = spec.block_origin(idx)
                block = arr[iz, iy, ix]
                for d in dirs if dirs is not None else DIRECTIONS_26:
                    if spec.radius.dir(d) == 0:
                        continue
                    rect = spec.halo_rect(d, size, halo=True)
                    ext = rect.extent()
                    if ext.flatten() == 0:
                        continue
                    for az in range(rect.lo.z, rect.hi.z):
                        for ay in range(rect.lo.y, rect.hi.y):
                            for ax in range(rect.lo.x, rect.hi.x):
                                gx = (origin.x + ax - off.x) % g.x
                                gy = (origin.y + ay - off.y) % g.y
                                gz = (origin.z + az - off.z) % g.z
                                got = block[az, ay, ax]
                                want = ref[gz, gy, gx]
                                assert got == want, (
                                    f"block {idx} dir {d} halo cell ({ax},{ay},{az}): "
                                    f"got {got:#x} want {want:#x} (src {gx},{gy},{gz})"
                                )
                                checked += 1
    assert checked > 0


def run_exchange(global_size, dim, radius, method, devices=None):
    spec = GridSpec(Dim3.of(global_size), Dim3.of(dim), radius)
    n = spec.num_blocks()
    devs = devices if devices is not None else jax.devices()[:n]
    mesh = grid_mesh(spec.dim, devs)
    ex = HaloExchange(spec, mesh, method)
    field = coord_field(spec.global_size)
    stacked = shard_blocks(field, spec, mesh)
    out = ex(stacked)
    # compute region must be untouched
    np.testing.assert_array_equal(unshard_blocks(out, spec), field)
    return out, spec


@pytest.mark.parametrize("method", [Method.AXIS_COMPOSED, Method.DIRECT26])
@pytest.mark.parametrize(
    "size,dim,r",
    [
        ((8, 8, 8), (2, 2, 2), 1),
        ((12, 8, 10), (2, 2, 2), 3),
        ((8, 8, 8), (4, 2, 1), 2),
        ((16, 8, 8), (8, 1, 1), 2),
        ((6, 6, 6), (1, 1, 1), 2),  # single device: periodic self-wrap
    ],
)
def test_constant_radius(size, dim, r, method):
    out, spec = run_exchange(size, dim, Radius.constant(r), method)
    check_halos(out, spec)


@pytest.mark.parametrize("method", [Method.AXIS_COMPOSED, Method.DIRECT26])
def test_asymmetric_faces(method):
    r = Radius.constant(0)
    r.set_dir((-1, 0, 0), 1)
    r.set_dir((1, 0, 0), 2)
    r.set_dir((0, -1, 0), 3)
    r.set_dir((0, 1, 0), 1)
    r.set_dir((0, 0, -1), 2)
    r.set_dir((0, 0, 1), 0)
    out, spec = run_exchange((10, 12, 8), (2, 2, 2), r, method)
    check_halos(out, spec)


@pytest.mark.parametrize("method", [Method.AXIS_COMPOSED, Method.DIRECT26])
def test_face_edge_corner_gates(method):
    # corners gated off (radius 0): reference skips those messages; both
    # methods must still deliver faces and edges correctly.
    r = Radius.face_edge_corner(2, 2, 0)
    out, spec = run_exchange((8, 8, 8), (2, 2, 2), r, method)
    check_halos(out, spec)


def test_uneven_partition():
    out, spec = run_exchange((11, 9, 13), (2, 2, 2), Radius.constant(2), Method.AXIS_COMPOSED)
    assert not spec.is_uniform()
    check_halos(out, spec)


def test_uneven_three_way():
    out, spec = run_exchange((13, 7, 5), (2, 2, 2), Radius.constant(1), Method.AXIS_COMPOSED)
    check_halos(out, spec)


def test_direct26_uneven_partition():
    """DIRECT26 on a remainder partition (ROADMAP #4, VERDICT r5 "Next"
    #5): slab extents padded to the base size along orthogonal axes,
    face→edge→corner apply order, traced per-block compute extents — every
    halo cell must still carry its wrapped source coordinate."""
    out, spec = run_exchange((11, 9, 13), (2, 2, 2), Radius.constant(2), Method.DIRECT26)
    assert not spec.is_uniform()
    check_halos(out, spec)


def test_direct26_uneven_parity_with_composed():
    """Pin: at a uniform radius the DIRECT26 result on a remainder
    partition is bit-identical to AXIS_COMPOSED (the ISSUE 2 acceptance
    bar; anisotropic gating is exempt — composed full-extent slabs fill
    cells DIRECT26's skipped directions own)."""
    out_d, spec = run_exchange((13, 7, 5), (2, 2, 2), Radius.constant(1), Method.DIRECT26)
    out_c, _ = run_exchange((13, 7, 5), (2, 2, 2), Radius.constant(1), Method.AXIS_COMPOSED)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(out_d)), np.asarray(jax.device_get(out_c))
    )


def test_direct26_uneven_oversubscribed():
    """Uneven split along a RESIDENT axis under DIRECT26 (z = 7+6 on 4
    devices): per-resident traced starts must match the fully distributed
    exchange."""
    size = Dim3(12, 12, 13)
    spec = GridSpec(size, Dim3(2, 2, 2), Radius.constant(2))
    coord = coord_field(size)
    results = {}
    for label, mesh_dim, ndev in (("over", Dim3(2, 2, 1), 4),
                                  ("full", Dim3(2, 2, 2), 8)):
        mesh = grid_mesh(mesh_dim, jax.devices()[:ndev])
        ex = HaloExchange(spec, mesh, Method.DIRECT26)
        state = ex({0: shard_blocks(coord, spec, mesh)})
        results[label] = np.asarray(jax.device_get(state[0]))
    np.testing.assert_array_equal(results["over"], results["full"])


def test_multi_quantity_pytree():
    """Exchange a pytree of quantities with distinct dtypes in one call."""
    spec = GridSpec(Dim3(8, 8, 8), Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    ex = HaloExchange(spec, mesh)
    field = coord_field(spec.global_size)
    state = {
        "a": shard_blocks(field, spec, mesh),
        "b": shard_blocks(field.astype(np.float64), spec, mesh),
    }
    out = ex(state)
    check_halos(out["a"], spec)
    check_halos(out["b"].astype(np.int64), spec)


def test_bytes_accounting():
    spec = GridSpec(Dim3(8, 8, 8), Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    ex = HaloExchange(spec, mesh)
    # per block: 6 faces 4*4*1 + 12 edges 4*1*1 + 8 corners 1 = 16*6+4*12+8 = 152
    assert ex.bytes_logical([4]) == 8 * (6 * 16 + 12 * 4 + 8) * 4
    assert ex.bytes_moved([4]) >= ex.bytes_logical([4])


def test_oversubscribed_exchange_halo_parity():
    """8 blocks on 4 devices (2 z-blocks resident per device, reference:
    dd.set_gpus({0,0}), test_exchange.cu:52): every halo cell must carry
    its periodically wrapped source coordinate, and the result must equal
    the same partition realized on 8 devices."""
    import jax

    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks

    size = Dim3(12, 12, 12)
    spec = GridSpec(size, Dim3(2, 2, 2), Radius.constant(2))
    coord = (
        np.arange(size.z)[:, None, None] * 1_000_000
        + np.arange(size.y)[None, :, None] * 1_000
        + np.arange(size.x)[None, None, :]
    ).astype(np.float32)

    results = {}
    for label, mesh_dim, ndev in (("over", Dim3(2, 2, 1), 4),
                                  ("full", Dim3(2, 2, 2), 8)):
        mesh = grid_mesh(mesh_dim, jax.devices()[:ndev])
        ex = HaloExchange(spec, mesh)
        assert ex.resident_z == (2 if label == "over" else 1)
        state = ex({0: shard_blocks(coord, spec, mesh)})
        results[label] = np.asarray(jax.device_get(state[0]))
    np.testing.assert_array_equal(results["over"], results["full"])

    # independent halo check on the oversubscribed result, every block
    arr = results["over"]
    off = spec.compute_offset()
    r = spec.radius
    for bz in range(2):
        for by in range(2):
            for bx in range(2):
                blk = arr[bz, by, bx]
                org = spec.block_origin((bx, by, bz))
                bs = spec.block_size((bx, by, bz))
                for z in range(off.z - r.z(-1), off.z + bs.z + r.z(1)):
                    gz = (org.z + z - off.z) % size.z
                    for (y, x) in ((off.y - 1, off.x), (off.y + bs.y, off.x + bs.x - 1)):
                        gy = (org.y + y - off.y) % size.y
                        gx = (org.x + x - off.x) % size.x
                        want = gz * 1_000_000 + gy * 1_000 + gx
                        assert blk[z, y, x] == want, (bz, by, bx, z, y, x)


def _coord_field(size):
    return (
        np.arange(size.z)[:, None, None] * 1_000_000
        + np.arange(size.y)[None, :, None] * 1_000
        + np.arange(size.x)[None, None, :]
    ).astype(np.float32)


def _assert_halos_wrap(arr, spec, size):
    """Every face-halo cell of every block carries its periodically wrapped
    source coordinate (spot rows on each face)."""
    off = spec.compute_offset()
    r = spec.radius
    for bz in range(spec.dim.z):
        for by in range(spec.dim.y):
            for bx in range(spec.dim.x):
                blk = arr[bz, by, bx]
                org = spec.block_origin((bx, by, bz))
                bs = spec.block_size((bx, by, bz))
                for z in range(off.z - r.z(-1), off.z + bs.z + r.z(1)):
                    gz = (org.z + z - off.z) % size.z
                    for (y, x) in ((off.y - 1, off.x),
                                   (off.y + bs.y, off.x + bs.x - 1)):
                        gy = (org.y + y - off.y) % size.y
                        gx = (org.x + x - off.x) % size.x
                        want = gz * 1_000_000 + gy * 1_000 + gx
                        assert blk[z, y, x] == want, (bz, by, bx, z, y, x)


def test_oversubscribed_uneven_z_halo_parity():
    """Uneven split along the RESIDENT axis (z = 7+6): per-resident sizes
    come from traced size-table lookups; the result must equal the same
    partition on 8 devices (round-3 rejected this; VERDICT r3 item 4)."""
    import jax

    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks

    size = Dim3(12, 12, 13)
    spec = GridSpec(size, Dim3(2, 2, 2), Radius.constant(2))
    coord = _coord_field(size)
    results = {}
    for label, mesh_dim, ndev in (("over", Dim3(2, 2, 1), 4),
                                  ("full", Dim3(2, 2, 2), 8)):
        mesh = grid_mesh(mesh_dim, jax.devices()[:ndev])
        ex = HaloExchange(spec, mesh)
        state = ex({0: shard_blocks(coord, spec, mesh)})
        results[label] = np.asarray(jax.device_get(state[0]))
    np.testing.assert_array_equal(results["over"], results["full"])
    _assert_halos_wrap(results["over"], spec, size)


def test_oversubscribed_uneven_multidevice_axis_halo_parity():
    """Uneven split (z = 4+4+3+3) with the resident axis spanning MULTIPLE
    devices (4 z-blocks, 2 residents on each of 2 devices): exercises the
    axis_index*c+j size-table lookup at axis_index > 0, which the
    single-device-axis tests never reach."""
    import jax

    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks

    size = Dim3(12, 12, 14)
    spec = GridSpec(size, Dim3(1, 1, 4), Radius.constant(2))
    assert tuple(spec.sizes_z) == (4, 4, 3, 3)
    coord = _coord_field(size)
    results = {}
    for label, mesh_dim, ndev in (("over", Dim3(1, 1, 2), 2),
                                  ("full", Dim3(1, 1, 4), 4)):
        mesh = grid_mesh(mesh_dim, jax.devices()[:ndev])
        ex = HaloExchange(spec, mesh)
        state = ex({0: shard_blocks(coord, spec, mesh)})
        results[label] = np.asarray(jax.device_get(state[0]))
    np.testing.assert_array_equal(results["over"], results["full"])
    _assert_halos_wrap(results["over"], spec, size)


def test_x_side_buffers_carry_neighbor_columns():
    """Tight-x multi-block transport: x_side_buffers must deliver the -x
    neighbor's top r columns as xlo and the +x neighbor's first r columns
    as xhi, periodically wrapped, for r=1 and r=2."""
    size = Dim3(256, 8, 6)  # two 128-wide x blocks
    spec = GridSpec(size, Dim3(2, 1, 1), Radius.constant(1).without_x())
    mesh = grid_mesh(spec.dim, jax.devices()[:2])
    ex = HaloExchange(spec, mesh)
    coord = _coord_field(size)
    state = shard_blocks(coord, spec, mesh)

    for r in (1, 2):
        fn = jax.jit(jax.shard_map(
            lambda b: ex.x_side_buffers(b, r),
            mesh=mesh, in_specs=BLOCK_PSPEC,
            out_specs=(BLOCK_PSPEC, BLOCK_PSPEC),
        ))
        xlo, xhi = fn(state)
        xlo = np.asarray(jax.device_get(xlo))
        xhi = np.asarray(jax.device_get(xhi))
        off = spec.compute_offset()
        for bx in range(2):
            org = spec.block_origin((bx, 0, 0))
            blk_lo = xlo[0, 0, bx]
            blk_hi = xhi[0, 0, bx]
            for j in range(r):
                # xlo[..., j] = global x = org.x - r + j (wrapped)
                gx = (org.x - r + j) % size.x
                np.testing.assert_array_equal(
                    blk_lo[off.z, off.y, j],
                    coord[0, 0, gx], err_msg=f"xlo r={r} bx={bx} j={j}",
                )
                # xhi[..., j] = global x = org.x + nx + j (wrapped)
                gx = (org.x + spec.sizes_x[bx] + j) % size.x
                np.testing.assert_array_equal(
                    blk_hi[off.z, off.y, j],
                    coord[0, 0, gx], err_msg=f"xhi r={r} bx={bx} j={j}",
                )


def test_oversubscribed_mixed_axes_halo_parity():
    """(cz, cy) = (2, 2) mixed stacking — a 2x2x2 partition on TWO devices
    (mesh 1x1x2 on x) — and pure-y stacking on 4: both must equal the fully
    distributed 8-device exchange (VERDICT r3 item 4)."""
    import jax

    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks

    size = Dim3(12, 12, 12)
    spec = GridSpec(size, Dim3(2, 2, 2), Radius.constant(2))
    coord = _coord_field(size)
    results = {}
    for label, mesh_dim, ndev in (("mixed2", Dim3(2, 1, 1), 2),
                                  ("ystack", Dim3(2, 1, 2), 4),
                                  ("full", Dim3(2, 2, 2), 8)):
        mesh = grid_mesh(mesh_dim, jax.devices()[:ndev])
        ex = HaloExchange(spec, mesh)
        assert ex.oversubscribed == (label != "full")
        state = ex({0: shard_blocks(coord, spec, mesh)})
        results[label] = np.asarray(jax.device_get(state[0]))
    np.testing.assert_array_equal(results["mixed2"], results["full"])
    np.testing.assert_array_equal(results["ystack"], results["full"])
    _assert_halos_wrap(results["mixed2"], spec, size)


def test_oversubscribed_direct26_halo_parity():
    """DIRECT26 under oversubscription (exclusion lifted, VERDICT r3
    item 4): resident rolls + boundary permutes must match the fully
    distributed DIRECT26 exchange, on z-stacked AND mixed meshes."""
    import jax

    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.parallel import HaloExchange, Method, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks

    size = Dim3(12, 12, 12)
    spec = GridSpec(size, Dim3(2, 2, 2), Radius.constant(2))
    coord = _coord_field(size)
    results = {}
    for label, mesh_dim, ndev in (("zstack", Dim3(2, 2, 1), 4),
                                  ("mixed2", Dim3(2, 1, 1), 2),
                                  ("full", Dim3(2, 2, 2), 8)):
        mesh = grid_mesh(mesh_dim, jax.devices()[:ndev])
        ex = HaloExchange(spec, mesh, method=Method.DIRECT26)
        state = ex({0: shard_blocks(coord, spec, mesh)})
        results[label] = np.asarray(jax.device_get(state[0]))
    np.testing.assert_array_equal(results["zstack"], results["full"])
    np.testing.assert_array_equal(results["mixed2"], results["full"])
    _assert_halos_wrap(results["mixed2"], spec, size)


# -- one-level parity grid against DIRECT26, the reference lowering ----------
#
# Geometry/knob pairs nothing else ran: an uneven (1, 2, 4) split (x
# self-wraps, four blocks on z) and a (2, 2, 4) partition stacked on four
# devices, with mixed dtypes, bf16 on the wire and batching off.


def _ramp_fields(spec, dtypes, scale=1.0):
    g = spec.global_size
    base = (np.arange(g.z)[:, None, None] * 1_000_000.0
            + np.arange(g.y)[None, :, None] * 1_000.0
            + np.arange(g.x)[None, None, :])
    return [((base + i) * scale).astype(dt) for i, dt in enumerate(dtypes)]


def _ramp_state(spec, mesh, dtypes, scale=1.0):
    return {i: shard_blocks(f, spec, mesh)
            for i, f in enumerate(_ramp_fields(spec, dtypes, scale))}


F32x2 = (np.float32, np.float32)


@pytest.mark.parametrize("size,part,mesh_dim,ndev,dtypes,kw", [
    ((14, 18, 20), (1, 2, 4), (1, 2, 4), 8, F32x2, {}),
    ((14, 18, 20), (1, 2, 4), (1, 2, 4), 8,
     (np.float32, np.float64, np.float32), {}),
    ((12, 12, 16), (2, 2, 4), (1, 2, 2), 4, F32x2, {}),
    ((16, 16, 16), (2, 2, 2), (2, 2, 2), 8, F32x2,
     {"wire_dtype": "bfloat16"}),
    ((14, 18, 20), (1, 2, 4), (1, 2, 4), 8, F32x2,
     {"batch_quantities": False}),
], ids=["uneven-1x2x4", "uneven-1x2x4-mixed", "oversub-2x2x4-on-4",
        "bf16-wire", "uneven-1x2x4-batch-off"])
def test_composed_parity_with_direct26(size, part, mesh_dim, ndev, dtypes, kw):
    spec = GridSpec(Dim3(*size), Dim3(*part), Radius.constant(2))
    mesh = grid_mesh(Dim3(*mesh_dim), jax.devices()[:ndev])
    outs = {}
    for method in (Method.DIRECT26, Method.AXIS_COMPOSED):
        ex = HaloExchange(spec, mesh, method, **kw)
        out = ex(_ramp_state(spec, mesh, dtypes))
        outs[method] = [jax.device_get(out[i]) for i in sorted(out)]
    for a, b, dt in zip(outs[Method.DIRECT26], outs[Method.AXIS_COMPOSED],
                        dtypes):
        assert a.dtype == b.dtype == dt
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("method,kw", [
    (Method.AXIS_COMPOSED, {}),
], ids=["composed"])
def test_jacobi_loop_parity_with_direct26_uneven_1x2x4(method, kw):
    """Five iterations of the step loop on the uneven (1, 2, 4) split land
    bit-identical to the DIRECT26 loop."""
    from stencil_tpu.ops.jacobi import make_jacobi_loop, sphere_sel

    spec = GridSpec(Dim3(14, 18, 20), Dim3(1, 2, 4), Radius.constant(2))
    g = spec.global_size
    curr = np.random.default_rng(0).standard_normal(
        (g.z, g.y, g.x)).astype(np.float32)
    sel = sphere_sel(g)
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    outs = []
    for m, k in ((Method.DIRECT26, {}), (method, kw)):
        loop = make_jacobi_loop(HaloExchange(spec, mesh, m, **k), 5)
        out, _ = loop(shard_blocks(curr, spec, mesh),
                      shard_blocks(np.zeros_like(curr), spec, mesh),
                      shard_blocks(sel, spec, mesh))
        outs.append(unshard_blocks(out, spec))
    np.testing.assert_array_equal(outs[0], outs[1])


# -- the composed exchange against a plain numpy halo reference ---------------


def _assert_halos_are_the_wrapped_field(got, field, spec, wire=None):
    """Every held cell of every block (the compute region and the halo box
    round it: faces, edges and corners) against plain numpy indexing of the
    global field at the periodically wrapped coordinate. ``wire``: what a
    halo cell that crossed the wire was rounded to on the way."""
    g, off, r = spec.global_size, spec.compute_offset(), spec.radius
    arr = np.asarray(got)
    for bz in range(spec.dim.z):
        for by in range(spec.dim.y):
            for bx in range(spec.dim.x):
                org = spec.block_origin((bx, by, bz))
                bs = spec.block_size((bx, by, bz))
                ax = [(np.arange(-lo, n + hi) + o) % gn for lo, hi, n, o, gn
                      in ((r.z(-1), r.z(1), bs.z, org.z, g.z),
                          (r.y(-1), r.y(1), bs.y, org.y, g.y),
                          (r.x(-1), r.x(1), bs.x, org.x, g.x))]
                want = field[np.ix_(*ax)]
                if wire is not None:
                    import jax.numpy as jnp

                    rounded = np.array(
                        jnp.asarray(want).astype(wire).astype(want.dtype))
                    inner = (slice(r.z(-1), r.z(-1) + bs.z),
                             slice(r.y(-1), r.y(-1) + bs.y),
                             slice(r.x(-1), r.x(-1) + bs.x))
                    rounded[inner] = want[inner]
                    want = rounded
                have = arr[bz, by, bx][
                    off.z - r.z(-1):off.z + bs.z + r.z(1),
                    off.y - r.y(-1):off.y + bs.y + r.y(1),
                    off.x - r.x(-1):off.x + bs.x + r.x(1)]
                np.testing.assert_array_equal(have, want,
                                              err_msg=str((bx, by, bz)))


F64x2 = (np.float64, np.float64)
MIXED = (np.float32, np.float64, np.float32)


@pytest.mark.parametrize("size,part,mesh_dim,ndev,dtypes,radius,kw", [
    ((16, 16, 16), (2, 2, 2), (2, 2, 1), 4, F32x2, 1, {}),
    ((17, 16, 16), (2, 2, 2), (2, 1, 2), 4, F64x2, 1, {}),
    ((16, 16, 16), (2, 2, 2), (2, 2, 2), 8, MIXED, 1, {}),
    ((16, 16, 16), (2, 2, 2), (2, 2, 2), 8, F32x2, 2,
     {"batch_quantities": False}),
    ((17, 16, 16), (2, 2, 2), (2, 2, 2), 8, F32x2, 2,
     {"wire_dtype": "bfloat16"}),
    ((17, 16, 16), (2, 2, 2), (2, 2, 2), 8, F32x2, 2,
     {"wire_dtype": "float8_e4m3fn"}),
    ((16, 16, 16), (2, 2, 2), (2, 2, 2), 8, F64x2, 2, {}),
    ((16, 16, 16), (1, 2, 4), (1, 2, 4), 8, MIXED, 2, {}),
], ids=["oversub-2x2x2-on-2x2x1", "uneven-oversub-f64", "mixed-f32-f64-f32",
        "batch-off-r2", "uneven-bf16-wire", "uneven-fp8-wire", "f64-r2",
        "anisotropic-1x2x4-mixed"])
def test_composed_against_numpy_halo_reference(size, part, mesh_dim, ndev,
                                               dtypes, radius, kw):
    spec = GridSpec(Dim3(*size), Dim3(*part), Radius.constant(radius))
    mesh = grid_mesh(Dim3(*mesh_dim), jax.devices()[:ndev])
    wire = kw.get("wire_dtype")
    # fp8's finite range tops out at 448 and overflow is NaN there: the
    # fixture is scaled into range, as user data under that wire must be
    scale = 2e-5 if wire == "float8_e4m3fn" else 1.0
    fields = _ramp_fields(spec, dtypes, scale)
    out = HaloExchange(spec, mesh, Method.AXIS_COMPOSED, **kw)(
        _ramp_state(spec, mesh, dtypes, scale))
    for i, (field, dt) in enumerate(zip(fields, dtypes)):
        got = jax.device_get(out[i])
        assert got.dtype == dt
        _assert_halos_are_the_wrapped_field(got, field, spec, wire=wire)


# -- the wire dtype: byte model, narrowing policy, lowered bytes, error -------


def test_wire_dtype_byte_model():
    from stencil_tpu.plan.ir import PlanConfig, build_plan

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
    # aligned with itemsizes(): sorted dtype order puts float32 first
    assert list(zip(cfg.itemsizes(), cfg.floating_flags())) == \
        [(4, True), (4, False)]


def test_fp8_wire_itemsize_in_byte_model():
    from stencil_tpu.plan.ir import build_plan, wire_itemsize

    assert wire_itemsize("float8_e4m3fn") == 1
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    native = build_plan(spec, Dim3(2, 2, 2), Method.AXIS_COMPOSED)
    fp8 = build_plan(spec, Dim3(2, 2, 2), Method.AXIS_COMPOSED,
                     wire_dtype="float8_e4m3fn")
    assert native.wire_bytes([4]) == 4 * fp8.wire_bytes([4])
    # local hand-offs never compress
    assert native.local_bytes([4]) == fp8.local_bytes([4])


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


def test_wire_compression_halves_lowered_wire_bytes():
    from stencil_tpu.utils.hlo_check import stablehlo_wire_census

    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    st = _ramp_state(spec, mesh, F32x2)
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
        out = ex(_ramp_state(spec, mesh, (np.float32,)))
        outs[wd] = np.asarray(jax.device_get(out[0]))
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


def test_wire_dtype_ignored_for_auto_spmd(capfd):
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    ex = HaloExchange(spec, mesh, Method.AUTO_SPMD, wire_dtype="bfloat16")
    assert ex.wire_dtype is None
    assert "ignored" in capfd.readouterr().err


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


# -- what arrives from outside: the retired transport is refused by name -----


def test_retired_method_string_is_named():
    spec = GridSpec(Dim3(16, 16, 16), Dim3(2, 2, 2), Radius.constant(1))
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    with pytest.raises(ValueError, match="'remote-dma' is retired"):
        HaloExchange(spec, mesh, "remote-dma")
    with pytest.raises(ValueError, match="not a valid Method"):
        HaloExchange(spec, mesh, "remote-dam")
    assert HaloExchange(spec, mesh, "direct26").method == Method.DIRECT26


@pytest.mark.parametrize("argv", [
    ["--method", "remote-dma"],
    ["--fused"],
], ids=["method-remote-dma", "fused"])
def test_bench_exchange_refuses_the_retired_spellings(argv, capsys):
    from stencil_tpu.apps import bench_exchange

    with pytest.raises(SystemExit) as e:
        bench_exchange.main(["--x", "8", "--y", "8", "--z", "8"] + argv)
    assert e.value.code == 2
    assert argv[-1] in capsys.readouterr().err
