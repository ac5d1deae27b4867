"""The slab path of the composed exchange since both directions of an axis
leave together (pack both, wire both, place both), a fixed axis of two
blocks sends ONE carrier in ONE permute, and the axes of a faces-only plan
that read nothing of each other share a wave: every held cell of every
quantity against a plain numpy exchange, one side after another, on the
CPU mesh; what the counter ``halo.wire_schedule`` says against what the
lowered program holds."""

import functools

import numpy as np
import pytest

import jax

from stencil_tpu.api import DistributedDomain
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.obs import telemetry
from stencil_tpu.parallel.mesh import BLOCK_PSPEC
from stencil_tpu.utils.hlo_check import build_graph

BLOCK = 8                   # owned cells a block an axis
# partition (x, y, z) and which axes wrap (x, y, z): a fixed axis of 2 and
# of 4 blocks, a periodic axis of 2 and of 4, both kinds on one mesh
MESHES = {
    "fixed-1x2x2": ((1, 2, 2), (False, False, False)),
    "fixed-1x4x2": ((1, 4, 2), (False, False, False)),
    "fixed-1x2x4": ((1, 2, 4), (False, False, False)),
    "periodic-1x2x2": ((1, 2, 2), (True, True, True)),
    "periodic-1x4x2": ((1, 4, 2), (True, True, True)),
    "mixed-2x2x2": ((2, 2, 2), (True, False, True)),
}
# low / high radius on every axis: one shape both ways, and rm != rp
RADII = {"r2": (2, 2), "r1r3": (1, 3)}


def _radius(rm, rp, star):
    r = Radius.face_edge_corner(0, 0, 0) if star else Radius.constant(
        max(rm, rp))
    for axis in range(3):
        for sign, width in ((-1, rm), (1, rp)):
            d = [0, 0, 0]
            d[axis] = sign
            r.set_dir(tuple(d), width)
    return r


def _reference(arr, spec, periodic, faces_only):
    """The composed exchange in numpy, x then y then z, the low halo then
    the high one, a block at a time: a slab spans the whole padded extent
    of the other axes (so later phases carry edges and corners), or, for
    a star, the compute region of its orthogonal leading axes."""
    out = arr.copy()
    off, b, r = spec.compute_offset(), spec.base, spec.radius
    dims = (spec.dim.z, spec.dim.y, spec.dim.x)
    lo = {3: off.z, 4: off.y, 5: off.x}
    own = {3: b.z, 4: b.y, 5: b.x}
    for adim, bdim, rm, rp, wraps, cut in (
            (5, 2, r.x(-1), r.x(1), periodic[0], (3, 4)),
            (4, 1, r.y(-1), r.y(1), periodic[1], (3,)),
            (3, 0, r.z(-1), r.z(1), periodic[2], (4,))):
        n = dims[bdim]
        if n == 1 and not wraps:
            continue                     # a fixed axis of one block
        where = [slice(None)] * 6
        if faces_only:
            for dim in cut:
                where[dim] = slice(lo[dim], lo[dim] + own[dim])

        def cells(i, start, width):
            at = list(where)
            at[bdim] = i
            at[adim] = slice(start, start + width)
            return tuple(at)

        o, sz = lo[adim], own[adim]
        for width, src, dst, step in ((rm, o + sz - rm, o - rm, -1),
                                      (rp, o, o + sz, 1)):
            before = out.copy()
            for i in range(n):
                j = i + step             # who fills block i's halo there
                if width and (wraps or 0 <= j < n):
                    out[cells(i, dst, width)] = before[
                        cells(j % n, src, width)]
    return out


@functools.lru_cache(maxsize=None)
def _exchanged(mesh, radii, faces_only, nq):
    """One ``DistributedDomain.exchange()`` of ``nq`` exchanged quantities
    and one that is not: (spec, held before, held after, the exchange)."""
    part, periodic = MESHES[mesh]
    d = Dim3(*part)
    dd = DistributedDomain(BLOCK * d.x, BLOCK * d.y, BLOCK * d.z)
    dd.set_radius(_radius(*RADII[radii], star=faces_only))
    dd.set_boundary(periodic=periodic, faces_only=faces_only)
    dd.set_devices(jax.devices()[:d.flatten()])
    dd.set_partition(part)
    handles = [dd.add_data(f"q{i}") for i in range(nq)]
    handles.append(dd.add_data("coeff", exchanged=False, buffered=False))
    dd.realize()
    shape = dd.spec.stacked_shape_zyx()
    cells = int(np.prod(shape))
    assert cells * (nq + 1) < 2 ** 24    # every held cell its own float32
    before = [np.arange(q * cells, (q + 1) * cells, dtype=np.float32)
              .reshape(shape) for q in range(nq + 1)]
    for h, a in zip(handles, before):
        dd.set_curr(h, jax.device_put(a, dd.sharding()))
    dd.exchange()
    return (dd.spec, before, [np.asarray(dd.get_curr(h)) for h in handles],
            dd.halo_exchange)


@pytest.mark.parametrize("nq", [1, 8])
@pytest.mark.parametrize("faces_only", [False, True])
@pytest.mark.parametrize("radii", sorted(RADII))
@pytest.mark.parametrize("mesh", sorted(MESHES))
def test_every_held_cell_is_what_one_side_after_another_gives(
        mesh, radii, faces_only, nq):
    spec, before, after, ex = _exchanged(mesh, radii, faces_only, nq)
    assert ex.faces_only == faces_only
    _part, periodic = MESHES[mesh]
    for q in range(nq):
        want = _reference(before[q], spec, periodic, faces_only)
        assert (want != before[q]).any()
        assert np.array_equal(want.view(np.uint32), after[q].view(np.uint32))
    # the quantity that is not exchanged: every cell as it was seeded
    assert np.array_equal(before[nq].view(np.uint32),
                          after[nq].view(np.uint32))


def _schedules():
    return telemetry.get().records(kind="counter", name="halo.wire_schedule")


def _permute_depth(text):
    """(collective_permutes in the lowered program, the longest chain of
    them in which each consumes the one before)."""
    graph = build_graph(text)
    permutes = [k for k, (op, _) in graph.items()
                if "collective_permute" in op]
    depth = {}

    def walk(node):
        if node not in depth:
            depth[node] = 0             # (an SSA graph has no cycle)
            below = max((walk(o) for o in graph[node][1] if o in graph),
                        default=0)
            depth[node] = below + (node in permutes)
        return depth[node]

    return len(permutes), max((walk(p) for p in permutes), default=0)


@pytest.mark.parametrize("faces_only", [False, True])
@pytest.mark.parametrize("radii", sorted(RADII))
@pytest.mark.parametrize("mesh", sorted(MESHES))
def test_the_counter_says_what_the_lowered_exchange_holds(
        mesh, radii, faces_only):
    """``merged`` exactly on a fixed axis of two blocks with one radius
    both ways; ``permutes`` and ``waves`` as the lowered program's: one
    wave an axis, and one for y and z together where the plan is a star's
    (their slabs are cut to each other's compute region)."""
    spec, before, _after, ex = _exchanged(mesh, radii, faces_only, 8)
    part, periodic = MESHES[mesh]
    like = {q: jax.ShapeDtypeStruct(spec.stacked_shape_zyx(), np.float32,
                                    sharding=ex.sharding()) for q in range(8)}
    # a body of its own: lowering ``ex._compiled`` again traces nothing
    fn = jax.jit(jax.shard_map(ex.exchange_blocks, mesh=ex.mesh,
                               in_specs=BLOCK_PSPEC, out_specs=BLOCK_PSPEC))
    built = len(_schedules())
    text = fn.lower(like).as_text()
    assert len(_schedules()) == built + 1
    said = _schedules()[-1]
    rm, rp = RADII[radii]
    want = []
    for axis, blocks, wraps in zip("xyz", part, periodic):
        if blocks == 1 and not wraps:
            continue
        merged = blocks == 2 and not wraps and rm == rp
        want.append({"axis": axis, "merged": merged, "permutes":
                     0 if blocks == 1 else 1 if merged else 2})
    assert said["phases"] == want
    assert [p.merged for p in ex.plan.axis_phases] == [
        w["merged"] for w in want]
    crossing = [w["axis"] for w in want if w["permutes"]]
    waves = len(crossing) - (faces_only and crossing[-2:] == ["y", "z"])
    assert said["waves"] == waves
    assert said["value"] == sum(w["permutes"] for w in want)
    assert _permute_depth(text) == (said["value"], said["waves"])
    assert ex.plan.collectives_per_exchange(8, 1) == said["value"]


def test_resident_blocks_still_send_one_direction_after_the_other():
    """Two blocks a device along z (the resident body, left as it was): its
    high side packs from what the low side placed, and the counter says
    two waves for the one axis."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.parallel import HaloExchange, grid_mesh

    spec = GridSpec(Dim3(16, 16, 32), Dim3(1, 1, 4), Radius.constant(2))
    ex = HaloExchange(spec, grid_mesh(Dim3(1, 1, 2), jax.devices()[:2]))
    assert ex.resident == Dim3(1, 1, 2)
    like = jax.ShapeDtypeStruct(spec.stacked_shape_zyx(), np.float32,
                                sharding=ex.sharding())
    fn = jax.jit(jax.shard_map(ex.exchange_blocks, mesh=ex.mesh,
                               in_specs=BLOCK_PSPEC, out_specs=BLOCK_PSPEC))
    text = fn.lower({0: like}).as_text()
    said = _schedules()[-1]
    assert said["phases"] == [
        {"axis": "x", "permutes": 0, "merged": False},
        {"axis": "y", "permutes": 0, "merged": False},
        {"axis": "z", "permutes": 2, "merged": False}]
    assert (said["value"], said["waves"]) == (2, 2) == _permute_depth(text)
