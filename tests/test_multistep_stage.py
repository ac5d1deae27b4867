"""The ONE stage body of the temporal multistep
(``ops/pallas_stencil._make_multistep_stage``): aligned 8-row groups, a few
a trip of a loop that carries nothing, between a scratch array a stage.

In every form the one stage serves (both builders, both x layouts, single
blocks and deep halos, strips with a seam and a re-anchored last one) the
loop is held BIT FOR BIT to the per-step path in interpret mode; a block
whose every cell outside what a pass may read is NaN shows that nothing
from outside a stage's extent reaches an owned cell where extents start
inside a group; and the build-time counter ``kernel.multistep.staging`` is
held to the traced body and, by hand, to the three jacobi cells' blocks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu.ops.jacobi import jacobi_reference, sphere_masks
from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.ops import pallas_stencil as ps

TIGHT = Radius.constant(1).without_x()

# (size, partition, radius, iterations, multistep_rows): the depth k is the
# planner's (the radius on a split axis, else the iterations), so iterations
# beyond a multiple of k run as single-step tails
FORMS = {
    "single block, tight-x": (Dim3(128, 24, 12), Dim3(1, 1, 1), TIGHT, 4, None),
    "single block, inline-x": (Dim3(20, 16, 12), Dim3(1, 1, 1),
                               Radius.constant(1), 3, None),
    "(1,2,2) deep halos, k=2 and a tail": (
        Dim3(128, 32, 24), Dim3(1, 2, 2), Radius.constant(2).without_x(), 5,
        None),
    "(1,2,2) deep halos, k=3 and a tail": (
        Dim3(128, 32, 28), Dim3(1, 2, 2), Radius.constant(3).without_x(), 4,
        None),
    "(1,2,2) deep halos, k=4 and two tails": (
        Dim3(128, 32, 36), Dim3(1, 2, 2), Radius.constant(4).without_x(), 6,
        None),
    "x split, inline-x deep halos": (Dim3(32, 16, 12), Dim3(2, 1, 1),
                                     Radius.constant(2), 4, None),
    "two row strips": (Dim3(128, 32, 12), Dim3(1, 1, 1), TIGHT, 4, 16),
    "three row strips": (Dim3(128, 48, 12), Dim3(1, 1, 1), TIGHT, 4, 16),
    "row strips, the last re-anchored": (Dim3(128, 40, 12), Dim3(1, 1, 1),
                                         TIGHT, 3, 16),
    "row strips, inline-x": (Dim3(20, 32, 12), Dim3(1, 1, 1),
                             Radius.constant(1), 3, 16),
    # the forms of the strips' periodic y rows that no cell runs (the 768^3
    # cell has two strips: a wrap DMA each): one strip is both edges
    "one row strip, both wraps": (Dim3(128, 32, 12), Dim3(1, 1, 1), TIGHT, 4,
                                  32),
    "one row strip, inline-x": (Dim3(20, 32, 12), Dim3(1, 1, 1),
                                Radius.constant(1), 3, 32),
    "z split, row strips on deep halos": (
        Dim3(128, 32, 24), Dim3(1, 1, 2), Radius.constant(2).without_x(), 5,
        16),
    "z split, one row strip": (
        Dim3(128, 32, 24), Dim3(1, 1, 2), Radius.constant(3).without_x(), 4,
        32),
    "x split, inline-x row strips": (Dim3(32, 32, 12), Dim3(2, 1, 1),
                                     Radius.constant(2), 4, 16),
}


def _few_groups_a_trip(form, n=2):
    return pytest.param(form, n, id=f"{form}, {n} a trip")


# at one or two groups a trip these planes take two or three trips a stage,
# the last re-anchored where the groups are odd (the cells' planes take two
# or three trips of up to 32; at 32 every plane here is ONE trip)
@pytest.mark.parametrize("form, groups_per_trip", [
    *[(form, None) for form in FORMS],
    _few_groups_a_trip("single block, tight-x"),
    _few_groups_a_trip("single block, inline-x", 1),
    _few_groups_a_trip("(1,2,2) deep halos, k=3 and a tail"),
    _few_groups_a_trip("x split, inline-x deep halos", 1),
    _few_groups_a_trip("row strips, the last re-anchored"),
])
def test_every_form_of_the_stage_equals_the_per_step_path_bit_for_bit(
        form, groups_per_trip, monkeypatch):
    if groups_per_trip:
        monkeypatch.setattr(ps, "_GROUPS_PER_TRIP", groups_per_trip)
    from stencil_tpu.obs import telemetry
    from stencil_tpu.ops.jacobi import make_jacobi_loop, sphere_sel
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    size, part, radius, iters, rows = FORMS[form]
    spec = GridSpec(size, part, radius)
    mesh = grid_mesh(spec.dim, jax.devices()[:part.flatten()])
    ex = HaloExchange(spec, mesh)
    field = np.random.RandomState(49).rand(
        size.z, size.y, size.x).astype(np.float32)
    sel = shard_blocks(sphere_sel(size), spec, mesh)
    rec = telemetry.get()
    before = len(rec.records(kind="counter", name="kernel.multistep.staging"))
    outs = {}
    for label, kwargs in (
        ("multistep", dict(use_pallas=True, interpret=True,
                           multistep_rows=rows)),
        # the zero-x-radius layout has no XLA path: its per-step path is
        # the sweep kernel, a step a call
        ("per step", dict(use_pallas=True, interpret=True, temporal_k=1)
         if radius.x(-1) == 0 else dict(use_pallas=False)),
    ):
        loop = make_jacobi_loop(ex, iters, **kwargs)
        curr = shard_blocks(field, spec, mesh)
        nxt = shard_blocks(np.zeros_like(field), spec, mesh)
        curr, nxt = loop(curr, nxt, sel)
        outs[label] = unshard_blocks(curr, spec)
    staged = rec.records(kind="counter",
                         name="kernel.multistep.staging")[before:]
    assert len(staged) == 1, "the multistep engages, and only where asked"
    assert staged[0]["k"] >= 2 and staged[0]["body"] == "row_groups"
    assert staged[0]["rows"] == (rows or 0)
    if groups_per_trip:
        assert staged[0]["groups_per_trip"] == groups_per_trip
        assert staged[0]["rows_walked"] >= staged[0]["rows_computed"]
    np.testing.assert_array_equal(outs["multistep"], outs["per step"])
    want = jacobi_reference(field, sphere_masks(size), iters)
    np.testing.assert_allclose(outs["multistep"], want, rtol=1e-5, atol=1e-6)


def _block_of(field, spec, index, halo, fill):
    """Block ``index`` (z, y, x) of the periodic global ``field`` in the
    padded layout: its own cells and ``halo`` (z, y, x) cells round them,
    ``fill`` everywhere else."""
    p, off, b = spec.padded(), spec.compute_offset(), spec.base
    out = np.full((p.z, p.y, p.x), fill, np.float32)
    src, dst = [], []
    for n, o, i, h in zip((b.z, b.y, b.x), (off.z, off.y, off.x), index,
                          halo):
        src.append(np.arange(i * n - h, (i + 1) * n + h))
        dst.append(slice(o - h, o + n + h))
    gz, gy, gx = field.shape
    out[tuple(dst)] = field[np.ix_(src[0] % gz, src[1] % gy, src[2] % gx)]
    return out


@pytest.mark.parametrize("k, rows", [(3, None), (2, None), (3, 16), (7, 16),
                                     (3, "x split")])
def test_nothing_outside_a_stage_extent_reaches_an_owned_cell(k, rows):
    """Extents that start INSIDE a group (the y origin is row 8: stage s of
    a deep-halo pass starts ``k - s`` rows before it, a strip's at
    ``round8(k) - (k - s)``), on a block whose every cell a pass may not
    read is NaN: the alignment padding beyond the radius-k halos, and for a
    single block all of its y halo but the ring the kernel fills itself.
    A whole group is computed where an extent cuts it, so such a value in
    an owned cell would show as NaN. The x split does the same to columns:
    whole rows are computed and rolled on inline x halos, the columns
    beyond a stage's x extent and the row's two ends with them."""
    if rows == "x split":   # block (0, 0, 1) of (2,1,1): deep halos in x
        size, part, index = Dim3(48, 16, 2 * k + 2), Dim3(2, 1, 1), (0, 0, 1)
        spec = GridSpec(size, part, Radius.constant(k))
        halo, rows = (0, 0, k), None
    elif rows is None:  # block (1, 0) of (1,2,2): deep halos in y and z
        size, part, index = Dim3(128, 32, 8 * k + 8), Dim3(1, 2, 2), (1, 0, 0)
        spec = GridSpec(size, part, Radius.constant(k).without_x())
        halo = (k, k, 0)
    else:               # one block on strips: the kernel wraps y and z
        size, part, index = Dim3(128, 48, 2 * k + 2), Dim3(1, 1, 1), (0, 0, 0)
        spec = GridSpec(size, part, Radius.constant(1).without_x())
        halo = (0, 0, 0)
    assert spec.compute_offset().y == 8
    if part.x == 1:
        starts = {ps._stage_rows(spec, k, rows, s)[0] % 8
                  for s in range(1, k)}
        assert starts - {0}, "no extent starts inside a group"
    field = np.random.RandomState(7 + k).rand(
        size.z, size.y, size.x).astype(np.float32)
    fn = ps.make_pallas_jacobi_multistep(spec, k, interpret=True, rows=rows)
    b, off = spec.base, spec.compute_offset()
    org = jnp.asarray([i * n for i, n in zip(index, (b.z, b.y, b.x))],
                      jnp.int32)
    got = {}
    for fill in (np.nan, 0.0):
        curr = jnp.asarray(_block_of(field, spec, index, halo, fill))
        args = (curr, jnp.full_like(curr, fill))
        out = fn(org, *args) if part.flatten() > 1 else fn(*args)
        got[fill == 0.0] = np.asarray(out)[
            off.z:off.z + b.z, off.y:off.y + b.y, off.x:off.x + b.x]
    assert np.isfinite(got[False]).all()
    np.testing.assert_array_equal(got[False], got[True])
    want = jacobi_reference(field, sphere_masks(size), k)[
        index[0] * b.z:(index[0] + 1) * b.z,
        index[1] * b.y:(index[1] + 1) * b.y,
        index[2] * b.x:(index[2] + 1) * b.x]
    np.testing.assert_allclose(got[False], want, rtol=1e-5, atol=1e-6)


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


def _ring_dma_groups(jaxpr, ring, depth=0, out=None):
    """The DMAs into the input ring (VMEM of shape ``ring``) of a kernel's
    jaxpr, in program order, grouped by the ``pl.when`` body that holds
    them: ``(depth, [(primitive, rows, first row, slots, semaphores)])`` a
    body, ``depth`` the bodies round it; ``slots`` (the ring's, the
    semaphore's) and ``semaphores`` (the array) are the jaxpr's variables,
    one object where one value is used twice."""
    out = [] if out is None else out
    here = []
    for e in jaxpr.eqns:
        if e.primitive.name in ("dma_start", "dma_wait"):
            _, _, dst, (at,), sem, (sem_at,), *_ = jax.tree_util.tree_unflatten(
                e.params["tree"], e.invars)
            if dst.aval.shape == ring:
                slot, rows = at.indices[0], at.indices[1]
                assert slot.size == 1 and sem.aval.shape == (ps._N_IN,)
                here.append((e.primitive.name, rows.size, rows.start,
                             (slot.start, sem_at.indices[0]), sem))
        for branch in e.params.get("branches", ()):
            _ring_dma_groups(branch.jaxpr, ring, depth + 1, out)
    if here:
        out.append((depth, here))
    return out


@pytest.mark.parametrize("layout, rows", [("tight", None), ("tight", 16),
                                          ("inline", None), ("tight", 24),
                                          ("tight", 48), ("inline", 16),
                                          ("inline", 48)])
def test_staging_counter_says_what_the_traced_body_does(layout, rows):
    """``kernel.multistep.staging``'s walk against the jaxpr of the built
    kernel: a loop a stage and sphere branch, its trips; two lane rolls and
    two sublane rotations a group in both layouts (whole rows are loaded
    and rolled, inline x halos too); a scratch array a stage; and no vector
    value larger than a trip's groups. And an edge strip's ``wrap_dmas``
    (three strips, two, one; full planes have none): started in the branch
    that starts the slab of the same ring slot, ``wrap_prefetch`` grid
    step ahead (the branches of ``j == 0`` and ``j + 1 < J``), waited for
    beside the slab's wait, and nothing starts after a wait."""
    k = 3
    nx = 256 if layout == "tight" else 20
    spec = GridSpec(Dim3(nx, 48, 12), Dim3(1, 1, 1),
                    TIGHT if layout == "tight" else Radius.constant(1))
    plan = ps.multistep_staging(spec, k, rows)
    assert plan["body"] == "row_groups" and plan["group_rows"] == 8
    assert plan["stage_buffers"] == k - 1
    assert plan["lane_rolls_per_vreg"] == 2
    fn = ps.make_pallas_jacobi_multistep(spec, k, rows=rows)
    p = spec.padded()
    like = jax.ShapeDtypeStruct((p.z, p.y, p.x), jnp.float32)
    eqns = _eqns(jax.make_jaxpr(fn)(like, like).jaxpr, [])

    (call,) = [e for e in eqns if e.primitive.name == "pallas_call"]
    kx = p.x
    staged = (rows + 16) if rows else p.y
    scratch = [v.aval.shape for v in call.params["jaxpr"].invars
               if len(v.aval.shape) == 3]
    assert scratch.count((3, staged, kx)) == plan["stage_buffers"]
    assert (ps._N_IN, staged, kx) in scratch

    groups = _ring_dma_groups(call.params["jaxpr"], (ps._N_IN, staged, kx))
    kinds = [{name for name, *_ in dmas} for _, dmas in groups]
    assert all(len(kind) == 1 for kind in kinds), "a body starts OR waits"
    starts = [g for g, kind in zip(groups, kinds) if kind == {"dma_start"}]
    waits = [g for g, kind in zip(groups, kinds) if kind == {"dma_wait"}]
    assert groups == starts + waits, "no DMA into the ring starts after a wait"

    def shape_of(group):
        depth, dmas = group
        if rows:    # the slab and the wraps of ONE step: one slot value
            assert len({id(v) for *_, slots, _ in dmas for v in slots}) == 1
        sems = [id(sem) for *_, sem in dmas]
        # the slab on one semaphore array, the wrap rows on the other
        assert len(set(sems)) == min(len(dmas), 2) and len(set(sems[1:])) <= 1
        return depth, tuple((n, first) for _, n, first, _, _ in dmas)

    # a strip position (first, last, interior: ``pl.when`` bodies, one
    # deeper) waits for what it started, and starts it twice: step 0's at
    # ``j == 0``, step j + 1's a grid step ahead
    held = [shape_of(g) for g in waits]
    ahead = [shape_of(g) for g in starts]
    assert ahead == 2 * [(depth + 1, dmas) for depth, dmas in held]
    # ... the wrap rows with the slab, in the same bodies
    edge = [dmas for _, dmas in held if len(dmas) > 1]
    assert plan["wrap_prefetch"] == (1 if edge else 0)
    strips, hp, ty = plan["strips"], 8, rows or 0
    assert [dmas for _, dmas in held] == {
        None: [((staged, 0),)],
        # slab, then the rows below and above it
        1: [((ty, hp), (hp, 0), (hp, hp + ty))],
        2: [((ty + hp, hp), (hp, 0)), ((hp + ty, 0), (hp, hp + ty))],
        3: [((ty + hp, hp), (hp, 0)), ((hp + ty, 0), (hp, hp + ty)),
            ((staged, 0),)],
    }[strips if rows else None]
    assert {len(dmas) - 1 for dmas in edge} == (
        {plan["wrap_dmas"]} if rows else set())

    walks = [ps._stage_walk(*ps._stage_rows(spec, k, rows, s))
             for s in range(1, k + 1)]
    loops = [e for e in eqns if e.primitive.name == "scan"]
    # a stage's two sphere branches, in stage order
    assert [e.params["length"] for e in loops] == [
        trips for _, _, _, trips in walks for _ in range(2)]
    assert plan["groups_per_trip"] == max(w[2] for w in walks)
    assert plan["rows_walked"] == plan["strips"] * 8 * sum(
        w[2] * w[3] for w in walks) >= plan["rows_computed"]
    trip_cells = plan["groups_per_trip"] * 8 * kx
    for loop, (_, _, per_trip, _) in zip(loops, (w for w in walks
                                                 for _ in range(2))):
        body = _eqns(loop.params["jaxpr"].jaxpr, [])
        rolls = [e for e in body if e.primitive.name == "roll"]
        lane = [e for e in rolls if e.params["axis"] == 1]
        assert len(lane) == plan["lane_rolls_per_vreg"] * per_trip
        assert len(rolls) - len(lane) == 2 * per_trip
        assert loop.params["num_carry"] == 1    # the counter alone
        for e in body:
            for v in list(e.invars) + list(e.outvars):
                shape = getattr(v.aval, "shape", ())
                if len(shape) == 2:
                    assert shape[0] * shape[1] <= trip_cells, (e, shape)
                    assert shape[0] % 8 == 0, (e, shape)


@pytest.mark.parametrize("spec, rows, computed, walked, per_trip", [
    # 512^3: every stage is rows [8, 520), 64 groups in 2 trips of 32
    (GridSpec(Dim3(512, 512, 512), Dim3(1, 1, 1), TIGHT), None,
     10 * 512, 10 * 64 * 8, 32),
    # 768^3 on 2 strips of 384 (slab 416 rows = 52 groups): stage 1 is rows
    # [7, 409), all 52 groups in 2 trips of 26; stages 2..9 rows [8.., ..408],
    # 50 groups in 2 of 25; stage 10 rows [16, 400), 48 groups in 2 of 24:
    # nothing is walked twice
    (GridSpec(Dim3(768, 768, 768), Dim3(1, 1, 1), TIGHT), 384,
     7860, 2 * 8 * (52 + 8 * 50 + 48), 26),
    # the (1,2,2) block of jacobi512x4.weak (rows 544, origin 16): stage 1
    # is rows [7, 537), 68 groups in 3 trips of 23 (one group twice);
    # stages 2..9 66 groups in 3 of 22; stage 10 rows [16, 528), 64 groups
    # in 2 trips of 32
    (GridSpec(Dim3(512, 1024, 1024), Dim3(1, 2, 2),
              Radius.constant(10).without_x()), None,
     10 * 512 + 90, 8 * (3 * 23 + 8 * 66 + 64), 32),
])
def test_rows_walked_by_hand_at_the_three_cells(spec, rows, computed, walked,
                                                per_trip):
    k, planned = ps.plan_multistep_staging(spec, 10, ps.MULTISTEP_VMEM_BUDGET)
    assert (k, planned) == (10, rows)
    got = ps.multistep_staging(spec, k, rows)
    assert got["rows_computed"] == computed
    assert got["rows_walked"] == walked >= computed
    assert got["groups_per_trip"] == per_trip
    assert (got["stage_buffers"], got["lane_rolls_per_vreg"]) == (9, 2)
    # the scratch arrays hold the bytes the one 4-D array held
    assert got["vmem_bytes"] == ps._staging_bytes(
        spec.base.x, k, (rows + 32) if rows else spec.padded().y,
        rows or spec.padded().y) <= ps.MULTISTEP_VMEM_BUDGET
