"""The temporal depth across chips is the application's pick
(``ops/pallas_stencil.pick_temporal_depth``, ``apps/jacobi3d.run``): what
the pick returns for a mesh, a block, a chunk and a VMEM budget; what
``run()`` realizes and builds from it where the Pallas path would run (the
TPU gate is turned on in the test and the kernels are interpreted, as the
benchmark's rehearsal does); and the deep-halo multistep itself at the
four-chip cell's shape and a depth that is no multiple of 8.
"""

import jax
import numpy as np
import pytest

from stencil_tpu.geometry import Dim3
from stencil_tpu.obs import telemetry
from stencil_tpu.ops.jacobi import (INIT_TEMP, jacobi_reference, sphere_masks,
                                    sphere_sel)
from stencil_tpu.ops.pallas_stencil import (MULTISTEP_VMEM_BUDGET,
                                            pick_temporal_depth)

MB = 1024 * 1024

PICKS = {
    # one block wraps in the kernel at any radius: jacobi512.steady and
    # jacobi768.steady realize what they always realized
    "one_block_512": ((512, 512, 512), (1, 1, 1), 10, None, (1, "mesh")),
    "one_block_768": ((768, 768, 768), (1, 1, 1), 10, None, (1, "mesh")),
    # the four-chip cell: 512^3 a chip, one pass a dispatch of ten
    "cell_512x4": ((512, 1024, 1024), (1, 2, 2), 10, None, (10, "chunk")),
    "cell_512x4_chunk5": ((512, 1024, 1024), (1, 2, 2), 5, None,
                          (5, "chunk")),
    # a dispatch longer than the deepest build: a divisor under the cap
    "cell_512x4_chunk40": ((512, 1024, 1024), (1, 2, 2), 40, None,
                           (10, "cap")),
    # a prime chunk the block cannot reach: the deepest k, a tail is left
    "prime_chunk": ((128, 32, 32), (1, 2, 2), 11, None, (7, "block")),
    # planes too large for VMEM at depth 10: the deepest divisor that fits
    "plane_768": ((768, 1536, 1536), (1, 2, 2), 10, None, (5, "vmem")),
    "plane_1024": ((1024, 2048, 1024), (1, 2, 2), 10, None, (2, "vmem")),
    "plane_2048": ((2048, 2048, 1024), (1, 2, 2), 10, None, (1, "vmem")),
    "small_budget": ((512, 1024, 1024), (1, 2, 2), 10, 24 * MB,
                     (5, "vmem")),
    # 16^3 blocks: the wavefront needs nz >= 2k + 1
    "block_16": ((128, 32, 32), (1, 2, 2), 10, None, (5, "block")),
    "block_16_chunk7": ((128, 32, 32), (1, 2, 2), 7, None, (7, "chunk")),
    # a z split alone keeps row strips: 768^2 planes still reach depth 10
    "z_split_768": ((768, 768, 3072), (1, 1, 4), 10, None, (10, "chunk")),
    # where the deep-halo multistep cannot engage
    "split_x": ((1024, 1024, 512), (2, 2, 1), 10, None, (1, "mesh")),
    "uneven_y": ((512, 1023, 1024), (1, 2, 2), 10, None, (1, "mesh")),
    "x_not_lanes": ((500, 1024, 1024), (1, 2, 2), 10, None, (1, "mesh")),
    "chunk_of_one": ((512, 1024, 1024), (1, 2, 2), 1, None, (1, "chunk")),
}


@pytest.mark.parametrize("name", sorted(PICKS))
def test_pick_temporal_depth(name):
    size, part, chunk, budget, want = PICKS[name]
    got = pick_temporal_depth(Dim3(*size), Dim3(*part), chunk,
                              budget or MULTISTEP_VMEM_BUDGET)
    assert got == want
    k = got[0]
    assert 1 <= k <= max(chunk, 1)
    if k >= 2:
        # what the pick promises is what the loop builder's planner gives
        from stencil_tpu.domain.grid import GridSpec
        from stencil_tpu.geometry import Radius
        from stencil_tpu.ops.pallas_stencil import plan_multistep_staging

        spec = GridSpec(Dim3(*size), Dim3(*part),
                        Radius.constant(k).without_x())
        assert plan_multistep_staging(
            spec, k, budget or MULTISTEP_VMEM_BUDGET)[0] == k
        assert spec.base.z >= 2 * k + 1


def test_the_cap_bounds_the_pick(monkeypatch):
    monkeypatch.setenv("STENCIL_TEMPORAL_K_CAP", "4")
    assert pick_temporal_depth(Dim3(512, 1024, 1024), Dim3(1, 2, 2),
                               10) == (2, "cap")


# ------------------------------------------------- what run() makes of it


class _Builds:
    """``make_jacobi_loop`` / ``make_jacobi_step`` as ``run()`` looks them
    up, told to interpret (the CPU has no Mosaic), their arguments kept."""

    def __init__(self, monkeypatch):
        from stencil_tpu.apps import jacobi3d

        self.calls = []
        for name in ("make_jacobi_loop", "make_jacobi_step"):
            monkeypatch.setattr(
                jacobi3d, name, self._forced(name, getattr(jacobi3d, name)))
        # the pick engages where the Pallas path is the one that runs
        monkeypatch.setattr(jacobi3d, "_on_tpu", lambda devices: True)

    def _forced(self, name, builder):
        def build(ex, *args, **kwargs):
            self.calls.append((name, args, kwargs))
            return builder(ex, *args, use_pallas=True, interpret=True,
                           **kwargs)

        return build


def _depth_records():
    return telemetry.get().records(kind="counter",
                                   name="jacobi.temporal_depth")


RUNS = {
    # (devices, run() kwargs, radius z/y/x, temporal_k, bound)
    "four_chips_default": (4, {}, (5, 5, 0), 5, "block"),
    "four_chips_chunk7": (4, {"chunk": 7}, (7, 7, 0), 7, "chunk"),
    "one_chip_default": (1, {}, (1, 1, 0), None, "mesh"),
    "no_overlap": (4, {"overlap": False}, (1, 1, 0), None, "mesh"),
    "explicit_1": (4, {"deep_halo": 1}, (1, 1, 0), None, "explicit"),
    "explicit_2": (4, {"deep_halo": 2}, (2, 2, 0), 2, "explicit"),
    "split_x": (4, {"partition": (2, 2, 1)}, (1, 1, 1), None, "mesh"),
    # depths that do not divide the dispatch: passes, then single steps
    "explicit_3_tail1": (4, {"deep_halo": 3}, (3, 3, 0), 3, "explicit"),
    "explicit_4_tail2": (4, {"deep_halo": 4}, (4, 4, 0), 4, "explicit"),
    "explicit_2_chunk7": (4, {"deep_halo": 2, "chunk": 7}, (2, 2, 0), 2,
                          "explicit"),
    # eight chips: (1,2,4), four blocks on z
    "eight_chips_default": (8, {}, (5, 5, 0), 5, "block"),
    "eight_chips_3_tail1": (8, {"deep_halo": 3}, (3, 3, 0), 3, "explicit"),
}


@pytest.mark.parametrize("name", sorted(RUNS))
def test_run_realizes_the_depth_it_picks(name, monkeypatch):
    from stencil_tpu.apps import jacobi3d

    ndev, kwargs, radius, tk, bound = RUNS[name]
    builds = _Builds(monkeypatch)
    before = len(_depth_records())
    iters = 14
    r = jacobi3d.run(128, 16, 16, iters=iters, weak=True, warmup=0,
                     devices=jax.devices()[:ndev], **kwargs)
    rad = r["domain"].spec.radius
    assert (rad.z(1), rad.y(1), rad.x(1)) == radius
    assert (rad.z(-1), rad.y(-1), rad.x(-1)) == radius
    chunk = kwargs.get("chunk", 10)
    # the dispatch is the application's chunk, whatever the depth
    kind, args, kw = builds.calls[0]
    assert (kind, args[0]) == ("make_jacobi_loop", chunk)
    assert kw["temporal_k"] == tk and kw["overlap"] == kwargs.get(
        "overlap", True)
    # recorded once a run()
    (rec,) = _depth_records()[before:]
    k = tk or 1
    assert rec["value"] == k and rec["bound"] == bound
    assert rec["chunk"] == chunk and rec["halo_zyx"] == list(radius)
    assert (rec["passes"], rec["single_steps"]) == (
        divmod(chunk, k) if k >= 2 else (0, chunk))
    assert telemetry.validate_record(rec) == []
    # and the same mathematics, all `iters` steps from the uniform start
    size = Dim3(r["x"], r["y"], r["z"])
    want = jacobi_reference(
        np.full((size.z, size.y, size.x), INIT_TEMP, np.float32),
        sphere_masks(size), iters)
    got = r["domain"].get_curr_global(r["handle"])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("k, iters", [(2, 6), (3, 7)],
                         ids=["k2", "k3-tail1"])
def test_deep_halos_on_uneven_blocks_step_one_exchange_at_a_time(k, iters):
    """Radius-k halos on the uneven (1, 2, 4) split (y 10 + 10, z 6 + 6 + 5
    + 5; x not lanes): the multistep wants even tight-x blocks, so the loop
    exchanges every step at the depth it was given, overlap shells at
    dynamic offsets, and lands on the plain reference from a random field."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.jacobi import make_jacobi_loop
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    spec = GridSpec(Dim3(18, 20, 22), Dim3(1, 2, 4), Radius.constant(k))
    assert not spec.is_uniform()
    g = spec.global_size
    mesh = grid_mesh(spec.dim, jax.devices()[:8])
    staged = len(telemetry.get().records(kind="counter",
                                         name="kernel.multistep.staging"))
    loop = make_jacobi_loop(HaloExchange(spec, mesh), iters, temporal_k=k)
    assert len(telemetry.get().records(
        kind="counter", name="kernel.multistep.staging")) == staged
    field = np.random.default_rng(k).standard_normal(
        (g.z, g.y, g.x)).astype(np.float32)
    out, _ = loop(shard_blocks(field, spec, mesh),
                  shard_blocks(np.zeros_like(field), spec, mesh),
                  shard_blocks(sphere_sel(g), spec, mesh))
    want = jacobi_reference(field, sphere_masks(g), iters)
    np.testing.assert_allclose(unshard_blocks(out, spec), want, rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("argv", [
    ["--method", "remote-dma"],
    ["--fused"],
    ["--kernel-variant", "fused"],
    ["--kernel-variant", "persistent", "--deep-halo", "2"],
], ids=["method-remote-dma", "fused", "variant-fused", "variant-persistent"])
def test_jacobi3d_app_refuses_the_retired_spellings(argv, capsys):
    """What selected the kernel-initiated transport and its two kernel
    variants on the command line is refused by ``argparse``, by name; the
    depth is the application's pick or ``--deep-halo``."""
    from stencil_tpu.apps import jacobi3d

    with pytest.raises(SystemExit) as e:
        jacobi3d.main(["--x", "8", "--y", "8", "--z", "8"] + argv)
    assert e.value.code == 2
    err = capsys.readouterr().err
    assert (argv[1] if argv[0] == "--method" else argv[0]) in err


# ------------------------------- the kernel at the cell's shape, k = 5


def test_deep_halo_k5_across_the_block_corner_two_dispatches():
    """Tight-x, dim 1x2x2, radius 5 on y and z (no multiple of 8: stage s
    computes rows ``yo - (5 - s)`` on), the hot sphere's centre ON the
    corner where the four blocks meet and its surface through all four,
    from a random field; two dispatches of one pass each, so the second
    reads what the first wrote into the other buffer of the pair."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.ops.jacobi import make_jacobi_loop
    from stencil_tpu.parallel import HaloExchange, grid_mesh
    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    k = 5
    size = Dim3(128, 32, 32)
    spec = GridSpec(size, Dim3(1, 2, 2), Radius.constant(k).without_x())
    assert spec.compute_offset().y == 8 and spec.padded().y == 32
    hot, cold = sphere_masks(size)
    # the hot sphere (centre y = z = 16, radius 12) lies in all four blocks
    for zs in (slice(0, 16), slice(16, 32)):
        for ys in (slice(0, 16), slice(16, 32)):
            assert hot[zs, ys].any() and not hot[zs, ys].all()
    mesh = grid_mesh(spec.dim, jax.devices()[:4])
    ex = HaloExchange(spec, mesh)
    field = np.random.RandomState(31).rand(
        size.z, size.y, size.x).astype(np.float32)
    sel = shard_blocks(sphere_sel(size), spec, mesh)
    before = len(telemetry.get().records(
        kind="counter", name="kernel.multistep.staging"))
    loop = make_jacobi_loop(ex, k, temporal_k=k, use_pallas=True,
                            interpret=True)
    staged = telemetry.get().records(
        kind="counter", name="kernel.multistep.staging")[before:]
    assert [s["k"] for s in staged] == [k]
    # stage s computes k - s rows beyond each side of the split y axis
    assert staged[0]["rows_computed"] == k * 16 + k * (k - 1)
    curr = shard_blocks(field, spec, mesh)
    nxt = shard_blocks(np.zeros_like(field), spec, mesh)
    for dispatch in (1, 2):
        curr, nxt = loop(curr, nxt, sel)
        got = unshard_blocks(curr, spec)
        want = jacobi_reference(field, (hot, cold), dispatch * k)
        np.testing.assert_allclose(got, want.astype(np.float32), rtol=1e-6,
                                   atol=1e-7, err_msg=f"dispatch {dispatch}")
