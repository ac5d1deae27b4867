"""Which permutes wait for which, in the steps three four-chip cells
dispatch, lowered at their real sizes for a described ``v5e:2x2`` (the
StableHLO through ``utils/hlo_check.build_graph``; nothing is compiled and
nothing runs): ``iso3dfd1024x4.steady`` holds ONE permute an axis (every
axis fixed, two blocks: a block has one neighbour) and, its plan being a
star's, neither consumes the other's result; ``astaroth256x4.weak`` and
``jacobi512x4.weak`` are periodic rings of two and keep two an axis, which
since PR 36 are independent of each other, while z's still consume y's
(their slabs carry the y halos into edges and corners). The counter
``halo.wire_schedule`` of each build says the same.

The topology is described inside a module-scoped fixture (the
on-chip-measurement guide, section 2): only the worker that gets this file
loads libtpu.
"""

import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _iso3dfd(topo):
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.obs import scopes
    from stencil_tpu.ops.iso3dfd import make_iso3dfd_step
    from stencil_tpu.parallel import HaloExchange, grid_mesh

    d = Dim3(1, 2, 2)
    spec = GridSpec(Dim3(1008, 1008, 2032), d,
                    Radius.face_edge_corner(8, 0, 0))
    ex = HaloExchange(spec, grid_mesh(d, list(topo.devices)[:4]),
                      periodic=(False,) * 3, faces_only=True)
    make_iso3dfd_step(ex, iters=1)
    return scopes.ISO3DFD_LOOP


def _tight(topo, n, radius):
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.parallel import HaloExchange, grid_mesh

    d = Dim3(1, 2, 2)
    spec = GridSpec(Dim3(n * d.x, n * d.y, n * d.z), d,
                    Radius.constant(radius).without_x())
    return HaloExchange(spec, grid_mesh(d, list(topo.devices)[:4]))


def _astaroth(topo):
    from stencil_tpu.apps.astaroth import DEFAULT_CONF
    from stencil_tpu.astaroth.config import load_config
    from stencil_tpu.astaroth.integrate import make_astaroth_step
    from stencil_tpu.obs import scopes

    info, _ = load_config(DEFAULT_CONF)
    make_astaroth_step(_tight(topo, 256, 3), info, dt=1e-8, dtype="float32",
                       iters=1)
    return scopes.ASTAROTH_ITER


def _jacobi(topo):
    from stencil_tpu.geometry import Dim3
    from stencil_tpu.obs import scopes
    from stencil_tpu.ops.jacobi import make_jacobi_loop
    from stencil_tpu.ops.pallas_stencil import pick_temporal_depth

    k = pick_temporal_depth(Dim3(512, 1024, 1024), Dim3(1, 2, 2), 10)[0]
    assert k == 10
    make_jacobi_loop(_tight(topo, 512, k), 10, temporal_k=k)
    return scopes.JACOBI_LOOP


# builder, and what the step holds: permutes an axis, whether one carrier
# went both ways, waves
STEPS = {"iso3dfd1024x4": (_iso3dfd, 1, True, 1),
         "astaroth256x4": (_astaroth, 2, False, 2),
         "jacobi512x4": (_jacobi, 2, False, 2)}


@pytest.fixture(scope="module", params=sorted(STEPS))
def step(request, topo, as_on_the_chip):
    """The lowered step: for every permute the permutes its operands reach
    (in the program's order), and the wire schedules its lowering
    recorded."""
    from stencil_tpu.obs import scopes, telemetry
    from stencil_tpu.utils.hlo_check import _closure, build_graph

    def schedules():
        return telemetry.get().records(kind="counter",
                                       name="halo.wire_schedule")

    scopes.clear()
    module = STEPS[request.param][0](topo)
    rec = scopes._registry[module][-1]
    before = len(schedules())
    graph = build_graph(rec["fn"].lower(*rec["args"]).as_text())
    permutes = [k for k, (op, _) in graph.items()
                if "collective_permute" in op]
    return {"name": request.param,
            "consumes": [_closure(graph, graph[p][1]).intersection(permutes)
                         for p in permutes],
            "permutes": permutes, "schedules": schedules()[before:]}


def test_an_axis_permutes_are_independent_and_z_waits_for_y_unless_a_star(
        step):
    _build, per_axis, _merged, waves = STEPS[step["name"]]
    permutes, consumes = step["permutes"], step["consumes"]
    assert len(permutes) == 2 * per_axis          # y and z
    y, z = permutes[:per_axis], permutes[per_axis:]
    for reached in consumes[:per_axis]:
        assert reached == set()
    for reached in consumes[per_axis:]:
        # the star's z slab is cut to the compute rows: nothing of y's in it
        assert reached == (set(y) if waves == 2 else set())
    assert not set(z) & set().union(*consumes)


def test_the_counter_says_what_the_lowered_step_holds(step):
    _build, per_axis, merged, waves = STEPS[step["name"]]
    assert step["schedules"], "the lowering built no exchange body"
    for said in step["schedules"]:
        assert said["phases"] == [
            {"axis": axis, "permutes": per_axis, "merged": merged}
            for axis in ("y", "z")]
        assert said["waves"] == waves
        assert said["value"] == len(step["permutes"])
    depth = 1 + max(len(reached) > 0 for reached in step["consumes"])
    assert depth == waves
