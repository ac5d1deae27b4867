"""Ping-pong buffers keep their slots (ops/double_buffer.py): whatever the
parity of the steps a compiled program runs, the builders' contract holds
(``loop(curr, nxt, ...) -> (new_curr, new_nxt)``, new state first), the
fields are bit-identical to the same number of single steps, and the
``loop.pingpong`` counter says what was built."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu.domain.grid import GridSpec
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.obs import scopes, telemetry
from stencil_tpu.ops import double_buffer
from stencil_tpu.ops.jacobi import (jacobi_reference, make_jacobi_loop,
                                    make_jacobi_step, sphere_masks, sphere_sel)
from stencil_tpu.parallel import HaloExchange, grid_mesh
from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks


def _pingpong_records(build):
    """What ``build()`` returns, and the ``loop.pingpong`` counters it
    recorded."""
    rec = telemetry.get()
    seen = {id(r) for r in rec.records()}
    out = build()
    return out, [r for r in rec.records(name="loop.pingpong")
                 if id(r) not in seen]


def _expect_counter(records, module, counts):
    steps = sum(counts)
    trips = sum(n // 2 for n in counts)
    assert len(records) == 1, "one loop.pingpong counter per build"
    got = records[0]
    assert telemetry.validate_record(got) == []
    want = dict(module=module, steps=steps, steps_per_trip=2, trips=trips,
                tail_steps=steps - 2 * trips, host_swap=bool(steps % 2))
    assert {k: got[k] for k in want} == want


# -- the helper alone ---------------------------------------------------------


def _toy_step(pair):
    """An exchanging step: the new state is written over ``nxt``."""
    curr, nxt = pair
    return nxt * 0 + curr * 2 + 1, curr


@pytest.mark.parametrize("n", [0, 1, 2, 3, 4, 5, 10, 11])
def test_repeat_equals_n_steps_and_loops_in_pairs(n):
    a = jnp.arange(4.0)
    b = jnp.full(4, -1.0)
    got = jax.jit(lambda a, b: double_buffer.repeat(_toy_step, n, (a, b)))(a, b)
    want = (a, b)
    for _ in range(n):
        want = _toy_step(want)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    # a loop only for two trips or more (a static fori_loop traces as scan)
    jaxpr = str(jax.make_jaxpr(
        lambda a, b: double_buffer.repeat(_toy_step, n, (a, b)))(a, b))
    assert ("scan" in jaxpr or "while" in jaxpr) == (n // 2 >= 2)
    assert jaxpr.count("length=") == (n // 2 >= 2) and (
        n // 2 < 2 or f"length={n // 2}" in jaxpr)


@pytest.mark.parametrize("counts", [(1,), (2,), (3,), (2, 1), (3, 1, 1)])
def test_jit_in_place_keeps_slots_and_swaps_on_the_host(counts):
    def fn(curr, nxt, gain):
        pair = (curr, nxt)
        for n in counts:
            pair = double_buffer.repeat(
                lambda p: (p[1] * 0 + p[0] * gain, p[0]), n, pair)
        return pair

    like = jax.ShapeDtypeStruct((4,), jnp.float32)
    scopes.clear()
    loop, records = _pingpong_records(lambda: double_buffer.jit_in_place(
        scopes.JACOBI_LOOP, fn, (like, like, like), counts))
    _expect_counter(records, scopes.JACOBI_LOOP, counts)
    steps = sum(counts)
    assert loop.host_swap == bool(steps % 2)
    # the jitted program is what the registry holds, under its module name
    assert scopes.registered(scopes.JACOBI_LOOP) == 1
    text = loop.lower(like, like, like).as_text()
    assert "jit_" + scopes.JACOBI_LOOP in text
    curr = jnp.arange(4, dtype=jnp.float32)
    nxt = jnp.zeros(4, jnp.float32)
    gain = jnp.full(4, 3, jnp.float32)
    # the program returns the buffers where they came in; the loop returns
    # the new state first
    first, second = loop.program(curr + 0, nxt + 0, gain)
    new_curr, new_nxt = loop(curr, nxt, gain)
    np.testing.assert_array_equal(new_curr, np.arange(4) * 3.0 ** steps)
    np.testing.assert_array_equal(new_nxt, np.arange(4) * 3.0 ** (steps - 1))
    if steps % 2:
        np.testing.assert_array_equal(second, new_curr)
    else:
        np.testing.assert_array_equal(first, new_curr)


# -- jacobi -------------------------------------------------------------------

_PATHS = {
    "xla": dict(use_pallas=False),
    "pallas": dict(use_pallas=True, interpret=True),
}
_MESHES = {"block": Dim3(1, 1, 1), "mesh122": Dim3(1, 2, 2)}
_SIZE = Dim3(16, 16, 12)


@pytest.fixture(scope="module")
def jacobi_case():
    """Per (path, mesh): the exchange, the inputs and the fields after 1 to
    11 calls of ``make_jacobi_step``, built once."""
    cache = {}

    def get(path, mesh_name):
        key = (path, mesh_name)
        if key not in cache:
            spec = GridSpec(_SIZE, _MESHES[mesh_name], Radius.constant(1))
            mesh = grid_mesh(spec.dim, jax.devices()[:spec.dim.flatten()])
            ex = HaloExchange(spec, mesh)
            field = np.random.RandomState(26).rand(
                _SIZE.z, _SIZE.y, _SIZE.x).astype(np.float32)
            sel = shard_blocks(sphere_sel(_SIZE), spec, mesh)

            def fresh():
                return (shard_blocks(field, spec, mesh),
                        shard_blocks(np.zeros_like(field), spec, mesh))

            step, records = _pingpong_records(
                lambda: make_jacobi_step(ex, **_PATHS[path]))
            _expect_counter(records, scopes.JACOBI_STEP, (1,))
            curr, nxt = fresh()
            after = {}
            for i in range(1, 12):
                curr, nxt = step(curr, nxt, sel)
                after[i] = unshard_blocks(curr, spec)
            cache[key] = dict(spec=spec, ex=ex, field=field, sel=sel,
                              fresh=fresh, after=after)
        return cache[key]

    return get


def _check_loop(case, loop, iters):
    spec = case["spec"]
    curr, nxt = case["fresh"]()
    lowered = loop.lower(curr, nxt, case["sel"])
    assert "jit_" + scopes.JACOBI_LOOP in lowered.as_text()
    out = loop(curr, nxt, case["sel"])
    assert isinstance(out, tuple) and len(out) == 2
    new_curr, new_nxt = out
    assert new_curr.shape == new_nxt.shape == curr.shape
    assert curr.is_deleted() and nxt.is_deleted(), "inputs are donated"
    got = unshard_blocks(new_curr, spec)
    np.testing.assert_array_equal(got, case["after"][iters])
    want = jacobi_reference(case["field"], sphere_masks(_SIZE), iters)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-6)
    # the second array is a usable scratch: one more dispatch stays right
    if iters + iters <= 11:
        again, _ = loop(new_curr, new_nxt, case["sel"])
        np.testing.assert_array_equal(unshard_blocks(again, spec),
                                      case["after"][2 * iters])


@pytest.mark.parametrize("iters", [1, 2, 3, 10, 11])
@pytest.mark.parametrize("mesh_name", sorted(_MESHES))
@pytest.mark.parametrize("path", sorted(_PATHS))
def test_jacobi_loop_equals_single_steps(jacobi_case, path, mesh_name, iters):
    case = jacobi_case(path, mesh_name)
    # temporal_k=1: the per-step loop on every path (the multistep cases
    # are below)
    loop, records = _pingpong_records(lambda: make_jacobi_loop(
        case["ex"], iters, temporal_k=1, **_PATHS[path]))
    _expect_counter(records, scopes.JACOBI_LOOP, (iters,))
    _check_loop(case, loop, iters)


@pytest.mark.parametrize("iters,counts", [
    (4, (2,)),            # two passes of k=2: one trip, no host swap
    (6, (3,)),            # three passes: one trip and the odd pass
    (5, (2, 1)),          # two passes and a single step
    (7, (3, 1)),          # three passes and a single step
    (8, (4,)),            # two while trips
])
def test_jacobi_multistep_loop_equals_single_steps(jacobi_case, iters, counts):
    case = jacobi_case("pallas", "block")
    loop, records = _pingpong_records(lambda: make_jacobi_loop(
        case["ex"], iters, temporal_k=2, use_pallas=True, interpret=True))
    _expect_counter(records, scopes.JACOBI_LOOP, counts)
    _check_loop(case, loop, iters)


# -- astaroth -----------------------------------------------------------------

_N = 8


@pytest.fixture(scope="module")
def astaroth_case():
    """Per swap mode: the exchange, the inputs and the fields after 1 to 4
    calls of the ``iters=1`` step, built once. One block, XLA path, no
    overlap: the cheapest program that still ends every iteration with the
    pair exchanged."""
    from stencil_tpu.apps.astaroth import DEFAULT_CONF
    from stencil_tpu.astaroth import config as ac_config
    from stencil_tpu.astaroth.integrate import FIELDS, make_astaroth_step

    info = ac_config.AcMeshInfo()
    with open(DEFAULT_CONF) as f:
        ac_config.parse_config(f.read(), info)
    for a in "xyz":
        info.int_params[f"AC_n{a}"] = _N
    info.update_builtin_params()
    spec = GridSpec(Dim3(_N, _N, _N), Dim3(1, 1, 1), Radius.constant(3))
    mesh = grid_mesh(spec.dim, jax.devices()[:1])
    ex = HaloExchange(spec, mesh)
    rng = np.random.RandomState(26)
    fields = {k: (rng.randn(_N, _N, _N) * 0.05).astype(np.float32)
              for k in FIELDS}
    fields["lnrho"] += np.float32(0.5)

    def fresh():
        zeros = np.zeros((_N, _N, _N), np.float32)
        return ({k: shard_blocks(fields[k], spec, mesh) for k in FIELDS},
                {k: shard_blocks(zeros, spec, mesh) for k in FIELDS})

    def build(swap_per_substep, iters):
        return _pingpong_records(lambda: make_astaroth_step(
            ex, info, dt=1e-3, overlap=False, use_pallas=False,
            swap_per_substep=swap_per_substep, iters=iters, dtype="float32"))

    cache = {}

    def get(swap_per_substep):
        if swap_per_substep not in cache:
            step, records = build(swap_per_substep, 1)
            _expect_counter(records, scopes.ASTAROTH_ITER, (1,))
            curr, out = fresh()
            after = {}
            for i in range(1, 5):
                curr, out = step(curr, out)
                after[i] = {k: unshard_blocks(curr[k], spec) for k in FIELDS}
            cache[swap_per_substep] = after
        return dict(after=cache[swap_per_substep], build=build, fresh=fresh,
                    spec=spec, fields=FIELDS)

    return get


@pytest.mark.parametrize("iters,swap_per_substep", [
    (1, False), (2, False), (3, False), (4, False),   # 4: two while trips
    (1, True), (2, True), (3, True),
])
def test_astaroth_iters_equal_single_iterations(astaroth_case, iters,
                                                swap_per_substep):
    case = astaroth_case(swap_per_substep)
    step, records = case["build"](swap_per_substep, iters)
    _expect_counter(records, scopes.ASTAROTH_ITER, (iters,))
    curr, out = case["fresh"]()
    assert ("jit_" + scopes.ASTAROTH_ITER
            in step.lower(curr, out).as_text())
    result = step(curr, out)
    assert isinstance(result, tuple) and len(result) == 2
    new_curr, new_out = result
    assert set(new_curr) == set(new_out) == set(case["fields"])
    assert all(a.is_deleted() for a in (*curr.values(), *out.values()))
    for k in case["fields"]:
        np.testing.assert_array_equal(
            unshard_blocks(new_curr[k], case["spec"]),
            case["after"][iters][k], err_msg=k)
