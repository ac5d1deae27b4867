"""utils/sync.py tests — the scalar-fetch completion barrier (hard_sync) and
the one function that runs and times a chunk of a step loop (timed_chunk).

hard_sync is the timing discipline every bench app rides (fetch one
scalar, forcing completion of everything queued before it). Pinned here: it
works on bare arrays, on pytrees (first leaf in jax.tree order), and on
0-d leaves, and returns the fetched element as a float.
"""

import jax
import jax.numpy as jnp
import numpy as np

from stencil_tpu.utils.sync import SYNCS, hard_sync, timed_chunk


def test_scalar_fetch_returns_first_element():
    x = jnp.arange(12.0).reshape(3, 4) + 5.0
    assert hard_sync(x) == 5.0
    assert isinstance(hard_sync(x), float)


def test_forces_completion_of_queued_work():
    # the fetched value reflects the finished computation, not the input
    x = jnp.ones((8, 8))
    y = jax.jit(lambda a: a * 3 + 1)(x)
    assert hard_sync(y) == 4.0


def test_pytree_dict_uses_first_leaf():
    # jax.tree order for dicts is sorted keys: "a" is the first leaf
    tree = {"b": jnp.full((2, 2), 7.0), "a": jnp.full((3,), 2.0)}
    assert hard_sync(tree) == 2.0


def test_nested_pytree():
    tree = {"x": [jnp.array([[9.0, 1.0]]), jnp.zeros(4)], "y": jnp.ones(2)}
    assert hard_sync(tree) == 9.0


def test_zero_d_leaf():
    # a 0-d leaf has no indexable axes: the empty index tuple must work
    assert hard_sync(jnp.float32(3.5)) == 3.5
    assert hard_sync({"s": jnp.array(2.25)}) == 2.25


def test_sharded_stacked_array():
    # the shape the apps actually sync: a sharded stacked-block array
    from jax.sharding import NamedSharding

    from stencil_tpu.parallel.mesh import BLOCK_PSPEC, grid_mesh
    from stencil_tpu.geometry import Dim3

    mesh = grid_mesh(Dim3(2, 2, 2), jax.devices()[:8])
    arr = jax.device_put(
        jnp.full((2, 2, 2, 4, 4, 4), 1.5, jnp.float32),
        NamedSharding(mesh, BLOCK_PSPEC),
    )
    assert hard_sync(arr) == 1.5
    assert hard_sync({"q": arr}) == 1.5


def test_timed_chunk_marks_the_call_and_the_wait():
    # a loop that returns (curr, nxt): the wait is for the new state's leaf
    loop = jax.jit(lambda x: (x + 1, x))
    out, marks = timed_chunk("stencil_jacobi_loop", loop, jnp.zeros((4, 4)))
    assert hard_sync(out[0]) == 1.0 and marks.value == 1.0
    assert marks.sync == "hard_sync" and marks.module == "stencil_jacobi_loop"
    assert marks.enqueue_s > 0 and marks.wait_s > 0 and marks.t0_ns > 0
    assert marks.wall_s == marks.enqueue_s + marks.wait_s
    # a scalar of the result that the caller reads anyway
    out, marks = timed_chunk("m", lambda x: {"n": x.sum()}, jnp.ones(3),
                             scalar=lambda o: o["n"])
    assert (marks.value, marks.sync, marks.module) == (3.0, "scalar", "m")
    assert {"hard_sync", "scalar"} == set(SYNCS)
