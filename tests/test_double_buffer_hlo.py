"""The three application cells' real loops, compiled at their real sizes
for a described ``v5e:2x2`` topology: the optimized HLO holds no ``copy``
of a whole block. A ``while`` trip or a program that ends with its
ping-pong pair exchanged costs three such copies a step on the chip (24 for
Astaroth's eight fields); before ops/double_buffer.py these counted 3, 3
and 24 (PERF.md, PR 26). Nothing runs; a compile that passes is not a chip
result.

The topology is described inside a module-scoped fixture (the
on-chip-measurement guide, section 2): only the worker that gets this file
loads libtpu.
"""

import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

_COPY = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\S+)\s+copy\(", re.M)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: skip, do not fail
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def as_on_the_chip():
    """x64 off (the test session turns it on; no application enables it for
    fp32 fields, and Mosaic's lowering recurses without end under it) and no
    persistent cache (a described-device compile cannot be read back)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    x64 = jax.config.jax_enable_x64
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_x64", x64)
    jax.config.update("jax_enable_compilation_cache", cache)
    cc.reset_cache()


def _exchange(topo, n, radius, dim):
    """The tight-x exchange the applications realize on TPU devices."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.parallel import HaloExchange, grid_mesh

    d = Dim3(*dim)
    spec = GridSpec(Dim3(n * d.x, n * d.y, n * d.z), d,
                    Radius.constant(radius).without_x())
    return HaloExchange(spec, grid_mesh(d, list(topo.devices)[:d.flatten()]))


def _jacobi(dim, iters):
    def build(topo):
        from stencil_tpu.obs import scopes
        from stencil_tpu.ops.jacobi import make_jacobi_loop

        ex = _exchange(topo, 512, 1, dim)
        make_jacobi_loop(ex, iters)
        return scopes.JACOBI_LOOP, ex.spec

    return build


def _astaroth(topo):
    from stencil_tpu.apps.astaroth import DEFAULT_CONF
    from stencil_tpu.astaroth.config import load_config
    from stencil_tpu.astaroth.integrate import make_astaroth_step
    from stencil_tpu.obs import scopes

    info, _ = load_config(DEFAULT_CONF)
    ex = _exchange(topo, 256, 3, (1, 1, 1))
    make_astaroth_step(ex, info, iters=1)
    return scopes.ASTAROTH_ITER, ex.spec


LOOPS = {
    # the application's default 10 iterations a dispatch, and an odd count
    "jacobi512x4.weak.iters10": _jacobi((1, 2, 2), 10),
    "jacobi512x4.weak.iters11": _jacobi((1, 2, 2), 11),
    "jacobi512.steady.iters10": _jacobi((1, 1, 1), 10),
    "astaroth256.steady.iters1": _astaroth,
}


@pytest.mark.parametrize("name", sorted(LOOPS))
def test_no_whole_block_copy_in_the_compiled_loop(name, topo, as_on_the_chip):
    from stencil_tpu.obs import scopes

    scopes.clear()
    module, spec = LOOPS[name](topo)
    text = scopes.hlo_text(module)
    assert "tpu_custom_call" in text, "the Pallas path did not engage"
    p = spec.padded()
    block = f"{p.z},{p.y},{p.x}]"
    whole = [(instr, shape) for instr, shape in _COPY.findall(text)
             if block in shape]
    assert not whole, (
        f"{len(whole)} whole-block copies in {module}: {whole}")
