"""The stencil and halo kernels of the four cells, compiled at their real
shapes for a described ``v5e:2x2`` topology: what libtpu's compiler would
refuse on the chip (VMEM limits, tile alignment) it refuses here, at no
chip time. Nothing runs; a compile that passes is not a chip result.

All in ONE file with the topology described inside a module-scoped fixture
(the on-chip-measurement guide, section 2): only the worker that gets this
file loads libtpu.
"""

import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: skip, do not fail
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def as_on_the_chip():
    """The process settings of a chip run. x64 off: the test session turns
    it on, and Mosaic's lowering of these kernels' index arithmetic then
    recurses without end; no application enables it for fp32 fields. No
    persistent cache: a described-device compile is written to it but
    cannot be read back."""
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


def _spec(n, radius, tight_x, dim=(1, 1, 1)):
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3, Radius

    r = Radius.constant(radius)
    return GridSpec(Dim3(n * dim[0], n * dim[1], n * dim[2]), Dim3(*dim),
                    r.without_x() if tight_x else r)


def _block(spec, sharding, dtype="float32"):
    import jax

    return jax.ShapeDtypeStruct(spec.block_shape_zyx(), dtype,
                                sharding=sharding)


def _jacobi_multistep(sh):
    from stencil_tpu.ops.pallas_stencil import make_pallas_jacobi_multistep

    spec = _spec(512, 1, True)
    # a default 10-iteration dispatch compiles depth 10 (PR 24, chip run)
    return make_pallas_jacobi_multistep(spec, 10), (_block(spec, sh),) * 2


def _jacobi_sweep(sh):
    from stencil_tpu.ops.pallas_stencil import (make_pallas_jacobi_sweep,
                                                sel_z_range)

    spec = _spec(512, 1, True, dim=(1, 2, 2))     # the four-chip cell's block
    fn = make_pallas_jacobi_sweep(spec, sel_z_range(spec),
                                  wrap=(False, False, True))
    return fn, (_block(spec, sh), _block(spec, sh), _block(spec, sh, "int32"))


def _self_fill(axis, n, nq, tight_x):
    def build(sh):
        from stencil_tpu.ops.halo_fill import make_self_fill

        spec = _spec(n, 3, tight_x)
        return make_self_fill(spec, axis, nq=nq), (_block(spec, sh),) * nq

    return build


def _astaroth_substep(s):
    def build(sh):
        from stencil_tpu.apps.astaroth import DEFAULT_CONF
        from stencil_tpu.astaroth.config import load_config
        from stencil_tpu.astaroth.equations import Constants
        from stencil_tpu.ops.pallas_astaroth import make_pallas_substep

        info, _ = load_config(DEFAULT_CONF)
        inv = tuple(info.real_params[f"AC_inv_ds{a}"] for a in "xyz")
        spec = _spec(256, 3, True)
        fn = make_pallas_substep(spec, Constants.from_info(info), inv, s, 1e-8)
        eight = (_block(spec, sh),) * 8
        return fn, (eight, eight)

    return build


KERNELS = {
    "jacobi512.multistep_k10": _jacobi_multistep,
    "jacobi512x4.sweep": _jacobi_sweep,
    "exchange512.self_fill_x": _self_fill("x", 512, 4, False),
    "exchange512.self_fill_y": _self_fill("y", 512, 4, False),
    "exchange512.self_fill_z": _self_fill("z", 512, 4, False),
    "astaroth256.self_fill_y": _self_fill("y", 256, 8, True),
    "astaroth256.self_fill_z": _self_fill("z", 256, 8, True),
    "astaroth256.substep0": _astaroth_substep(0),
    "astaroth256.substep1": _astaroth_substep(1),
    "astaroth256.substep2": _astaroth_substep(2),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, as_on_the_chip):
    import jax

    fn, shapes = KERNELS[name](one_chip)
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
