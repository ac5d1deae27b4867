"""Test configuration: run JAX on a virtual 8-device CPU mesh.

This is the TPU build's analogue of the reference's trick of running 2 MPI
ranks / multiple subdomains per GPU on one node to exercise distributed
paths without a cluster (reference: test/CMakeLists.txt:49,
test/test_exchange.cu:52). ``xla_force_host_platform_device_count=8`` gives
8 virtual devices so 2x2x2 meshes run anywhere.

Must set the env vars before JAX initializes.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax
import pytest

jax.config.update("jax_platforms", "cpu")

# float64 quantities are first-class in the reference (astaroth uses double)
jax.config.update("jax_enable_x64", True)


# The *_hlo tests compile a cell's program at its real size for a described
# TPU. Module-scoped (the on-chip-measurement guide, section 2): only a
# worker that gets such a file loads libtpu.


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
