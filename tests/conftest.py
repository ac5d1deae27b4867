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

jax.config.update("jax_platforms", "cpu")

# float64 quantities are first-class in the reference (astaroth uses double)
jax.config.update("jax_enable_x64", True)
