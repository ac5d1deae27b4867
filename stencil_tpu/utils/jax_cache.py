"""Where JAX's persistent compilation cache lives.

Called once by every process entry point that can hold the chip
(``chip_smoke.py``, the apps' ``main()``, the bench child,
``apps/serve.py``) — never at package import, so importing
``stencil_tpu`` configures nothing.

The directory is part of the cache key, so it must not move between runs:
it is ``JAX_COMPILATION_CACHE_DIR`` when the environment sets that (JAX
reads the variable itself, and this function then sets nothing in code),
and otherwise the fixed, git-ignored ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Place the persistent compile cache and return its directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
