"""Plain numpy statement of what a halo exchange must deliver: every held
cell (owned or halo; faces, edges, corners) of every quantity holds the
value of its periodically wrapped source coordinate, bit for bit. Imports
nothing of ``stencil_tpu``."""

from __future__ import annotations

import numpy as np

from benchmark import fields


def expected(seed: int, q: int, z, y, x, global_zyx, dtype=np.float32):
    """Value at (possibly out-of-range) global coordinates ``z, y, x``."""
    gz, gy, gx = global_zyx
    return fields.uniform(np, seed, q, np.mod(z, gz), np.mod(y, gy),
                          np.mod(x, gx)).astype(dtype)
