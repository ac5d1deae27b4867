"""Plain numpy reference of the jacobi3d application (reference:
bin/jacobi3d.cu): 7-point averaging in a periodic box with a hot and a cold
sphere held fixed. Imports nothing of ``stencil_tpu``.

It works on a box cut out of the global grid: the core plus a margin of one
cell per step, which the steps eat. ``dtype`` is float64 for the reference
and a lower precision for the control (state and arithmetic both).
"""

from __future__ import annotations

import numpy as np

from benchmark import fields

HOT, COLD = 1.0, 0.0


def sphere_codes(z, y, x, global_zyx):
    """0 stencil, 1 hot, 2 cold for broadcastable integer coordinates: the
    reference's integer-truncated float distance (bin/jacobi3d.cu:30-49),
    hot sphere at (X/3, Y/2, Z/2), cold at (2X/3, Y/2, Z/2), radius X/10."""
    gz, gy, gx = (int(v) for v in global_zyx)
    rad = gx // 10

    def dist(cx):
        d2 = (x - cx) ** 2 + (y - gy // 2) ** 2 + (z - gz // 2) ** 2
        return np.sqrt(d2.astype(np.float32)).astype(np.int64)

    hot = dist(gx // 3) <= rad
    cold = ~hot & (dist(gx * 2 // 3) <= rad)
    return np.where(hot, 1, np.where(cold, 2, 0)).astype(np.int32)


def sphere_surface_point(global_zyx):
    """A cell on the hot sphere's surface (global z, y, x)."""
    gz, gy, gx = (int(v) for v in global_zyx)
    return (gz // 2, gy // 2, gx // 3 + gx // 10)


def advance(box, codes, steps: int):
    """``steps`` sweeps of a (n+2*steps)^3 box; returns the n^3 core."""
    dt = box.dtype.type
    six = dt(6)
    for _ in range(steps):
        avg = (box[1:-1, 1:-1, :-2] + box[1:-1, 1:-1, 2:]
               + box[1:-1, :-2, 1:-1] + box[1:-1, 2:, 1:-1]
               + box[:-2, 1:-1, 1:-1] + box[2:, 1:-1, 1:-1]) / six
        codes = codes[1:-1, 1:-1, 1:-1]
        box = np.where(codes == 1, dt(HOT), np.where(codes == 2, dt(COLD), avg))
    return box


def box_after(seed: int, origin, core, steps: int, global_zyx,
              dtype=np.float64):
    """The core of one sampled box after ``steps`` iterations from the
    seeded state."""
    z, y, x = fields.box_coords(origin, core, steps, global_zyx)
    zz, yy, xx = z[:, None, None], y[None, :, None], x[None, None, :]
    start = fields.uniform(np, seed, 0, zz, yy, xx).astype(dtype)
    return advance(start, sphere_codes(zz, yy, xx, global_zyx), steps)
