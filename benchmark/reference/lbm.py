"""D3Q19 lattice-Boltzmann, BGK: the plain reference.

``numpy`` float64 over whole periodic arrays, ``np.roll`` for the streaming,
19 explicit equilibria, no kernel, no halo, nothing of ``stencil_tpu``.

The lattice and the update are those of SPEC CPU2006 470.lbm (``lbm.c``,
``LBM_performStreamCollide``; Parboil's ``lbm`` and CPU2017's 519.lbm_r /
619.lbm_s are the same code) and of FluidX3D's ``D3Q19`` / ``SRT``
(``src/kernel.cpp``): velocities ``c_i`` and weights ``w_i``: the rest
vector, w = 1/3; the six axis vectors, w = 1/18; the twelve with exactly
two non-zero components, w = 1/36; no corner vector. The state is the
post-collision populations ``f_i(x)``. One step, pull form:

    g_i   = f_i(x - c_i)
    rho   = sum_i g_i
    rho u = sum_i c_i g_i
    e_i   = w_i rho (1 + 3 c_i.u + 9/2 (c_i.u)^2 - 3/2 u.u)
    f_i(x) <- g_i - omega (g_i - e_i),   omega = 1 / (3 nu + 1/2)

Mass ``sum rho`` and momentum ``sum rho u`` over the periodic box are
invariants of the exact update.

Departures from the sources, each on purpose: TWO lattices, as SPEC keeps
(``srcGrid`` / ``dstGrid`` and ``LBM_swapGrids``), not FluidX3D's in-place
streaming (Esoteric-Pull), which stores the same populations after the
same update in another order in memory; no ``rho`` / ``u`` arrays kept
from step to step (FluidX3D keeps them for its graphics and its
``UPDATE_FIELDS`` option; the update itself reads none of them); SPEC's
obstacle and acceleration cells do not exist in FluidX3D's benchmark set-up
(no boundary cells) and are left out.

Populations are in :data:`VELOCITIES`' order, which the program shares
(``stencil_tpu/ops/pallas_lbm.py``): the order is the one thing the two
must agree on to be compared, and the adapter checks it.
"""

from __future__ import annotations

import numpy as np

# c_i as (x, y, z)
VELOCITIES = (
    (0, 0, 0),
    (1, 0, 0), (-1, 0, 0), (0, 1, 0), (0, -1, 0), (0, 0, 1), (0, 0, -1),
    (1, 1, 0), (-1, -1, 0), (1, -1, 0), (-1, 1, 0),
    (1, 0, 1), (-1, 0, -1), (1, 0, -1), (-1, 0, 1),
    (0, 1, 1), (0, -1, -1), (0, 1, -1), (0, -1, 1),
)
Q = 19
WEIGHTS = (1 / 3,) + (1 / 18,) * 6 + (1 / 36,) * 12

# the seeded state (benchmark/apps/lbm.py): the uniform draw ``q`` of each
# part, its lowest value and its width
DRAW_RHO, DRAW_U, DRAW_EPS = 0, 1, 4            # rho; u_x, u_y, u_z; eps_i
RHO_RANGE = (0.9, 0.2)
U_RANGE = (-0.05, 0.1)
EPS_RANGE = (-0.01, 0.02)

# operations a cell a step, counted term by term from :func:`step` as it
# is written (an add, a multiply or a divide each one):
#   rho: 18 adds; rho u: 3 x (4 + 4 adds, 1 subtract) = 27; u: 1 divide and
#   3 multiplies = 4; u.u: 3 multiplies, 2 adds, then x 3/2 = 6;
#   c_i.u: 0 for the rest and the 6 axis vectors, 1 add for each of the 12
#   diagonals = 12; an equilibrium: 3 c.u, (c.u)^2, x 9/2, three adds or
#   subtracts inside the bracket, w_i rho (shared by a weight class: 3
#   multiplies in all) times the bracket = 7 each = 133, + 3;
#   the relaxation: g - e, x omega, g - that = 3 each = 57.
FLOPS_PER_CELL = 18 + 27 + 4 + 6 + 12 + 133 + 3 + 57


def omega_of(nu: float) -> float:
    return 1.0 / (3.0 * nu + 0.5)


def equilibrium(i: int, rho, ux, uy, uz):
    """``e_i`` in the precision of ``rho`` (an array, or a float for
    float64): the constants are rounded to it first, so a bfloat16 state
    gets bfloat16 arithmetic."""
    k = getattr(rho, "dtype", np.dtype(np.float64)).type
    cx, cy, cz = (k(c) for c in VELOCITIES[i])
    cu = cx * ux + cy * uy + cz * uz
    return k(WEIGHTS[i]) * rho * (
        k(1.0) + k(3.0) * cu + k(4.5) * cu * cu
        - k(1.5) * (ux * ux + uy * uy + uz * uz))


def moments(g):
    """(rho, rho u_x, rho u_y, rho u_z) of populations ``g[i]``."""
    rho = g[0]
    for i in range(1, Q):
        rho = rho + g[i]
    mom = []
    for a in range(3):
        m = None
        for i in range(Q):
            c = VELOCITIES[i][a]
            if c:
                term = g[i] if c > 0 else -g[i]
                m = term if m is None else m + term
        mom.append(m)
    return rho, mom[0], mom[1], mom[2]


def stream(f, edges: bool = True):
    """``g_i(x) = f_i(x - c_i)`` over whole periodic ``[z, y, x]`` arrays.
    ``edges=False`` is a fault to plant: a population that moves along y
    AND z pulls 0 where both wrap at once (the domain's four y-z edges: a
    program whose exchange left that edge halo unfilled)."""
    out = []
    for i, (cx, cy, cz) in enumerate(VELOCITIES):
        g = np.roll(f[i], (cz, cy, cx), axis=(0, 1, 2))
        if not edges and cy and cz:
            g = g.copy()
            g[0 if cz > 0 else -1, 0 if cy > 0 else -1, :] = 0
        out.append(g)
    return out


def collide(g, omega):
    rho, jx, jy, jz = moments(g)
    ux, uy, uz = jx / rho, jy / rho, jz / rho
    return [g[i] - omega * (g[i] - equilibrium(i, rho, ux, uy, uz))
            for i in range(Q)]


def step(f, omega, edges: bool = True):
    """One step of the 19 whole periodic arrays ``f[i][z, y, x]``, in the
    arrays' own precision (float64 for the reference; the control hands
    bfloat16 arrays and gets bfloat16 arithmetic)."""
    one = f[0].dtype.type
    return collide(stream(f, edges), one(omega))


def run(f, omega, steps: int, edges: bool = True):
    for _ in range(steps):
        f = step(f, omega, edges)
    return f


def invariants(f):
    """(mass, momentum x, y, z) summed over the box, in float64."""
    return tuple(float(np.sum(m, dtype=np.float64)) for m in moments(
        [np.asarray(a, np.float64) for a in f]))


# ------------------------------------------------------------ seeded state


def seeded_population(xp, c, w, rho_draw, u_draws, eps_draw):
    """One population of the seeded state from uniform draws in [0, 1)
    (arrays of one shape): ``rho`` in [0.9, 1.1), each component of ``u``
    in [-0.05, 0.05), the population at its equilibrium times ``1 + eps``,
    ``eps`` in [-0.01, 0.01), so that the first step's relaxation is not a
    no-op. ``c`` (x, y, z) and ``w`` are its velocity and weight, as
    float32 scalars. Written for any array module: the adapter makes the
    state on the device with ``jax.numpy`` and the reference the same cells
    with ``numpy``, both in the draws' float32 (a compiler may round a
    product's last bit otherwise)."""
    f32 = xp.float32
    rho = f32(RHO_RANGE[0]) + f32(RHO_RANGE[1]) * rho_draw
    u = [f32(U_RANGE[0]) + f32(U_RANGE[1]) * d for d in u_draws]
    usq = u[0] * u[0] + u[1] * u[1] + u[2] * u[2]
    cu = c[0] * u[0] + c[1] * u[1] + c[2] * u[2]
    e = w * rho * (f32(1.0) + f32(3.0) * cu + f32(4.5) * cu * cu
                   - f32(1.5) * usq)
    eps = f32(EPS_RANGE[0]) + f32(EPS_RANGE[1]) * eps_draw
    return e * (f32(1.0) + eps)


def seeded_box(uniform, seed: int, z, y, x):
    """The 19 seeded populations (float32) at the global cells ``z, y,
    x``; ``uniform(xp, seed, q, z, y, x)`` is the benchmark's hash."""
    rho = uniform(np, seed, DRAW_RHO, z, y, x)
    u = [uniform(np, seed, DRAW_U + a, z, y, x) for a in range(3)]
    return [seeded_population(
        np, [np.float32(v) for v in c], np.float32(w), rho, u,
        uniform(np, seed, DRAW_EPS + i, z, y, x))
        for i, (c, w) in enumerate(zip(VELOCITIES, WEIGHTS))]


def first_chunk_box(uniform, seed: int, origin, core, steps: int,
                    global_zyx, omega: float, dtype=np.float64,
                    edges: bool = True):
    """The 19 populations in the ``core`` cells at ``origin`` (global z, y,
    x of its first cell; the box wraps) after ``steps`` steps from the
    seeded state, computed on the core grown by ``steps`` cells a side: a
    step moves information one cell, so what the grown box's own wrap
    spoils never reaches the core. ``uniform(xp, seed, q, z, y, x)`` is the
    benchmark's hash (``fields.uniform``). A box that would overlap itself
    round the domain is refused. ``edges=False`` plants :func:`stream`'s
    fault on the DOMAIN's y-z edges, wherever the box lies."""
    margin = int(steps)
    g = [int(n) for n in global_zyx]
    if any(n + 2 * margin > m for n, m in zip(core, g)):
        raise ValueError(f"a core of {core} grown by {margin} does not fit "
                         f"a domain of {g}")
    raw = [np.arange(o - margin, o + n + margin)
           for o, n in zip(origin, core)]
    wrapped = [np.mod(r, m) for r, m in zip(raw, g)]
    z, y, x = np.meshgrid(*wrapped, indexing="ij")
    f = [a.astype(dtype) for a in seeded_box(uniform, seed, z, y, x)]
    one = f[0].dtype.type
    for _ in range(steps):
        g_i = stream(f)
        if not edges:
            # the planted fault follows the DOMAIN's y-z edges wherever the
            # box lies: a cell whose pull wraps the domain in y and in z
            for i, (_cx, cy, cz) in enumerate(VELOCITIES):
                if cy and cz:
                    at_z = wrapped[0] == (0 if cz > 0 else g[0] - 1)
                    at_y = wrapped[1] == (0 if cy > 0 else g[1] - 1)
                    g_i[i] = np.where(at_z[:, None, None] & at_y[None, :, None],
                                      one(0), g_i[i])
        f = collide(g_i, one(omega))
    crop = tuple(slice(margin, margin + n) for n in core)
    return [a[crop] for a in f]
