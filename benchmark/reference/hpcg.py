"""HPCG's problem and algorithm: the plain reference.

``numpy`` float64 over whole arrays, a plane at a time in a few threads,
nothing of ``stencil_tpu``. Written from memory of HPCG 3.1 (Dongarra,
Heroux, Luszczek; ``github.com/hpcg-benchmark/hpcg``), file by file:

``src/GenerateProblem_ref.cpp``
    A global grid ``nx x ny x nz``; row i = point (ix, iy, iz) has the
    diagonal 26 and -1 for each of its up to 26 neighbours (``|dx|, |dy|,
    |dz| <= 1``) INSIDE the grid; a neighbour outside contributes nothing
    (homogeneous Dirichlet: boundary rows are shorter, the diagonal stays
    26). ``b_i = 26 - (neighbours of i)``, so the exact solution is all
    ones; ``x0 = 0``. (:func:`row_lengths`, :func:`rhs`, :func:`spmv`;
    :func:`matrix` builds the same rows as a sparse matrix, for the tests.)
``src/CG_ref.cpp``
    One iteration, k counted from 1 in a set: ``z = MG(r)``; ``rtz' =
    r.z``; k = 1: ``p = z``, else ``beta = rtz' / rtz``, ``p = z + beta
    p``; ``Ap = A p``; ``alpha = rtz' / (p.Ap)``; ``x += alpha p``; ``r -=
    alpha Ap``; ``normr = sqrt(r.r)``. (:func:`cg_iteration`.)
``src/main.cpp``
    Sets of 50 iterations with tolerance 0, x zeroed before each: a set
    starts from ``x = 0``, ``r = b``, ``normr0 = |b|``. (The restart is in
    :func:`cg_iteration`: a state that has made its 50 starts the next.)
``src/ComputeMG_ref.cpp``, ``ComputeRestriction_ref.cpp``,
``ComputeProlongation_ref.cpp``, ``GenerateCoarseProblem.cpp``
    ``numberOfMgLevels`` 4, each level half the one above an axis; ``x =
    0``; above the coarsest: one SYMGS, ``Axf = A x``, restriction by
    INJECTION ``rc[c] = r[f] - Axf[f]`` with the coarse point (ix, iy, iz)
    on the fine point (2ix, 2iy, 2iz), recurse, prolongation ``x[f] +=
    xc[c]`` on the same points, one SYMGS; on the coarsest one SYMGS.
    Every coarse operator is the SAME 27-point operator on the coarse
    grid. (:func:`mg`.)
``src/ComputeSYMGS_ref.cpp``
    A forward then a backward Gauss-Seidel sweep, in place: ``x_i <- (r_i
    + sum of x_j over the neighbours of i) / 26`` with the CURRENT ``x_j``.
    (:func:`symgs`.)

Departures from the source, each where it is made:

(a) precision: the source is double; this file computes in whatever
    ``dtype`` it is handed (float64 by default: the reference proper; the
    deployment runs float32, and the control hands in bfloat16).
(b) matrix-free: HPCG stores the matrix and forbids using its structure;
    :func:`spmv` and :func:`symgs` use it (so no number here is an HPCG
    rating). :func:`matrix` and :func:`cg_ref` are the stored-matrix form,
    for the tests.
(c) the sweep's ORDER: ``ComputeSYMGS_ref`` is lexicographic
    (:func:`cg_ref`); the deployment orders rows
    by eight colours, ``c = (ix mod 2) + 2 (iy mod 2) + 4 (iz mod 2)``,
    forward c = 0 .. 7, backward 7 .. 0, all rows of a colour at once (no
    two rows of a colour are neighbours): :func:`symgs`.
(d) the grid: one device holds 512^3 where HPCG's default is 104^3 a rank
    (nothing in this file depends on the size beyond its divisibility by
    8, for four levels).
(e) seeded data: :func:`seeded_state` makes a mid-set state (x, r, p, b
    dense, ``rtz`` > 0, k of 1 .. 48) from the benchmark's hash, so that
    the first iteration is a general one on every operator and level;
    :func:`rhs` with ``x = 0`` is HPCG's own problem.

``wrap_x`` (the fault, never the reference): every operator with its x
neighbours taken periodically, what a program computes that forms ``x -+
1`` by a lane roll and lets it wrap.
"""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

DIAGONAL = 26.0
LEVELS = 4                  # numberOfMgLevels
SET_ITERS = 50              # iterations a set (main.cpp)
COLOURS = 8
# operations an updated row of one Gauss-Seidel sweep: 26 additions (r and
# up to 26 neighbours, less one) and the division; HPCG counts 2 a nonzero
FLOPS_PER_ROW_SWEEP = 27
FLOPS_PER_ROW_SPMV = 27     # 26 additions and the diagonal's multiplication
_THREADS = 8

# the seeded state: the draw ``q`` of each array
DRAW_X, DRAW_R, DRAW_P, DRAW_B, DRAW_SCALARS = 0, 1, 2, 3, 4


def level_shapes(shape):
    """(nz, ny, nx) of the four levels, finest first."""
    shape = tuple(int(n) for n in shape)
    if any(n % (1 << (LEVELS - 1)) or n < (1 << LEVELS) for n in shape):
        raise ValueError(f"four levels halve {shape} three times: every "
                         f"axis a multiple of 8, 16 or more")
    return [tuple(n >> k for n in shape) for k in range(LEVELS)]


def colour_of(iz, iy, ix):
    return (ix % 2) + 2 * (iy % 2) + 4 * (iz % 2)


def _axis_count(n: int):
    c = np.full(n, 3, np.int64)
    c[0] -= 1
    c[-1] -= 1
    return c


def row_lengths(shape):
    """Nonzeros of every row, the diagonal included: 27 inside, 18 on a
    face, 12 on an edge, 8 at a corner."""
    nz, ny, nx = shape
    return (_axis_count(nz)[:, None, None] * _axis_count(ny)[None, :, None]
            * _axis_count(nx)[None, None, :])


def rhs(shape, dtype=np.float64):
    """``b_i = 26 - (neighbours of i)``: A applied to all ones."""
    return (DIAGONAL - (row_lengths(shape) - 1)).astype(dtype)


def _over_planes(make, planes) -> None:
    """``make()(z)`` for every z of ``planes``, in threads (numpy frees the
    lock inside its loops); the calls must not depend on each other.
    ``make`` is called once a thread and returns the plane's function with
    work arrays of its own: a temporary a numpy operation is a page-faulting
    allocation that the threads would queue for."""
    planes = list(planes)
    runs = ([planes] if len(planes) < 2 * _THREADS
            else [planes[i::_THREADS] for i in range(_THREADS)])

    def run(zs):
        fn = make()
        for z in zs:
            fn(z)

    if len(runs) == 1:
        run(runs[0])
        return
    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(run, runs))


def _ring(a, wrap_x: bool = False):
    """``a`` with one cell of zeros round it (the Dirichlet ring); with
    ``wrap_x`` the two x ghost columns hold the periodic wrap."""
    nz, ny, nx = a.shape
    out = np.zeros((nz + 2, ny + 2, nx + 2), a.dtype)

    def make():
        def plane(z):
            out[z + 1, 1:-1, 1:-1] = a[z]

        return plane

    _over_planes(make, range(nz))
    if wrap_x:
        _wrap_columns(out)
    return out


def _wrap_columns(p) -> None:
    p[..., 0] = p[..., -2]
    p[..., -1] = p[..., 1]


def _box9(w, y, out):
    """The 3 x 3 sums of a ringed plane ``w`` (ny + 2, nx + 2) into ``out``
    (ny, nx), by way of ``y`` (ny, nx + 2)."""
    np.add(w[:-2], w[1:-1], out=y)
    y += w[2:]
    np.add(y[:, :-2], y[:, 1:-1], out=out)
    out += y[:, 2:]
    return out


def spmv(x, wrap_x: bool = False):
    """``A x``: 26 x less the sum of the neighbours inside the grid."""
    xp = _ring(x, wrap_x)
    nz, ny, nx = x.shape
    out = np.empty_like(x)
    diag = x.dtype.type(DIAGONAL + 1)

    def make():
        w = np.empty((ny + 2, nx + 2), x.dtype)
        y = np.empty((ny, nx + 2), x.dtype)

        def plane(z):
            # all 27 of the box, then 27 x - box = 26 x - (the 26 others)
            np.add(xp[z], xp[z + 1], out=w)
            np.add(w, xp[z + 2], out=w)
            _box9(w, y, out[z])
            np.subtract(diag * x[z], out[z], out=out[z])

        return plane

    _over_planes(make, range(nz))
    return out


def symgs(x, r, wrap_x: bool = False):
    """One symmetric Gauss-Seidel sweep of ``A x = r`` in the EIGHT-COLOUR
    order (departure (c)): forward colours 0 .. 7, backward 7 .. 0, every
    row of a colour from the current values of its neighbours. Returns the
    new x (``x`` is not written).

    A colour's rows lie on planes of one z parity, and the planes of the
    other parity do not change while they are updated: so a sweep is two
    passes, and in a pass the neighbouring planes' part of a row's sum is
    taken once a plane, before its four in-plane colours."""
    xp = _ring(x, wrap_x)
    nz, ny, nx = x.shape
    diag = x.dtype.type(DIAGONAL)

    def half(parity: int, in_plane):
        def make():
            w = np.empty((ny + 2, nx + 2), x.dtype)
            y = np.empty((ny, nx + 2), x.dtype)
            fixed = np.empty((ny, nx), x.dtype)
            around = np.empty((ny // 2, nx // 2), x.dtype)

            def plane(z):
                np.add(xp[z], xp[z + 2], out=w)
                _box9(w, y, fixed)
                np.add(fixed, r[z], out=fixed)
                p = xp[z + 1]
                for c in in_plane:
                    cx, cy = c % 2, c // 2
                    up, mid, dn = (p[cy + d:ny + cy + d:2] for d in (0, 1, 2))
                    lo, at, hi = (slice(cx + d, nx + cx + d, 2)
                                  for d in (0, 1, 2))
                    np.add(up[:, lo], up[:, at], out=around)
                    for part in (up[:, hi], dn[:, lo], dn[:, at], dn[:, hi],
                                 mid[:, lo], mid[:, hi], fixed[cy::2, cx::2]):
                        np.add(around, part, out=around)
                    np.divide(around, diag, out=mid[:, at])
                    if wrap_x:
                        _wrap_columns(p)

            return plane

        _over_planes(make, range(parity, nz, 2))

    half(0, (0, 1, 2, 3))           # colours 0 .. 3
    half(1, (0, 1, 2, 3))           # colours 4 .. 7
    half(1, (3, 2, 1, 0))           # colours 7 .. 4
    half(0, (3, 2, 1, 0))           # colours 3 .. 0
    return xp[1:-1, 1:-1, 1:-1].copy()


def symgs_lexicographic(x, r):
    """``ComputeSYMGS_ref`` as the source has it: rows in their own order,
    forward then backward, one at a time (small grids only)."""
    xp = _ring(x)
    nz, ny, nx = x.shape
    rows = [(z, y, c) for z in range(nz) for y in range(ny)
            for c in range(nx)]
    for z, y, c in rows + rows[::-1]:
        around = xp[z:z + 3, y:y + 3, c:c + 3].sum() - xp[z + 1, y + 1, c + 1]
        xp[z + 1, y + 1, c + 1] = (r[z, y, c] + around) / DIAGONAL
    return xp[1:-1, 1:-1, 1:-1].copy()


def restrict(r, axf):
    """Injection: ``rc[c] = r[f] - Axf[f]``, f = 2c on every axis."""
    return r[::2, ::2, ::2] - axf[::2, ::2, ::2]


def prolong(x, xc):
    """``x[f] += xc[c]`` on the same points; returns the new x."""
    out = x.copy()
    out[::2, ::2, ::2] += xc
    return out


def mg(r, depth: int = 0, wrap_x: bool = False, smooth=None):
    """``ComputeMG_ref``: the V-cycle's answer to ``A z = r`` from z = 0.
    ``smooth(x, r)``: the smoother in :func:`symgs`'s place."""
    smooth = smooth or (lambda x, r: symgs(x, r, wrap_x))
    x = smooth(np.zeros_like(r), r)
    if depth < LEVELS - 1:
        xc = mg(restrict(r, spmv(x, wrap_x)), depth + 1, wrap_x, smooth)
        x = smooth(prolong(x, xc), r)
    return x


def dot(a, b):
    """A sum over EVERY row, accumulated in float64 and rounded to the
    arrays' precision."""
    total = np.zeros(a.shape[0])

    def make():
        def plane(z):
            total[z] = np.dot(a[z].astype(np.float64, copy=False).ravel(),
                              b[z].astype(np.float64, copy=False).ravel())

        return plane

    _over_planes(make, range(a.shape[0]))
    return a.dtype.type(total.sum())


def cg_iteration(state: dict, b, wrap_x: bool = False, smooth=None) -> dict:
    """One iteration of ``CG_ref`` from ``state`` (x, r, p; the scalars
    rtz, normr0 and k, the iterations this set has made): a state that has
    made its 50 starts the next set first (``x = 0``, ``r = b``, ``normr0
    = |b|``). Returns the new state, with alpha, beta and normr."""
    dtype = b.dtype.type
    x, r, p = state["x"], state["r"], state["p"]
    rtz, normr0, k = state["rtz"], state["normr0"], int(state["k"])
    if k >= SET_ITERS:
        x, r, k = np.zeros_like(b), b.copy(), 0
        normr0 = dtype(math.sqrt(float(dot(b, b))))
    z = mg(r, wrap_x=wrap_x, smooth=smooth)
    rtz_new = dot(r, z)
    if k == 0:
        beta, p = dtype(0), z
    else:
        # CG_ref's loop test (normr / normr0 > 0) ends a set at a residual
        # of exactly zero; the iterations after it change nothing
        beta = dtype(rtz_new / dtype(rtz)) if rtz != 0 else dtype(0)
        p = z + beta * p
    ap = spmv(p, wrap_x)
    pap = dot(p, ap)
    alpha = dtype(rtz_new / pap) if pap != 0 else dtype(0)
    x = x + alpha * p
    r = r - alpha * ap
    normr = dtype(math.sqrt(float(dot(r, r))))
    return {"x": x, "r": r, "p": p, "rtz": rtz_new, "normr": normr,
            "normr0": dtype(normr0), "k": k + 1, "alpha": alpha,
            "beta": beta}


def fresh_state(b) -> dict:
    """HPCG's own start: a state whose first iteration opens a set."""
    zero = np.zeros_like(b)
    return {"x": zero, "r": zero.copy(), "p": zero.copy(),
            "rtz": b.dtype.type(1), "normr": b.dtype.type(0),
            "normr0": b.dtype.type(0), "k": SET_ITERS}


# ------------------------------------------------------------ stored matrix


def matrix(shape):
    """``GenerateProblem_ref``'s matrix in CSR (scipy), rows in the grid's
    own order: for the tests, and for :func:`cg_ref`."""
    import scipy.sparse as sp

    nz, ny, nx = shape
    n = nz * ny * nx
    iz, iy, ix = np.meshgrid(np.arange(nz), np.arange(ny), np.arange(nx),
                             indexing="ij")
    rows, cols, vals = [], [], []
    for dz in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                jz, jy, jx = iz + dz, iy + dy, ix + dx
                ok = ((jz >= 0) & (jz < nz) & (jy >= 0) & (jy < ny)
                      & (jx >= 0) & (jx < nx))
                rows.append(((iz * ny + iy) * nx + ix)[ok])
                cols.append(((jz * ny + jy) * nx + jx)[ok])
                vals.append(np.full(int(ok.sum()), DIAGONAL
                                    if (dz, dy, dx) == (0, 0, 0) else -1.0))
    return sp.csr_matrix((np.concatenate(vals), (np.concatenate(rows),
                                                 np.concatenate(cols))),
                         shape=(n, n))


def solve(shape, iters: int = SET_ITERS, smooth=None, dtype=np.float64,
          sets: bool = True):
    """HPCG's own problem (``b = A 1``, ``x0 = 0``) for ``iters``
    iterations, in sets of 50 or (``sets`` false) as ONE solve however
    long. Returns ``(x, [normr after every iteration], normr0)``."""
    b = rhs(shape, dtype)
    state = fresh_state(b)
    norms = []
    for _ in range(iters):
        state = cg_iteration(state, b, smooth=smooth)
        norms.append(float(state["normr"]))
        if not sets:
            state["k"] = min(state["k"], SET_ITERS - 1)
    return state["x"], norms, float(state["normr0"])


def cg_ref(shape, iters: int = SET_ITERS):
    """``CG_ref`` whole on HPCG's own problem, as the source has it: the
    STORED matrix of every level and the LEXICOGRAPHIC Gauss-Seidel, a
    sweep two triangular solves (small grids: the matrices are built).
    Returns what :func:`solve` does."""
    import scipy.sparse as sp
    from scipy.sparse.linalg import spsolve_triangular

    shapes = level_shapes(shape)
    mats = [matrix(s) for s in shapes]
    lower = [sp.tril(m, format="csr") for m in mats]
    upper = [sp.triu(m, format="csr") for m in mats]
    strict_l = [sp.tril(m, k=-1, format="csr") for m in mats]
    strict_u = [sp.triu(m, k=1, format="csr") for m in mats]

    def smooth(x, r):
        level = shapes.index(x.shape)
        v, rhs_ = x.ravel(), r.ravel()
        v = spsolve_triangular(lower[level], rhs_ - strict_u[level] @ v,
                               lower=True)
        v = spsolve_triangular(upper[level], rhs_ - strict_l[level] @ v,
                               lower=False)
        return v.reshape(x.shape)

    return solve(shape, iters, smooth)


# ------------------------------------------------------------ seeded data


def from_uniform(xp, u):
    """A draw of [0, 1) as the seeded arrays hold it: [-1, 1)."""
    return u * xp.float32(2.0) - xp.float32(1.0)


def seeded_scalars(seed: int, shape):
    """``(rtz, k)`` of the seeded mid-set state. rtz = (rows / 72) (1/2 +
    u): what ``r.MG(r)`` comes to for r uniform in [-1, 1) (a variance of
    1/3 over a diagonal of about 24), times a draw, so that beta is of
    order one and the iteration moves x, r and p alike; k in 1 .. 48 (the
    first iteration is neither a set's first nor its last)."""
    from benchmark import fields

    zero = np.zeros(1, np.int64)
    u = fields.uniform(np, seed, DRAW_SCALARS, zero, zero, zero)
    v = fields.uniform(np, seed, DRAW_SCALARS, zero, zero, zero + 1)
    rows = int(shape[0]) * int(shape[1]) * int(shape[2])
    return (float(np.float32(rows / 72.0) * (np.float32(0.5) + u[0])),
            1 + int(v[0] * 48))


def seeded_array(seed: int, q: int, shape, dtype=np.float64):
    """Draw ``q`` over the whole grid, as the adapter seeds it on the
    device (bit for bit in float32)."""
    from benchmark import fields

    nz, ny, nx = shape
    out = np.empty(shape, dtype)
    y = np.arange(ny)[None, :, None]
    x = np.arange(nx)[None, None, :]

    def make():
        def plane(z):
            u = fields.uniform(np, seed, q, np.full((1, 1, 1), z), y, x)
            out[z] = from_uniform(np, u)[0].astype(dtype)

        return plane

    _over_planes(make, range(nz))
    return out


def seeded_state(seed: int, shape, dtype=np.float64):
    """``(state, b)``: departure (e)."""
    rtz, k = seeded_scalars(seed, shape)
    t = np.dtype(dtype).type
    state = {name: seeded_array(seed, q, shape, dtype)
             for name, q in (("x", DRAW_X), ("r", DRAW_R), ("p", DRAW_P))}
    state.update(rtz=t(rtz), normr=t(0), normr0=t(1), k=k)
    return state, seeded_array(seed, DRAW_B, shape, dtype)
