"""Plain numpy reference of NAS Parallel Benchmarks 3.x, kernel MG
(``NPB3.x-SER/MG/mg.f``; Bailey et al., "The NAS Parallel Benchmarks",
RNR-94-007, section 2.3): V-cycle multigrid for ``lap(u) = v`` on a periodic
cube of ``n = 2^lt`` cells an axis, levels ``n, n/2, ..., 2``. Imports
nothing of ``stencil_tpu``.

Four operators, each a 27-point box whose weight depends only on the
neighbour's class (0 the centre, 1 the six faces, 2 the twelve edges, 3 the
eight corners):

    resid    r = v - A u            A = (-8/3, 0, 1/6, 1/12)
    psinv    u = u + S r            S = (-3/8, 1/32, -1/64, 0) for classes
                                    S, W, A; (-3/17, 1/33, -1/61, 0) from B
    rprj3    coarse c sits on fine 2c + 1 and takes the full weighting
             (1/2, 1/4, 1/8, 1/16) of the 27 fine cells about it
    interp   trilinear prolongation ADDED to the fine u: fine 2c + 1 takes
             coarse c, fine 2c the mean of coarse c - 1 and c, on each axis
             (weights 1, 1/2, 1/4, 1/8)

(0-based cells of the periodic cube; ``mg.f`` writes the same as ``i = 2j -
1`` over 1-based arrays that hold one ghost cell a side.) One iteration is
``mg3P`` then ``resid``: 34 operator calls at nine levels, each followed in
the source by ``comm3``, the periodic ghost exchange, which a numpy array
that wraps its own indices does not need.

Every operator is written as its explicit terms, one a neighbour, summed
class by class and weighted once a class; the source's partial sums (the
y-z plane's faces and diagonals summed once and combined along x) are the
program's business, not the reference's. A class whose weight is 0 (A's
faces, S's corners) is left out, as the source leaves it out.

Departures, none in the arithmetic of an operator:

- arrays are indexed ``[z, y, x]`` and hold no ghost cells: an operator
  takes an array ALREADY grown by one cell a side (``grow`` wraps a whole
  periodic level; a box cut out of a level brings its margin) and returns
  it shrunk, so that the same function serves a whole level and a box;
- a level's planes are shared out over threads (numpy frees the
  interpreter in its loops), which changes no term and no order of terms;
- the benchmark's data is seeded (``seeded_*``): u and r dense in [-1, 1)
  from ``fields.uniform``, v = +1 at ten cells and -1 at ten drawn from
  the seed. The source's own data (``zran3``: u = 0, v = +1 at the ten
  largest and -1 at the ten smallest values of a field of its linear
  congruential generator ``x <- 5^13 x mod 2^46`` started at 314159265,
  cell (i1, i2, i3) taking draw ``1 + i1 + n i2 + n^2 i3``) is here too;
- ``dtype`` is float64 for the reference and a lower precision for the
  control (state and arithmetic both). ``corners=False`` is the second
  control: ``resid`` and ``rprj3`` with their corner weights left out.
"""

from __future__ import annotations

import itertools
import math
import os
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import fields

A = (-8.0 / 3.0, 0.0, 1.0 / 6.0, 1.0 / 12.0)
S_SMALL = (-3.0 / 8.0, 1.0 / 32.0, -1.0 / 64.0, 0.0)       # classes S, W, A
S_LARGE = (-3.0 / 17.0, 1.0 / 33.0, -1.0 / 61.0, 0.0)      # class B and up
RESTRICT = (1.0 / 2.0, 1.0 / 4.0, 1.0 / 8.0, 1.0 / 16.0)
PROLONG = (1.0, 1.0 / 2.0, 1.0 / 4.0, 1.0 / 8.0)
# class -> (cells an axis, iterations, the smoother's weights, the L2 norm
# of the last residual that npbparams.h / verify hold the run to, 1e-8).
# Class S is held by tests/test_mg.py; the others are written from memory.
CLASSES = {
    "S": (32, 4, S_SMALL, 0.5307707005734e-04),
    "W": (128, 4, S_SMALL, 0.6467329375339e-05),
    "A": (256, 4, S_SMALL, 0.2433365309069e-05),
    "B": (256, 20, S_LARGE, 0.1800564401355e-05),
    "C": (512, 20, S_LARGE, 0.5706732285740e-06),
    "D": (1024, 50, S_LARGE, 0.1583275060440e-09),
}
VERIFY_EPSILON = 1e-8
LCG_A = 5 ** 13
LCG_SEED = 314159265
LCG_MOD = 1 << 46
CHARGES = 10                      # of each sign
# NPB's own count: 58 operations a finest-level cell an iteration
# (mg.f: ``nn * 58 * nit`` over the time), all levels included
FLOPS_PER_CELL_ITER = 58
# ... shared out over the operators by their additions and multiplications
# as mg.f writes them (partial sums): resid 15 and psinv 16 a cell of their
# level, rprj3 24 and interp 23 a COARSE cell; 2 x 15 + 16 + 3 + 2.9 at the
# top and an eighth of 15 + 16 + 3 + 2.9 more a level down is 57
FLOPS_RESID, FLOPS_PSINV, FLOPS_RPRJ3, FLOPS_INTERP = 15, 16, 24, 23

_OFFSETS = [[d for d in itertools.product((-1, 0, 1), repeat=3)
             if sum(map(abs, d)) == k] for k in range(4)]
_THREADS = max(1, min(16, (os.cpu_count() or 2) - 1))
_PARALLEL_FROM = 1 << 18          # cells of a result worth sharing out
_RUN_CELLS = 1 << 21              # cells of one run of planes
_in_run = threading.local()


def levels(n: int):
    """The cells an axis of every level, finest first: n, n/2, ..., 2."""
    lt = int(round(math.log2(n)))
    if n < 2 or 1 << lt != n:
        raise ValueError(f"MG takes a power of two of at least 2, not {n}")
    return [n >> k for k in range(lt)]


def grow(a):
    """A whole periodic level grown by one wrapped cell a side."""
    return np.pad(a, 1, mode="wrap")


def _weights(w, dtype):
    return [np.dtype(dtype).type(x) for x in w]


def _planes(n_out: int, cells: int, fn):
    """``fn(z0, z1)`` over runs of result planes, in threads where the
    result is large (and the caller is not itself such a run); the parts
    joined along z. A run holds ``_RUN_CELLS`` cells at most, so that an
    operator's temporaries stay small."""
    if cells < _PARALLEL_FROM or n_out < 2 or getattr(_in_run, "on", False):
        return fn(0, n_out)
    step = max(1, min(-(-n_out // _THREADS), _RUN_CELLS * n_out // cells))
    runs = [(z, min(z + step, n_out)) for z in range(0, n_out, step)]

    def one(run):
        _in_run.on = True
        try:
            return fn(*run)
        finally:
            _in_run.on = False

    with ThreadPoolExecutor(_THREADS) as pool:
        return np.concatenate(list(pool.map(one, runs)))


def box27(q, w):
    """``sum_d w[class(d)] q[i + d]`` over the 27 offsets, for every cell
    of ``q`` that has all its neighbours: the result is ``q`` less one cell
    a side. One explicit term a neighbour, summed class by class."""
    w = _weights(w, q.dtype)
    nz, ny, nx = (m - 2 for m in q.shape)

    def part(z0, z1):
        out = None
        for k, offsets in enumerate(_OFFSETS):
            if w[k] == 0:
                continue
            acc = None
            for dz, dy, dx in offsets:
                term = q[1 + dz + z0:1 + dz + z1, 1 + dy:1 + dy + ny,
                         1 + dx:1 + dx + nx]
                acc = term if acc is None else acc + term
            acc = w[k] * acc
            out = acc if out is None else out + acc
        return out

    return _planes(nz, nz * ny * nx, part)


def _inner(a):
    return a[1:-1, 1:-1, 1:-1]


def resid(u, v, corners: bool = True):
    """``v - A u``: ``u`` grown by one cell a side, ``v`` not."""
    a = A if corners else A[:3] + (0.0,)
    return v - box27(u, a)


def psinv(r, u, s):
    """``u + S r``: ``r`` grown by one cell a side, ``u`` not."""
    return u + box27(r, s)


def rprj3(r, corners: bool = True):
    """Full weighting of a whole level: ``r`` is the fine level grown by
    one cell a side (n + 2), the result the coarse level (n / 2), coarse
    cell c centred on fine cell 2c + 1."""
    w = _weights(RESTRICT if corners else RESTRICT[:3] + (0.0,), r.dtype)
    n = [m - 2 for m in r.shape]
    mz, my, mx = (m // 2 for m in n)

    def part(z0, z1):
        out = None
        for k, offsets in enumerate(_OFFSETS):
            if w[k] == 0:
                continue
            acc = None
            for dz, dy, dx in offsets:
                term = r[2 + dz + 2 * z0:2 + dz + 2 * z1:2,
                         2 + dy:2 + dy + n[1]:2, 2 + dx:2 + dx + n[2]:2]
                acc = term if acc is None else acc + term
            acc = w[k] * acc
            out = acc if out is None else out + acc
        return out

    return _planes(mz, mz * my * mx, part)


def interp(z):
    """Trilinear prolongation of a whole coarse level ``z`` grown by one
    cell a side (m + 2): the fine level (2m) it adds to u. By parity: an
    odd fine coordinate 2c + 1 sits on coarse c, an even one 2c between
    coarse c - 1 and c."""
    w = _weights(PROLONG, z.dtype)
    m = [s - 2 for s in z.shape]
    out = np.empty([2 * s for s in m], z.dtype)
    for parity in itertools.product((0, 1), repeat=3):
        # per axis the coarse cells a fine cell of this parity reads, as
        # starts into the grown array: c alone (odd), c - 1 and c (even)
        reads = [((1,) if p else (0, 1)) for p in parity]
        acc = None
        for sz, sy, sx in itertools.product(*reads):
            term = z[sz:sz + m[0], sy:sy + m[1], sx:sx + m[2]]
            acc = term if acc is None else acc + term
        out[parity[0]::2, parity[1]::2, parity[2]::2] = \
            w[3 - sum(parity)] * acc
    return out


def norm2u3(r) -> float:
    """The L2 norm NPB verifies: sqrt(sum r^2 / cells)."""
    r = np.asarray(r, np.float64)
    return math.sqrt(float(np.sum(r * r)) / r.size)


# ------------------------------------------------------------ the V-cycle


def down(r, corners: bool = True):
    """The residuals of every level, finest first, from the finest's."""
    rs = [r]
    while rs[-1].shape[0] > 2:
        rs.append(rprj3(grow(rs[-1]), corners))
    return rs


def up(rs, s, to: int = 0, corners: bool = True):
    """The up-cycle from the coarsest level to level ``to`` (0 the finest
    of ``rs``) for levels that start from u = 0: at the bottom u = S r,
    above it u = P u_below, r = r - A u, u = u + S r. Returns ``(us, rs)``
    for levels ``to`` and below, finest first."""
    rs = list(rs)
    us = [None] * len(rs)
    us[-1] = psinv(grow(rs[-1]), np.zeros_like(rs[-1]), s)
    for k in range(len(rs) - 2, to - 1, -1):
        u = interp(grow(us[k + 1]))
        rs[k] = resid(grow(u), rs[k], corners)
        us[k] = psinv(grow(rs[k]), u, s)
    return us[to:], rs[to:]


def iteration(u, v, r, s, corners: bool = True):
    """``mg3P`` then ``resid`` on whole periodic levels: returns the finest
    level's new ``(u, r)``."""
    rs = down(r, corners)
    if len(rs) < 2:
        raise ValueError("a V-cycle takes two levels or more (n >= 4)")
    us, _ = up(rs, s, to=1, corners=corners)
    u = u + interp(grow(us[0]))
    r = resid(grow(u), v, corners)
    u = psinv(grow(r), u, s)
    return u, resid(grow(u), v, corners)


def run(n: int, nit: int, s, v, dtype=np.float64):
    """NPB's timed section from u = 0: r = v - A u, ``nit`` iterations,
    the norm of the last residual. Returns ``(u, r, norm)``."""
    v = v.astype(dtype)
    u = np.zeros((n, n, n), dtype)
    r = resid(grow(u), v)
    for _ in range(nit):
        u, r = iteration(u, v, r, s)
    return u, r, norm2u3(r)


# ------------------------------------------------------------ the data


def lcg_field(n: int):
    """The source's field of ``n^3`` draws as uint64 states ``[z, y, x]``:
    cell (x, y, z) holds ``a^(1 + x + n y + n^2 z) seed mod 2^46`` (a draw
    is the state over 2^46; the order of states is the order of draws)."""
    total = n ** 3
    out = np.empty(total, np.uint64)
    out[0] = (LCG_A * LCG_SEED) % LCG_MOD
    done = 1
    while done < total:
        m = min(done, total - done)
        out[done:done + m] = _mulmod46(pow(LCG_A, done, LCG_MOD), out[:m])
        done += m
    return out.reshape(n, n, n)


def _mulmod46(a: int, x):
    """``a x mod 2^46`` for a Python int and a uint64 array, in 23-bit
    halves as the source's ``randlc`` splits them."""
    mask = np.uint64((1 << 23) - 1)
    a1, a2 = np.uint64(a >> 23), np.uint64(a & ((1 << 23) - 1))
    x1, x2 = x >> np.uint64(23), x & mask
    t = (a1 * x2 + a2 * x1) & mask
    return ((t << np.uint64(23)) + a2 * x2) & np.uint64(LCG_MOD - 1)


def zran3(n: int):
    """``(plus, minus)``: the ``[z, y, x]`` cells of the ten largest and
    the ten smallest draws of the source's field."""
    flat = lcg_field(n).ravel()
    order = np.argsort(flat, kind="stable")
    cells = lambda idx: [tuple(int(c) for c in np.unravel_index(i, (n,) * 3))  # noqa: E731
                         for i in idx]
    return cells(order[-CHARGES:]), cells(order[:CHARGES])


def charges_field(n: int, plus, minus, dtype=np.float64):
    v = np.zeros((n, n, n), dtype)
    for cell in minus:
        v[cell] = -1.0
    for cell in plus:
        v[cell] = 1.0
    return v


def seeded_charges(seed: int, n: int):
    """The benchmark's twenty charges: distinct cells drawn from the seed,
    the first ten +1 and the last ten -1."""
    rng = np.random.RandomState((int(seed) ^ 0x4D47) % (2 ** 32))
    cells = set()
    out = []
    while len(out) < 2 * CHARGES:
        cell = tuple(int(c) for c in rng.randint(0, n, 3))
        if cell not in cells:
            cells.add(cell)
            out.append(cell)
    return out[:CHARGES], out[CHARGES:]


def from_uniform(xp, u):
    """A dense seeded value in [-1, 1) from ``u`` in [0, 1), float32: one
    formula for the device's fill and this file."""
    return xp.float32(2.0) * u - xp.float32(1.0)


def seeded_dense(seed: int, q: int, z, y, x):
    """Quantity ``q`` (0 u, 1 r) of the seeded finest level at integer
    coordinates (broadcastable arrays, already wrapped), float32."""
    return from_uniform(np, fields.uniform(np, seed, q, z, y, x))


def seeded_level(seed: int, q: int, n: int, dtype=np.float64):
    """A whole seeded finest level (tests: small n)."""
    c = np.arange(n)
    return seeded_dense(seed, q, c[:, None, None], c[None, :, None],
                        c[None, None, :]).astype(dtype)


def restrict_seeded(seed: int, n: int, dtype=np.float64,
                    corners: bool = True):
    """``rprj3`` of the seeded finest r, whole, WITHOUT holding the finest
    level: a run of coarse planes at a time from the fine planes it reads,
    made from the hash where they are needed."""
    edge = np.mod(np.arange(-1, n + 1), n)
    y, x = edge[None, :, None], edge[None, None, :]

    def part(z0, z1):
        z = np.mod(np.arange(2 * z0 - 1, 2 * z1 + 1), n)[:, None, None]
        return rprj3(seeded_dense(seed, 1, z, y, x).astype(dtype), corners)

    return _planes(n // 2, (n // 2) ** 3 * 8, part)


def _take(a, starts, lens):
    """A box of a whole periodic level: ``lens`` cells an axis from
    ``starts`` (any integers: they wrap)."""
    return a[np.ix_(*(np.mod(np.arange(s, s + m), g)
                      for s, m, g in zip(starts, lens, a.shape)))]


def _coarse_range(starts, lens):
    """The coarse cells that the fine cells [start, start + len) read when
    prolonged: from (start - 1) // 2 to (start + len - 1) // 2."""
    first = [(s - 1) // 2 for s in starts]
    return first, [(s + m - 1) // 2 - c + 1
                   for s, m, c in zip(starts, lens, first)]


def interp_box(zbox, zfirst, starts, lens):
    """The prolongation at the fine cells [starts, starts + lens) from a
    coarse BOX whose first cell is ``zfirst``: the eight coarse cells about
    each fine cell, an eighth each (on an odd fine coordinate the two
    along that axis are one cell: fine 2c + 1 sits on coarse c, fine 2c
    between c - 1 and c)."""
    eighth = zbox.dtype.type(0.125)
    pick = []
    for s, m, c in zip(starts, lens, zfirst):
        f = np.arange(s, s + m)
        pick.append(((f - 1) // 2 - c, f // 2 - c))
    acc = None
    for cz, cy, cx in itertools.product(*pick):
        term = zbox[np.ix_(cz, cy, cx)]
        acc = term if acc is None else acc + term
    return eighth * acc


def interp_at(z, starts, lens):
    """:func:`interp_box` from a whole periodic coarse level (``starts``
    may be any integers: they wrap)."""
    first, count = _coarse_range(starts, lens)
    return interp_box(_take(z, first, count), first, starts, lens)


def _up_on_box(u_below, rhs, starts, lens, s, corners, start_u=None):
    """One level's up-cycle step on the box [starts, starts + lens):
    prolong ``u_below(first, lens) -> coarse box`` onto the box grown by 2
    (added to ``start_u(starts, lens)`` where the level starts from a u of
    its own), the residual against ``rhs(starts, lens)`` on the box grown
    by 1, the smoother on the box. Returns ``(u, r)`` on the box."""
    s2, l2 = [a - 2 for a in starts], [m + 4 for m in lens]
    s1, l1 = [a - 1 for a in starts], [m + 2 for m in lens]
    first, count = _coarse_range(s2, l2)
    u = interp_box(u_below(first, count), first, s2, l2)
    if start_u is not None:
        u = start_u(s2, l2) + u
    r = resid(u, rhs(s1, l1), corners)
    return psinv(r, _inner(_inner(u)), s), _inner(r)


def first_iteration_boxes(seed: int, n: int, s, origins, core,
                          dtype=np.float64, corners: bool = True):
    """The finest level's ``u`` and ``r`` after ONE iteration from the
    seeded state, on the cores of boxes (``origins``: z, y, x of each
    core's first cell; boxes wrap). Down: the finest level's restriction
    once, whole but streamed (``restrict_seeded``), every level below it
    whole. Up: every level from n/4 down whole; the levels n/2 and n on
    each box alone, with the margin their operators eat (an operator reads
    one cell beyond what it writes, so a box computed alone is what the
    whole level would hold there). Returns ``[{"u": core, "r": core}]``."""
    dt = np.dtype(dtype).type
    plus, minus = seeded_charges(seed, n)
    rs = down(restrict_seeded(seed, n, dtype, corners), corners)
    if len(rs) == 1:                    # n = 4: the level below is the last
        whole = psinv(grow(rs[0]), np.zeros_like(rs[0]), s)
        below = lambda first, count: _take(whole, first, count)  # noqa: E731
    else:
        us, _ = up(rs[1:], s, to=0, corners=corners)

        def below(first, count):
            # level n/2 on the box: from n/4 whole and its own r
            return _up_on_box(
                lambda f, c: _take(us[0], f, c),
                lambda f, c: _take(rs[0], f, c), first, count, s, corners)[0]

    def v_on(starts, lens):
        v = np.zeros(lens, dtype)
        coords = [np.mod(np.arange(a, a + m), n) for a, m in zip(starts, lens)]
        for sign, cells in ((1.0, plus), (-1.0, minus)):
            for cell in cells:
                at = [np.nonzero(c == p)[0] for c, p in zip(coords, cell)]
                v[np.ix_(*at)] = dt(sign)
        return v

    def u_seeded(starts, lens):
        z, y, x = (np.mod(np.arange(a, a + m), n)
                   for a, m in zip(starts, lens))
        return seeded_dense(seed, 0, z[:, None, None], y[None, :, None],
                            x[None, None, :]).astype(dtype)

    out = []
    for origin in origins:
        # u after the finest level's psinv on the core grown by 1, for the
        # iteration's own residual on the core
        starts, lens = [o - 1 for o in origin], [c + 2 for c in core]
        u, _ = _up_on_box(below, v_on, starts, lens, s, corners, u_seeded)
        out.append({"u": _inner(u),
                    "r": resid(u, v_on(list(origin), list(core)), corners)})
    return out
