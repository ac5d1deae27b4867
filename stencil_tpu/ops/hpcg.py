"""One iteration of HPCG's preconditioned conjugate gradient over a
hierarchy of realized FIXED domains.

HPCG 3.1 (``src/CG_ref.cpp``, ``ComputeMG_ref.cpp``,
``ComputeSYMGS_ref.cpp``, ``GenerateProblem_ref.cpp``): ``A x = b`` with A
the 27-point operator, 26 on the diagonal and -1 for every neighbour INSIDE
the grid (homogeneous Dirichlet: a neighbour outside contributes nothing),
solved by CG preconditioned with one V-cycle over four levels, each half
the one above, every coarse operator the same 27-point one:

    z = MG(r);  rtz' = r.z;  beta = rtz' / rtz (0 on a set's first);
    p = z + beta p;  Ap = A p;  alpha = rtz' / (p.Ap);
    x += alpha p;  r -= alpha Ap;  normr = sqrt(r.r)

    MG(r):  x = 0; SYMGS(x, r); above the coarsest: rc = (r - A x) at the
            even points (injection); xc = MG(rc); x += xc at the even
            points; SYMGS(x, r)

``SYMGS`` is a forward then a backward Gauss-Seidel sweep IN PLACE, here in
the eight-colour order ``c = (ix mod 2) + 2 (iy mod 2) + 4 (iz mod 2)``
(0 .. 7, then 7 .. 0; ``ops/pallas_hpcg``). A set is ``SET_ITERS``
iterations from ``x = 0``, ``r = b``.

Every level is a ``DistributedDomain`` of its own, fixed on every axis
(``set_boundary(periodic=(False,) * 3)``): the ghost ring round a block is
the Dirichlet face, holds zero and is never written, and so do the
alignment rows. A level whose rows are whole lane tiles takes the tight-x
layout, where x has no ring and the kernels drop the lane roll's wrap; on
a TPU its operator is ``pallas_mg``'s box kernel at HPCG's weights and its
sweep ``hpcg_symgs``, and the transfers between two such levels are
``hpcg_restrict`` and ``hpcg_prolong``: they fetch the even owned planes of
the fine level alone (the cells a transfer uses are those planes' even
rows, a quarter of the level), and the prolongation adds in place.
The others (64^3 of the 512^3 problem, and the transfers between it and
128^3; everything off a TPU) are plain XLA over the same arrays: there a
transfer is a product with a 0/1 matrix along x and a pass over the whole
fine block.

:func:`make_hpcg_iter` returns ONE jitted program, ``step(state, b) ->
state``: the state is donated and comes back in its slots, ``b`` is read
only. The three dot products of an iteration feed the kernels after them
as device scalars, and so does the set's count: a state that has made its
``SET_ITERS`` starts the next set inside the same program (``r <- b``,
``normr0 <- |b|`` under a ``cond``; ``x <- 0`` and ``beta <- 0`` in the
updates that follow). Nothing goes to the host inside a dispatch. ONE
block: the weak-scaled form (a halo before every operator and colour,
``psum`` under every dot) is refused until it is written.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..obs import scopes, telemetry
from ..parallel.exchange import HaloExchange, Method
from . import mg as _mg
from .pallas_hpcg import (DIAGONAL, WEIGHTS, even_columns,
                          make_pallas_hpcg_prolong,
                          make_pallas_hpcg_restrict, make_pallas_hpcg_spmv,
                          make_pallas_hpcg_symgs, symgs_supported)
from .pallas_mg import make_pallas_mg_box, transfer_supported

LEVELS = 4                  # numberOfMgLevels
SET_ITERS = 50              # iterations a set
COLOURS = (0, 1, 2, 3, 4, 5, 6, 7)      # a forward sweep's order
OPERATORS = ("hpcg_symgs", "hpcg_resid", "hpcg_spmv", "hpcg_restrict",
             "hpcg_prolong")
FINE = ("x", "r", "p", "z", "t")        # the finest level's arrays
COARSE = ("x", "r", "t")                # a lower level's (no t on the last)
SCALARS = ("rtz", "normr", "normr0", "alpha", "beta")


def level_sizes(size):
    """(x, y, z) of the four levels, finest first."""
    x, y, z = (int(n) for n in size)
    step = 1 << (LEVELS - 1)
    if any(n % step or n < 2 * step for n in (x, y, z)):
        raise ValueError(f"hpcg halves {(x, y, z)} three times: every axis "
                         f"a multiple of {step}, {2 * step} or more")
    return [(x >> k, y >> k, z >> k) for k in range(LEVELS)]


def level_radius(size):
    """The ring of a level: radius 1 on every face, edge and corner; a
    level whose rows are whole lane tiles takes the tight-x layout."""
    return _mg.level_radius(size[0], (1, 1, 1))


def _level(ex: HaloExchange, number: int, dtype, use_pallas) -> _mg._Level:
    spec = ex.spec
    if ex.method != Method.AXIS_COMPOSED:
        raise ValueError("hpcg steps through Method.AXIS_COMPOSED")
    if ex.faces_only or any(ex.periodic):
        raise ValueError(
            "hpcg's operator has Dirichlet faces and reads all 26 "
            "neighbours: every axis fixed (set_boundary(periodic=(False, "
            "False, False))), edges and corners kept")
    d = spec.dim
    if (d.x, d.y, d.z) != (1, 1, 1):
        raise ValueError(
            f"hpcg runs ONE block; partition {d} needs a halo before every "
            f"operator and colour and a psum under every dot, which are not "
            f"written yet")
    r = spec.radius
    tight = _mg.is_tight(spec)
    if min(r.y(-1), r.y(1), r.z(-1), r.z(1)) < 1 or (
            not tight and min(r.x(-1), r.x(1)) < 1):
        raise ValueError(f"level {spec.global_size}: the ring is one cell")
    o, b = spec.compute_offset(), spec.base
    pallas = use_pallas is not False and symgs_supported(spec, dtype) and (
        bool(use_pallas) or all(dv.platform == "tpu"
                                for dv in ex.mesh.devices.flatten()))
    return _mg._Level(ex, number, spec.block_shape_zyx(), (o.z, o.y, o.x),
                      (b.z, b.y, b.x), tight, pallas, wrap_x=False)


# ------------------------------------------------------------ XLA operators


def _colours(lv: _mg._Level):
    """The colour of every owned cell (one block: global indices)."""
    idx = [lax.broadcasted_iota(jnp.int32, lv.n, a) for a in range(3)]
    return (idx[2] & 1) + 2 * (idx[1] & 1) + 4 * (idx[0] & 1)


def _xla_symgs(lv: _mg._Level):
    """``fn(x, r) -> x`` over padded blocks: the sixteen colour updates of
    a symmetric sweep (0 .. 7, then 7 .. 0), each from the array as the
    one before left it; one loop, the colour its index."""
    around = (0.0, 1.0, 1.0, 1.0)
    last = len(COLOURS) - 1

    def fn(x, r):
        inv = x.dtype.type(1.0 / DIAGONAL)
        rhs = _mg._owned(r, lv)
        colour = _colours(lv)

        def update(i, x):
            c = jnp.where(i <= last, i, 2 * last + 1 - i)
            new = (rhs + _mg._box(x, lv, around)) * inv
            return _mg._put(x, jnp.where(colour == c, new,
                                         _mg._owned(x, lv)), lv)

        return lax.fori_loop(0, 2 * len(COLOURS), update, x)

    return fn


def _xla_restrict(fine: _mg._Level, coarse: _mg._Level):
    """``fn(t_fine, r_coarse) -> r_coarse``: injection, the coarse point c
    on the fine point 2c."""

    def fn(t, rc):
        even = _mg._owned(t, fine)[::2, ::2, :]
        picked = jnp.einsum("zyx,xc->zyc", even,
                            even_columns(fine.n[2], t.dtype),
                            precision=lax.Precision.HIGHEST)
        return _mg._put(rc, picked, coarse)

    return fn


def _xla_prolong(coarse: _mg._Level, fine: _mg._Level):
    """``fn(x_coarse, x_fine) -> x_fine``: ``x[2c] += xc[c]``, as one
    addition of the coarse level spread out with zeros between (along x by
    the transpose of the restriction's product, along y and z by
    padding)."""

    def fn(xc, xf):
        wide = jnp.einsum("zyc,xc->zyx", _mg._owned(xc, coarse),
                          even_columns(fine.n[2], xf.dtype),
                          precision=lax.Precision.HIGHEST)
        (zo, yo, xo), (pz, py, px), (mz, my, _) = fine.lo, fine.block, coarse.n
        spread = lax.pad(wide, xf.dtype.type(0),
                         [(zo, pz - zo - (2 * mz - 1), 1),
                          (yo, py - yo - (2 * my - 1), 1),
                          (xo, px - xo - fine.n[2], 0)])
        return xf + spread

    return fn


# ------------------------------------------------------------ the iteration


def _blocks(fn, name: str, pallas: bool):
    """``fn`` over padded blocks as a function of the stacked arrays it is
    handed, under the kernel's name where plain XLA computes it."""

    def call(*arrays):
        # the result lies where the last array does (a transfer's arrays
        # are of two levels: each keeps its own block)
        if pallas:
            with scopes.scope(scopes.CARRY):
                args = [a.reshape(a.shape[3:]) for a in arrays]
            out = fn(*args)
            with scopes.scope(scopes.CARRY):
                return out.reshape(arrays[-1].shape)
        with scopes.kernel_scope(name):
            return fn(*(a.reshape(a.shape[3:]) for a in arrays)).reshape(
                arrays[-1].shape)

    return call


def _build(exchanges, dtype, use_pallas, interpret):
    """``(levels, ops, impls)``: per level (finest first) its layout, per
    ``(level index, operator)`` the function over stacked arrays, and what
    implements it."""
    if len(exchanges) != LEVELS:
        raise ValueError(f"hpcg's V-cycle has {LEVELS} levels")
    levels = [_level(ex, LEVELS - i, dtype, use_pallas)
              for i, ex in enumerate(exchanges)]
    for fine, coarse in zip(levels, levels[1:]):
        if tuple(2 * m for m in coarse.n) != fine.n:
            raise ValueError(f"level {coarse.number}'s grid {coarse.n} is "
                             f"not half level {fine.number}'s {fine.n}")
    ops, impls = {}, {}

    def put(i, name, fn, pallas):
        ops[(i, name)] = _blocks(fn, name, pallas)
        impls[(i, name)] = "pallas" if pallas else "xla"

    for i, lv in enumerate(levels):
        spec = lv.ex.spec
        if lv.pallas:
            halves = {(parity, rev): make_pallas_hpcg_symgs(
                spec, parity, rev, interpret=interpret)
                for parity in (0, 1) for rev in (False, True)}

            def sweep(x, r, halves=halves):
                # colours 0 .. 3, 4 .. 7, then 7 .. 4, 3 .. 0
                for key in ((0, False), (1, False), (1, True), (0, True)):
                    x = halves[key](x, r)
                return x

            put(i, "hpcg_symgs", sweep, True)
        else:
            put(i, "hpcg_symgs", _xla_symgs(lv), False)
        box = lv.pallas          # the sweep's layout is the box kernel's
        if i + 1 < LEVELS:
            put(i, "hpcg_resid", make_pallas_mg_box(
                spec, "hpcg_resid", WEIGHTS, -1.0, separate_dst=True,
                interpret=interpret, periodic_x=False) if box
                else _mg._xla_box(lv, WEIGHTS, -1.0), box)
            below = levels[i + 1]
            # both levels on the box kernel's layout: the transfers fetch
            # the even fine planes alone
            kernels = box and below.pallas and transfer_supported(
                spec, below.ex.spec, dtype)
            put(i, "hpcg_restrict", make_pallas_hpcg_restrict(
                spec, below.ex.spec, interpret=interpret) if kernels
                else _xla_restrict(lv, below), kernels)
            put(i, "hpcg_prolong", make_pallas_hpcg_prolong(
                below.ex.spec, spec, interpret=interpret) if kernels
                else _xla_prolong(below, lv), kernels)
        if i == 0:
            if box:
                put(i, "hpcg_spmv", make_pallas_hpcg_spmv(
                    spec, interpret=interpret), True)
            else:
                body = _mg._xla_box(lv, WEIGHTS, 1.0, has_p=False)
                put(i, "hpcg_spmv", lambda q, dst: body(q, None, dst), False)
    return levels, ops, impls


def iter_plan(levels, impls, itemsize: int) -> list:
    """Per level, finest first, what one iteration runs there."""
    out = []
    for i, lv in enumerate(levels):
        cells = lv.n[0] * lv.n[1] * lv.n[2]
        calls = {"hpcg_symgs": 2 if i + 1 < LEVELS else 1,
                 "hpcg_resid": 1, "hpcg_spmv": 1, "hpcg_restrict": 1,
                 "hpcg_prolong": 1}
        # a sweep reads x whole twice a direction and r once, writes x
        # once; a transfer uses the even rows of the level's even planes
        # (a quarter of it: read, and for the prolongation written back)
        # and the level below (an eighth)
        arrays = {"hpcg_symgs": 8 * cells, "hpcg_resid": 3 * cells,
                  "hpcg_spmv": 2 * cells,
                  "hpcg_restrict": cells // 4 + cells // 8,
                  "hpcg_prolong": 2 * (cells // 4) + cells // 8}
        g = lv.ex.spec.global_size
        out.append({
            "level": lv.number, "grid": [g.z, g.y, g.x],
            "layout": "tight_x" if lv.tight else "inline",
            "operators": {
                name: {"impl": impls[(i, name)],
                       "calls_per_iter": calls[name],
                       "bytes_min": arrays[name] * itemsize}
                for name in OPERATORS if (i, name) in impls}})
    return out


def _at(levels, i: int):
    """The tag of level ``i`` (finest first), outside what runs on it."""
    return scopes.level_scope(levels[i].number)


def vcycle(levels, ops, z, r, t, coarse):
    """``ComputeMG_ref`` over the built ``ops``: ``(z, t, coarse)`` with
    ``z = MG(r)`` from ``z = 0``, the finest level's scratch ``t`` and the
    lower levels' arrays (``coarse``: a dict a level) as it leaves them."""
    at = functools.partial(_at, levels)
    xs = [z] + [c["x"] for c in coarse]
    rs = [r] + [c["r"] for c in coarse]
    ts = [t] + [c.get("t") for c in coarse]
    for i in range(LEVELS):                             # down
        with at(i):
            xs[i] = ops[(i, "hpcg_symgs")](jnp.zeros_like(xs[i]), rs[i])
            if i + 1 < LEVELS:
                ts[i] = ops[(i, "hpcg_resid")](xs[i], rs[i], ts[i])
                rs[i + 1] = ops[(i, "hpcg_restrict")](ts[i], rs[i + 1])
    for i in range(LEVELS - 2, -1, -1):                 # up
        with at(i):
            xs[i] = ops[(i, "hpcg_prolong")](xs[i + 1], xs[i])
            xs[i] = ops[(i, "hpcg_symgs")](xs[i], rs[i])
    lower = [dict(x=x, r=r_, **({} if t_ is None else {"t": t_}))
             for x, r_, t_ in zip(xs[1:], rs[1:], ts[1:])]
    return xs[0], ts[0], lower


def make_hpcg_iter(exchanges: Sequence[HaloExchange], dtype="float32",
                   use_pallas=None, interpret: bool = False):
    """``step(state, b) -> state``: ONE iteration of preconditioned CG, the
    V-cycle included (module docstring). ``exchanges``: the four levels'
    ``HaloExchange``, finest first, each of ONE block fixed on every axis.
    ``state`` (:func:`state_like`): the finest level's stacked arrays x, r,
    p, z and t (scratch: ``A z`` in the V-cycle, then ``A p``), ``coarse``:
    per lower level its x, r and t, and the scalars rtz, normr, normr0,
    alpha, beta and ``k``, the iterations the set has made (``SET_ITERS``:
    the next dispatch opens a set). ``b`` is the finest level's right-hand
    side, read only. Every array's ring and padding hold zero on entry and
    on return. The counter ``hpcg.iter_plan`` says what was built."""
    dtype = jnp.dtype(dtype)
    levels, ops, impls = _build(exchanges, dtype, use_pallas, interpret)
    at = functools.partial(_at, levels)

    def dot(a, c):
        with scopes.scope(scopes.SOLVER_DOT):
            return jnp.sum(a * c)

    def program(state, b):
        x, r, p, k = state["x"], state["r"], state["p"], state["k"]
        opens = k >= SET_ITERS              # this iteration opens a set

        def opened(r, b, normr0):
            with at(0):
                with scopes.scope(scopes.SOLVER_AXPY):
                    fresh = jnp.copy(b)
                return fresh, jnp.sqrt(dot(b, b))

        r, normr0 = lax.cond(opens, opened, lambda r, b, n0: (r, n0),
                             r, b, state["normr0"])
        k = jnp.where(opens, 0, k)
        z, t, coarse = vcycle(levels, ops, state["z"], r, state["t"],
                              state["coarse"])
        with at(0):
            rtz = dot(r, z)
            beta = jnp.where((k == 0) | (state["rtz"] == 0), 0.0,
                             rtz / state["rtz"]).astype(dtype)
            with scopes.scope(scopes.SOLVER_AXPY):
                p = z + beta * p
            t = ops[(0, "hpcg_spmv")](p, t)
            pap = dot(p, t)
            # CG_ref's loop ends a set at a residual of exactly zero
            # (``normr / normr0 > tolerance`` with tolerance 0); a program
            # that cannot end early makes the iterations after it no-ops
            alpha = jnp.where(pap == 0, 0.0, rtz / pap).astype(dtype)
            with scopes.scope(scopes.SOLVER_AXPY):
                # a set's first iteration starts from x = 0
                x = jnp.where(k == 0, 0.0, x).astype(dtype) + alpha * p
                r = r - alpha * t
            normr = jnp.sqrt(dot(r, r))
        return {"x": x, "r": r, "p": p, "z": z, "t": t, "coarse": coarse,
                "rtz": rtz, "normr": normr, "normr0": normr0,
                "alpha": alpha, "beta": beta, "k": k + 1}

    like = state_like(levels, dtype)
    b_like = like["x"]
    telemetry.get().counter(
        "hpcg.iter_plan", value=1, phase="compute", module=scopes.HPCG_ITER,
        levels=iter_plan(levels, impls, dtype.itemsize))
    return scopes.jit_loop(scopes.HPCG_ITER, program, (like, b_like),
                           donate_argnums=(0,))


def state_like(levels, dtype):
    """The abstract state of :func:`make_hpcg_iter`'s program (``levels``:
    its layouts, or the four ``HaloExchange``)."""
    exs = [getattr(lv, "ex", lv) for lv in levels]
    arr = [jax.ShapeDtypeStruct(ex.spec.stacked_shape_zyx(), dtype,
                                sharding=ex.sharding()) for ex in exs]
    scalar = jax.ShapeDtypeStruct((), dtype)
    state = {name: arr[0] for name in FINE}
    state["coarse"] = [
        {name: a for name in COARSE if name != "t" or i + 2 < LEVELS}
        for i, a in enumerate(arr[1:])]
    state.update({name: scalar for name in SCALARS})
    state["k"] = jax.ShapeDtypeStruct((), jnp.int32)
    return state
