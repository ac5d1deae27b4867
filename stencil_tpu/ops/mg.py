"""One iteration of NPB MG's V-cycle over a hierarchy of realized domains.

NAS Parallel Benchmarks 3.x, kernel MG (``mg.f``): ``lap(u) = v`` on a
periodic cube by V-cycles over the levels ``n, n/2, ..., 2``. Four
operators, each a 27-point box whose weight depends only on the
neighbour's class (centre, 6 faces, 12 edges, 8 corners):

    resid    r = v - A u        A = (-8/3, 0, 1/6, 1/12)
    psinv    u = u + S r        S by class (``S_SMALL`` for S, W, A;
                                ``S_LARGE`` from B)
    rprj3    coarse cell c = full weighting (1/2, 1/4, 1/8, 1/16) of the
             27 fine cells about fine cell 2c + 1
    interp   fine u += trilinear prolongation: fine 2c + 1 takes coarse c,
             fine 2c the mean of coarse c - 1 and c, on each axis

One iteration is ``mg3P`` then ``resid``: down, ``rprj3`` level by level;
at the bottom u = S r; up, u = P u_below, r = r - A u, u = u + S r; at the
top the prolongation ADDS to u and the residual is against v; then the
iteration's own r = v - A u. 34 operator calls at nine levels for 512^3,
and after every one the source's ``comm3``: here the level's own
``HaloExchange`` on the array just written, all 26 neighbours.

Every level is a domain of its own on the same devices and partition, its
block half the one above on every axis, so that a coarse cell and the fine
cells it reads or feeds lie in the same block (or its halo). A level whose
rows are whole lane tiles takes the tight-x layout (no x halo: x wraps by a
lane roll) and, on a TPU, the Pallas kernels of ``pallas_mg`` (the box,
and the transfers between two such levels); the others keep inline x
halos. Where such a kernel level lies above them and the partition is ONE
block (what the realized specs say: ``pallas_mg.coarse_supported``; fp32),
those others are RESIDENT: one call, ``mg_coarse``, keeps their u and r in
VMEM from the restriction out of the tight-x level above to the
prolongation back onto it, 23 of class C's 34 operator calls with the 22
fills between them, and writes each to its HBM slot once. Everywhere else
(a split partition, whose coarse blocks need the wire; off a TPU; a
hierarchy with no tight-x level, such as class S) they are plain XLA with
the level's own exchange after each operator.

:func:`make_mg_iter` returns ONE jitted program that updates the
hierarchy's state where it lies: every array is donated and comes back in
its slot (``ops/double_buffer``'s discipline without the pair: no operator
here has two time levels), v is only read.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..domain.grid import GridSpec
from ..geometry import Dim3, Radius
from ..obs import scopes, telemetry
from ..parallel.exchange import HaloExchange, Method
from ..parallel.mesh import BLOCK_PSPEC, MESH_AXES
from .pallas_mg import (LANE, box_supported, coarse_supported,
                        coarse_vmem_bytes, make_pallas_mg_box,
                        make_pallas_mg_coarse, make_pallas_mg_interp,
                        make_pallas_mg_rprj3, transfer_supported)

A = (-8.0 / 3.0, 0.0, 1.0 / 6.0, 1.0 / 12.0)
S_SMALL = (-3.0 / 8.0, 1.0 / 32.0, -1.0 / 64.0, 0.0)     # classes S, W, A
S_LARGE = (-3.0 / 17.0, 1.0 / 33.0, -1.0 / 61.0, 0.0)    # class B and up
OPERATORS = ("mg_resid", "mg_psinv", "mg_rprj3", "mg_interp")


def level_sizes(n: int):
    """Cells an axis of every level, finest first: n, n/2, ..., 2."""
    if n < 4 or n & (n - 1):
        raise ValueError(f"MG takes a power of two of at least 4, not {n}")
    return [n >> k for k in range(n.bit_length() - 1)]


def level_radius(m: int, partition) -> Radius:
    """The halos of an ``m^3`` level on ``partition`` (x, y, z): radius 1
    on every face, edge and corner (a box reads them all); a level whose
    rows are whole lane tiles on an unsplit x axis takes the tight-x layout
    (no x halo)."""
    radius = Radius.constant(1)
    if Dim3.of(partition).x == 1 and m % LANE == 0:
        radius = radius.without_x()
    return radius


def is_tight(spec: GridSpec) -> bool:
    """No x halo: the block's rows are its cells and x wraps by a roll."""
    return spec.radius.x(-1) == 0 and spec.radius.x(1) == 0


class _Level(NamedTuple):
    """What the operators need of one level's realized layout."""

    ex: HaloExchange
    number: int                 # NPB's k: 1 the 2^3 level
    block: tuple                # padded (pz, py, px)
    lo: tuple                   # compute offset (z, y, x)
    n: tuple                    # owned cells of a block (z, y, x)
    tight: bool
    pallas: bool
    # what a tight-x row's x -+ 1 means at the row's two ends: the periodic
    # wrap (NPB MG), or nothing (a fixed x: ``ops/hpcg``'s hierarchy)
    wrap_x: bool = True


def _level(ex: HaloExchange, number: int, dtype, use_pallas) -> _Level:
    spec = ex.spec
    if ex.method != Method.AXIS_COMPOSED:
        raise ValueError("MG steps through Method.AXIS_COMPOSED")
    if ex.faces_only or not all(ex.periodic):
        raise ValueError(
            "MG's boxes read all 26 neighbours across a periodic wrap: a "
            "faces-only plan leaves edges and corners unfilled, a fixed "
            "axis its ghost (a hierarchy of FIXED domains is hpcg's: "
            "ops/hpcg.make_hpcg_iter)")
    if not spec.is_uniform():
        raise ValueError(f"level {spec.global_size}: blocks must be equal")
    r = spec.radius
    tight = is_tight(spec)
    if tight and spec.dim.x != 1:
        raise ValueError("a tight-x level keeps x whole")
    if min(r.y(-1), r.y(1), r.z(-1), r.z(1)) < 1 or (
            not tight and min(r.x(-1), r.x(1)) < 1):
        raise ValueError(f"level {spec.global_size}: a box reads radius 1")
    o, b = spec.compute_offset(), spec.base
    pallas = use_pallas is not False and box_supported(spec, dtype) and (
        bool(use_pallas) or all(d.platform == "tpu"
                                for d in ex.mesh.devices.flatten()))
    return _Level(ex, number, spec.block_shape_zyx(), (o.z, o.y, o.x),
                  (b.z, b.y, b.x), tight, pallas)


# ------------------------------------------------------------ XLA operators


def _rows(a, lv: _Level, dz: int = 0, dy: int = 0):
    """The owned planes and rows of a padded block shifted by (dz, dy),
    every column kept."""
    (zo, yo, _), (nz, ny, _) = lv.lo, lv.n
    return a[zo + dz:zo + dz + nz, yo + dy:yo + dy + ny, :]


def _cols(t, lv: _Level, dx: int = 0):
    """The owned columns of whole rows shifted by dx: a slice where the x
    halo is inline; where the row is the whole axis (tight-x) a roll on a
    periodic x, and on a fixed one a shift that brings zeros in."""
    if lv.tight:
        if not dx:
            return t
        if lv.wrap_x:
            return jnp.roll(t, -dx, axis=2)
        pad = [(0, 0), (0, 0), (max(-dx, 0), max(dx, 0))]
        return lax.slice_in_dim(jnp.pad(t, pad), max(dx, 0),
                                max(dx, 0) + t.shape[2], axis=2)
    xo, nx = lv.lo[2], lv.n[2]
    return t[:, :, xo + dx:xo + dx + nx]


def _box(q, lv: _Level, w):
    """``Box(q)`` over the owned cells, by the source's partial sums: the
    four face and the four diagonal neighbours in the y-z plane summed
    once, then combined along x."""
    w = [q.dtype.type(x) for x in w]
    c = _rows(q, lv)
    u1 = (_rows(q, lv, 0, -1) + _rows(q, lv, 0, 1)
          + _rows(q, lv, -1, 0) + _rows(q, lv, 1, 0))
    u2 = (_rows(q, lv, -1, -1) + _rows(q, lv, -1, 1)
          + _rows(q, lv, 1, -1) + _rows(q, lv, 1, 1))
    out = w[0] * _cols(c, lv)
    if w[1] != 0:
        out = out + w[1] * (_cols(c, lv, -1) + _cols(c, lv, 1) + _cols(u1, lv))
    if w[2] != 0:
        out = out + w[2] * (_cols(u2, lv) + _cols(u1, lv, -1)
                            + _cols(u1, lv, 1))
    if w[3] != 0:
        out = out + w[3] * (_cols(u2, lv, -1) + _cols(u2, lv, 1))
    return out


def _owned(a, lv: _Level):
    return _cols(_rows(a, lv), lv)


def _put(dst, owned, lv: _Level):
    return lax.dynamic_update_slice(dst, owned.astype(dst.dtype), lv.lo)


def _xla_box(lv: _Level, w, sign: float, has_p: bool = True):
    """``fn(q, p, dst) -> dst`` with ``p +- Box(q)`` in its owned cells
    (``p`` and ``dst`` may be one array; without ``p`` the box alone).

    What it assumes of the x axis: an inline level reads its x halo
    columns as they stand (the fill's, or a fixed axis's ghost); a tight-x
    level is the whole axis in its rows, and ``x -+ 1`` wraps where
    ``lv.wrap_x`` (a periodic x) and reads zero beyond the two ends where
    not (a fixed x). In y and z it reads the halo rows and planes as they
    stand."""

    def fn(q, p, dst):
        box = _box(q, lv, w)
        if not has_p:
            return _put(dst, box if sign > 0 else -box, lv)
        return _put(dst, _owned(p, lv) + box if sign > 0
                    else _owned(p, lv) - box, lv)

    return fn


def _halve(t, axis: int, start: int, m: int, wrap: bool):
    """Along one axis, ``0.5 t[2c] + t[2c + 1] + 0.5 t[2c + 2]`` for c in
    [0, m), index 0 at ``start``; ``wrap``: the axis holds its 2m cells
    alone and 2c + 2 wraps."""
    half = t.dtype.type(0.5)

    def take(first, count=m):
        return lax.slice_in_dim(t, start + first, start + first + 2 * count - 1,
                                stride=2, axis=axis)

    even, odd = take(0), take(1)
    nxt = jnp.roll(even, -1, axis=axis) if wrap else take(2)
    return half * (even + nxt) + odd


def _xla_rprj3(fine: _Level, coarse: _Level):
    """``fn(r_fine, r_coarse) -> r_coarse``: the full weighting as the
    product of (1/2, 1, 1/2) along each axis, halved once: z first (planes
    are cheap and the work halves), x last on a quarter of the cells."""

    def fn(rf, rc):
        (zo, yo, xo), (mz, my, mx) = fine.lo, coarse.n
        t = _halve(rf, 0, zo, mz, False)
        t = _halve(t, 1, yo, my, False)
        t = _halve(t, 2, 0 if fine.tight else xo, mx, fine.tight)
        return _put(rc, t.dtype.type(0.5) * t, coarse)

    return fn


def _double(t, axis: int, start: int, m: int, wrap: bool):
    """Along one axis, 2m cells from m: cell 2c the mean of t[c - 1] and
    t[c], cell 2c + 1 t[c] itself, index 0 at ``start``; ``wrap``: the
    axis holds its m cells alone and c - 1 wraps."""
    half = t.dtype.type(0.5)
    here = lax.slice_in_dim(t, start, start + m, axis=axis)
    below = (jnp.roll(here, 1, axis=axis) if wrap
             else lax.slice_in_dim(t, start - 1, start - 1 + m, axis=axis))
    both = jnp.stack([half * (below + here), here], axis=axis + 1)
    shape = list(here.shape)
    shape[axis] = 2 * m
    return both.reshape(shape)


def _xla_interp(coarse: _Level, fine: _Level, add: bool):
    """``fn(u_coarse, u_fine) -> u_fine``: the trilinear prolongation as a
    doubling along each axis, x first on the coarse cells and z last, ADDED
    to the fine level's owned cells or (``add`` false: the source zeroes
    the level first) put in their place."""

    def fn(uc, uf):
        (zo, yo, xo), (mz, my, mx) = coarse.lo, coarse.n
        t = _double(uc, 2, 0 if coarse.tight else xo, mx, coarse.tight)
        t = _double(t, 1, yo, my, False)
        t = _double(t, 0, zo, mz, False)
        return _put(uf, _owned(uf, fine) + t if add else t, fine)

    return fn


# ------------------------------------------------------------ the iteration


def _box_op(lv: _Level, name: str, w, sign: float, has_p: bool = True,
            separate_dst: bool = False, interpret: bool = False):
    """One of ``resid`` / ``psinv`` on one level as ``fn(q, p, dst) ->
    dst`` over (1, 1, 1, pz, py, px) blocks, and what implements it."""
    if lv.pallas and has_p:
        kernel = make_pallas_mg_box(
            lv.ex.spec, name, w, sign, separate_dst=separate_dst,
            interpret=interpret, vma=MESH_AXES)

        def fn(q, p, dst):
            with scopes.scope(scopes.CARRY):
                args = [a.reshape(lv.block) for a in
                        ((q, p, dst) if separate_dst else (q, p))]
            out = kernel(*args)
            with scopes.scope(scopes.CARRY):
                return out.reshape(dst.shape)

        return fn, "pallas"
    body = _xla_box(lv, w, sign, has_p)

    def fn(q, p, dst):
        with scopes.kernel_scope(name):
            return body(q.reshape(lv.block), None if p is None
                        else p.reshape(lv.block),
                        dst.reshape(lv.block)).reshape(dst.shape)

    return fn, "xla"


def _transfer(name: str, src: _Level, dst: _Level, add: bool = False,
              interpret: bool = False):
    """``rprj3`` (``src`` the fine level) or ``interp`` (``src`` the coarse
    one) as ``fn(a_src, b_dst) -> b_dst`` over (1, 1, 1, pz, py, px)
    blocks, and what implements it: the Pallas kernel between two levels
    that both take the box kernel, XLA otherwise."""
    fine, coarse = (src, dst) if name == "mg_rprj3" else (dst, src)
    if src.pallas and dst.pallas and transfer_supported(
            fine.ex.spec, coarse.ex.spec, jnp.float32):
        kernel = (make_pallas_mg_rprj3(fine.ex.spec, coarse.ex.spec,
                                       interpret=interpret, vma=MESH_AXES)
                  if name == "mg_rprj3" else
                  make_pallas_mg_interp(coarse.ex.spec, fine.ex.spec, add,
                                        interpret=interpret, vma=MESH_AXES))

        def fn(a, b):
            with scopes.scope(scopes.CARRY):
                a3, b3 = a.reshape(src.block), b.reshape(dst.block)
            out = kernel(a3, b3)
            with scopes.scope(scopes.CARRY):
                return out.reshape(b.shape)

        return fn, "pallas"
    body = (_xla_rprj3(fine, coarse) if name == "mg_rprj3"
            else _xla_interp(coarse, fine, add))

    def fn(a, b):
        with scopes.kernel_scope(name):
            return body(a.reshape(src.block),
                        b.reshape(dst.block)).reshape(b.shape)

    return fn, "xla"


def cycle_plan(levels: Sequence[_Level], impls: dict, itemsize: int) -> list:
    """Per level, finest first, what one iteration runs there: the grid,
    a block, the layout of its HBM slot, whether it is resident in the
    coarse call, and per operator what implements it, its calls an
    iteration, the least bytes a call moves (its own arrays' owned cells
    once each, a block) and the fills that follow with the halo bytes one
    fill writes (a resident level's are wraps in VMEM)."""
    top = len(levels)
    out = []
    for i, lv in enumerate(levels):
        k = lv.number
        cells = lv.n[0] * lv.n[1] * lv.n[2]
        held = 1
        for n, (rm, rp) in zip(lv.n, _radii(lv.ex.spec)):
            held *= n + rm + rp
        halo = (held - cells) * itemsize
        calls = {
            # the iteration's own resid at the top, one in the up-cycle
            "mg_resid": (2 if k == top else 1) if k > 1 else 0,
            "mg_psinv": 1,
            "mg_rprj3": 1 if k > 1 else 0,          # FROM this level
            "mg_interp": 1 if k > 1 else 0,         # ONTO this level
        }
        arrays = {"mg_resid": 3 * cells, "mg_psinv": 3 * cells,
                  "mg_rprj3": cells + cells // 8,
                  "mg_interp": cells // 8 + (2 if k == top else 1) * cells}
        g = lv.ex.spec.global_size
        out.append({
            "level": k, "grid": [g.z, g.y, g.x], "block": list(lv.n),
            "layout": "tight_x" if lv.tight else "inline",
            # u and r live in VMEM for the whole coarse call (``layout``
            # still says how the HBM slot lies, where they are written once)
            "resident": impls[(k, "mg_psinv")] == "resident",
            "operators": {
                name: {"impl": impls[(k, name)], "calls_per_iter": calls[name],
                       "bytes_min": arrays[name] * itemsize,
                       # rprj3's fill is of the level it writes, below
                       "fills_per_iter": 0 if name == "mg_rprj3"
                       else calls[name], "halo_bytes": halo}
                for name in OPERATORS if calls[name]},
            # fills of this level's arrays: after its resid, psinv and
            # interp, and after the rprj3 that writes its r from above
            "fills_per_iter": calls["mg_resid"] + calls["mg_psinv"]
            + calls["mg_interp"] + (1 if k < top else 0),
        })
    return out


def _radii(spec: GridSpec):
    r = spec.radius
    return ((r.z(-1), r.z(1)), (r.y(-1), r.y(1)), (r.x(-1), r.x(1)))


def _resident_from(levels: Sequence[_Level], dtype):
    """The index of the finest level that the coarse call keeps in VMEM
    (it and every level below), or ``None``: the first level off the
    tight-x layout, where the level above runs the box kernel and
    ``coarse_supported`` takes the specs (one block a level, fp32, the
    arrays fit)."""
    first = next((i for i, lv in enumerate(levels) if not lv.tight), 0)
    above = levels[first - 1]           # tight, as every level before it
    if first and above.pallas and coarse_supported(
            above.ex.spec, [lv.ex.spec for lv in levels[first:]], dtype):
        return first
    return None


def _coarse(levels: Sequence[_Level], first: int, smoother, interpret):
    """The levels from ``first`` down as ONE call, with both transfers to
    and from the level above: ``fn(r_above, u_above, us, rs) -> (u_above,
    us, rs)`` over (1, 1, 1, pz, py, px) blocks."""
    above, below = levels[first - 1], levels[first:]
    kernel = make_pallas_mg_coarse(
        above.ex.spec, [lv.ex.spec for lv in below], A, smoother,
        add=first == 1, interpret=interpret, vma=MESH_AXES)

    def fn(r_above, u_above, us, rs):
        with scopes.scope(scopes.CARRY):
            args = (r_above.reshape(above.block), u_above.reshape(above.block),
                    [a.reshape(lv.block) for a, lv in zip(us, below)],
                    [a.reshape(lv.block) for a, lv in zip(rs, below)])
        u_out, us_out, rs_out = kernel(*args)
        with scopes.scope(scopes.CARRY):
            return (u_out.reshape(u_above.shape),
                    [a.reshape(b.shape) for a, b in zip(us_out, us)],
                    [a.reshape(b.shape) for a, b in zip(rs_out, rs)])

    return fn


def _build(exchanges, smoother, dtype, use_pallas, interpret):
    """``(levels, ops, impls)``: the hierarchy's layouts finest first, per
    ``(k, operator)`` the per-block function (k NPB's level; ``resid_v``
    is the finest level's residual against v, which lands in r: three
    arrays; ``coarse``, on the level above the resident ones, the one call
    that runs them all), and per ``(k, kernel name)`` what implements it
    (``resident``: inside that call)."""
    top = len(exchanges)
    if top < 2:
        raise ValueError("a V-cycle takes two levels or more")
    levels = [_level(ex, top - i, dtype, use_pallas)
              for i, ex in enumerate(exchanges)]
    for fine, coarse in zip(levels, levels[1:]):
        if tuple(2 * m for m in coarse.n) != fine.n or \
                coarse.ex.spec.dim != fine.ex.spec.dim:
            raise ValueError(
                f"level {coarse.number}'s blocks {coarse.n} are not half "
                f"level {fine.number}'s {fine.n} on the same partition")
    impls, ops = {}, {}
    first = _resident_from(levels, dtype)
    if first is not None:
        k = levels[first - 1].number
        ops[(k, "coarse")] = _coarse(levels, first, smoother, interpret)
        impls[(k, "mg_rprj3")] = impls[(k, "mg_interp")] = "resident"
        for lv in levels[first:]:
            impls.update({(lv.number, name): "resident"
                          for name in OPERATORS})

    def build(i, name, *args, **kw):
        fn, impl = _box_op(levels[i], name, *args, interpret=interpret, **kw)
        impls[(levels[i].number, name)] = impl
        return fn

    for i, lv in enumerate(levels[:first]):         # all of them for None
        k = lv.number
        ops[(k, "psinv")] = build(i, "mg_psinv", smoother, 1.0, has_p=k > 1)
        if k == 1:
            continue
        ops[(k, "resid")] = build(i, "mg_resid", A, -1.0)
        if i + 1 == first:          # its transfers are the coarse call's
            continue
        ops[(k, "rprj3")], impls[(k, "mg_rprj3")] = _transfer(
            "mg_rprj3", lv, levels[i + 1], interpret=interpret)
        ops[(k, "interp")], impls[(k, "mg_interp")] = _transfer(
            "mg_interp", levels[i + 1], lv, add=k == top,
            interpret=interpret)
    ops[(top, "resid_v")] = build(0, "mg_resid", A, -1.0, separate_dst=True)
    return levels, ops, impls


def make_mg_iter(exchanges: Sequence[HaloExchange], smoother=S_LARGE,
                 dtype="float32", iters: int = 1, use_pallas=None,
                 interpret: bool = False):
    """``step(state, v) -> state`` over ``state = {"u": [...], "r":
    [...]}``, the stacked sharded arrays of every level FINEST FIRST
    (``exchanges``: each level's ``HaloExchange``, same order, same mesh),
    and ``v`` of the finest level: ``iters`` iterations (``mg3P`` then
    ``resid``) in one program. ``state`` is donated and every array comes
    back where it lay; ``v`` is read only. Every array's halos are valid on
    entry and on return, a resident level's too (module docstring): the
    coarse call writes it back whole, halos wrapped. The counter
    ``mg.cycle_plan`` says what was built: per level ``layout`` and
    ``resident``, per operator ``impl`` (pallas / xla / resident), and how
    far the coarse call reaches (``resident_levels``, ``resident_calls``
    and ``resident_fills`` of 34, ``resident_vmem_bytes``)."""
    dtype = jnp.dtype(dtype)
    levels, ops, impls = _build(exchanges, smoother, dtype, use_pallas,
                                interpret)
    top = len(levels)
    mesh = levels[0].ex.mesh
    first = next((i for i, lv in enumerate(levels)
                  if impls[(lv.number, "mg_psinv")] == "resident"), None)

    def at(i):
        return scopes.level_scope(levels[i].number)

    def filled(i, a):
        with at(i):
            return levels[i].ex.exchange_block(a)

    def one(u, r, v):
        u, r = list(u), list(r)
        # the last level with calls of its own: the 2^3 level, or the one
        # above the resident levels
        bottom = top - 1 if first is None else first - 1
        for i in range(bottom):                       # down
            with at(i):
                r[i + 1] = ops[(top - i, "rprj3")](r[i], r[i + 1])
            r[i + 1] = filled(i + 1, r[i + 1])
        if first is None:
            with at(bottom):
                u[bottom] = ops[(1, "psinv")](r[bottom], None, u[bottom])
            u[bottom] = filled(bottom, u[bottom])
            start = bottom - 1
        else:
            # down from ``bottom`` and back, its own interp included
            with at(first):
                u[bottom], u[first:], r[first:] = ops[
                    (top - bottom, "coarse")](r[bottom], u[bottom], u[first:],
                                              r[first:])
            start = bottom
        for i in range(start, -1, -1):                # up
            k = top - i
            if i < bottom:
                with at(i):
                    u[i] = ops[(k, "interp")](u[i + 1], u[i])
            u[i] = filled(i, u[i])
            with at(i):
                r[i] = (ops[(k, "resid_v")](u[i], v, r[i]) if i == 0
                        else ops[(k, "resid")](u[i], r[i], r[i]))
            r[i] = filled(i, r[i])
            with at(i):
                u[i] = ops[(k, "psinv")](r[i], u[i], u[i])
            u[i] = filled(i, u[i])
        with at(0):
            r[0] = ops[(top, "resid_v")](u[0], v, r[0])
        r[0] = filled(0, r[0])
        return u, r

    def entry_fn(u, r, v):
        for _ in range(iters):
            u, r = one(u, r, v)
        return u, r

    n = len(levels)
    sharded = jax.shard_map(
        entry_fn, mesh=mesh,
        in_specs=([BLOCK_PSPEC] * n, [BLOCK_PSPEC] * n, BLOCK_PSPEC),
        out_specs=([BLOCK_PSPEC] * n, [BLOCK_PSPEC] * n),
        check_vma=not interpret)

    def program(state, v):
        u, r = sharded(state["u"], state["r"], v)
        return {"u": u, "r": r}

    like = [jax.ShapeDtypeStruct(lv.ex.spec.stacked_shape_zyx(), dtype,
                                 sharding=lv.ex.sharding()) for lv in levels]
    plan = cycle_plan(levels, impls, dtype.itemsize)
    telemetry.get().counter(
        "mg.cycle_plan", value=iters, phase="compute", module=scopes.MG_ITER,
        levels=plan,
        # how far the coarse call reaches, of 34 calls and 34 fills
        resident_levels=sum(lv["resident"] for lv in plan),
        resident_calls=sum(
            op["calls_per_iter"] for lv in plan
            for op in lv["operators"].values() if op["impl"] == "resident"),
        resident_fills=sum(lv["fills_per_iter"] for lv in plan
                           if lv["resident"]),
        resident_vmem_bytes=0 if first is None else coarse_vmem_bytes(
            levels[first - 1].ex.spec, [lv.ex.spec for lv in levels[first:]]))
    return scopes.jit_loop(scopes.MG_ITER, program,
                           ({"u": like, "r": like}, like[0]),
                           donate_argnums=(0,))
