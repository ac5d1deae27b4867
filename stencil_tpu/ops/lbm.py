"""The D3Q19 lattice-Boltzmann step over a realized domain: exchange the 19
populations of the current lattice, each on the ONE side it is read from,
then one stream-collide pass into the next lattice, then the two change
roles (``ops/double_buffer``: SPEC 470.lbm's two grids and their swap).

Population ``i`` is read at ``x - c_i`` and nowhere else, so of its 26
halos it wants the face on the side ``-c_i`` of each axis where ``c_i`` is
not zero and the one edge between two such faces, and no corner; the rest
population wants none (:func:`population_radius`: the radius ``add_data``
takes for it). An axis phase then carries 5 populations a direction where
a plan of one radius carries 19 both ways.

The pass is the Pallas kernel (``ops/pallas_lbm.py``) where a block lies
tight-x with whole lane tiles, and the same update in XLA
(:func:`_xla_pass`) for every other block: a mesh without TPUs, a split x
axis (the x halo inline, radius 1), rows that are not whole lane tiles.
Which, is in the counter ``lbm.step_plan``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..geometry import Radius
from ..obs import scopes, telemetry
from ..parallel.exchange import HaloExchange, Method
from ..parallel.mesh import BLOCK_PSPEC, MESH_AXES, mesh_dim
from ..plan.ir import build_plan
from . import double_buffer
from .pallas_lbm import (Q, VELOCITIES, WEIGHTS, collide,
                         make_pallas_lbm_step, omega_of, step_supported)

__all__ = ["Q", "VELOCITIES", "WEIGHTS", "domain_radius", "make_lbm_step",
           "omega_of", "population_radius", "uses_pallas"]


def domain_radius(tight_x: bool) -> Radius:
    """What a D3Q19 domain allocates: one cell on every face and edge, no
    corner; on the tight-x layout nothing along x."""
    r = Radius.face_edge_corner(1, 1, 0)
    return r.without_x() if tight_x else r


def population_radius(i: int, tight_x: bool = False,
                      edges: bool = True) -> Radius:
    """The halos population ``i`` is read from: the faces on the side
    ``-c_i`` and, with ``edges``, the one edge between two of them."""
    c = VELOCITIES[i]
    r = Radius()
    axes = [a for a in range(3) if c[a]]
    for a in axes:
        d = [0, 0, 0]
        d[a] = -c[a]
        r.set_dir(tuple(d), 1)
    if edges and len(axes) == 2:
        r.set_dir(tuple(-v for v in c), 1)
    return r.without_x() if tight_x else r


def is_tight(spec) -> bool:
    """The tight-x layout: x whole in the block and no x halo allocated."""
    return (spec.dim.x == 1 and not spec.radius.x(-1)
            and not spec.radius.x(1))


def uses_pallas(ex: HaloExchange, use_pallas, dtype) -> bool:
    """The kernel on an all-TPU mesh whose block layout it supports (or
    where a caller forces it, e.g. interpreted on the CPU)."""
    if use_pallas is False:
        return False
    if not step_supported(ex.spec, jnp.dtype(dtype)):
        return False
    return bool(use_pallas) or all(
        d.platform == "tpu" for d in ex.mesh.devices.flatten())


def _xla_pass(spec, omega):
    """The same update as shifted slices of the padded blocks: population
    ``i``'s owned cells displaced by ``-c_i`` (into its halo on that side;
    along a tight x, a roll of the block's own cells)."""
    o, b = spec.compute_offset(), spec.base
    tight = is_tight(spec)

    def pulled(a, c):
        cx, cy, cz = c
        start = [0, 0, 0, o.z - cz, o.y - cy, o.x - (0 if tight else cx)]
        g = lax.slice(a, start, [1, 1, 1, start[3] + b.z, start[4] + b.y,
                                 start[5] + b.x])
        return jnp.roll(g, cx, axis=5) if tight and cx else g

    def run(curr, nxt):
        new = collide([pulled(a, c) for a, c in zip(curr, VELOCITIES)], omega)
        return [lax.dynamic_update_slice(old, f.astype(old.dtype),
                                         (0, 0, 0, o.z, o.y, o.x))
                for old, f in zip(nxt, new)]

    return run


def _keys(ex: HaloExchange) -> list:
    """The exchange's state keys in population order: its quantities as
    they were declared, or 0..18 under one radius."""
    return list(ex.quantity_radius) if ex.quantity_radius else list(range(Q))


def _carried(ex: HaloExchange) -> dict:
    """``{"y-": [populations], ...}``: what each direction of each active
    axis phase fills, by the exchange's own plan."""
    keys = _keys(ex)
    out = {}
    for ph in ex.plan.axis_phases:
        if not ph.active:
            continue
        for sign, side in zip("-+", ph.sides or (None, None)):
            out[ph.axis + sign] = (list(range(Q)) if side is None
                                   else [keys.index(k) for k in side.keys])
    return out


def halo_bytes(ex: HaloExchange, itemsize: int) -> dict:
    """A chip's bytes an exchange by the plan (``sent``: what its slabs
    carry, over the wire or in place; ``wire``: the part that crosses
    chips) and what a plan of ONE radius, all 19 on both sides, carries
    (``if_all``, ``wire_if_all``)."""
    chips = ex.mesh.devices.size
    every = [itemsize] * Q
    sizes = (dict.fromkeys(_keys(ex), itemsize) if ex.quantity_radius
             else every)
    whole = build_plan(ex.spec, mesh_dim(ex.mesh), ex.method)
    wire, wire_if_all = ex.plan.wire_bytes(sizes), whole.wire_bytes(every)
    return {
        "sent": (wire + ex.plan.local_bytes(sizes)) // chips,
        "wire": wire // chips,
        "if_all": (wire_if_all + whole.local_bytes(every)) // chips,
        "wire_if_all": wire_if_all // chips,
    }


def make_lbm_step(ex: HaloExchange, omega: float, dtype="float32",
                  iters: int = 1, use_pallas: Optional[bool] = None,
                  interpret: bool = False):
    """``step(curr, nxt) -> (curr, nxt)`` over two lattices, each a list of
    19 stacked sharded arrays (:data:`VELOCITIES`' order; the exchange's
    quantities in the order they were declared): ``iters`` steps in ONE
    program, both lattices donated and updated where they lie. ``curr``'s
    halos need not be valid on entry: every step exchanges first."""
    if ex.method != Method.AXIS_COMPOSED:
        raise ValueError("lbm steps through Method.AXIS_COMPOSED")
    keys = _keys(ex)
    if len(keys) != Q:
        raise ValueError(f"the exchange has {len(keys)} quantities; a D3Q19 "
                         f"lattice has {Q}")
    spec = ex.spec
    dtype = jnp.dtype(dtype)
    omega = float(omega)
    pallas_on = uses_pallas(ex, use_pallas, dtype)
    if pallas_on:
        kernel = make_pallas_lbm_step(spec, omega, interpret=interpret,
                                      vma=MESH_AXES)
        block = spec.block_shape_zyx()

        def run(curr, nxt):
            with scopes.scope(scopes.CARRY):
                args = [a.reshape(block) for a in list(curr) + list(nxt)]
            out = kernel(*args)
            with scopes.scope(scopes.CARRY):
                return [a.reshape(b.shape) for a, b in zip(out, nxt)]
    else:
        xla = _xla_pass(spec, dtype.type(omega))

        def run(curr, nxt):
            with scopes.kernel_scope("lbm_d3q19"):
                return xla(curr, nxt)

    def entry_fn(curr, nxt):
        def one(pair):
            curr, nxt = pair
            filled = ex.exchange_blocks(dict(zip(keys, curr)))
            curr = [filled[k] for k in keys]
            return run(curr, nxt), curr

        return double_buffer.repeat(one, iters, (list(curr), list(nxt)))

    fn = jax.shard_map(
        entry_fn, mesh=ex.mesh, in_specs=([BLOCK_PSPEC] * Q,) * 2,
        out_specs=([BLOCK_PSPEC] * Q,) * 2, check_vma=not interpret)
    like = [jax.ShapeDtypeStruct(spec.stacked_shape_zyx(), dtype,
                                 sharding=ex.sharding())] * Q
    moved = halo_bytes(ex, dtype.itemsize)
    telemetry.get().counter(
        "lbm.step_plan", value=iters, phase="compute",
        module=scopes.LBM_STEP, blocks=spec.dim.flatten(),
        layout="tight_x" if is_tight(spec) else "inline",
        kernel="pallas" if pallas_on else "xla", chunk=iters,
        block_cells=spec.base.flatten(), carried=_carried(ex),
        halo_bytes_sent=moved["sent"], halo_bytes_if_all=moved["if_all"],
        halo_bytes_wire=moved["wire"],
        halo_bytes_wire_if_all=moved["wire_if_all"])
    return double_buffer.jit_in_place(scopes.LBM_STEP, fn, (like, like),
                                      (iters,))
