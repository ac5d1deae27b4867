"""iso3dfd's leapfrog step over a realized domain: exchange the halos of
``prev`` (the one field of three whose neighbours the star reads), then one
sweep that writes ``next`` in place, then the two change roles.

    lap  = c0 * prev + sum_{r=1..8} c_r * (prev[x+-r] + prev[y+-r] + prev[z+-r])
    next = 2 * prev - next + lap * vel

(Intel oneAPI samples, ``iso3dfd_dpcpp``: src/iso3dfd.cpp, include/
iso3dfd.h; 16th order in space, 2nd in time.) The domain's ring of 8 cells
is the halo beyond a FIXED boundary: the exchange never writes it and the
sweep writes owned cells only, so it keeps what it was seeded with.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..obs import scopes, telemetry
from ..parallel.exchange import HaloExchange, Method
from ..parallel.mesh import BLOCK_PSPEC, MESH_AXES, mesh_dim
from ..plan.ir import build_plan
from . import double_buffer
from .pallas_iso3dfd import (RADIUS, kernel_plan, make_pallas_iso3dfd_step,
                             step_supported)

# the sample's constants (include/iso3dfd.h, src/iso3dfd.cpp main())
DT = 0.002
DXYZ = 50.0
COEFF = (-3.0548446, +1.7777778, -3.1111111e-1, +7.572087e-2,
         -1.76767677e-2, +3.480962e-3, -5.180005e-4, +5.074287e-5,
         -2.42812e-6)
QUANTITIES = 3          # prev, next, vel
EXCHANGED = 1           # of them, a step exchanges prev alone


def coefficients():
    """``(c0, c_1, ..., c_8)`` as the sample forms them: over ``dxyz^2``,
    the centre weight once for each of the three axes."""
    inv = 1.0 / (DXYZ * DXYZ)
    return (3.0 * COEFF[0] * inv,) + tuple(c * inv for c in COEFF[1:])


def uses_pallas(ex: HaloExchange, use_pallas, dtype) -> bool:
    """The kernel on an all-TPU mesh whose block layout it supports (or
    where a caller forces it, e.g. interpreted on the CPU)."""
    if use_pallas is False:
        return False
    if not step_supported(ex.spec, jnp.dtype(dtype)):
        return False
    return bool(use_pallas) or all(
        d.platform == "tpu" for d in ex.mesh.devices.flatten())


def _xla_sweep(spec, coeffs):
    """The same update as shifted slices of the padded block: the path of
    a mesh without TPUs and of layouts the kernel does not take."""
    o, b = spec.compute_offset(), spec.base
    lo = (o.z, o.y, o.x)
    n = (b.z, b.y, b.x)

    def cut(a, axis=None, shift=0):
        start = [0, 0, 0] + [s + (shift if i == axis else 0)
                             for i, s in enumerate(lo)]
        return lax.slice(a, start, [1, 1, 1] + [s + m for s, m in
                                                zip(start[3:], n)])

    def sweep(prev, nxt, vel):
        ctr = cut(prev)
        lap = coeffs[0] * ctr
        for r in range(1, RADIUS + 1):
            lap = lap + coeffs[r] * sum(
                cut(prev, axis, s) for axis in (2, 1, 0) for s in (r, -r))
        new = (2.0 * ctr - cut(nxt)) + lap * cut(vel)
        return lax.dynamic_update_slice(
            nxt, new.astype(nxt.dtype), (0, 0, 0) + lo)

    return sweep


def halo_bytes_if_all(ex: HaloExchange, itemsize: int) -> int:
    """What a chip would send a step were this domain periodic on every
    axis, every quantity exchanged, edges and corners carried: the plan of
    the defaults, for the byte counter."""
    plan = build_plan(ex.spec, mesh_dim(ex.mesh), ex.method)
    return plan.wire_bytes([itemsize] * QUANTITIES) // ex.mesh.devices.size


def make_iso3dfd_step(ex: HaloExchange, iters: int = 1, use_pallas=None,
                      interpret: bool = False, dtype="float32",
                      tiles: Optional[tuple] = None):
    """``step(prev, nxt, vel) -> (prev, nxt)`` over stacked sharded arrays:
    ``iters`` leapfrog steps in one program, both wave fields donated and
    updated where they lie (``ops/double_buffer``), ``vel`` only read."""
    if ex.method != Method.AXIS_COMPOSED:
        raise ValueError("iso3dfd steps through Method.AXIS_COMPOSED")
    spec = ex.spec
    if not spec.is_uniform():
        # the sweep covers the base block: a shorter block's fixed ring
        # lies inside it and would be overwritten
        raise ValueError(
            f"iso3dfd: blocks of {spec.sizes_x} x {spec.sizes_y} x "
            f"{spec.sizes_z} cells: an uneven split is not supported")
    dtype = jnp.dtype(dtype)
    coeffs = coefficients()
    pallas_on = uses_pallas(ex, use_pallas, dtype)
    if pallas_on:
        kernel = make_pallas_iso3dfd_step(
            spec, coeffs, interpret=interpret, vma=MESH_AXES, tiles=tiles)
        block = spec.block_shape_zyx()

        def sweep(prev, nxt, vel):
            with scopes.scope(scopes.CARRY):
                args = [a.reshape(block) for a in (prev, nxt, vel)]
            out = kernel(*args)
            with scopes.scope(scopes.CARRY):
                return out.reshape(nxt.shape)
    else:
        sweep = _xla_sweep(spec, [dtype.type(c) for c in coeffs])

    def entry_fn(prev, nxt, vel):
        def one(pair):
            prev, nxt = pair
            prev = ex.exchange_block(prev)
            return sweep(prev, nxt, vel), prev

        return double_buffer.repeat(one, iters, (prev, nxt))

    fn = jax.shard_map(
        entry_fn, mesh=ex.mesh, in_specs=(BLOCK_PSPEC,) * 3,
        out_specs=(BLOCK_PSPEC,) * 2, check_vma=not interpret)
    like = jax.ShapeDtypeStruct(spec.stacked_shape_zyx(), dtype,
                                sharding=ex.sharding())
    telemetry.get().counter(
        "iso3dfd.step_plan", value=iters, phase="compute",
        module=scopes.ISO3DFD_LOOP, blocks=spec.dim.flatten(), radius=RADIUS,
        quantities=QUANTITIES, exchanged=EXCHANGED,
        periodic=list(ex.periodic), faces_only=ex.faces_only, chunk=iters,
        block_cells=spec.base.flatten(),
        halo_bytes_sent=ex.plan.wire_bytes([dtype.itemsize] * EXCHANGED)
        // ex.mesh.devices.size,
        halo_bytes_if_all=halo_bytes_if_all(ex, dtype.itemsize),
        **(kernel_plan(spec, tiles) if pallas_on else {}))
    return double_buffer.jit_in_place(scopes.ISO3DFD_LOOP, fn,
                                      (like, like, like), (iters,))
