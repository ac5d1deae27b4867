"""Dynamic-offset boundary shells: comm/compute overlap on uneven partitions.

The reference computes per-LocalDomain interior/exterior regions for uneven
subdomains as a matter of course (reference: src/stencil.cu:878-977 — each
rank owns its own extents, so the slabs are just different constants per
rank). Under ``shard_map`` one program is traced for every block, so
per-block extents cannot be Python constants — but they ARE static per
block *index*: along each axis the remainder rule makes trailing blocks one
cell smaller (domain/grid.py:_axis_sizes). This module turns that into
traced-but-shape-static geometry:

- :func:`dyn_block_sizes` reads this block's logical sizes with
  ``lax.axis_index`` lookups into the per-axis size tables (a scalar gather,
  free next to the stencil);
- :func:`shell_regions` lists the boundary shells (one per side of each
  included axis) as ``(lo, size)`` pairs where ``size`` is static (slab
  thickness = that side's radius, cross-section = the base extents) and
  ``lo`` is traced only on the high side of an uneven axis;
- :func:`interior_mask` is the masked-interior-write companion: a boolean
  over the (static) compute extents that is True where a stencil of the
  face radii reads no halo cell of an included axis.

Shells overlap at edges/corners; every patch recomputes from the same
exchanged source, so double-written cells get identical values and the
patch order is immaterial. Cross-sections span the *base* extents: on an
uneven partner axis the overhang lands in the block's dead pad tail
(grid.py:39), never in another block's data.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax.numpy as jnp
from jax import lax

from ..domain.grid import GridSpec
from ..obs import scopes


def dyn_block_sizes(spec: GridSpec):
    """This block's logical (z, y, x) sizes inside ``shard_map``: traced
    table lookups on uneven axes, Python ints elsewhere."""
    from ..parallel.mesh import AXIS_X, AXIS_Y, AXIS_Z

    out = []
    for name, d, szs, base in (
        (AXIS_Z, spec.dim.z, spec.sizes_z, spec.base.z),
        (AXIS_Y, spec.dim.y, spec.sizes_y, spec.base.y),
        (AXIS_X, spec.dim.x, spec.sizes_x, spec.base.x),
    ):
        if d > 1 and min(szs) != max(szs):
            with scopes.scope(scopes.SWEEP_SHELL):
                out.append(jnp.asarray(szs, jnp.int32)[lax.axis_index(name)])
        else:
            out.append(base)
    return tuple(out)


def shell_regions(spec: GridSpec, sizes, include: Sequence[bool]):
    """Boundary shells to re-sweep from exchanged halos.

    ``sizes`` is :func:`dyn_block_sizes`'s (z, y, x); ``include`` is a
    (z, y, x) boolean triple — which axes' sides need patching (all axes for
    paths whose pre-exchange pass read stale periodic halos; multi-block
    axes only when self-wrap is filled in-kernel). Returns ``(lo, size)``
    pairs in array (z, y, x) order; ``size`` entries are Python ints."""
    off = spec.compute_offset()
    o = (off.z, off.y, off.x)
    base = (spec.base.z, spec.base.y, spec.base.x)
    r = spec.radius
    rad = (r.z, r.y, r.x)
    regs = []
    for ax in range(3):
        if not include[ax]:
            continue
        r_lo, r_hi = rad[ax](-1), rad[ax](1)
        if r_lo > 0:
            lo = list(o)
            size = list(base)
            size[ax] = r_lo
            regs.append((_i32(lo), tuple(size)))
        if r_hi > 0:
            lo = list(o)
            size = list(base)
            lo[ax] = o[ax] + sizes[ax] - r_hi
            size[ax] = r_hi
            regs.append((_i32(lo), tuple(size)))
    return regs


def _i32(lo):
    # uniform start dtype: mixed Python-int / traced-int32 starts trip
    # dynamic_slice's same-dtype requirement under x64 (cf. exchange._starts)
    return tuple(jnp.asarray(v, jnp.int32) for v in lo)


def interior_mask(spec: GridSpec, sizes, include: Sequence[bool]):
    """Boolean over the (base.z, base.y, base.x) compute extents: True where
    a face-radius stencil reads no halo of an included axis. The
    masked-interior write (out = where(mask, new, old)) replaces the
    shrunk-extent interior sweep when extents are per-block."""
    shape = (spec.base.z, spec.base.y, spec.base.x)
    r = spec.radius
    rad = (r.z, r.y, r.x)
    with scopes.scope(scopes.MASK):
        m = jnp.ones(shape, jnp.bool_)
        for ax in range(3):
            if not include[ax]:
                continue
            rel = lax.broadcasted_iota(jnp.int32, shape, ax)
            m = m & (rel >= rad[ax](-1)) & (rel < sizes[ax] - rad[ax](1))
        return m


def include_axes(spec: GridSpec, multi_block_only: bool) -> Tuple[bool, bool, bool]:
    """(z, y, x) axis-include triple for :func:`shell_regions` /
    :func:`interior_mask`."""
    if not multi_block_only:
        return (True, True, True)
    return (spec.dim.z > 1, spec.dim.y > 1, spec.dim.x > 1)
