"""D3Q19 lattice-Boltzmann, one stream-collide pass: the lattice, the BGK
update and its Pallas TPU kernel.

The state is the post-collision populations ``f_i(x)``, one array a
velocity ``c_i`` (:data:`VELOCITIES`, weights :data:`WEIGHTS`; the lattice
of SPEC CPU2006 470.lbm's ``lbm.c`` and of FluidX3D's ``D3Q19``). One step,
pull form, from the current lattice into the next:

    g_i   = f_i(x - c_i)                      (stream: ONE offset a population)
    rho   = sum_i g_i,   rho u = sum_i c_i g_i
    e_i   = w_i rho (1 + 3 c_i.u + 9/2 (c_i.u)^2 - 3/2 u.u)
    f_i(x) <- g_i - omega (g_i - e_i)         (collide: BGK, one rate)

:func:`collide` is the arithmetic, written once for the kernel's row chunks
and for the XLA form's whole blocks (``ops/lbm.py``).

The kernel (:func:`make_pallas_lbm_step`) is 19 arrays read and 19 written,
where no other kernel of the package has more than 8 and 8. One grid step
is one z plane of the block on the tight-x layout: population ``i``'s
plane ``z - c_iz`` comes in through its own ``BlockSpec`` (the z offset is
a plane index, so the pipeline streams 19 planes, each read once), the y
offset is a row load at a static offset of -1, 0 or +1 into that plane
(its one halo row is the row the exchange filled on the side ``-c_iy``),
and the x offset a lane roll of whole rows (x is periodic inside the
block: no x halo exists). The 19 new planes go out through ``BlockSpec``s
aliased onto the next lattice. A 384 x 384 plane with its halo rows is
614 KB, so the 38 double-buffered streams hold 47 MB of VMEM: over the
default scoped limit, hence ``vmem_limit_bytes``.

What ROADMAP C7's kernel generator would have to express for this one: N
input arrays each read at ONE offset (a plane index, a row offset, a lane
roll), N outputs, and a body over all N at once; the four stencil kernels
there are each read ONE array at many offsets.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..domain.grid import GridSpec
from ..obs import scopes

LANE = 128
_ROWS = 8           # rows of a plane the body computes at a time

# c_i as (x, y, z): rest, the six axis vectors, the twelve with exactly two
# non-zero components; no corner vector. Population 2k is the opposite of
# 2k - 1.
VELOCITIES = (
    (0, 0, 0),
    (1, 0, 0), (-1, 0, 0), (0, 1, 0), (0, -1, 0), (0, 0, 1), (0, 0, -1),
    (1, 1, 0), (-1, -1, 0), (1, -1, 0), (-1, 1, 0),
    (1, 0, 1), (-1, 0, -1), (1, 0, -1), (-1, 0, 1),
    (0, 1, 1), (0, -1, -1), (0, 1, -1), (0, -1, 1),
)
Q = len(VELOCITIES)
W_REST, W_AXIS, W_DIAGONAL = 1.0 / 3.0, 1.0 / 18.0, 1.0 / 36.0
WEIGHTS = (W_REST,) + (W_AXIS,) * 6 + (W_DIAGONAL,) * 12


def omega_of(nu: float) -> float:
    """The BGK relaxation rate of kinematic viscosity ``nu`` in lattice
    units: ``1 / (3 nu + 1/2)``."""
    return 1.0 / (3.0 * float(nu) + 0.5)


def _tree_sum(terms):
    terms = list(terms)
    while len(terms) > 1:
        terms = [a + b for a, b in zip(terms[::2], terms[1::2])] + (
            [terms[-1]] if len(terms) % 2 else [])
    return terms[0]


def collide(g: Sequence, omega: float):
    """The 19 relaxed populations of the 19 streamed ones ``g`` (arrays of
    one shape, :data:`VELOCITIES`' order). A velocity and its opposite
    share the even part of their equilibria, so a pair costs one square."""
    rho = _tree_sum(g)
    mom = []
    for axis in range(3):
        plus = _tree_sum(g[i] for i, c in enumerate(VELOCITIES) if c[axis] > 0)
        minus = _tree_sum(g[i] for i, c in enumerate(VELOCITIES)
                          if c[axis] < 0)
        mom.append(plus - minus)
    inv = 1.0 / rho
    u = [m * inv for m in mom]
    base = 1.0 - 1.5 * (u[0] * u[0] + u[1] * u[1] + u[2] * u[2])
    keep = 1.0 - omega
    rate = {w: (omega * w) * rho for w in (W_REST, W_AXIS, W_DIAGONAL)}
    out = [None] * Q
    out[0] = keep * g[0] + rate[W_REST] * base
    for i in range(1, Q, 2):
        cu = _tree_sum(u[a] if c > 0 else -u[a]
                       for a, c in enumerate(VELOCITIES[i]) if c)
        even = base + 4.5 * (cu * cu)
        odd = 3.0 * cu
        w = rate[WEIGHTS[i]]
        out[i] = keep * g[i] + w * (even + odd)
        out[i + 1] = keep * g[i + 1] + w * (even - odd)
    return out


def step_supported(spec: GridSpec, dtype) -> bool:
    """Whether the kernel takes this block layout: aligned fp32 blocks of a
    uniform partition on the tight-x layout (x whole, no x halo, rows a
    multiple of the 128-lane tile), halos of 1 or more in y and z, owned
    rows a multiple of the 8-row tile starting on one."""
    if not spec.aligned or dtype != jnp.float32 or not spec.is_uniform():
        return False
    r, o, p, b = spec.radius, spec.compute_offset(), spec.padded(), spec.base
    if r.x(-1) or r.x(1) or spec.dim.x != 1 or b.x % LANE or p.x != b.x:
        return False
    if min(r.y(-1), r.y(1), r.z(-1), r.z(1)) < 1:
        return False
    return b.y % _ROWS == 0 and o.y % _ROWS == 0 and o.y + b.y < p.y


def make_pallas_lbm_step(spec: GridSpec, omega: float,
                         interpret: bool = False, vma=None):
    """Build ``fn(*curr, *nxt) -> (new_0, .., new_18)`` over padded ``(pz,
    py, px)`` fp32 blocks: one stream-collide pass from the 19 current
    populations (halos filled on the side each is read from) into the 19
    of the next lattice, which are aliased to the results and not read.
    Owned cells are written; a result plane's rows outside them are
    zeroed (they are halo and padding, which the next exchange fills where
    anything reads them), and its halo planes keep what they hold."""
    if not step_supported(spec, jnp.float32):
        raise ValueError("pallas lbm step unsupported on this spec")
    omega = float(omega)
    p, off, b = spec.padded(), spec.compute_offset(), spec.base
    pz, py, px = p.z, p.y, p.x
    zo, yo = off.z, off.y
    nz, ny = b.z, b.y
    # traced ONCE: the body below calls it for each of a plane's row
    # chunks, and 48 traces of its 200 operations are most of a build
    relax = jax.jit(lambda *g: collide(g, omega))

    def kernel(*refs):
        ins, outs = refs[:Q], refs[2 * Q:]
        for row in range(0, ny, _ROWS):
            g = []
            for ref, (cx, cy, _cz) in zip(ins, VELOCITIES):
                v = ref[pl.ds(yo + row - cy, _ROWS), :]
                g.append(pltpu.roll(v, cx % px, 1) if cx else v)
            at = pl.ds(yo + row, _ROWS)
            for ref, new in zip(outs, relax(*g)):
                ref[at, :] = new
        for start, stop in ((0, yo), (yo + ny, py)):
            edge = jnp.zeros((stop - start, px), jnp.float32)
            for ref in outs:
                ref[pl.ds(start, stop - start), :] = edge

    plane = (None, py, px)
    shape = jax.ShapeDtypeStruct(
        (pz, py, px), jnp.float32,
        vma=frozenset(vma) if vma is not None else None)
    return scopes.kernel_call(
        "lbm_d3q19", kernel,
        grid=(nz,),
        out_shape=(shape,) * Q,
        in_specs=[pl.BlockSpec(plane, lambda s, cz=c[2]: (s + zo - cz, 0, 0))
                  for c in VELOCITIES]
        + [pl.BlockSpec(memory_space=pl.ANY)] * Q,
        out_specs=[pl.BlockSpec(plane, lambda s: (s + zo, 0, 0))] * Q,
        input_output_aliases={Q + i: i for i in range(Q)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
    )
