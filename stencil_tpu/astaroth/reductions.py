"""Global reductions over the distributed domain.

TPU-native analogue of Astaroth's three-phase device reductions
(reference: astaroth/reductions.cuh:1-60 — max/min/rms/sum over scalar
fields and vector magnitudes). On TPU a reduction is one jitted
``shard_map`` with a masked local reduce and a ``psum``/``pmax`` over the
mesh; the reference's multi-kernel tree reduction is XLA's job.

The pad-and-mask layout requires masking: pad-tail and halo cells must not
contribute. The mask is built from the per-axis logical sizes.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..domain.grid import GridSpec
from ..parallel.exchange import BLOCK_PSPEC, HaloExchange
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import AXIS_X, AXIS_Y, AXIS_Z

_AXES = (AXIS_Z, AXIS_Y, AXIS_X)


def compute_mask(spec: GridSpec) -> np.ndarray:
    """Stacked bool array marking owned compute cells of every block."""
    mask = np.zeros(spec.stacked_shape_zyx(), dtype=bool)
    off = spec.compute_offset()
    for iz in range(spec.dim.z):
        for iy in range(spec.dim.y):
            for ix in range(spec.dim.x):
                s = spec.block_size((ix, iy, iz))
                mask[
                    iz, iy, ix,
                    off.z : off.z + s.z,
                    off.y : off.y + s.y,
                    off.x : off.x + s.x,
                ] = True
    return mask


class Reductions:
    """Compiled scalar/vector reductions over a domain's stacked arrays."""

    def __init__(self, ex: HaloExchange):
        self.ex = ex
        # from the host straight to each device's shard: a jnp.asarray
        # first would stage the whole mask on the default device
        self.mask = jax.device_put(compute_mask(ex.spec), ex.sharding())
        self._scal = jax.jit(self._build_scal())
        self._vec = jax.jit(self._build_vec())

    def _build_scal(self):
        def fn(arr, mask):
            m = mask
            neg_inf = -jnp.inf
            vmax = lax.pmax(jnp.max(jnp.where(m, arr, neg_inf)), _AXES)
            vmin = lax.pmin(jnp.min(jnp.where(m, arr, jnp.inf)), _AXES)
            vsum = lax.psum(jnp.sum(jnp.where(m, arr, 0.0)), _AXES)
            vsq = lax.psum(jnp.sum(jnp.where(m, arr * arr, 0.0)), _AXES)
            count = lax.psum(jnp.sum(m), _AXES)
            return vmax, vmin, vsum, jnp.sqrt(vsq / count)

        return jax.shard_map(
            fn,
            mesh=self.ex.mesh,
            in_specs=(BLOCK_PSPEC, BLOCK_PSPEC),
            out_specs=(P(), P(), P(), P()),
        )

    def _build_vec(self):
        def fn(x, y, z, mask):
            mag = jnp.sqrt(x * x + y * y + z * z)
            m = mask
            vmax = lax.pmax(jnp.max(jnp.where(m, mag, -jnp.inf)), _AXES)
            vmin = lax.pmin(jnp.min(jnp.where(m, mag, jnp.inf)), _AXES)
            vsum = lax.psum(jnp.sum(jnp.where(m, mag, 0.0)), _AXES)
            vsq = lax.psum(jnp.sum(jnp.where(m, mag * mag, 0.0)), _AXES)
            count = lax.psum(jnp.sum(m), _AXES)
            return vmax, vmin, vsum, jnp.sqrt(vsq / count)

        return jax.shard_map(
            fn,
            mesh=self.ex.mesh,
            in_specs=(BLOCK_PSPEC,) * 4,
            out_specs=(P(), P(), P(), P()),
        )

    # reference: RTYPE_MAX / RTYPE_MIN / RTYPE_SUM / RTYPE_RMS
    def scal(self, arr):
        vmax, vmin, vsum, rms = self._scal(arr, self.mask)
        return {
            "max": float(vmax),
            "min": float(vmin),
            "sum": float(vsum),
            "rms": float(rms),
        }

    def vec(self, x, y, z):
        vmax, vmin, vsum, rms = self._vec(x, y, z, self.mask)
        return {
            "max": float(vmax),
            "min": float(vmin),
            "sum": float(vsum),
            "rms": float(rms),
        }
