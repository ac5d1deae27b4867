"""Seeded fields and sampled boxes: the benchmark's data, made where it is used.

Every value is a pure function of ``(seed, quantity, global z, y, x)``,
written once for any array module: ``jax.numpy`` makes the state on the
device, shard by shard and with the domain's own sharding (no global array
is ever built on the host), and ``numpy`` makes the same values for the
few cells a reference needs. The two agree bit for bit, because the hash is
32-bit integer arithmetic and the last step (24 bits into a float32) is
exact.

The only things taken from the program here are the facts of a realized
domain's layout (``GridSpec``: global size, block size, radius, compute
offset, stacked shape) and its sharding.
"""

from __future__ import annotations

import numpy as np

_M1, _M2 = 0x85EBCA6B, 0xC2B2AE35          # murmur3's finalizer
_KZ, _KY, _KX, _KQ = 0x9E3779B1, 0x7FEB352D, 0x846CA68B, 0x27D4EB2F
GARBAGE = 0x5BD1E995                        # salt of what halos start with


def _mix(xp, h):
    h = h ^ (h >> 16)
    h = h * xp.uint32(_M1)
    h = h ^ (h >> 13)
    h = h * xp.uint32(_M2)
    return h ^ (h >> 16)


def _hashed(xp, base, z, y, x):
    """float32 in [0, 1) from a uint32 ``base`` and integer coordinates."""
    u = xp.uint32
    h = _mix(xp, z.astype(u) * u(_KZ) + base)
    h = _mix(xp, h ^ (y.astype(u) * u(_KY)))
    h = _mix(xp, h ^ (x.astype(u) * u(_KX)))
    return (h >> 8).astype(xp.float32) * xp.float32(1.0 / (1 << 24))


def uniform(xp, seed: int, q: int, z, y, x, salt: int = 0):
    """float32 in [0, 1) for integer coordinate arrays ``z, y, x`` (any
    broadcastable shapes). ``seed`` may exceed 32 bits."""
    lo, hi = (int(w) for w in seed_words(seed))
    base = (lo ^ ((hi * _KQ) & 0xFFFFFFFF) ^ ((int(q) * _KQ) & 0xFFFFFFFF)
            ^ int(salt)) & 0xFFFFFFFF
    with np.errstate(over="ignore"):
        return _hashed(xp, xp.uint32(base), z, y, x)


# ------------------------------------------------------------ on the device


def _axes(spec):
    """Per data axis (z, y, x): (block dim, base, offset, r-, r+, global)."""
    g, b, r, off = (spec.global_size, spec.base, spec.radius,
                    spec.compute_offset())
    return ((0, b.z, off.z, r.z(-1), r.z(1), g.z),
            (1, b.y, off.y, r.y(-1), r.y(1), g.y),
            (2, b.x, off.x, r.x(-1), r.x(1), g.x))


def _cells(spec):
    """(wrapped global z, y, x), owned mask, held mask, raw (block-major)
    coordinates over the stacked shape. Uniform partitions only."""
    import jax.numpy as jnp
    from jax import lax

    if not spec.is_uniform():
        raise ValueError("seeded fields need a uniform partition")
    shape = spec.stacked_shape_zyx()
    coords, raw, owned, held = [], [], True, True
    for bdim, base, o, rm, rp, glob in _axes(spec):
        bi = lax.broadcasted_iota(jnp.int32, shape, bdim)
        li = lax.broadcasted_iota(jnp.int32, shape, bdim + 3)
        coords.append(jnp.mod(bi * base + li - o, glob))
        raw.append(bi * shape[bdim + 3] + li)
        owned = owned & (li >= o) & (li < o + base)
        held = held & (li >= o - rm) & (li < o + base + rp)
    return coords, owned, held, raw


def make_fill(spec, sharding, dtype="float32"):
    """``fill(seed_lo, seed_hi, q) -> stacked array`` born with ``sharding``:
    owned cells hold ``uniform(seed, q, global coordinate)``; every other
    allocated cell (halo, alignment pad) holds garbage the program must
    overwrite or ignore. One compile serves every seed and quantity."""
    import jax
    import jax.numpy as jnp

    def fill(seed, q):
        (gz, gy, gx), owned, _, (rz, ry, rx) = _cells(spec)
        good = _uniform_traced(seed, q, gz, gy, gx, 0)
        junk = _uniform_traced(seed, q, rz, ry, rx, GARBAGE)
        return jnp.where(owned, good, junk).astype(dtype)

    return jax.jit(fill, out_shardings=sharding)


def make_halo_check(spec, sharding):
    """``check(arr, seed, q) -> (wrong, halo)``: over every owned and halo
    cell (faces, edges, corners), how many differ from the periodically
    wrapped source value, and how many halo cells were looked at."""
    import jax
    import jax.numpy as jnp

    def check(arr, seed, q):
        (gz, gy, gx), owned, held, _ = _cells(spec)
        want = _uniform_traced(seed, q, gz, gy, gx, 0).astype(arr.dtype)
        return (jnp.sum((arr != want) & held, dtype=jnp.int32),
                jnp.sum(held & ~owned, dtype=jnp.int32))

    return jax.jit(check, in_shardings=(sharding, None, None))


def _uniform_traced(seed, q, z, y, x, salt):
    """``uniform`` with ``seed`` a traced uint32[2] (lo, hi) and ``q`` a
    traced uint32, so that one program serves all seeds."""
    import jax.numpy as jnp

    u = jnp.uint32
    base = seed[0] ^ (seed[1] * u(_KQ)) ^ (q * u(_KQ)) ^ u(salt)
    return _hashed(jnp, base, z, y, x)


def seed_words(seed: int):
    seed = int(seed)
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)


def make_all_finite(spec, sharding):
    """``finite(arr) -> bool`` over every owned cell. Halo and alignment
    cells are the program's scratch: what they hold between exchanges is
    its own business (interpret-mode kernels leave NaN there)."""
    import jax
    import jax.numpy as jnp

    def finite(a):
        _, owned, _, _ = _cells(spec)
        return jnp.all(jnp.isfinite(a) | ~owned)

    return jax.jit(finite, in_shardings=(sharding,))


# ------------------------------------------------------------ sampled boxes


def plan_boxes(global_zyx, dims_zyx, core, seed: int, n_random: int,
               through=()):
    """Origins (global z, y, x of the core's first cell) of the sampled
    boxes: one whose core crosses the periodic wrap on every axis, one
    whose core crosses a block boundary on every split axis, one through
    each point of ``through`` (the jacobi sphere's surface), the rest drawn
    from the seed."""
    g = np.asarray(global_zyx)
    c = np.asarray(core)
    boxes = [tuple(int(v) for v in g - c // 2)]             # periodic wrap
    if any(d > 1 for d in dims_zyx):
        base = g // np.asarray(dims_zyx)
        o = [int(b - h) if d > 1 else int(b // 3)
             for b, h, d in zip(base, c // 2, dims_zyx)]
        boxes.append(tuple(o))                              # block boundary
    for p in through:
        boxes.append(tuple(int(v) for v in (np.asarray(p) - c // 2) % g))
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    for _ in range(n_random):
        boxes.append(tuple(int(rng.randint(0, n)) for n in g))
    return boxes


def box_coords(origin, core, margin, global_zyx):
    """Wrapped global coordinates (three 1-D arrays) of a box's core grown
    by ``margin`` on every side."""
    return [np.mod(np.arange(o - margin, o + n + margin), g)
            for o, n, g in zip(origin, core, global_zyx)]


class BoxReader:
    """Reads the cores of sampled boxes back from a stacked sharded array:
    small slices cut on the device that holds them, never a whole field."""

    def __init__(self, spec):
        import jax
        from jax import lax

        self.spec = spec
        self._axes = _axes(spec)
        def cut(a, start, size):
            zero = start[0] * 0          # one index type, x64 on or off
            return lax.dynamic_slice(
                a, (zero, zero, zero, start[0], start[1], start[2]),
                (1, 1, 1) + size)

        self._slice = jax.jit(cut, static_argnums=2)

    def _segments(self, origin, core):
        """Per axis: [(block, local start, length, offset in core)]."""
        out = []
        for (_, base, off, _, _, glob), o, n in zip(self._axes, origin, core):
            segs, done = [], 0
            while done < n:
                gpos = (o + done) % glob
                blk, loc = divmod(gpos, base)
                ln = min(n - done, base - loc)
                segs.append((blk, off + loc, ln, done))
                done += ln
            out.append(segs)
        return out

    def read(self, arr, origin, core):
        """Every slice cut on the device has the core's own shape (clamped
        into the block and cropped on the host), so one compiled program
        serves every box of every seed, wherever it straddles blocks."""
        shards = {tuple(s.start or 0 for s in sh.index[:3]): sh.data
                  for sh in arr.addressable_shards}
        padded = self.spec.block_shape_zyx()
        core = tuple(int(n) for n in core)
        res = np.empty(core, np.dtype(arr.dtype))
        sz, sy, sx = self._segments(origin, core)
        for bz, lz, nz, oz in sz:
            for by, ly, ny, oy in sy:
                for bx, lx, nx, ox in sx:
                    start = [min(l, p - n) for l, p, n in
                             zip((lz, ly, lx), padded, core)]
                    got = np.asarray(self._slice(
                        shards[(bz, by, bx)], np.array(start, np.int32),
                        core))[0, 0, 0]
                    dz, dy, dx = lz - start[0], ly - start[1], lx - start[2]
                    res[oz:oz + nz, oy:oy + ny, ox:ox + nx] = got[
                        dz:dz + nz, dy:dy + ny, dx:dx + nx]
        return res
