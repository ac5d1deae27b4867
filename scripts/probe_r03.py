"""Round-3 probes on the real chip.

1. VMEM scratch compile ceiling: at what explicit-scratch size does a
   trivial kernel stop compiling? (pins _SCRATCH_BUDGET headroom)
2. Astaroth substep tile ablation: same tile count at different shapes vs
   half the tile count — separates HBM-traffic cost from per-tile
   (DMA-descriptor / scalar-core) cost.

Usage: python scripts/probe_r03.py [vmem|tiles]
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def probe_vmem():
    for mb in (24, 28, 32, 36, 40, 44):
        n_planes = mb * 1024 * 1024 // (4 * 128 * 512)

        def kernel(x_hbm, o_hbm, scratch, sem):
            cp = pltpu.make_async_copy(x_hbm, scratch.at[0], sem)
            cp.start()
            cp.wait()
            scratch[1] = scratch[0] * 2.0
            cp2 = pltpu.make_async_copy(scratch.at[1], o_hbm, sem)
            cp2.start()
            cp2.wait()

        fn = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((128, 512), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((n_planes, 128, 512), jnp.float32),
                pltpu.SemaphoreType.DMA(()),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=128 * 1024 * 1024,
            ),
        )
        x = jnp.ones((128, 512), jnp.float32)
        t0 = time.time()
        try:
            out = jax.jit(fn)(x)
            out.block_until_ready()
            print(f"vmem {mb} MB ({n_planes} planes): OK "
                  f"(compile+run {time.time()-t0:.1f}s)", flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"vmem {mb} MB: FAIL {type(e).__name__}: {str(e)[:200]}",
                  flush=True)
            break


def probe_tiles():
    from stencil_tpu.astaroth.config import load_config
    from stencil_tpu.astaroth.equations import Constants
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.ops.pallas_astaroth import FIELDS, make_pallas_substep
    from stencil_tpu.utils.statistics import Statistics
    from stencil_tpu.utils.sync import hard_sync

    n = 256
    spec = GridSpec(Dim3(n, n, n), Dim3(1, 1, 1), Radius.constant(3))
    info, _ = load_config("stencil_tpu/astaroth/astaroth.conf")
    c = Constants.from_info(info)
    inv_ds = (
        info.real_params["AC_inv_dsx"],
        info.real_params["AC_inv_dsy"],
        info.real_params["AC_inv_dsz"],
    )
    p = spec.padded()
    rng = np.random.RandomState(7)
    curr = tuple(
        jnp.asarray(rng.rand(p.z, p.y, p.x) * 0.1, jnp.float32) for _ in FIELDS
    )
    out_np = rng.rand(p.z, p.y, p.x) * 0.1

    chunk = 60
    # sliding-window scratch at 256^3 (px=384): (2,64) 16.5 MB [pick];
    # (4,32) 15.3 MB; (4,64)/(8,32) 27.1 MB; (2,128)/(16,16) 30.7 MB
    for tiles in ((4, 32), (4, 64), (8, 32), (2, 128), (16, 16)):
        # fresh out buffers each variant: the timing loop donates them
        out = tuple(jnp.asarray(out_np, jnp.float32) for _ in FIELDS)
        try:
            sub = make_pallas_substep(spec, c, inv_ds, 1, 1e-8, tiles=tiles)

            def many(cu, ou):
                def body(_, o):
                    return sub(cu, o)
                return jax.lax.fori_loop(0, chunk, body, ou)

            fn = jax.jit(many, donate_argnums=(1,))
            t0 = time.time()
            out2 = fn(curr, out)
            hard_sync(out2)
            compile_s = time.time() - t0
            st = Statistics()
            for _ in range(3):
                t0 = time.perf_counter()
                out2 = fn(curr, out2)
                hard_sync(out2)
                st.insert((time.perf_counter() - t0) / chunk)
            print(
                f"tiles {tiles}: {st.trimean()*1e3:.2f} ms/substep "
                f"(compile {compile_s:.0f}s)", flush=True,
            )
        except Exception as e:  # noqa: BLE001
            print(f"tiles {tiles}: FAIL {type(e).__name__}: {str(e)[:300]}",
                  flush=True)




def probe_decomp():
    """Decompose substep cost: full vs trivial-physics (taps kept) vs
    trivial-derivatives (physics kept) at the best tile shape."""
    import stencil_tpu.ops.pallas_astaroth as pa
    from stencil_tpu.astaroth.config import load_config
    from stencil_tpu.astaroth.equations import Constants
    from stencil_tpu.astaroth.fd import FieldData, field_data
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.utils.statistics import Statistics
    from stencil_tpu.utils.sync import hard_sync

    n = 256
    # round-3 tight-x layout (the production single-chip path)
    spec = GridSpec(Dim3(n, n, n), Dim3(1, 1, 1), Radius.constant(3).without_x())
    info, _ = load_config("stencil_tpu/astaroth/astaroth.conf")
    c = Constants.from_info(info)
    inv_ds = (
        info.real_params["AC_inv_dsx"],
        info.real_params["AC_inv_dsy"],
        info.real_params["AC_inv_dsz"],
    )
    p = spec.padded()
    rng = np.random.RandomState(7)
    curr = tuple(
        jnp.asarray(rng.rand(p.z, p.y, p.x) * 0.1, jnp.float32) for _ in pa.FIELDS
    )
    out_np = rng.rand(p.z, p.y, p.x) * 0.1

    orig = dict(
        continuity=pa.continuity, momentum=pa.momentum,
        induction=pa.induction, entropy=pa.entropy, field_data=pa.field_data,
    )

    def trivial_physics():
        pa.continuity = lambda uu, l: l.laplace()
        pa.momentum = lambda c, uu, l, s, aa: tuple(u.laplace() for u in uu)
        pa.induction = lambda c, uu, aa: tuple(
            a.laplace() + a.hxy + a.hxz + a.hyz + a.gx + a.gy + a.gz
            for a in aa
        )
        pa.entropy = lambda c, s, uu, l, aa: s.laplace()

    def trivial_derivs():
        def fake(arr, rect, ids):
            val = arr[...,
                      slice(rect.lo.z, rect.hi.z),
                      slice(rect.lo.y, rect.hi.y),
                      slice(rect.lo.x, rect.hi.x)]
            k = [val * (1.0 + 0.01 * i) for i in range(14)]
            # fd.FieldData since PR 44: the diagonal with the gradient, the
            # y and z differences the mixed x derivatives are shifted from,
            # the view's x shift (none here) and deryz on demand
            return FieldData(*k[:7], dy=tuple(k[7:10]), dz=tuple(k[10:13]),
                             xshift=lambda v, d: v, inv_ds=tuple(ids),
                             hyz_of=lambda: k[13])
        pa.field_data = fake

    chunk = 60
    for label, setup in (("full", None), ("triv-phys", trivial_physics),
                         ("triv-derivs", trivial_derivs)):
        for k, v in orig.items():
            setattr(pa, k, v)
        if setup:
            setup()
        try:
            sub = pa.make_pallas_substep(spec, c, inv_ds, 1, 1e-8)
            out = tuple(jnp.asarray(out_np, jnp.float32) for _ in pa.FIELDS)

            def many(cu, ou):
                return jax.lax.fori_loop(0, chunk, lambda _, o: sub(cu, o), ou)

            fn = jax.jit(many, donate_argnums=(1,))
            t0 = time.time()
            out2 = fn(curr, out)
            hard_sync(out2)
            cs = time.time() - t0
            st = Statistics()
            for _ in range(3):
                t0 = time.perf_counter()
                out2 = fn(curr, out2)
                hard_sync(out2)
                st.insert((time.perf_counter() - t0) / chunk)
            print(f"decomp {label}: {st.trimean()*1e3:.2f} ms/substep "
                  f"(compile {cs:.0f}s)", flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"decomp {label}: FAIL {type(e).__name__}: {str(e)[:300]}",
                  flush=True)
    for k, v in orig.items():
        setattr(pa, k, v)


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    print("devices:", jax.devices(), flush=True)
    if which in ("vmem", "all"):
        probe_vmem()
    if which in ("tiles", "all"):
        probe_tiles()
    if which == "decomp":
        probe_decomp()
