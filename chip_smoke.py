#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no children. Drives the main path once through the entry
points a user would call, at sizes users of a stencil library call real,
on the TPU this process finds (one chip, or the four chips of one host):

- jacobi3d 512^3 fp32 (``apps.jacobi3d.run``): tight-x layout, temporal
  multistep kernel; checked against the numpy reference at 128^3 through
  the same kernels and against the XLA path at 512^3;
- jacobi3d 768^3 fp32: the row-tiled multistep, checked against the XLA
  path at 768^3;
- halo exchange 512^3, radius 3, 4 x fp32 (``DistributedDomain.exchange``):
  the Pallas self-fills, every halo cell of every quantity verified;
- Astaroth 256^3, 8 x fp32, radius 3 (``apps.astaroth.run``): the three
  fused RK3 substep kernels; finite, and equal to the XLA path at 64^3;
- serving: 16 jacobi jobs of 64^3 through ``serve.ServeScheduler``, each
  result bit-identical to ``campaign.run_sequential`` on the same chip;
- with four chips: weak-scaled jacobi3d (global 512x1024x1024 on (1,2,2),
  multi-block tight-x, overlap on), weak-scaled Astaroth (global
  256x512x512 on (1,2,2): fused substeps after ONE batched exchange; equal
  to the XLA path at 128^3 a chip with the overlap shells asked for and
  without) and the r3 4 x fp32 exchange at 512^3
  per chip with every halo cell verified — ``ppermute`` over ICI — plus a
  small global size against the numpy reference, four addressable shards
  per array and balanced device memory.

It fails at once, non-zero, when ``jax.devices()[0].platform`` is not
``tpu``; any phase that fails makes the run exit non-zero after the other
phases have run; the last line of stdout is the pass line
``{"ok": true, "device": {...}}`` only when every phase this machine can
run has passed. ``--rehearsal`` walks the same phase functions on the CPU
at tiny sizes with interpret-mode kernels: it prints ``rehearsal``, never
the pass line, and never exits 0.

    python chip_smoke.py                # on the chip
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python chip_smoke.py --rehearsal
"""

from __future__ import annotations

import argparse
import functools
import importlib.metadata
import json
import os
import statistics
import sys
import tempfile
import time
import traceback

import jax

NO_TPU_RC = 2
REHEARSAL_RC = 3
PARTIAL_RC = 4

_PRIME = 16777213  # largest prime below 2**24: every pattern value is exact in fp32


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ------------------------------------------------------------ observation


class PallasRecorder:
    """Records every ``pl.pallas_call`` built while active: which kernel,
    its grid, and whether it is an interpret-mode build. The smoke asserts
    the kernel path from what was actually built, not from the selection
    helpers (``_want_pallas`` / ``_self_fills``) that chose it."""

    def __enter__(self):
        from jax.experimental import pallas as pl

        self._pl, self._orig, self.calls = pl, pl.pallas_call, []

        def recording(kernel, *args, **kw):
            gs = kw.get("grid_spec")
            self.calls.append({
                "kernel": kernel.__qualname__.split(".")[0],
                "name": kw.get("name"),
                "grid": tuple(kw.get("grid") or getattr(gs, "grid", ())),
                "interpret": bool(kw.get("interpret", False)),
            })
            return self._orig(kernel, *args, **kw)

        pl.pallas_call = recording
        return self

    def __exit__(self, *exc):
        self._pl.pallas_call = self._orig

    def of(self, kernel: str) -> list:
        return [c for c in self.calls if c["kernel"] == kernel]


def require_compiled_kernels(rec: PallasRecorder, kernels, rehearsal: bool):
    """Every listed kernel family was built, and (on the chip) none of the
    recorded builds is an interpret-mode one — libtpu compiled them all."""
    missing = [k for k in kernels if not rec.of(k)]
    assert not missing, f"kernels never built: {missing}; built {rec.calls}"
    # the names the profiler will show them under (obs/scopes.KERNELS)
    say(f"kernels built, by name: {sorted({str(c['name']) for c in rec.calls})}")
    if not rehearsal:
        interp = [c for c in rec.calls if c["interpret"]]
        assert not interp, f"interpret-mode kernels on the chip: {interp}"


def require_balanced(devs, what: str, tol: float = 0.10) -> list:
    """Per-device ``bytes_in_use`` and ``peak_bytes_in_use`` within ``tol``
    of each other — no chip staged another chip's share."""
    keys = ("bytes_in_use", "peak_bytes_in_use")
    stats = [{k: int(d.memory_stats()[k]) for k in keys} for d in devs]
    for key in keys:
        vals = [s[key] for s in stats]
        assert min(vals) > 0 and max(vals) <= (1 + tol) * min(vals), (
            f"{what}: {key} unbalanced across chips: {vals}")
    return stats


def require_four_shards(arr, devs, what: str) -> None:
    shards = arr.addressable_shards
    on = {s.device for s in shards}
    assert len(shards) == len(devs) and on == set(devs), (
        f"{what}: {len(shards)} shards on {sorted(d.id for d in on)}, "
        f"want one on each of {sorted(d.id for d in devs)}")


# ------------------------------------------------------------ jacobi


@functools.lru_cache(maxsize=2)
def _sel(size):
    """``sphere_sel(size)``, computed once per size: at 768^3 the host
    takes longer over it than the chip over the whole phase."""
    from stencil_tpu.ops.jacobi import sphere_sel

    return sphere_sel(size)


def _masks(size):
    sel = _sel(size)
    return sel == 1, sel == 2


def _random_field(size, seed: int):
    import numpy as np

    return np.random.RandomState(seed).rand(size.z, size.y, size.x).astype(
        np.float32)


def _run_loop(ex, loop, field, size):
    """``field`` advanced by ``loop`` on ``ex``'s layout, back on the host."""
    import numpy as np

    from stencil_tpu.parallel.exchange import shard_blocks, unshard_blocks

    curr = shard_blocks(field, ex.spec, ex.mesh)
    nxt = shard_blocks(np.zeros_like(field), ex.spec, ex.mesh)
    sel = shard_blocks(_sel(size), ex.spec, ex.mesh)
    curr, nxt = loop(curr, nxt, sel)
    return unshard_blocks(curr, ex.spec)


def _jacobi_exchange(size, dim, devs, tight_x: bool, radius: int = 1):
    """An exchange of ``radius`` halos. ``tight_x`` is the
    layout ``jacobi3d.run`` realizes on TPU devices (zero x radius,
    single-block x axis; the rehearsal builds it by hand because ``run``
    only chooses it on a TPU); otherwise inline halos on every axis, which
    the XLA path needs."""
    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Radius
    from stencil_tpu.parallel import HaloExchange, grid_mesh

    r = Radius.constant(radius)
    spec = GridSpec(size, dim, r.without_x() if tight_x else r)
    return HaloExchange(spec, grid_mesh(dim, devs))


def _multistep_depth(rec: PallasRecorder, nz: int):
    """(k, row_tiled, grid) of the temporal multistep that was built, k
    read off its grid: the wavefront runs J = nz + 2k steps, once a strip."""
    rows = rec.of("_make_multistep_row_tiled")
    full = rec.of("make_pallas_jacobi_multistep")
    assert rows or full, f"no temporal multistep built: {rec.calls}"
    grid = (rows or full)[-1]["grid"]
    return (grid[-1] - nz) // 2, bool(rows), grid


def _check_spheres(out, size, what: str) -> None:
    import numpy as np

    from stencil_tpu.ops.jacobi import COLD_TEMP, HOT_TEMP

    hot, cold = _masks(size)
    assert np.isfinite(out).all(), f"{what}: non-finite cells"
    assert (out[hot] == HOT_TEMP).all() and (out[cold] == COLD_TEMP).all(), (
        f"{what}: hot/cold sphere cells changed")


def phase_jacobi(devs, n: int, rehearsal: bool, *, ref_n=None,
                 want_rows: bool = False, time_sync: bool = False,
                 chunk=12) -> dict:
    """jacobi3d at ``n``^3 on one chip through ``apps.jacobi3d.run``, then
    the same kernels against numpy (at ``ref_n``^3) and against the XLA
    path (at ``n``^3), at the temporal depth (and rows) that ``chunk``
    iterations a dispatch give; ``chunk=None`` is the application's own
    default, what the benchmark's cells run."""
    import numpy as np

    from stencil_tpu.apps import jacobi3d
    from stencil_tpu.geometry import Dim3
    from stencil_tpu.ops.jacobi import (_want_pallas, jacobi_reference,
                                        make_jacobi_loop)

    one = Dim3(1, 1, 1)
    dev = devs[:1]
    # rehearsal: x stays a lane multiple so the tight-x kernels can run
    size = Dim3(128, n, n) if rehearsal else Dim3(n, n, n)
    facts = {}

    # 1. the function main() calls; 60 iterations are whole chunks of 12 and
    # of the default 10, so no tail chunk compiles inside run()'s timed loop
    with PallasRecorder() as rec:
        r = jacobi3d.run(size.x, size.y, size.z, weak=False, devices=dev,
                         iters=60, chunk=chunk)
    ex = r["domain"].halo_exchange
    k = min(12, (size.z - 1) // 2)
    if not rehearsal:
        rx = ex.spec.radius
        assert rx.x(-1) == 0 and rx.x(1) == 0, f"not tight-x: {rx}"
        assert ex.spec == _jacobi_exchange(size, one, dev, True).spec
        assert _want_pallas(ex, None)
        require_compiled_kernels(rec, [], rehearsal)
        k, row_tiled, grid = _multistep_depth(rec, size.z)
        assert k >= 2, f"temporal multistep did not engage (k={k})"
        assert row_tiled == want_rows, (
            f"row-tiled staging {row_tiled}, expected {want_rows}")
        facts.update(temporal_k=k, row_tiled=row_tiled, grid=list(grid),
                     mcells_per_s=round(r["mcells_per_s"], 1))
    _check_spheres(r["domain"].get_curr_global(r["handle"]), size,
                   f"jacobi3d.run {size}")
    del r

    def kernel_loop(exch, iters):
        # the chip takes the users' selection (use_pallas=None ->
        # _want_pallas, asserted above); the rehearsal forces interpret
        return make_jacobi_loop(exch, iters,
                                use_pallas=True if rehearsal else None,
                                interpret=rehearsal)

    # 2. the same kernels against the plain numpy reference: one full
    # temporal block from a random field, on the layout run() chose
    if ref_n is not None:
        rsize = Dim3(128, ref_n, ref_n) if rehearsal else Dim3(ref_n, ref_n,
                                                               ref_n)
        rk = min(12, (rsize.z - 1) // 2)
        rex = _jacobi_exchange(rsize, one, dev, True)
        field = _random_field(rsize, 1)
        with PallasRecorder() as rec:
            got = _run_loop(rex, kernel_loop(rex, rk), field, rsize)
        require_compiled_kernels(rec, ["make_pallas_jacobi_multistep"],
                                 rehearsal)
        want = jacobi_reference(field, _masks(rsize), rk)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        facts["numpy_ref"] = f"{rsize} x {rk} steps ok"

    # 3. Pallas path against the XLA path, one full temporal block
    if rehearsal:
        ex = _jacobi_exchange(size, one, dev, True)
    field = _random_field(size, 2)
    loop = kernel_loop(ex, k)
    got = _run_loop(ex, loop, field, size)
    if time_sync:
        facts["sync"] = _time_sync(ex, loop, field, size)
    del loop
    ex_xla = _jacobi_exchange(size, one, dev, False)
    want = _run_loop(ex_xla, make_jacobi_loop(ex_xla, k, use_pallas=False),
                     field, size)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    _check_spheres(got, size, f"pallas {size}")
    facts["vs_xla"] = (f"{k} steps, max|diff| "
                       f"{float(np.abs(got - want).max()):.3g}, "
                       f"bit-identical {bool(np.array_equal(got, want))}")
    return facts


def _time_sync(ex, loop, field, size) -> dict:
    """One fused chunk timed both ways: ``jax.block_until_ready`` and the
    scalar-fetch ``hard_sync``. ``fetch_after_bur`` is a ``hard_sync``
    issued right after ``block_until_ready`` returned: were readiness
    reported early, the remaining work would show up there."""
    import numpy as np

    from stencil_tpu.parallel.exchange import shard_blocks
    from stencil_tpu.utils.sync import hard_sync

    curr = shard_blocks(field, ex.spec, ex.mesh)
    nxt = shard_blocks(np.zeros_like(field), ex.spec, ex.mesh)
    sel = shard_blocks(_sel(size), ex.spec, ex.mesh)
    t = {"block_until_ready": [], "hard_sync": [], "fetch_after_bur": []}
    for _ in range(7):
        t0 = time.perf_counter()
        curr, nxt = loop(curr, nxt, sel)
        jax.block_until_ready(curr)
        t1 = time.perf_counter()
        hard_sync(curr)
        t2 = time.perf_counter()
        curr, nxt = loop(curr, nxt, sel)
        hard_sync(curr)
        t3 = time.perf_counter()
        t["block_until_ready"].append(t1 - t0)
        t["fetch_after_bur"].append(t2 - t1)
        t["hard_sync"].append(t3 - t2)
    return {f"{k}_ms": round(1e3 * statistics.median(v), 4)
            for k, v in t.items()}


# ------------------------------------------------------------ exchange


def _pattern_fns(spec, sharding):
    """``(fill, check)`` for the coordinate pattern on the stacked layout
    (the idiom of ``__graft_entry__._dryrun_direct26``, built on the
    device so no chip stages the global array): cell (gz, gy, gx) of
    quantity q holds ``(linear index + 7919 q) mod _PRIME`` — exact in
    fp32. ``fill(q)`` writes compute cells only (halos zero);
    ``check(arr, q)`` counts, over every compute and halo cell (faces,
    edges and corners), the cells that differ from the periodically
    wrapped source coordinate, and the halo cells it looked at."""
    import jax.numpy as jnp
    from jax import lax

    g, b, r = spec.global_size, spec.base, spec.radius
    off = spec.compute_offset()
    shape = spec.stacked_shape_zyx()
    assert spec.is_uniform()

    def axis(bdim, base, o, rm, rp, glob):
        bi = lax.broadcasted_iota(jnp.int32, shape, bdim)
        li = lax.broadcasted_iota(jnp.int32, shape, bdim + 3)
        owned = (li >= o) & (li < o + base)
        held = (li >= o - rm) & (li < o + base + rp)
        return jnp.mod(bi * base + li - o, glob), owned, held

    def cells(q):
        gz, oz, hz = axis(0, b.z, off.z, r.z(-1), r.z(1), g.z)
        gy, oy, hy = axis(1, b.y, off.y, r.y(-1), r.y(1), g.y)
        gx, ox, hx = axis(2, b.x, off.x, r.x(-1), r.x(1), g.x)
        want = jnp.mod(gz * (g.y * g.x) + gy * g.x + gx + 7919 * q, _PRIME)
        return want.astype(jnp.float32), oz & oy & ox, hz & hy & hx

    def fill(q):
        want, owned, _ = cells(q)
        return jnp.where(owned, want, 0.0)

    def check(arr, q):
        want, owned, held = cells(q)
        bad = jnp.sum((arr != want) & held, dtype=jnp.int32)
        return bad, jnp.sum(held & ~owned, dtype=jnp.int32)

    return (jax.jit(fill, out_shardings=sharding),
            jax.jit(check, in_shardings=(sharding, None)))


def phase_exchange(devs, size, partition, rehearsal: bool) -> dict:
    """The exchange_weak configuration (radius 3, four fp32 quantities)
    through ``DistributedDomain`` -> ``exchange()``, every halo cell of
    every quantity verified."""
    from stencil_tpu.api import DistributedDomain
    from stencil_tpu.parallel import NodeAware

    nq = 4
    multi = len(devs) > 1
    dd = DistributedDomain(size.x, size.y, size.z)
    dd.set_radius(3)
    dd.set_devices(devs)
    dd.set_partition(partition)
    # exchange_weak's default placement: QAP over the halo volumes and the
    # chips' ICI distances (device.coords, parallel/device_topo.py)
    dd.set_placement(NodeAware())
    handles = [dd.add_data(f"q{i}", "float32") for i in range(nq)]
    with PallasRecorder() as rec:
        dd.realize()
        fill, check = _pattern_fns(dd.spec, dd.sharding())
        for q, h in enumerate(handles):
            dd.set_curr(h, fill(q))
        if multi:
            for h in handles:
                require_four_shards(dd.get_curr(h), devs, f"exchange {h.name}")
        facts = {}
        if multi and not rehearsal:
            facts["bytes_after_init"] = require_balanced(devs, "exchange init")
        before = [check(dd.get_curr(h), q) for q, h in enumerate(handles)]
        dd.exchange()
    ex = dd.halo_exchange
    if not rehearsal:
        # single-block axes must take the Pallas self-fills, built here
        single = [a for a, d in zip("xyz", (partition.x, partition.y,
                                            partition.z)) if d == 1]
        assert sorted(ex._self_fills) == sorted(single), (
            f"self-fills {sorted(ex._self_fills)} != single-block axes "
            f"{single}")
        want = ["make_self_fill"]
        if partition.x > 1:
            # a split lane axis packs and unpacks on the edge lane-tiles
            want += ["make_split_x_pack", "make_split_x_unpack"]
        require_compiled_kernels(rec, want, rehearsal)
        assert len(rec.of("make_self_fill")) >= len(single)
    halo_cells = 0
    for q, h in enumerate(handles):
        bad0, n_halo = (int(v) for v in before[q])
        bad, _ = (int(v) for v in check(dd.get_curr(h), q))
        # the checker must have seen the unfilled halos as wrong, or a
        # zero count afterwards would prove nothing
        assert bad0 > 0.99 * n_halo > 0, (q, bad0, n_halo)
        assert bad == 0, f"quantity {q}: {bad} wrong cells after exchange"
        halo_cells += n_halo
    if multi:
        for h in handles:
            require_four_shards(dd.get_curr(h), devs, f"exchange {h.name}")
        if not rehearsal:
            facts["bytes_after_run"] = require_balanced(devs, "exchange run")
    facts.update(halo_cells_verified=halo_cells,
                 self_fills=sorted(ex._self_fills),
                 mesh=[[d.id, list(getattr(d, "coords", ()))]
                       for d in dd.mesh.devices.flat])
    return facts


# ------------------------------------------------------------ astaroth


def phase_astaroth(devs, nx: int, ref_nx: int, rehearsal: bool) -> dict:
    """Astaroth 8 x fp32 radius 3 through ``apps.astaroth.run``: finite at
    ``nx``^3, and equal to the XLA path at ``ref_nx``^3 to the tolerance
    tests/test_pallas_astaroth.py uses."""
    import numpy as np

    from stencil_tpu.apps import astaroth
    from stencil_tpu.astaroth.integrate import uses_pallas

    dev = devs[:1]
    kernels = ["make_pallas_substep"]

    with PallasRecorder() as rec:
        r = astaroth.run(iters=3, nx=nx, dtype="float32", devices=dev)
    facts = {"iter_ms": round(1e3 * r["iter_trimean_s"], 3)}
    if not rehearsal:
        assert uses_pallas(r["domain"].halo_exchange, None, "float32")
        require_compiled_kernels(rec, kernels, rehearsal)
        assert len(rec.of("make_pallas_substep")) == 3
        facts["fills"] = sorted(r["domain"].halo_exchange._self_fills)
    for name, f in _astaroth_fields(r).items():
        assert np.isfinite(f).all(), f"astaroth {nx}^3: {name} not finite"
    del r
    facts["vs_xla"] = _astaroth_vs_xla(dev, ref_nx, rehearsal)
    return facts


def _astaroth_fields(r) -> dict:
    from stencil_tpu.astaroth.integrate import FIELDS

    return {k: r["domain"].get_curr_global(r["handles"][k]) for k in FIELDS}


def _astaroth_vs_xla(devs, nx: int, rehearsal: bool,
                     schedules=((None, "serial"),)) -> str:
    """Three iterations at ``nx``^3 a device through ``apps.astaroth.run``
    on ``devs``: the fused kernels against the XLA path, every cell of every
    field, to the tolerance tests/test_pallas_astaroth.py uses; once for
    each ``(overlap, mode)`` of ``schedules``: what ``run()`` is asked (None:
    it picks by itself) and the mode its step plan must then record."""
    import numpy as np

    from stencil_tpu.apps import astaroth
    from stencil_tpu.astaroth.integrate import FIELDS, make_astaroth_step
    from stencil_tpu.obs import telemetry

    want = astaroth.run(iters=2, nx=nx, dtype="float32", devices=devs,
                        use_pallas=False)
    b = _astaroth_fields(want)
    said = []
    for overlap, want_mode in schedules:
        with PallasRecorder() as rec:
            if rehearsal:
                # run() only takes the fused kernels on a TPU: drive the same
                # step builder with interpret kernels over the same 3
                # iterations
                got = astaroth.run(iters=0, nx=nx, dtype="float32",
                                   devices=devs, use_pallas=False,
                                   no_compute=True)
                dd, hs = got["domain"], got["handles"]
                step = make_astaroth_step(dd.halo_exchange, got["info"],
                                          iters=3, use_pallas=True,
                                          interpret=True, overlap=overlap)
                curr, _ = step({k: dd.get_curr(hs[k]) for k in FIELDS},
                               {k: dd.get_next(hs[k]) for k in FIELDS})
                for k in FIELDS:
                    dd.set_curr(hs[k], curr[k])
            else:
                got = astaroth.run(iters=2, nx=nx, dtype="float32",
                                   devices=devs, overlap=overlap)
        require_compiled_kernels(rec, ["make_pallas_substep"], rehearsal)
        mode = telemetry.get().records(
            kind="counter", name="astaroth.step_plan")[-1]["mode"]
        assert mode == want_mode, (overlap, mode, want_mode)
        a = _astaroth_fields(got)
        for k in FIELDS:
            np.testing.assert_allclose(
                a[k], b[k], rtol=1e-4, atol=1e-5,
                err_msg=f"astaroth {nx}^3 field {k}, overlap={overlap}")
        diff = max(float(np.abs(a[k] - b[k]).max()) for k in FIELDS)
        said.append(f"overlap={overlap}: plan {mode}, max|diff| {diff:.3g}")
        del got
    return f"{want['global']} x 3 iterations; " + "; ".join(said)


# ------------------------------------------------------------ serving


def phase_serve(devs, n: int, jobs: int, rehearsal: bool) -> dict:
    """``jobs`` jacobi jobs of ``n``^3 dropped into a serve directory and
    drained by ``ServeScheduler.serve()``; every result bit-identical to
    ``campaign.run_sequential`` on the same chip."""
    import numpy as np

    from stencil_tpu.campaign import TenantJob, run_sequential
    from stencil_tpu.serve import ServeScheduler

    dev = devs[:1]
    steps = 8
    with tempfile.TemporaryDirectory(prefix="smoke-serve-") as sdir:
        incoming = os.path.join(sdir, "jobs", "incoming")
        os.makedirs(incoming)
        for i in range(jobs):
            doc = {"job": f"s-{i:04d}", "size": n, "steps": steps,
                   "dtype": "float32", "workload": "jacobi", "seed": i,
                   "tenant": f"tenant-{i % 4}", "priority": "normal"}
            tmp = os.path.join(incoming, f".tmp-{i}")
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, os.path.join(incoming, f"{doc['job']}.json"))
        t0 = time.perf_counter()
        out = ServeScheduler(sdir, 8, devices=dev, chunk=2, poll_s=0.05,
                             max_idle_s=0.5).serve()
        wall = time.perf_counter() - t0
    assert out["retired"] == jobs, f"retired {out['retired']}/{jobs}"
    with PallasRecorder() as rec:
        seq = run_sequential(
            [TenantJob(f"s-{i:04d}", (n, n, n), steps, "float32", seed=i)
             for i in range(jobs)], devices=dev, chunk=2)
    require_compiled_kernels(rec, [], rehearsal)
    for tid, want in seq["results"].items():
        got = out["results"][tid]
        assert got.outcome == want.outcome == "done", (tid, got.outcome)
        assert got.steps == want.steps == steps
        assert np.array_equal(got.final, want.final), (
            f"{tid}: served result differs from run_sequential, max|diff| "
            f"{float(np.abs(got.final - want.final).max()):.3g}")
    return {"retired": out["retired"], "slots": out["slots"],
            "serve_wall_s": round(wall, 2)}


# ------------------------------------------------------------ four chips


def phase_four_jacobi(devs, per, small, rehearsal: bool) -> dict:
    """Weak-scaled jacobi3d over four chips through ``jacobi3d.run``, the
    four-chip cell's own call (``decompose_zy`` -> (1,2,2), multi-block
    tight-x, overlap on, ten steps a dispatch): the application realizes
    deep y/z halos and the loop is deep-halo multistep passes. Then a small
    global size against the numpy reference, and one dispatch of the pass
    against the per-step path over the whole field."""
    import numpy as np

    from stencil_tpu.apps import jacobi3d
    from stencil_tpu.geometry import Dim3
    from stencil_tpu.ops.jacobi import (INIT_TEMP, jacobi_reference,
                                        make_jacobi_loop, make_jacobi_step)
    from stencil_tpu.ops.pallas_stencil import pick_temporal_depth

    facts = {}
    p122 = Dim3(1, 2, 2)
    with PallasRecorder() as rec:
        r = jacobi3d.run(per.x, per.y, per.z, weak=True, devices=devs,
                         iters=10)
    dd, h = r["domain"], r["handle"]
    size = dd.size
    for name, arr in (("curr", dd.get_curr(h)), ("next", dd.get_next(h))):
        require_four_shards(arr, devs, f"jacobi {name}")
    if not rehearsal:
        assert dd.spec.dim == p122, dd.spec.dim
        assert size == Dim3(per.x, 2 * per.y, 2 * per.z), size
        assert r["overlap"]
        # the depth is the application's pick for a dispatch of ten, the
        # halos are that deep on y and z, and the kernel libtpu compiled
        # is the multistep at that depth
        want_k, bound = pick_temporal_depth(size, p122, 10)
        rx = dd.spec.radius
        assert rx.x(-1) == 0 and rx.x(1) == 0, f"not tight-x: {rx}"
        assert {rx.y(-1), rx.y(1), rx.z(-1), rx.z(1)} == {want_k}, rx
        require_compiled_kernels(rec, ["make_pallas_jacobi_multistep"],
                                 rehearsal)
        k, row_tiled, grid = _multistep_depth(rec, dd.spec.base.z)
        assert k == want_k >= 2 and not row_tiled, (k, want_k, row_tiled)
        facts.update(temporal_k=k, bound=bound, grid=list(grid))
        facts["bytes_after_run"] = require_balanced(devs, "jacobi 4 chips")
        facts["mcells_per_s_per_dev"] = round(r["mcells_per_s_per_dev"], 1)
    _check_spheres(dd.get_curr_global(h), size, f"jacobi3d.run {size}")
    facts["global"] = str(size)
    deep_ex = dd.halo_exchange
    del r, dd

    # small global size, exactly `steps` steps from the uniform start
    steps = 6
    r = jacobi3d.run(small.x, small.y, small.z, weak=True, devices=devs,
                     iters=steps, chunk=3, warmup=0)
    ssize = r["domain"].size
    got = r["domain"].get_curr_global(r["handle"])
    want = jacobi_reference(
        np.full((ssize.z, ssize.y, ssize.x), INIT_TEMP, np.float32),
        _masks(ssize), steps)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    # and one step of the multi-block tight-x kernels from a random field
    # (a uniform start cannot show an indexing error away from the spheres),
    # on the halos that run realized: the single step a tail would take
    ex = (_jacobi_exchange(ssize, p122, devs, True) if rehearsal
          else r["domain"].halo_exchange)
    field = _random_field(ssize, 3)
    with PallasRecorder() as rec:
        step = make_jacobi_step(ex, use_pallas=True if rehearsal else None,
                                interpret=rehearsal)
        got = _run_loop(ex, step, field, ssize)
    require_compiled_kernels(rec, ["make_pallas_jacobi_sweep"], rehearsal)
    want = jacobi_reference(field, _masks(ssize), 1)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    facts["numpy_ref"] = f"{ssize} ok"
    del r

    # one dispatch at the first run's size from a random field: the
    # deep-halo pass against the per-step path (--deep-halo 1: sweep, mask,
    # shells) over the WHOLE field, which no sampled box can give. The
    # rehearsal has no TPU to make run() pick, so it builds both by hand
    csize, chunk = (ssize, 3) if rehearsal else (size, 10)
    k = pick_temporal_depth(csize, p122, chunk)[0]
    assert k >= 2, k
    if rehearsal:
        deep_ex = _jacobi_exchange(csize, p122, devs, True, radius=k)
    field = _random_field(csize, 5)
    outs = {}
    for name, cex, tk in (("pass", deep_ex, k),
                          ("step", _jacobi_exchange(csize, p122, devs, True),
                           None)):
        with PallasRecorder() as rec:
            loop = make_jacobi_loop(cex, chunk, temporal_k=tk,
                                    use_pallas=True if rehearsal else None,
                                    interpret=rehearsal)
            outs[name] = _run_loop(cex, loop, field, csize)
        require_compiled_kernels(rec, ["make_pallas_jacobi_multistep"]
                                 if tk else ["make_pallas_jacobi_sweep"],
                                 rehearsal)
    diff = float(np.abs(outs["pass"] - outs["step"]).max())
    assert diff <= 2e-6, diff       # a NaN on either side fails it too
    facts["pass_vs_step"] = f"{csize} k={k} max|diff| {diff:.3g}"
    return facts


def phase_four_astaroth(devs, nx: int, small: int, rehearsal: bool) -> dict:
    """Weak-scaled Astaroth over four chips through ``astaroth.run``, the
    four-chip cell's own call (``decompose_zy`` -> (1,2,2), tight-x, batched
    quantities, one iteration a dispatch, the schedule left to the builder:
    exchange-first since PR 34): three compiled substep kernels, no
    self-fill, four permutes an exchange, a serial step plan without shells,
    every field finite. Then ``small``^3 a chip on the same mesh against the
    XLA path, every cell: ``overlap=True`` (substep 0's shells, which no
    default builds any more) and the default beside it."""
    import numpy as np

    from stencil_tpu.apps import astaroth
    from stencil_tpu.astaroth.integrate import FIELDS, uses_pallas
    from stencil_tpu.geometry import Dim3
    from stencil_tpu.obs import telemetry

    with PallasRecorder() as rec:
        r = astaroth.run(iters=3, nx=nx, dtype="float32", devices=devs)
    dd, hs = r["domain"], r["handles"]
    ex = dd.halo_exchange
    facts = {"global": str(r["global"]),
             "iter_ms": round(1e3 * r["iter_trimean_s"], 3)}
    for k in FIELDS:
        require_four_shards(dd.get_curr(hs[k]), devs, f"astaroth {k}")
    if not rehearsal:
        assert dd.spec.dim == Dim3(1, 2, 2), dd.spec.dim
        rx = dd.spec.radius
        assert rx.x(-1) == 0 and rx.x(1) == 0, f"not tight-x: {rx}"
        assert uses_pallas(ex, None, "float32")
        require_compiled_kernels(rec, ["make_pallas_substep"], rehearsal)
        assert len(rec.of("make_pallas_substep")) == 3
        fills = [c for c in rec.calls if c["kernel"] != "make_pallas_substep"]
        assert not fills and not ex._self_fills, (fills, ex._self_fills)
        plan = telemetry.get().records(kind="counter",
                                       name="astaroth.step_plan")[-1]
        assert (plan["mode"], plan["shells"], plan["pallas"],
                plan["tight_x"], plan["exchanges_per_iter"]) == (
                    "serial", 0, True, True, 1), plan
        census = ex.collective_census(
            {k: dd.get_curr(hs[k]) for k in FIELDS})
        assert census["collective-permute"][0] == 4, census
        facts.update(permutes=4, halo_bytes_sent=plan["halo_bytes_sent"],
                     shell_cells=plan["shell_cells"])
        facts["bytes_after_run"] = require_balanced(devs, "astaroth 4 chips")
    for name, f in _astaroth_fields(r).items():
        assert np.isfinite(f).all(), f"astaroth {r['global']}: {name} not finite"
    del r, dd, ex
    facts["vs_xla"] = _astaroth_vs_xla(
        devs, small, rehearsal,
        schedules=((True, "overlap"), (None, "serial")))
    return facts


def phase_four_iso3dfd(devs, n, cell, rehearsal: bool) -> dict:
    """iso3dfd over four chips through ``iso3dfd.run`` (the application's
    own mesh, (1,2,2): x whole, y and z split and FIXED, one field of three
    exchanged, faces only, the compiled ``iso3dfd_step`` kernel) against
    the same run on one chip and against the float64 reference of the
    benchmark, every interior cell of both wave fields after the same
    steps from the sample's own data; then the benchmark's cell ``cell``
    on two further seeds (its own session: seeded data, the first
    dispatch against the reference on its boxes, the bfloat16 and the
    periodic-wrap controls failing)."""
    import numpy as np

    from benchmark import control, harness
    from benchmark.reference import iso3dfd as reference
    from stencil_tpu.apps import iso3dfd
    from stencil_tpu.geometry import Dim3
    from stencil_tpu.obs import telemetry
    from stencil_tpu.ops.iso3dfd import uses_pallas
    from stencil_tpu.parallel.exchange import unshard_blocks

    n1, n2, n3 = n
    steps = 3                               # after one warm-up step
    facts, got = {}, {}
    for name, on in (("four", devs), ("one", devs[:1])):
        with PallasRecorder() as rec:
            r = iso3dfd.run(n1, n2, n3, iters=steps, devices=on)
        dd, hs = r["domain"], r["handles"]
        got[name] = {q: unshard_blocks(dd.get_curr(hs[q]), dd.spec)
                     for q in ("prev", "next")}
        facts[f"iter_ms_{name}"] = round(1e3 * r["iter_trimean_s"], 3)
        if name == "four":
            for q in hs:
                require_four_shards(dd.get_curr(hs[q]), devs, f"iso3dfd {q}")
        if name == "four" and not rehearsal:
            ex = dd.halo_exchange
            assert dd.spec.dim == Dim3(1, 2, 2), dd.spec.dim
            assert uses_pallas(ex, None, "float32")
            require_compiled_kernels(rec, ["make_pallas_iso3dfd_step"],
                                     rehearsal)
            plan = telemetry.get().records(kind="counter",
                                           name="iso3dfd.step_plan")[-1]
            assert (plan["exchanged"], plan["quantities"], plan["periodic"],
                    plan["faces_only"]) == (1, 3, [False] * 3, True), plan
            census = ex.collective_census(dd.get_curr(hs["prev"]))
            # a block has one neighbour an axis: ONE permute an axis, the
            # y and the z one in one wave (PR 36)
            assert census["collective-permute"][0] == 2, census
            wires = telemetry.get().records(kind="counter",
                                            name="halo.wire_schedule")[-1]
            assert wires["waves"] == 1 and all(
                ph["merged"] for ph in wires["phases"]), wires
            facts.update(permutes=2, halo_bytes_sent=plan["halo_bytes_sent"],
                         halo_bytes_if_all=plan["halo_bytes_if_all"])
            facts["bytes_after_run"] = require_balanced(devs, "iso3dfd 4 chips")
        del r, dd, hs
    prev, nxt, vel = reference.sample_initial((n3, n2, n1))
    want = dict(zip(("prev", "next"),
                    reference.advance(prev, nxt, vel, steps + 1)))
    cut = (slice(reference.HALF_LENGTH, -reference.HALF_LENGTH),) * 3
    for q in ("prev", "next"):
        w = want[q][cut]
        scale = float(np.abs(w).max())
        diff = float(np.abs(got["four"][q] - w).max())
        chips = float(np.abs(got["four"][q] - got["one"][q]).max())
        say(f"iso3dfd {n1}x{n2}x{n3} {q}: max |four chips - reference| = "
            f"{diff:.3e} of a largest value {scale:.3e}; max |four chips - "
            f"one chip| = {chips:.3e}")
        assert diff <= 2e-6 * scale, (q, diff, scale)
        assert chips == 0.0, (q, chips)
        facts[f"max_abs_diff_{q}"] = diff
    del got, want
    args = argparse.Namespace(workload=cell, seed=0, seconds=0.0, trace=0,
                              rehearsal=rehearsal)
    opened, rc = harness.open_session(args, time.perf_counter())
    assert rc == 0, rc
    rows = control.readings(opened.session, [3_000_000_019, 4_200_000_037])
    assert control.verdict(rows, say), "the cell's limits do not hold"
    facts["cell_first_chunk_max_abs_err"] = max(
        v for _, sound, _, _ in rows for name, v, _ in sound
        if name.startswith("first_chunk_max_abs_err"))
    return facts


def phase_mg(devs, klass: str, rehearsal: bool) -> dict:
    """NPB MG through ``mg.run`` on the application's own mesh: four
    iterations of class ``klass`` from NPB's own data, every owned cell of
    the finest u and r against the float64 reference of the benchmark on
    the host, and the norm beside the reference's; every level's every
    array is finite. On four chips ((1,2,2): x whole, every level's blocks
    halving with the level) the levels whose rows are whole lane tiles run
    the compiled box and transfer kernels and the others XLA, by the
    application's own ``mg.cycle_plan``. On ONE chip the levels under the
    coarsest tight-x level are besides ONE compiled call that keeps them
    in VMEM (``mg_coarse``: 23 of the calls, 22 of the fills): the cell
    ``mg512.steady``'s own path, whose ``correct`` reads the first
    iteration on 13 boxes only. The rehearsal's twin of that is class W,
    128^3, the smallest with a tight-x level, its kernels interpreted."""
    import functools
    from unittest import mock

    import numpy as np

    from benchmark.reference import mg as reference
    from stencil_tpu.apps import mg
    from stencil_tpu.geometry import Dim3
    from stencil_tpu.obs import telemetry
    from stencil_tpu.parallel.exchange import unshard_blocks

    nit, one = 4, len(devs) == 1
    # run() takes the kernels on a TPU only: the one-chip rehearsal tells
    # the builder to interpret them, as the benchmark's rehearsal does
    builder = functools.partial(
        mg.make_mg_iter, use_pallas=True, interpret=True) \
        if rehearsal and one else mg.make_mg_iter
    with PallasRecorder() as rec, \
            mock.patch.object(mg, "make_mg_iter", builder):
        r = mg.run(klass=klass, nit=nit, devices=devs)
    dd, hs = r["levels"][0]
    assert dd.spec.dim == (Dim3(1, 1, 1) if one else Dim3(1, 2, 2)), \
        dd.spec.dim
    if not one:
        for q in ("u", "r", "v"):
            require_four_shards(dd.get_curr(hs[q]), devs, f"mg {q}")
    plan = telemetry.get().records(kind="counter", name="mg.cycle_plan")[-1]
    assert len(plan["levels"]) == len(r["levels"]) == len(
        reference.levels(r["n"])), plan
    tight = [lv for lv in plan["levels"] if lv["layout"] == "tight_x"]
    facts = {"iter_ms": round(1e3 * r["iter_trimean_s"], 3),
             "levels": len(plan["levels"]), "tight_x_levels": len(tight),
             "resident_levels": plan["resident_levels"]}
    kernels = ["make_pallas_mg_box"]
    if one:
        assert (plan["resident_levels"], plan["resident_calls"],
                plan["resident_fills"]) == (6, 23, 22), plan
        kernels.append("make_pallas_mg_coarse")
    else:
        assert plan["resident_levels"] == 0, plan
    if not rehearsal:
        assert len(tight) >= 2, plan
        for lv in tight:
            assert {lv["operators"][op]["impl"] for op in
                    ("mg_resid", "mg_psinv")} == {"pallas"}, lv
        assert tight[0]["operators"]["mg_rprj3"]["impl"] == "pallas", tight[0]
        assert tight[0]["operators"]["mg_interp"]["impl"] == "pallas", tight[0]
        kernels += ["make_pallas_mg_rprj3", "make_pallas_mg_interp"]
        if not one:
            facts["bytes_after_run"] = require_balanced(devs, "mg 4 chips")
    if one or not rehearsal:
        require_compiled_kernels(rec, kernels, rehearsal)
    got = {q: unshard_blocks(dd.get_curr(hs[q]), dd.spec) for q in ("u", "r")}
    for lv, lhs in r["levels"]:
        for q in ("u", "r"):
            assert np.isfinite(unshard_blocks(lv.get_curr(lhs[q]),
                                              lv.spec)).all(), (lv.size, q)
    n, _, smoother, _ = reference.CLASSES[klass]
    plus, minus = reference.zran3(n)
    u, res, norm = reference.run(n, nit, smoother,
                                 reference.charges_field(n, plus, minus))
    for q, want in (("u", u), ("r", res)):
        scale = float(np.abs(want).max())
        diff = float(np.abs(got[q] - want).max())
        say(f"mg class {klass} {q}: max |{len(devs)} chip(s) - reference| = "
            f"{diff:.3e} of a largest value {scale:.3e}")
        # r is what is left of charges of 1 after four cycles
        assert diff <= 4e-6 * max(scale, 1.0 if q == "r" else scale), (
            q, diff, scale)
        facts[f"max_abs_diff_{q}"] = diff
    say(f"mg class {klass}: norm {r['rnm2']:.10e} after {nit} iterations, "
        f"the reference's {norm:.10e}")
    assert abs(r["rnm2"] - norm) <= 2e-3 * norm, (r["rnm2"], norm)
    facts["rnm2"] = r["rnm2"]
    return facts


def phase_hpcg(devs, n: int, rehearsal: bool) -> dict:
    """HPCG's own problem through ``hpcg.run`` on one chip: a whole set of
    50 iterations from x = 0 in ONE compiled program a dispatch (four
    levels, each a fixed ``DistributedDomain``; on the chip the three
    tight-x levels in the compiled ``hpcg_symgs`` sweep and the box kernel
    with its wrapped lanes dropped, the transfers between two of them in
    ``hpcg_restrict`` / ``hpcg_prolong``, by the application's own
    ``hpcg.iter_plan``), then every owned cell of x against the float64
    reference of the benchmark's 50 iterations on the host, with the
    residual's fall beside the reference's. float32 and float64 walk the
    same 50 iterations apart (CG is a recurrence): what is held is the
    distance to the reference where the solve stands, and the residual.
    The rehearsal walks 16^3, where every level is plain XLA."""
    import numpy as np

    from benchmark.reference import hpcg as reference
    from stencil_tpu.apps import hpcg
    from stencil_tpu.obs import telemetry
    from stencil_tpu.parallel.exchange import unshard_blocks

    with PallasRecorder() as rec:
        r = hpcg.run(n=n, sets=1, devices=devs[:1])
    dd, hs = r["levels"][0]
    plan = telemetry.get().records(kind="counter", name="hpcg.iter_plan")[-1]
    assert len(plan["levels"]) == len(r["levels"]) == reference.LEVELS
    tight = [lv for lv in plan["levels"] if lv["layout"] == "tight_x"]
    facts = {"iter_ms": round(1e3 * r["iter_trimean_s"], 3),
             "tight_x_levels": len(tight),
             "normr_over_normr0": r["relative_residual"][-1],
             "max_abs_x_minus_1": r["error"][-1]}
    if not rehearsal:
        assert len(tight) >= 2, plan
        for lv in tight:
            impls = {name: op["impl"] for name, op in lv["operators"].items()}
            assert impls["hpcg_symgs"] == "pallas", lv
            assert impls.get("hpcg_resid", "pallas") == "pallas", lv
        assert tight[0]["operators"]["hpcg_spmv"]["impl"] == "pallas"
        # between two tight-x levels the transfers are kernels too
        for lv in tight[:-1]:
            for name in ("hpcg_restrict", "hpcg_prolong"):
                assert lv["operators"][name]["impl"] == "pallas", lv
        require_compiled_kernels(
            rec, ["make_pallas_hpcg_symgs", "make_pallas_mg_box",
                  "make_pallas_hpcg_spmv", "make_pallas_hpcg_restrict",
                  "make_pallas_hpcg_prolong"], rehearsal)
    for lv, lhs in r["levels"]:
        for q in lhs:
            held = unshard_blocks(lv.get_curr(lhs[q]), lv.spec)
            assert np.isfinite(held).all(), (lv.size, q)
    want, norms, normr0 = reference.solve((n, n, n))
    got = unshard_blocks(dd.get_curr(hs["x"]), dd.spec)
    diff = float(np.abs(got - want).max())
    say(f"hpcg {n}^3: normr/normr0 {r['relative_residual'][-1]:.6e} after "
        f"50 iterations, the reference's {norms[-1] / normr0:.6e}; max |x - "
        f"reference| = {diff:.3e}, max |x - 1| = {r['error'][-1]:.3e} (the "
        f"reference's {float(np.abs(want - 1).max()):.3e})")
    # the residual fell as the reference's did, to float32's floor at most
    assert r["relative_residual"][-1] <= max(3.0 * norms[-1] / normr0, 1e-6)
    assert diff <= max(2e-2 * float(np.abs(want - 1).max()), 1e-5), diff
    facts["max_abs_diff_x"] = diff
    return facts


def _lbm_reference(f, omega: float, steps: int, planes: int = 8):
    """``steps`` steps of the benchmark's float64 reference from the 19
    whole periodic arrays ``f``: its own ``stream`` and ``collide``, the
    collision on ``planes`` z planes at a time over a few threads (it is
    pointwise, numpy drops the lock inside an operation and a slab's
    temporaries stay near the cache: on whole arrays of 33.5 M cells a step
    took the four-chip host 2.9 minutes; my chip run, PR 42)."""
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from benchmark.reference import lbm as reference

    f = [np.asarray(a, np.float64) for a in f]
    with ThreadPoolExecutor(max_workers=8) as pool:
        for _ in range(steps):
            g = reference.stream(f)
            f = [np.empty_like(a) for a in g]

            def slab(z, g=g, f=f):
                at = slice(z, z + planes)
                for new, out in zip(reference.collide(
                        [a[at] for a in g], omega), f):
                    out[at] = new

            list(pool.map(slab, range(0, g[0].shape[0], planes)))
    return f


def phase_lbm(devs, size, rehearsal: bool) -> dict:
    """D3Q19 lattice-Boltzmann through ``lbm.run`` on the application's
    own four-chip mesh ((1,2,2): x whole, so the blocks lie tight-x and the
    compiled ``lbm_d3q19`` kernel takes them; y and z are permutes): four
    steps from the application's own vortex (two dispatches of two), every
    cell of every population against the float64 reference of the
    benchmark on the host from the very state the first dispatch was
    handed. The exchange is the path no cell holds yet: a radius a
    quantity across chips, 5 populations a direction, so that a chip sends
    5/19 of what a plan of one radius sends on the split axes (by the
    application's own ``lbm.step_plan`` and by the permutes the body
    issued, ``halo.wire_schedule``: one a direction)."""
    from unittest import mock

    import numpy as np

    from benchmark.reference import lbm as reference
    from stencil_tpu.apps import lbm
    from stencil_tpu.geometry import Dim3
    from stencil_tpu.obs import telemetry
    from stencil_tpu.parallel.exchange import unshard_blocks

    first, make = {}, lbm.make_lbm_step

    def builder(ex, omega, **kw):
        step = make(ex, omega, **kw)

        def call(curr, nxt):
            if not first:
                first["f"] = [unshard_blocks(a, ex.spec) for a in curr]
            return step(curr, nxt)

        return call

    steps = 4
    with PallasRecorder() as rec, \
            mock.patch.object(lbm, "make_lbm_step", builder):
        r = lbm.run(x=size.x, y=size.y, z=size.z, steps=steps // 2,
                    chunk=steps // 2, devices=devs)
    assert r["steps_run"] == steps, r["steps_run"]
    dd, hs = r["domain"], r["handles"]
    assert dd.spec.dim == Dim3(1, 2, 2), dd.spec.dim
    for h in hs[:2]:
        require_four_shards(dd.get_curr(h), devs, f"lbm {h.name}")
    plan = telemetry.get().records(kind="counter", name="lbm.step_plan")[-1]
    wires = telemetry.get().records(kind="counter",
                                    name="halo.wire_schedule")[-1]
    assert plan["layout"] == "tight_x", plan
    assert sorted(plan["carried"]) == ["y+", "y-", "z+", "z-"], plan
    assert all(len(v) == 5 for v in plan["carried"].values()), plan
    # across chips every slab is on the wire, and 10 of 38 slabs are sent
    assert plan["halo_bytes_wire"] == plan["halo_bytes_sent"] > 0, plan
    assert (19 * plan["halo_bytes_wire"]
            == 5 * plan["halo_bytes_wire_if_all"]), plan
    assert [(ph["axis"], ph["permutes"]) for ph in wires["phases"]] == [
        ("y", 2), ("z", 2)], wires
    facts = {"step_ms": round(1e3 * r["step_trimean_s"], 3),
             "mlups": round(r["mlups"], 1), "kernel": plan["kernel"],
             "halo_bytes_sent": plan["halo_bytes_sent"],
             "halo_bytes_if_all": plan["halo_bytes_if_all"]}
    if not rehearsal:
        assert plan["kernel"] == "pallas", plan
        require_compiled_kernels(rec, ["make_pallas_lbm_step"], rehearsal)
        facts["bytes_after_run"] = require_balanced(devs, "lbm 4 chips")
    want = _lbm_reference(first["f"], reference.omega_of(1.0), steps)
    worst = 0.0
    for h, w in zip(hs, want):
        got = unshard_blocks(dd.get_curr(h), dd.spec)
        assert np.isfinite(got).all() and got.min() > 0, h.name
        worst = max(worst, float(np.abs(got - w).max()))
    say(f"lbm {size}: max |{len(devs)} chips - reference| = {worst:.3e} "
        f"over 19 populations after {steps} steps")
    # populations of 0.03 to 0.33, four float32 steps
    assert worst <= 5e-7, worst
    facts["max_abs_diff"] = worst
    mass = [r["invariants_before"][0], r["invariants_after"][0]]
    assert abs(mass[1] - mass[0]) <= 2e-5 * mass[0], mass
    facts["mass"] = mass
    return facts


# ------------------------------------------------------------ the run


def build_phases(devs, rehearsal: bool) -> list:
    """``[(name, min_devices, thunk)]`` in running order. The four-chip
    phases come first: ``peak_bytes_in_use`` is a process-lifetime peak,
    so the balance check must not see the one-chip phases' peaks."""
    from stencil_tpu.geometry import Dim3

    four = devs[:4]
    p122, p221 = Dim3(1, 2, 2), Dim3(2, 2, 1)
    if rehearsal:
        return [
            ("four_chip_jacobi", 4, lambda: phase_four_jacobi(
                four, Dim3(16, 16, 16), Dim3(128, 8, 8), True)),
            ("four_chip_astaroth", 4, lambda: phase_four_astaroth(
                four, 16, 16, True)),
            ("four_chip_iso3dfd", 4, lambda: phase_four_iso3dfd(
                four, (48, 48, 48), "iso3dfd1024x4.steady", True)),
            ("mg_class_b_x4", 4, lambda: phase_mg(four, "S", True)),
            ("lbm_x4", 4, lambda: phase_lbm(four, Dim3(128, 16, 16), True)),
            ("four_chip_exchange", 4, lambda: phase_exchange(
                four, Dim3(16, 32, 32), p122, True)),
            ("four_chip_exchange_x", 4, lambda: phase_exchange(
                four, Dim3(32, 32, 16), p221, True)),
            ("jacobi", 1, lambda: phase_jacobi(devs, 16, True, ref_n=16)),
            ("mg_class_a", 1, lambda: phase_mg(devs[:1], "W", True)),
            ("hpcg_256", 1, lambda: phase_hpcg(devs, 16, True)),
            ("exchange", 1, lambda: phase_exchange(
                devs[:1], Dim3(16, 16, 16), Dim3(1, 1, 1), True)),
            ("astaroth", 1, lambda: phase_astaroth(devs, 16, 16, True)),
            ("serve", 1, lambda: phase_serve(devs, 8, 4, True)),
        ]
    return [
        ("four_chip_jacobi", 4, lambda: phase_four_jacobi(
            four, Dim3(512, 512, 512), Dim3(128, 32, 32), False)),
        # the small size is 128: the lane floor of the tight-x layout
        ("four_chip_astaroth", 4, lambda: phase_four_astaroth(
            four, 256, 128, False)),
        ("four_chip_iso3dfd", 4, lambda: phase_four_iso3dfd(
            four, (256, 256, 512), "iso3dfd1024x4.steady", False)),
        # class B: 256^3, blocks of 128 x 128 x 256 and of 64 x 64 x 128 on
        # the tight-x layout, the six levels below them inline
        ("mg_class_b_x4", 4, lambda: phase_mg(four, "B", False)),
        # blocks of 256 x 128 x 256, tight-x: the kernel of the cell
        # lbm384.steady behind an exchange that crosses chips
        ("lbm_x4", 4, lambda: phase_lbm(four, Dim3(256, 256, 512), False)),
        ("four_chip_exchange", 4, lambda: phase_exchange(
            four, Dim3(512, 1024, 1024), p122, False)),
        # exchange_weak's own pick on four chips: x is split
        ("four_chip_exchange_x", 4, lambda: phase_exchange(
            four, Dim3(1024, 1024, 512), p221, False)),
        ("jacobi_512", 1, lambda: phase_jacobi(
            devs, 512, False, ref_n=128, time_sync=True)),
        ("jacobi_768", 1, lambda: phase_jacobi(
            devs, 768, False, want_rows=True, chunk=None)),
        ("jacobi_768_k12", 1, lambda: phase_jacobi(
            devs, 768, False, want_rows=True)),
        ("exchange_512", 1, lambda: phase_exchange(
            devs[:1], Dim3(512, 512, 512), Dim3(1, 1, 1), False)),
        ("astaroth_256", 1, lambda: phase_astaroth(devs, 256, 64, False)),
        # class A: 256^3, 256^3 and 128^3 on the tight-x layout, the six
        # levels below them ONE call that keeps them in VMEM
        ("mg_class_a", 1, lambda: phase_mg(devs[:1], "A", False)),
        # 256^3 and 128^3 on the tight-x layout in the sweep's and the box
        # kernel, 64^3 and 32^3 inline: a whole set of HPCG's own problem
        ("hpcg_256", 1, lambda: phase_hpcg(devs, 256, False)),
        ("serve", 1, lambda: phase_serve(devs, 64, 16, False)),
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU walk-through at tiny sizes with interpret "
                         "kernels; never prints the pass line")
    ap.add_argument("--phases", default="",
                    help="comma-separated phase names to run (debugging: "
                         "a run that leaves phases out never passes)")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    devs = jax.devices()
    d0 = devs[0]
    if not args.rehearsal and d0.platform != "tpu":
        print(f"chip_smoke: no TPU: jax.devices()[0].platform is "
              f"{d0.platform!r}; nothing ran", file=sys.stderr)
        return NO_TPU_RC

    from stencil_tpu.utils.jax_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    from stencil_tpu.obs import telemetry

    cache = telemetry.watch_compiles()   # counts cache hits and misses

    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    say(f"{'rehearsal ' if args.rehearsal else ''}platform={d0.platform} "
        f"device_kind={d0.device_kind} devices={len(devs)} "
        f"jax={jax.__version__} "
        f"libtpu={importlib.metadata.version('libtpu')} "
        f"compile_cache={cache_dir}")

    selected = [p for p in args.phases.split(",") if p]
    phases = build_phases(devs, args.rehearsal)
    unknown = set(selected) - {name for name, _, _ in phases}
    if unknown:
        ap.error(f"unknown phases {sorted(unknown)}")
    outcome = {}
    for name, need, thunk in phases:
        if selected and name not in selected:
            outcome[name] = "not run: not selected"
            continue
        if len(devs) < need:
            outcome[name] = (f"not run: needs {need} devices, "
                             f"this machine has {len(devs)}")
            continue
        say(f"{name}: start")
        t0 = time.perf_counter()
        try:
            facts = thunk()
        except Exception:
            # the other phases still run; the run exits non-zero below
            outcome[name] = "FAILED"
            say(f"{name}: FAILED after {time.perf_counter() - t0:.1f}s\n"
                f"{traceback.format_exc()}")
            continue
        outcome[name] = "passed"
        say(f"{name}: passed in {time.perf_counter() - t0:.1f}s "
            f"{json.dumps(facts)}")

    say("summary: " + json.dumps({
        "phases": outcome, "wall_s": round(time.perf_counter() - t_start, 1),
        "compile_cache": {"hits": cache.cache_hits,
                          "misses": cache.cache_misses, "dir": cache_dir},
        "device": device}))
    if any(v == "FAILED" for v in outcome.values()):
        return 1
    if args.rehearsal:
        say("rehearsal complete: not a chip result")
        return REHEARSAL_RC
    if any(v == "not run: not selected" for v in outcome.values()):
        return PARTIAL_RC
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
