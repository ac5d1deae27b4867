"""Williamson RK3 integration and the fused distributed Astaroth step.

TPU-native re-design of the reference's integration driver (reference:
astaroth/integration.cuh:14-49 ``rk3_integrate``; astaroth/kernels.cu:62-87
``integrate_substep`` dispatch; astaroth/astaroth.cu:551-663 iteration
structure): per iteration, three RK3 substeps each do
{interior integrate -> halo exchange -> exterior integrate}, then the
in/out buffers swap once. The reference's 1 + 26 CUDA streams per domain
become dataflow inside one jitted program: the interior sweep of each
substep depends only on pre-exchange data, so XLA can overlap the halo
``ppermute``s with it.

Note on semantics: this vendored workload evaluates all three stage rates
on the same ``in`` state (buffers swap per *iteration*, not per substep —
astaroth.cu:642-648). We replicate that for benchmark parity; pass
``swap_per_substep=True`` for textbook low-storage RK3 feeding each stage
forward.

A consequence worth stating (but deliberately NOT exploited): with the in
buffers constant across substeps, all three stages compute the *same*
rate field, so the reference-mode iteration is algebraically one Euler
step ``out = curr + K*dt*rate(curr)`` with
``K = b2*(1 - a2*(1 - a1)) = 1.525``. Collapsing the three substeps to
one would make this benchmark ~3x faster while producing identical
output, but it would no longer perform the work the reference's driver
performs (three full kernel passes, astaroth.cu:556-641), so the
recorded numbers keep the 3-substep structure.
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..geometry import Dim3, Rect3, exterior_regions, interior_region
from ..obs import scopes, telemetry
from ..ops import double_buffer
from ..parallel.exchange import BLOCK_PSPEC, HaloExchange
from .config import AcMeshInfo
from .equations import Constants, continuity, entropy, induction, momentum
from .fd import field_data

FIELDS = ("lnrho", "uux", "uuy", "uuz", "ax", "ay", "az", "entropy")

# the fused-substep sliding-window vocabulary (ops/pallas_astaroth.py)
_VARIANTS = ("shift", "ring")


def _check_variant(kernel_variant) -> None:
    """Loud validation of the substep window variant at step-BUILD time,
    env-var default included — off-TPU the Pallas kernel (which owns the
    in-kernel check) never builds, and a typo'd STENCIL_ASTAROTH_VARIANT
    must not silently run the default discipline."""
    v = kernel_variant or os.environ.get("STENCIL_ASTAROTH_VARIANT")
    if v is not None and v not in _VARIANTS:
        raise ValueError(
            f"unknown astaroth kernel variant {v!r} (--kernel-variant / "
            f"STENCIL_ASTAROTH_VARIANT): valid values are {_VARIANTS}")

# Williamson (1980) low-storage coefficients (reference: integration.cuh:19-21)
RK3_ALPHA = (0.0, -5.0 / 9.0, -153.0 / 128.0)
RK3_BETA = (1.0 / 3.0, 15.0 / 16.0, 8.0 / 15.0)


def rk3_integrate(step_number: int, state_previous, state_current, rate_of_change, dt):
    """One low-storage RK3 stage (reference: integration.cuh:14-38).

    ``state_previous`` is the out-buffer value (the previous stage's
    output), ``state_current`` the in-buffer value."""
    beta = RK3_BETA[step_number]
    if step_number == 0:
        return state_current + beta * rate_of_change * dt
    alpha = RK3_ALPHA[step_number]
    prev_beta = RK3_BETA[step_number - 1]
    return state_current + beta * (
        alpha / prev_beta * (state_current - state_previous) + rate_of_change * dt
    )


def _rects_slices(rect: Rect3):
    return (
        ...,
        slice(rect.lo.z, rect.hi.z),
        slice(rect.lo.y, rect.hi.y),
        slice(rect.lo.x, rect.hi.x),
    )


def _integrate_region(
    substep: int,
    rect: Rect3,
    inv_ds,
    c: Constants,
    dt,
    curr: Dict[str, jax.Array],
    out: Dict[str, jax.Array],
    mask=None,
) -> Dict[str, jax.Array]:
    """Integrate one region: read curr fields' derivatives over ``rect``,
    RK3-update the region in the out buffers (reference: solve<step> kernel,
    user_kernels.h:437-469). ``mask`` (broadcastable to the region) keeps
    ``out``'s prior value where False — the masked-interior write of the
    uneven-partition overlap path (shell extents are per-block there, so
    the interior cannot be a static shrunk rect)."""
    lnrho = field_data(curr["lnrho"], rect, inv_ds)
    uu = tuple(field_data(curr[k], rect, inv_ds) for k in ("uux", "uuy", "uuz"))
    aa = tuple(field_data(curr[k], rect, inv_ds) for k in ("ax", "ay", "az"))
    ss = field_data(curr["entropy"], rect, inv_ds)

    sl = _rects_slices(rect)
    rates = {"lnrho": continuity(uu, lnrho)}
    ind = induction(c, uu, aa)
    mom = momentum(c, uu, lnrho, ss, aa)
    for i, k in enumerate(("ax", "ay", "az")):
        rates[k] = ind[i]
    for i, k in enumerate(("uux", "uuy", "uuz")):
        rates[k] = mom[i]
    rates["entropy"] = entropy(c, ss, uu, lnrho, aa)

    new_out = {}
    for k in FIELDS:
        updated = rk3_integrate(substep, out[k][sl], curr[k][sl], rates[k], dt)
        if mask is not None:
            updated = jnp.where(mask, updated, out[k][sl])
        new_out[k] = out[k].at[sl].set(updated.astype(out[k].dtype))
    return new_out


def _integrate_region_dyn(spec, substep, lo, size, inv_ds, c, dt, curr, out,
                          out_read=None):
    """Integrate one dynamic-offset boundary shell ``[lo, lo + size)``
    (allocation-local z/y/x, ``lo`` may be traced — uneven partitions): the
    exterior pass when per-block extents are static only per block index.
    Slices a (size + 2·3)-halo slab of every field, runs the same
    :func:`_integrate_region` math over it, and writes the core back.

    ``out_read`` is the state_previous source. Dynamic shells overlap at
    edges/corners (cross-sections span the base extents), so all patches of
    one substep must read the SAME pre-patch out — overlapping writes then
    compute identical values, where reading the accumulating ``out`` would
    double-apply the RK3 stage at overlap cells for substeps > 0."""
    h = 3
    p = spec.padded()
    slab_lo = (lo[0] - h, lo[1] - h, lo[2] - h)
    slab_sz = (size[0] + 2 * h, size[1] + 2 * h, size[2] + 2 * h)

    def slab(a):
        return lax.dynamic_slice(a.reshape(p.z, p.y, p.x), slab_lo, slab_sz)

    curr_s = {k: slab(v) for k, v in curr.items()}
    out_s = {k: slab(v) for k, v in (out_read or out).items()}
    rect = Rect3(Dim3(h, h, h), Dim3(h + size[2], h + size[1], h + size[0]))
    new_s = _integrate_region(substep, rect, inv_ds, c, dt, curr_s, out_s)
    core = (slice(h, h + size[0]), slice(h, h + size[1]), slice(h, h + size[2]))
    res = {}
    for k in FIELDS:
        o3 = out[k].reshape(p.z, p.y, p.x)
        res[k] = lax.dynamic_update_slice(o3, new_s[k][core], lo).reshape(
            out[k].shape
        )
    return res


def _integrate_shell_wrap_x(substep, rect, inv_ds, c, dt, curr, out):
    """:func:`_integrate_region` for a shell rect spanning the FULL x
    extent of a tight-x block (``Radius.without_x``: no x halo columns, the
    x axis is single-block periodic): a thin x-wrapped slab is materialized
    for the shell's z/y reach and the same region math runs over it. Shells
    are r-thick faces, so the extended slab is small."""
    h = _H
    zsl = slice(rect.lo.z - h, rect.hi.z + h)
    ysl = slice(rect.lo.y - h, rect.hi.y + h)

    def ext(a):
        sl = a[(..., zsl, ysl, slice(None))]
        return jnp.concatenate([sl[..., -h:], sl, sl[..., :h]], axis=-1)

    curr_s = {k: ext(v) for k, v in curr.items()}
    out_s = {k: ext(v) for k, v in out.items()}
    dz = rect.hi.z - rect.lo.z
    dy = rect.hi.y - rect.lo.y
    nx = rect.hi.x - rect.lo.x
    rect_s = Rect3(Dim3(h, h, h), Dim3(h + nx, h + dy, h + dz))
    new_s = _integrate_region(substep, rect_s, inv_ds, c, dt, curr_s, out_s)
    res = {}
    core = (..., slice(h, h + dz), slice(h, h + dy), slice(h, h + nx))
    dst = (..., slice(rect.lo.z, rect.hi.z), slice(rect.lo.y, rect.hi.y),
           slice(rect.lo.x, rect.hi.x))
    for k in FIELDS:
        res[k] = out[k].at[dst].set(new_s[k][core].astype(out[k].dtype))
    return res


_H = 3  # 6th-order stencil reach (reference: astaroth.h STENCIL_ORDER 6)


def uses_pallas(ex: HaloExchange, use_pallas, dtype="float32") -> bool:
    """Whether :func:`make_astaroth_step` will take the fused Pallas path
    for fields of ``dtype`` (None = auto: TPU, fp32, aligned blocks;
    uneven partitions run the kernel over the padded base extents with
    dynamic-shell overlap). Resident (oversubscribed) shards keep the
    fused kernel — it runs once per stacked block (VERDICT r4 item 7;
    uneven + resident stays on the XLA path, the dynamic-shell machinery
    is single-resident)."""
    if use_pallas is not None:
        return bool(use_pallas)
    import jax.numpy as jnp

    from ..ops.pallas_astaroth import substep_supported

    devs = ex.mesh.devices.flatten()
    if ex.oversubscribed and not ex.spec.is_uniform():
        return False
    return (
        all(d.platform == "tpu" for d in devs)
        and substep_supported(ex.spec, jnp.dtype(dtype))
    )


def _record_step_plan(ex, exteriors, iters, dtype, pallas_on, tight_x,
                      swap_per_substep, use_overlap, use_dyn_overlap):
    """The counter ``astaroth.step_plan``, once a :func:`make_astaroth_step`
    build: the branch its ``iteration`` takes (mode, exchanges and shell
    passes an iteration), the rects a pass integrates from exchanged halos
    and the cells they hold (no benchmark entry reads it: no cell's plan
    has shells)."""
    spec = ex.spec
    if pallas_on:
        # the fused path exchanges once an iteration unless every substep
        # swaps, and only substep 0 has shells
        if swap_per_substep:
            mode, exchanges, passes = "per_substep", 3, 0
        elif use_overlap and spec.dim.flatten() > 1:
            mode, exchanges, passes = "overlap", 1, 1
        elif use_dyn_overlap:
            mode, exchanges, passes = "dyn_overlap", 1, 1
        else:
            mode, exchanges, passes = "serial", 1, 0
    elif use_overlap and not swap_per_substep:
        mode, exchanges, passes = "overlap", 1, 1      # the hoisted exchange
    else:
        mode = ("per_substep" if swap_per_substep
                else "dyn_overlap" if use_dyn_overlap else "serial")
        exchanges, passes = 3, 3 if use_overlap or use_dyn_overlap else 0
    if not passes:
        cells = []
    elif use_dyn_overlap:
        # a dynamic shell's extents are static: only its offset is traced
        from ..ops.shells import shell_regions

        cells = [sz[0] * sz[1] * sz[2] for _lo, sz in shell_regions(
            spec, (0, 0, 0), (True, True, True))]
    else:
        cells = [(rect.hi - rect.lo).flatten() for rect in exteriors]
    itemsize = jnp.dtype(dtype).itemsize
    telemetry.get().counter(
        "astaroth.step_plan", value=iters, phase="compute",
        module=scopes.ASTAROTH_ITER, mode=mode, pallas=pallas_on,
        tight_x=tight_x, blocks=spec.dim.flatten(),
        quantities=len(FIELDS), exchanges_per_iter=exchanges,
        shells=passes * len(cells), shell_cells=passes * sum(cells),
        block_cells=spec.base.flatten(),
        halo_bytes_sent=ex.plan.wire_bytes([itemsize] * len(FIELDS))
        // ex.mesh.devices.size)


def make_astaroth_step(
    ex: HaloExchange,
    info: AcMeshInfo,
    dt: float = 1e-8,
    overlap: Optional[bool] = None,
    swap_per_substep: bool = False,
    iters: int = 1,
    use_pallas=None,
    dtype="float32",
    interpret: bool = False,
    kernel_variant: str = None,
):
    """Build the jitted iteration: ``fn(curr, nxt) -> (curr, nxt)`` where
    curr/nxt are dicts of stacked sharded field arrays. Runs ``iters``
    iterations of 3 substeps in one compiled program; the dt=1e-8 default
    matches the reference driver (astaroth.cu:578). Both dicts are donated.
    An iteration ends with the pair exchanged (the reference's pointer swap,
    astaroth.cu:642-648: once an iteration, or after every substep under
    ``swap_per_substep``: three, so odd either way). Compiled code never
    exchanges the buffers (ops/double_buffer.py): a ``while`` trip runs two
    iterations, the jitted program returns all sixteen fields in the slots
    they came in, and the returned callable swaps the two dicts on the host
    when ``iters`` is odd.

    ``use_pallas`` (None = auto, see :func:`uses_pallas`; ``dtype`` is the
    field dtype the step will be driven with) selects the fused VMEM
    substep kernel (ops/pallas_astaroth.py). The Pallas path exchanges
    once per iteration — legitimate because the in buffers do not change
    between substeps in reference swap-per-iteration mode, and
    re-exchanged before every substep in swap_per_substep mode. With
    ``overlap=True`` on a multi-block mesh, that one exchange is scheduled
    concurrently with substep 0's full-region kernel pass (which reads
    pre-exchange data); the multi-block-axis shells of substep 0 are then
    re-integrated from the exchanged halos — the reference's
    interior/exterior overlap re-expressed as dataflow with the fused
    kernel as the interior. With ``overlap=False`` the exchange runs
    first and every substep reads exchanged halos.

    ``overlap=None`` (the default) is resolved from the path this builder
    takes: exchange-first on the fused Pallas path, the hoisted-overlap
    iteration on the XLA path. A shell cell re-integrated in XLA beside the
    fused kernel costs 9.95 ns where the 32 B it sends cost 0.8 ns on the
    wire (four v5e chips, PRs 33 and 34), at every block size, so the
    shells cannot pay for the one permute they hide. On the XLA path a
    shell cell costs what an interior cell costs, and the overlap stays.

    ``kernel_variant`` selects the fused kernel's sliding-window
    discipline: ``"shift"`` (plane-copy window shifts) or ``"ring"``
    (shift-free modular-slot rotation — ops/pallas_astaroth.py module
    docstring). ``None`` reads ``STENCIL_ASTAROTH_VARIANT`` (default
    ``shift``) so the A/B runs without touching call sites."""
    spec = ex.spec
    r = spec.radius
    _check_variant(kernel_variant)
    if min(r.y(-1), r.y(1), r.z(-1), r.z(1)) < 3:
        raise ValueError("astaroth needs face radius >= 3 (6th-order "
                         "stencils)")
    pallas_on = uses_pallas(ex, use_pallas, dtype)
    if overlap is None:
        overlap = not pallas_on
    tight_x = min(r.x(-1), r.x(1)) < 3
    if tight_x:
        # zero-x-radius tight layout (Radius.without_x): no x halo columns;
        # only the fused kernel can form the periodic x pencils (lane
        # rolls), and only on a single-BLOCK x axis — y/z may have any
        # number of blocks (their overlap shells integrate over x-wrapped
        # slabs, _integrate_shell_wrap_x)
        if not (r.x(-1) == 0 and r.x(1) == 0 and spec.dim.x == 1):
            raise ValueError(
                "x radius must be 3+ (inline halos) or exactly 0 (tight "
                "layout, single-block x axis)"
            )
        if not spec.is_uniform():
            raise ValueError(
                "tight-x with multi-block y/z requires uniform splits"
            )
        if not pallas_on:
            raise ValueError(
                "tight-x astaroth requires the fused Pallas path"
            )
    inv_ds = (
        info.real_params["AC_inv_dsx"],
        info.real_params["AC_inv_dsy"],
        info.real_params["AC_inv_dsz"],
    )
    c = Constants.from_info(info)
    off = spec.compute_offset()
    compute = Rect3(off, off + spec.base)
    interior = interior_region(compute, r)
    exteriors = exterior_regions(compute, interior)
    use_overlap = overlap and spec.is_uniform()
    # uneven partitions keep the overlap structure via per-block dynamic
    # geometry (ops/shells.py): masked interior write + dynamic-offset
    # shells, the analogue of the reference's per-LocalDomain regions
    # (src/stencil.cu:878-977). Resident (oversubscribed) shards carry a
    # stacked leading block dim the shell machinery's (pz,py,px) reshape
    # cannot express — serialized exchange-then-sweep instead of a
    # trace-time crash (ADVICE r3).
    use_dyn_overlap = overlap and not spec.is_uniform() and not ex.oversubscribed

    def _dyn_geometry():
        from ..ops.shells import dyn_block_sizes, interior_mask, shell_regions

        sizes = dyn_block_sizes(spec)
        inc = (True, True, True)  # pre-exchange halos are stale on all sides
        return interior_mask(spec, sizes, inc), shell_regions(spec, sizes, inc)

    if pallas_on:
        from ..ops.pallas_astaroth import make_pallas_substep
        from ..parallel.mesh import MESH_AXES

        variant = kernel_variant or os.environ.get(
            "STENCIL_ASTAROTH_VARIANT", "shift"
        )
        # interpret mode (CI integration tests): the pallas HLO interpreter
        # cannot propagate varying-manual-axes metadata, so drop the vma
        # annotations and disable shard_map's vma check for this step
        kernels = [
            make_pallas_substep(
                spec, c, inv_ds, s, dt,
                vma=None if interpret else MESH_AXES,
                interpret=interpret,
                variant=variant,
            )
            for s in range(3)
        ]
        p = spec.padded()
        nres = ex.resident.flatten()

        def to3(d):
            with scopes.scope(scopes.CARRY):
                return tuple(d[k].reshape(p.z, p.y, p.x) for k in FIELDS)

        def untuple(vals, like):
            with scopes.scope(scopes.CARRY):
                return {k: v.reshape(like[k].shape)
                        for k, v in zip(FIELDS, vals)}

        def run_kernel(s, curr, out):
            """One fused substep over the shard. Resident (oversubscribed)
            shards stack whole padded blocks along the leading block dims;
            the per-block kernel runs once per resident, each block's
            halos filled by the resident-shift exchange phases (the
            reference's same-GPU fast path under oversubscription,
            tx_cuda.cuh:41-113)."""
            if nres == 1:
                return untuple(kernels[s](to3(curr), to3(out)), out)
            with scopes.scope(scopes.CARRY):
                cf = tuple(curr[k].reshape(nres, p.z, p.y, p.x) for k in FIELDS)
                of = tuple(out[k].reshape(nres, p.z, p.y, p.x) for k in FIELDS)
            res = [
                kernels[s](tuple(c[j] for c in cf), tuple(o[j] for o in of))
                for j in range(nres)
            ]
            with scopes.scope(scopes.CARRY):
                return {
                    k: jnp.stack([res[j][i] for j in range(nres)]).reshape(
                        out[k].shape
                    )
                    for i, k in enumerate(FIELDS)
                }

        def exchange_all(curr):
            return ex.exchange_blocks(curr)

        # overlapped fast path: substep 0's kernel pass reads PRE-exchange
        # halos on EVERY axis (this kernel has no in-kernel wrap — the
        # wrap-in-kernel experiment was measured and removed, BASELINE.md),
        # so every side's shell must be re-integrated from the exchanged
        # state, self-wrap axes included: exactly the XLA path's
        # ``exteriors`` rects.
        multi_block = spec.dim.flatten() > 1

        def iteration(curr, out):
            if swap_per_substep:
                # textbook mode: every substep consumes a fresh exchange, so
                # nothing can be computed ahead of it (and substeps 1/2
                # would need the pre-update out at shell cells, which the
                # in-place kernel destroys) — exchange-then-compute
                for s in range(3):
                    curr = exchange_all(curr)
                    out = run_kernel(s, curr, out)
                    curr, out = out, curr
                return curr, out
            # reference swap-per-iteration mode: the in buffers are constant
            # across substeps, so the iteration's single exchange can fly
            # while substep 0 computes the full region from PRE-exchange
            # data (reference: interior integrate concurrent with
            # dd.exchange(), astaroth.cu:551-641). Substep 0's RK3 stage
            # never reads the out buffer, so re-integrating the
            # multi-block-axis shells from the exchanged halos afterwards
            # is exact; substeps 1 and 2 read post-exchange data directly.
            if use_overlap and multi_block:
                out = run_kernel(0, curr, out)
                curr = exchange_all(curr)
                with scopes.scope(scopes.SWEEP_SHELL):
                    for rect in exteriors:
                        if tight_x:
                            out = _integrate_shell_wrap_x(
                                0, rect, inv_ds, c, dt, curr, out
                            )
                        else:
                            out = _integrate_region(
                                0, rect, inv_ds, c, dt, curr, out)
            elif use_dyn_overlap:
                # uneven partition: same structure, shells at per-block
                # dynamic offsets (substep 0 never reads out, so the full
                # kernel pass before the shells is exact)
                out = run_kernel(0, curr, out)
                curr = exchange_all(curr)
                _, shells = _dyn_geometry()
                with scopes.scope(scopes.SWEEP_SHELL):
                    for lo, size in shells:
                        out = _integrate_region_dyn(
                            spec, 0, lo, size, inv_ds, c, dt, curr, out
                        )
            else:
                # exchange-first, what overlap=None resolves to on this
                # path: substep 0 reads exchanged halos like 1 and 2
                curr = exchange_all(curr)
                out = run_kernel(0, curr, out)
            for s in (1, 2):
                out = run_kernel(s, curr, out)
            return out, curr  # one swap per iteration (astaroth.cu:642-648)

    else:
        def hoisted_overlap_iteration(curr, out):
            """Reference swap-per-iteration mode, XLA path: the SAME
            hoisted-exchange dataflow the Pallas iteration uses. Substep 0
            integrates the full region from PRE-exchange data (never reads
            out, so re-integrating boundary shells from the exchanged
            state afterwards is exact); the iteration's single exchange is
            free to fly concurrently; substeps 1-2 read post-exchange
            data. 9 integrate bodies per iteration instead of the
            per-substep structure's 21 — which is also what makes
            fp64-on-TPU OVERLAP compile: the round-3 bounded negative
            (32^3 fp64 overlap > 25 min compile, scripts/probe_f64_overlap
            .py) was the 7-region x 3-substep op-graph under f64's ~10x
            emulation expansion, not fp64 itself."""
            out = _integrate_region(0, compute, inv_ds, c, dt, curr, out)
            # exchange_blocks: the 8 same-dtype fields ride packed
            # quantity-batched carriers (one ppermute pair per axis phase
            # for the whole dict); reads pre-update curr only, so the
            # overlap-as-dataflow structure is unchanged
            curr = ex.exchange_blocks(curr)
            with scopes.scope(scopes.SWEEP_SHELL):
                for rect in exteriors:
                    out = _integrate_region(0, rect, inv_ds, c, dt, curr, out)
            for s in (1, 2):
                out = _integrate_region(s, compute, inv_ds, c, dt, curr, out)
            return out, curr  # one swap per iteration (astaroth.cu:642-648)

        def substep_block(substep, curr, out):
            if use_overlap:
                out = _integrate_region(substep, interior, inv_ds, c, dt, curr, out)
                curr = ex.exchange_blocks(curr)
                with scopes.scope(scopes.SWEEP_SHELL):
                    for rect in exteriors:
                        out = _integrate_region(
                            substep, rect, inv_ds, c, dt, curr, out)
            elif use_dyn_overlap:
                # masked interior write (shell cells keep the pre-update out
                # that substeps > 0 read as state_previous), exchange, then
                # dynamic-offset shells from the exchanged halos
                imask, shells = _dyn_geometry()
                out = _integrate_region(
                    substep, compute, inv_ds, c, dt, curr, out, mask=imask
                )
                curr = ex.exchange_blocks(curr)
                out_read = out
                with scopes.scope(scopes.SWEEP_SHELL):
                    for lo, size in shells:
                        out = _integrate_region_dyn(
                            spec, substep, lo, size, inv_ds, c, dt, curr, out,
                            out_read=out_read,
                        )
            else:
                curr = ex.exchange_blocks(curr)
                out = _integrate_region(substep, compute, inv_ds, c, dt, curr, out)
            return curr, out

        def iteration(curr, out):
            if use_overlap and not swap_per_substep:
                return hoisted_overlap_iteration(curr, out)
            for substep in range(3):
                curr, out = substep_block(substep, curr, out)
                if swap_per_substep:
                    curr, out = out, curr
            if not swap_per_substep:
                # reference workload: one swap per iteration (astaroth.cu:642-648)
                curr, out = out, curr
            return curr, out

    def entry_fn(curr, out):
        # an iteration ends with the pair exchanged in either swap mode (one
        # swap, or three under swap_per_substep): the step of the ping-pong
        return double_buffer.repeat(lambda co: iteration(*co), iters,
                                    (curr, out))

    fn = jax.shard_map(
        entry_fn,
        mesh=ex.mesh,
        in_specs=(BLOCK_PSPEC, BLOCK_PSPEC),
        out_specs=(BLOCK_PSPEC, BLOCK_PSPEC),
        check_vma=not interpret,
    )
    # the abstract (curr, out) field dicts this iteration is built for:
    # what obs.scopes.op_map lowers it with
    field = jax.ShapeDtypeStruct(spec.stacked_shape_zyx(), jnp.dtype(dtype),
                                 sharding=ex.sharding())
    like = {k: field for k in FIELDS}
    _record_step_plan(ex, exteriors, iters, dtype, pallas_on, tight_x,
                      swap_per_substep, use_overlap, use_dyn_overlap)
    return double_buffer.jit_in_place(scopes.ASTAROTH_ITER, fn, (like, like),
                                      (iters,))


def make_batched_astaroth_step(spec, info: AcMeshInfo, dt: float = 1e-8,
                               iters: int = 1, sharding=None):
    """The multi-tenant batched astaroth iteration (XLA path):
    ``fn(curr, out) -> (curr, out)`` over dicts of ``(B, pz, py, px)``
    stacked tenant fields, each tenant an independent single-block
    periodic MHD box.

    ``spec`` describes ONE tenant (``GridSpec(size, Dim3(1, 1, 1),
    Radius.constant(3))``); the leading batch axis stacks B tenants.
    Per iteration the reference swap-per-iteration structure runs once:
    the halo fill is the per-tenant periodic self-wrap
    (ops/halo_fill.wrap_fill_batched — composed x->y->z order, so the
    6th-order cross-stencils see edge/corner halos identical to a
    single-block ``HaloExchange``), substep 0 integrates the full
    compute region from the exchanged state, substeps 1-2 read the same
    in buffers, and the buffers swap once. ``_integrate_region`` already
    rides leading dims (its slices open with ``...``), so every lane is
    bit-identical to the single-domain ``make_astaroth_step`` hoisted
    overlap iteration (tests/test_campaign.py pins it).

    ``sharding`` splits the batch axis over a 1-D device mesh — the
    program has zero collectives, so one jit serves B tenants across the
    whole mesh. Buffers are not donated (campaign stash semantics)."""
    from ..geometry import Dim3 as _D3
    from ..ops.halo_fill import wrap_fill_batched

    r = spec.radius
    if spec.dim != _D3(1, 1, 1):
        raise ValueError(
            f"batched tenants are single-block domains; got partition "
            f"{spec.dim}"
        )
    if min(r.x(-1), r.x(1), r.y(-1), r.y(1), r.z(-1), r.z(1)) < 3:
        raise ValueError("astaroth needs face radius >= 3 (6th-order "
                         "stencils)")
    inv_ds = (
        info.real_params["AC_inv_dsx"],
        info.real_params["AC_inv_dsy"],
        info.real_params["AC_inv_dsz"],
    )
    c = Constants.from_info(info)
    off = spec.compute_offset()
    compute = Rect3(off, off + spec.base)

    def iteration(curr, out):
        curr = {k: wrap_fill_batched(spec, v) for k, v in curr.items()}
        out = _integrate_region(0, compute, inv_ds, c, dt, curr, out)
        for s in (1, 2):
            out = _integrate_region(s, compute, inv_ds, c, dt, curr, out)
        return out, curr  # one swap per iteration (astaroth.cu:642-648)

    def entry_fn(curr, out):
        if iters == 1:
            return iteration(curr, out)
        return lax.fori_loop(
            0, iters, lambda _, co: iteration(co[0], co[1]), (curr, out))

    # named like the single-domain iteration; the batch is the caller's,
    # so nothing is registered for op_map
    if sharding is None:
        return scopes.jit_loop(scopes.ASTAROTH_ITER, entry_fn)
    sh = {k: sharding for k in FIELDS}
    return scopes.jit_loop(scopes.ASTAROTH_ITER, entry_fn,
                           in_shardings=(sh, sh), out_shardings=(sh, sh))
