"""Static exchange-plan cost model — rank candidates without compiling.

The model scores one :class:`~stencil_tpu.plan.ir.PlanChoice` for one
:class:`~stencil_tpu.plan.ir.PlanConfig` from the ExchangePlan IR alone:
collective-permute count, estimated on-wire bytes, and local slab bytes
fall out of the phase list (plan/ir.py), and the per-collective overhead
constants are calibrated from the censuses + wall-clocks this repo has
RECORDED (BASELINE.md rounds 7/10, 8-device CPU mesh, an older jax):

- Round 10 quantity-batching A/B (128^3, 2x2x2, fp32): Q=8 batched
  42.9 ms / 6 permutes vs per-quantity 70.6 ms / 48 permutes — the
  42-permute delta prices one composed ppermute at ~0.66 ms.
- Round 7 ablation (same leg, Q=4): composed 47.6 ms / 24 permutes /
  12.48 MB on-wire. Subtracting 24 x 0.66 ms leaves ~32 ms for the
  payload -> ~390 MB/s effective wire bandwidth.
- direct26: 200.7 ms / 104 permutes / 6.69 MB. With the same wire rate,
  the residual prices a direct26 permute at ~1.76 ms — the exact-extent
  messages are small and strided, so their per-collective overhead is
  ~2.7x the slab phases' (the reference found the same economics for
  many small MPI messages vs packed slabs).
- auto-spmd: 49.5 ms for the identical 24-permute/12.48 MB schedule ->
  ~0.73 ms per synthesized permute (manual wins ~4%).

These are RANKING constants, not performance claims: per-collective
overhead dominating payload is the recorded regime on this stack, and the
model's job is ordering candidates for the measured refinement pass
(plan/probe.py). A TPU-measured recalibration is the ROADMAP #1 ledger's
follow-up; ``calibration=`` overrides let a probe session supply one.

This module is jax-free: scoring builds GridSpecs and ExchangePlans
(pure geometry), so enumerating hundreds of candidates costs
milliseconds.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from ..domain.grid import GridSpec
from ..geometry import DIRECTIONS_26, Dim3, Radius, halo_extent, stack_residents
from .ir import (
    AUTO_SPMD,
    AXIS_COMPOSED,
    DIRECT26,
    METHODS,
    PlanChoice,
    PlanConfig,
    build_plan,
    validate_placement,
)

# Calibration provenance: BASELINE.md rounds 7/10 (see module docstring).
DEFAULT_CALIBRATION: Dict[str, object] = {
    "permute_overhead_s": {
        AXIS_COMPOSED: 6.6e-4,
        DIRECT26: 1.76e-3,
        AUTO_SPMD: 7.3e-4,
    },
    "wire_bytes_per_s": 3.9e8,
    "local_bytes_per_s": 4.0e9,
    # per-cell update cost for the multistep redundant-compute tradeoff
    # (order-of-magnitude CPU figure; the probe pass owns the truth)
    "cell_update_s": 1.0e-9,
    # relative compute factor per kernel variant (unknown -> 1.0: the
    # static model deliberately ties variants and lets the probes decide)
    "variant_factor": {},
}


@dataclass(frozen=True)
class PlanCost:
    """Static score of one candidate, per simulation step."""

    total_s: float          # the ranking key
    exchange_s: float       # one exchange's predicted wall-clock
    collectives: int        # permutes per exchange (census-comparable)
    wire_bytes: int         # estimated interconnect bytes per exchange
    local_bytes: int        # estimated local slab bytes per exchange
    compute_overhead_s: float  # multistep redundant-compute price per step

    def to_json(self) -> dict:
        return {
            "total_s": self.total_s,
            "exchange_s": self.exchange_s,
            "collectives": self.collectives,
            "wire_bytes": self.wire_bytes,
            "local_bytes": self.local_bytes,
            "compute_overhead_s": self.compute_overhead_s,
        }


def scale_radius(radius: Radius, k: int) -> Radius:
    """The radius a temporal-depth-k multistep realizes: every direction's
    halo (and diagonal gate) scaled by k, so one exchange feeds k steps."""
    if k == 1:
        return radius
    out = Radius.constant(0)
    for d, r in radius._r.items():
        out.set_dir(d, r * k)
    return out


# -- topology-aware placement (the reference's NodeAware/qap::solve leg) ------
#
# The reference's L3 places blocks by measured inter-GPU bandwidth: a QAP
# over (communication volume x link distance) decides which physical
# device hosts which subdomain (qap.hpp, partition.hpp:525-831). Here the
# same leg is a PlanChoice dimension: the wire-volume matrix between MESH
# positions falls out of the same halo_extent geometry the ExchangePlan
# IR's wire_bytes model prices, the link-cost matrix comes from the
# device objects (parallel/topology.link_cost_matrix — ICI hop distance
# on TPU, process-boundary penalty elsewhere), and the product prices a
# placement relative to identity.


def placement_wire_matrix(spec: GridSpec, mesh_dim,
                          per_cell_bytes: int = 1):
    """Pairwise wire-volume matrix between MESH positions (row-major
    z, y, x — the same linearization the placement assignment uses).

    Built from the exact halo_extent geometry the IR's ``wire_cells``
    model prices: every active direction's halo slab of every block,
    attributed to the (sender-slot, receiver-slot) pair, with self-wrap
    and resident-internal (same-device) traffic excluded — those never
    touch the interconnect, so a placement cannot change their cost
    (the reference's comm matrix, partition.hpp:722-752, aggregated to
    device granularity). Pure geometry, jax-free."""
    import numpy as np

    md = Dim3.of(mesh_dim)
    if spec.dim.x % md.x or spec.dim.y % md.y or spec.dim.z % md.z:
        raise ValueError(f"mesh {md} does not divide partition {spec.dim}")
    c = Dim3(spec.dim.x // md.x, spec.dim.y // md.y, spec.dim.z // md.z)
    n = md.flatten()
    m = np.zeros((n, n), dtype=np.float64)

    def slot(b: Dim3) -> int:
        return (b.x // c.x) + (b.y // c.y) * md.x + (b.z // c.z) * md.x * md.y

    for iz in range(spec.dim.z):
        for iy in range(spec.dim.y):
            for ix in range(spec.dim.x):
                src = Dim3(ix, iy, iz)
                sz = spec.block_size(src)
                for d in DIRECTIONS_26:
                    # send-extent rule: data toward d fills the receiver's
                    # -d halo, active iff radius.dir(-d) != 0
                    if spec.radius.dir(-d) == 0:
                        continue
                    dst = (src + d).wrap(spec.dim)
                    if dst == src:
                        continue  # self-wrap: no inter-device traffic
                    ss, ds = slot(src), slot(dst)
                    if ss == ds:
                        continue  # resident neighbors: local shifts
                    m[ss, ds] += (halo_extent(-d, sz, spec.radius).flatten()
                                  * per_cell_bytes)
    return m


# rank() scores every (method x batching x k) candidate of a
# partition, and each placed one needs the SAME wire matrix — a pure-
# Python O(blocks x 26) halo_extent sweep that must not be rebuilt per
# candidate (nor per between-chunk replan retune). Bounded: the key
# space is tiny (partitions of one tuning pass) but a long-lived service
# retuning many configs must not grow without bound.
_WIRE_MATRIX_CACHE: Dict[Tuple, object] = {}
_WIRE_MATRIX_CACHE_MAX = 128


def _cached_wire_matrix(spec: GridSpec, mesh_dim, config: PlanConfig,
                        multistep_k: int):
    key = (config.grid, config.radius, int(multistep_k),
           (spec.dim.x, spec.dim.y, spec.dim.z),
           (mesh_dim.x, mesh_dim.y, mesh_dim.z))
    w = _WIRE_MATRIX_CACHE.get(key)
    if w is None:
        if len(_WIRE_MATRIX_CACHE) >= _WIRE_MATRIX_CACHE_MAX:
            _WIRE_MATRIX_CACHE.clear()
        w = _WIRE_MATRIX_CACHE[key] = placement_wire_matrix(spec, mesh_dim)
    return w


def placement_cost(w, link_costs, placement=None) -> float:
    """Assignment cost ``sum_ab w[a,b] * link[f[a],f[b]]`` with the
    reference's ``0 * inf == 0`` rule (qap.hpp cost_product) — pinned
    equal to ``parallel.qap.cost`` by tests/test_plan_placement.py but
    implemented here so the jax-free cost model never imports the
    parallel package. ``placement=None`` is the identity assignment."""
    import numpy as np

    w = np.asarray(w, dtype=np.float64)
    d = np.asarray(link_costs, dtype=np.float64)
    n = w.shape[0]
    f = np.arange(n) if placement is None else np.asarray(placement,
                                                          dtype=np.intp)
    dperm = d[np.ix_(f, f)]
    prod = w * dperm
    prod[(w == 0) | (dperm == 0)] = 0.0
    return float(prod.sum())


def uniform_link_costs(link_costs) -> bool:
    """True when every off-diagonal link costs the same — placement is
    then cost-neutral and the QAP search is skipped (identity optimal)."""
    import numpy as np

    d = np.asarray(link_costs, dtype=np.float64)
    n = d.shape[0]
    if n < 2:
        return True
    off = d[~np.eye(n, dtype=bool)]
    return bool(np.all(off == off[0]))


# Exhaustive-search size limit for the placement QAP: at n <= 6 the full
# 720-permutation sweep completes in milliseconds even in pure Python, so
# the answer is deterministic and budget-independent; beyond it the
# greedy best-pairwise-swap descent (qap.hpp:87-180) runs instead — a
# timed-out partial exhaustive search would make the tuned plan depend on
# host load, which a persisted DB entry must never do.
PLACEMENT_EXACT_LIMIT = 6


def solve_placement(w, link_costs,
                    exact_limit: int = PLACEMENT_EXACT_LIMIT,
                    timeout_s: float = 10.0) -> Optional[Tuple[int, ...]]:
    """The QAP-optimal placement for (wire volumes, link costs), or None
    when identity is already (modeled) optimal — uniform links included.
    Dispatches to ``parallel.qap``: exhaustive ``solve`` at small n,
    greedy ``solve_catch`` beyond (see :data:`PLACEMENT_EXACT_LIMIT`).
    Imported lazily — the solvers are numpy-only but live in the
    parallel package; static-only callers that never search placements
    (plan_tool explain) stay jax-free."""
    import numpy as np

    if uniform_link_costs(link_costs):
        return None
    from ..parallel import qap

    w = np.asarray(w, dtype=np.float64)
    d = np.asarray(link_costs, dtype=np.float64)
    n = w.shape[0]
    if n <= exact_limit:
        f, cost = qap.solve(w, d, timeout_s=timeout_s)
    else:
        f, cost = qap.solve_catch(w, d)
    identity = placement_cost(w, d)
    if f == list(range(n)) or cost >= identity:
        return None  # identity is optimal (or the solver found nothing better)
    return tuple(f)


def feasible(config: PlanConfig, choice: PlanChoice) -> Optional[Tuple]:
    """(spec, mesh_dim, resident) when the candidate can realize on this
    config, else None. Mirrors realize()'s constraints exactly: the
    partition's block count must be a multiple of ndev (residents stacked
    by the same z-heavy factorization), and no block may be thinner than
    the effective radius — for a multistep choice that radius is
    ``radius * k``, so a deep-halo depth whose staging would exceed a
    block's interior extent (a negative valid strip) is refused HERE,
    before any kernel is planned. A ``placement`` must be a permutation
    of the config's ``ndev`` mesh positions (plan/ir.validate_placement —
    the same check realize() raises on)."""
    if validate_placement(choice.placement, config.ndev) is not None:
        return None
    dim = Dim3.of(choice.partition)
    g = Dim3.of(config.grid)
    if g.x < dim.x or g.y < dim.y or g.z < dim.z:
        return None
    nb = dim.flatten()
    if nb % config.ndev:
        return None
    radius = scale_radius(config.radius_obj(), choice.multistep_k)
    try:
        spec = GridSpec(g, dim, radius)
    except (AssertionError, ValueError):
        return None
    c = nb // config.ndev
    if c == 1:
        mesh_dim = dim
    else:
        try:
            mesh_dim = stack_residents(dim, c)
        except ValueError:
            return None
    for sizes, rm, rp in (
        (spec.sizes_x, radius.x(-1), radius.x(1)),
        (spec.sizes_y, radius.y(-1), radius.y(1)),
        (spec.sizes_z, radius.z(-1), radius.z(1)),
    ):
        if min(sizes) < max(rm, rp):
            return None  # halo would span multiple blocks
    resident = Dim3(dim.x // mesh_dim.x, dim.y // mesh_dim.y,
                    dim.z // mesh_dim.z)
    return spec, mesh_dim, resident


def score(config: PlanConfig, choice: PlanChoice,
          calibration: Optional[dict] = None,
          link_costs=None) -> Optional[PlanCost]:
    """Static per-step cost of one candidate (None when infeasible).

    The score is a function of the dtype MULTISET only — a config whose
    quantity list is a permutation of another's scores identically, so
    the ranking is invariant under quantity-dtype permutation
    (tests/test_plan_cost.py pins this).

    ``link_costs`` (an ndev x ndev per-device-pair distance matrix —
    parallel/topology.link_cost_matrix) prices the choice's block
    placement: the wire term scales by the QAP cost ratio
    ``placement_cost(w, link, f) / placement_cost(w, link, identity)``,
    so on a mesh with non-uniform links a topology-matched placement
    scores strictly cheaper than identity while the calibrated
    ``wire_bytes_per_s`` keeps its identity-baseline meaning. Without
    link costs every placement prices identically and the deterministic
    label tie-break keeps identity first."""
    cal = dict(DEFAULT_CALIBRATION)
    for k, v in (calibration or {}).items():
        # dict-valued keys (per-method overheads, variant factors) merge
        # per entry so a partial override falls back to the defaults for
        # every method it does not mention
        if isinstance(v, dict) and isinstance(cal.get(k), dict):
            cal[k] = {**cal[k], **v}
        else:
            cal[k] = v
    feas = feasible(config, choice)
    if feas is None:
        return None
    spec, mesh_dim, resident = feas
    plan = build_plan(spec, mesh_dim, choice.method,
                      batch_quantities=choice.batch_quantities,
                      resident=resident)
    itemsizes = config.itemsizes()
    nq = config.num_quantities
    ngroups = config.dtype_group_count
    collectives = plan.collectives_per_exchange(nq, ngroups)
    wire = plan.wire_bytes(itemsizes, floating=config.floating_flags())
    local = plan.local_bytes(itemsizes)
    # placement pricing: wire time scales by the QAP cost ratio vs the
    # identity assignment (1.0 when no link costs are known, when the
    # links are uniform, or when nothing crosses the wire)
    pratio = 1.0
    if link_costs is not None and choice.placement is not None and wire:
        w = _cached_wire_matrix(spec, mesh_dim, config, choice.multistep_k)
        base = placement_cost(w, link_costs)
        if base > 0:
            pratio = placement_cost(w, link_costs, choice.placement) / base
    overhead = cal["permute_overhead_s"][choice.method]
    exchange_s = (
        collectives * overhead
        + wire / cal["wire_bytes_per_s"] * pratio
        + local / cal["local_bytes_per_s"]
    )
    k = choice.multistep_k
    compute_overhead_s = 0.0
    if k > 1:
        # deep halos trade collective count for redundant edge compute:
        # each of the k-1 interior steps re-updates a shrinking halo
        # shell; the average extra shell is ~ (k-1)/2 radius-deep over
        # every block face
        b = spec.base
        r0 = config.radius_obj()
        rbar = (r0.x(-1) + r0.x(1) + r0.y(-1) + r0.y(1)
                + r0.z(-1) + r0.z(1)) / 6.0
        surface = 2 * (b.x * b.y + b.x * b.z + b.y * b.z) * spec.num_blocks()
        extra_cells = surface * rbar * (k - 1) / 2.0
        compute_overhead_s = extra_cells * nq * cal["cell_update_s"]
    vf = cal["variant_factor"].get(choice.kernel_variant, 1.0)
    total = exchange_s / k + compute_overhead_s * vf
    return PlanCost(
        total_s=total, exchange_s=exchange_s, collectives=collectives,
        wire_bytes=wire, local_bytes=local,
        compute_overhead_s=compute_overhead_s,
    )


def candidate_partitions(config: PlanConfig,
                         oversubscribe: Sequence[int] = (1,)) -> List[Tuple[int, int, int]]:
    """All (px, py, pz) block grids with ndev * c blocks (c in
    ``oversubscribe``), unfiltered for radius feasibility (score() is the
    gate). Ordered deterministically."""
    out = []
    for c in oversubscribe:
        n = config.ndev * c
        for px in range(1, n + 1):
            if n % px:
                continue
            nyz = n // px
            for py in range(1, nyz + 1):
                if nyz % py:
                    continue
                out.append((px, py, nyz // py))
    return out


def enumerate_candidates(
    config: PlanConfig,
    methods: Iterable[str] = METHODS,
    batch_options: Iterable[bool] = (True, False),
    ks: Iterable[int] = (1,),
    oversubscribe: Sequence[int] = (1,),
    link_costs=None,
) -> List[PlanChoice]:
    """The search space: partition shape x method x quantity batching x
    temporal depth k x block placement. Batching only
    branches when the config has more than one quantity (at Q=1 the two
    programs are identical — PR 5's degeneration contract). Infeasible
    points fall out at score() like every other constraint.

    With ``link_costs`` (non-uniform), every single-resident partition
    additionally branches on its QAP-solved placement
    (:func:`solve_placement` over :func:`placement_wire_matrix` — one
    placed candidate beside identity, never the factorial permutation
    space; the reference's NodeAware does exactly this). Uniform links
    solve to identity and add nothing, so the CPU-mesh search space is
    byte-identical to the pre-placement one."""
    if config.num_quantities <= 1:
        batch_options = (True,)
    ks = tuple(ks)  # consumed once per method below
    feas_by_part: Dict[Tuple[int, int, int], Optional[Tuple]] = {}
    placements_by_part: Dict[Tuple[int, int, int],
                             Optional[Tuple[int, ...]]] = {}

    def part_feas(part) -> Optional[Tuple]:
        if part not in feas_by_part:
            feas_by_part[part] = feasible(
                config, PlanChoice(partition=part, method=AXIS_COMPOSED))
        return feas_by_part[part]

    def placed_for(part) -> Optional[Tuple[int, ...]]:
        if link_costs is None:
            return None
        if part not in placements_by_part:
            placements_by_part[part] = None
            feas = part_feas(part)
            if feas is not None:
                spec, mesh_dim, resident = feas
                if resident == Dim3(1, 1, 1):
                    # single-resident only: the placement permutes mesh
                    # positions, and probing an oversubscribed placed
                    # mesh is a follow-up (the search default does not
                    # oversubscribe anyway)
                    w = _cached_wire_matrix(spec, mesh_dim, config, 1)
                    placements_by_part[part] = solve_placement(w, link_costs)
        return placements_by_part[part]

    out = []
    for part in candidate_partitions(config, oversubscribe):
        placements: Tuple[Optional[Tuple[int, ...]], ...] = (None,)
        placed = placed_for(part)
        if placed is not None:
            placements = (None, placed)
        for method in methods:
            for batch in batch_options:
                for k in ks:
                    for placement in placements:
                        out.append(PlanChoice(
                            partition=part, method=method,
                            batch_quantities=batch, multistep_k=k,
                            placement=placement,
                        ))
    return out


def rank(config: PlanConfig, candidates: Iterable[PlanChoice],
         calibration: Optional[dict] = None,
         link_costs=None) -> List[Tuple[PlanCost, PlanChoice]]:
    """Feasible candidates sorted cheapest-first. Ties break on the
    choice label so the order is total and deterministic (the
    permutation-invariance property needs a stable ranking; an identity
    placement's label is a strict prefix of its placed sibling's, so
    identity wins exact ties — placement must EARN its slot)."""
    scored = []
    for choice in candidates:
        c = score(config, choice, calibration, link_costs=link_costs)
        if c is not None:
            scored.append((c, choice))
    scored.sort(key=lambda t: (t[0].total_s, t[1].label()))
    return scored
