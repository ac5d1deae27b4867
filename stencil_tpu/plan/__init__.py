"""Exchange planning: the ExchangePlan IR, the partition/method autotuner,
and the on-disk plan DB.

This package is the production analogue of the reference's entire L3 —
``RankPartition``/``NodePartition`` searching partition shapes and the
``NodeAware`` placement costing candidates by link bandwidth (reference:
include/stencil/partition.hpp, placement.hpp). Four pieces:

- :mod:`ir` — the declarative ExchangePlan every exchange method lowers
  from (phases, directions, pack groups, permute pairs). The planner
  searches *plans*, not code paths; ``parallel/exchange.py`` is the
  lowering.
- :mod:`cost` — a static cost model fed by the plan's collective counts /
  on-wire bytes and the per-collective overhead ratios recorded in
  BASELINE.md rounds 7/10.
- :mod:`probe` — short measured refinement probes (reusing
  ``apps/_bench_common.time_exchange``) over the top static candidates.
- :mod:`db` — the on-disk JSON plan DB keyed by canonical config, so
  production runs replay tuned plans with zero probe runs.

Only :mod:`ir` is imported eagerly (pure geometry, no jax at import
time); import the tuner explicitly (``from stencil_tpu.plan.autotune
import autotune``) — a package-level alias would be shadowed by the
submodule of the same name as soon as anything imports it.
"""

from .ir import (
    AxisPhaseIR,
    DirectPhaseIR,
    ExchangePlan,
    PlanChoice,
    PlanConfig,
    build_plan,
    validate_placement,
)

__all__ = [
    "AxisPhaseIR",
    "DirectPhaseIR",
    "ExchangePlan",
    "PlanChoice",
    "PlanConfig",
    "build_plan",
    "validate_placement",
]
