"""Quadratic-assignment solvers for topology-aware placement.

TPU-native re-implementation of the reference's QAP machinery
(reference: include/stencil/qap.hpp): assign subdomains (with a pairwise
communication-volume matrix ``w``) to devices (with a pairwise distance
matrix ``d``) minimizing ``sum_ab w[a,b] * d[f[a], f[b]]``. Zero times
infinity counts as zero (qap.hpp ``cost_product``), so "no communication"
never pays an infinite-distance penalty.

Two solvers, matching the reference:
- :func:`solve` — exhaustive permutation search in lexicographic order from
  the identity, with a wall-clock timeout (qap.hpp:51-85, 10 s default).
- :func:`solve_catch` — greedy best-pairwise-swap descent with incremental
  cost updates (qap.hpp:87-180).

Both dispatch to the native C++ implementation
(``stencil_tpu/native/qap.cpp``) when the shared library is available —
the exhaustive search is the one compute-heavy host-side component of the
framework, and C++ explores ~100x more permutations within the same
timeout budget. The pure-Python paths remain as a fallback and as the
executable specification.
"""

from __future__ import annotations

import itertools
import time
from typing import List, Sequence, Tuple

import numpy as np

from ..utils import logging as log


def make_reciprocal(m: np.ndarray) -> np.ndarray:
    """Elementwise 1/x (reference: mat2d.hpp:184-199); 1/inf = 0."""
    m = np.asarray(m, dtype=np.float64)
    with np.errstate(divide="ignore"):
        return np.where(np.isinf(m), 0.0, np.divide(1.0, m))


def cost(w: np.ndarray, d: np.ndarray, f: Sequence[int]) -> float:
    """Assignment cost with 0*inf == 0 (reference: qap.hpp cost/cost_product)."""
    w = np.asarray(w, dtype=np.float64)
    d = np.asarray(d, dtype=np.float64)
    f = np.asarray(f, dtype=np.intp)
    dperm = d[np.ix_(f, f)]
    prod = w * dperm
    prod[(w == 0) | (dperm == 0)] = 0.0
    return float(prod.sum())


def solve(
    w: np.ndarray,
    d: np.ndarray,
    timeout_s: float = 10.0,
    use_native: bool = True,
) -> Tuple[List[int], float]:
    """Exhaustive search (timeout-bounded), returns (assignment, cost)."""
    w = np.ascontiguousarray(w, dtype=np.float64)
    d = np.ascontiguousarray(d, dtype=np.float64)
    n = w.shape[0]
    if w.shape != (n, n) or d.shape != (n, n):
        raise ValueError(
            f"weight/distance matrices must both be ({n}, {n}); got "
            f"{w.shape} and {d.shape}"
        )
    if use_native:
        native = _native()
        if native is not None:
            return native.solve(w, d, timeout_s)
    stop = time.monotonic() + timeout_s
    best_f = list(range(n))
    best_cost = cost(w, d, best_f)
    for perm in itertools.permutations(range(n)):
        if time.monotonic() > stop:
            log.warn("qap.solve timed out")
            break
        c = cost(w, d, perm)
        if c < best_cost:
            best_cost = c
            best_f = list(perm)
    return best_f, best_cost


def solve_catch(
    w: np.ndarray, d: np.ndarray, use_native: bool = True
) -> Tuple[List[int], float]:
    """Greedy best-pairwise-swap descent (reference: qap.hpp:87-180).

    Improvements must beat a relative epsilon: the incremental cost update
    accumulates float drift, and on symmetric inputs (many equal-cost
    assignments) drift-sized "improvements" would otherwise cycle forever
    (latent infinite loop in the reference's algorithm)."""
    w = np.ascontiguousarray(w, dtype=np.float64)
    d = np.ascontiguousarray(d, dtype=np.float64)
    n = w.shape[0]
    if w.shape != (n, n) or d.shape != (n, n):
        raise ValueError(
            f"weight/distance matrices must both be ({n}, {n}); got "
            f"{w.shape} and {d.shape}"
        )
    if use_native:
        native = _native()
        if native is not None:
            return native.solve_catch(w, d)

    def pair(a, b, fa, fb):
        we, de = w[a, b], d[fa, fb]
        return 0.0 if (we == 0 or de == 0) else we * de

    best_f = list(range(n))
    best_cost = cost(w, d, best_f)
    improved = True
    while improved:
        improved = False
        impr_f, impr_cost = best_f, best_cost
        for i in range(n):
            for j in range(i + 1, n):
                f = list(best_f)
                c = best_cost
                for k in range(n):
                    c -= pair(i, k, f[i], f[k])
                    c -= pair(j, k, f[j], f[k])
                    if k != i and k != j:
                        c -= pair(k, i, f[k], f[i])
                        c -= pair(k, j, f[k], f[j])
                f[i], f[j] = f[j], f[i]
                for k in range(n):
                    c += pair(i, k, f[i], f[k])
                    c += pair(j, k, f[j], f[k])
                    if k != i and k != j:
                        c += pair(k, i, f[k], f[i])
                        c += pair(k, j, f[k], f[j])
                if c < impr_cost - 1e-12 * (1.0 + abs(impr_cost)):
                    impr_f, impr_cost = f, c
                    improved = True
        if improved:
            best_f, best_cost = impr_f, impr_cost
    return best_f, best_cost


# -- native dispatch ----------------------------------------------------------

_NATIVE = None
_NATIVE_TRIED = False


def _native():
    global _NATIVE, _NATIVE_TRIED
    if not _NATIVE_TRIED:
        _NATIVE_TRIED = True
        try:
            from ..native import qap_native

            _NATIVE = qap_native
        except ImportError as e:
            # the library is built from native/ at import; no compiler or
            # no make means the Python solvers below do the work
            log.warn(f"{e}; qap takes the Python solvers")
            _NATIVE = None
    return _NATIVE
