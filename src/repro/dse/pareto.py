"""Non-dominated sorting over batched DSE objectives.

Objectives arrive as an (N, K) float matrix plus a per-column sense
(maximize / minimize); each column is flipped so that larger is better.
``pareto_mask`` finds the non-dominated set in two steps.

1. Ordering.  Rows with a NaN objective never survive and are dropped.
   The rest are sorted lexicographically, best first, on
   ``(obj0, obj1, ...)``, and a row equal to its predecessor in every
   objective is folded into its first copy and shares its fate, so
   duplicates keep each other.  A dominator is lexicographically
   greater than what it dominates, so among the distinct rows it always
   comes earlier: a row is dominated iff some earlier distinct row is
   >= in every objective, and, by transitivity, iff some earlier
   survivor is.
2. The query.  K = 1: only the first distinct row survives.  K = 2: a
   row survives iff its obj1 is above the running maximum of obj1
   before it.  K = 3: chunks of rows are asked, with one
   ``searchsorted``, whether the staircase of the survivors so far
   (sorted descending on obj1, obj2 rising along it) holds a point >=
   in obj1 and obj2; the few rows no step covers are settled among
   themselves pairwise and their survivors merged into the staircase.
   That is O(N log N) plus O(c^2) per chunk of c uncovered rows.
   K >= 4 compares each chunk pairwise against every survivor so far
   instead, O(N * front * K).

``nondominated_sort`` peels fronts NSGA-II-style and
``crowding_distance`` supplies the diversity metric for the
evolutionary driver.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

from repro.obs import metrics as obs_metrics


def _as_max(objectives: np.ndarray, maximize: Sequence[bool]) -> np.ndarray:
    obj = np.asarray(objectives, np.float64)
    if obj.ndim != 2:
        raise ValueError("objectives must be (N, K)")
    sign = np.where(np.asarray(maximize, bool), 1.0, -1.0)
    return obj * sign


def pareto_mask(objectives: np.ndarray, maximize: Sequence[bool],
                chunk: int = 512) -> np.ndarray:
    """(N,) bool — True where no other point weakly dominates the point
    (>= in every objective, > in at least one).  Duplicate points keep
    each other (neither strictly dominates); a point with a NaN
    objective is False.

    Rows are ranked lexicographically best first and exact duplicates
    folded into their first copy (module docstring); then K <= 2 is
    settled by a running maximum, K = 3 by a staircase query per
    ``chunk`` rows, K >= 4 by pairwise comparison of each ``chunk``
    against the survivors so far.  Counts ``pareto.rows`` (rows
    ranked), ``pareto.dup_rows`` (rows folded) and
    ``pareto.staircase_rows`` (rows settled with K <= 3)."""
    M = _as_max(objectives, maximize)
    keep = ~np.isnan(M).any(1)
    idx = np.nonzero(keep)[0]
    if not len(idx):
        return keep
    n, k = len(idx), M.shape[1]
    # np.lexsort sorts on its last key first
    order = idx[np.lexsort(-M[idx].T[::-1])]
    Ms = M[order]
    first = np.ones(n, bool)
    first[1:] = (Ms[1:] != Ms[:-1]).any(1)
    U = Ms[first]
    if k <= 2:
        alive = np.zeros(len(U), bool)
        alive[0] = True
        if k == 2:
            alive[1:] = U[1:, 1] > np.maximum.accumulate(U[:-1, 1])
    else:
        alive = _settle_chunks(U, chunk)
    keep[order] = alive[np.cumsum(first) - 1]
    obs_metrics.inc("pareto.rows", n)
    obs_metrics.inc("pareto.dup_rows", n - len(U))
    if k <= 3:
        obs_metrics.inc("pareto.staircase_rows", n)
    return keep


def _settle_chunks(U: np.ndarray, chunk: int) -> np.ndarray:
    """Survivors among distinct rows in lexicographic order, K >= 3.

    A chunk's rows are first held against the survivors of earlier
    chunks (the staircase for K = 3, every survivor for K >= 4); those
    left are compared among themselves.  A row culled by an earlier
    survivor cannot dominate one that was not, so the second step needs
    only the rows the first left."""
    m, k = U.shape
    alive = np.zeros(m, bool)
    stair = U[:0, 1:]
    for lo in range(0, m, chunk):
        blk = U[lo:lo + chunk]
        if k == 3:
            free = ~_under_staircase(stair, blk[:, 1:])
        else:
            free = ~_dominated_by(U[:lo][alive[:lo]], blk)
        new = np.nonzero(free)[0]
        new = new[~_dominated_by(blk[new], blk[new])]
        alive[lo + new] = True
        if k == 3:
            stair = _staircase(np.concatenate([stair, blk[new, 1:]]))
    return alive


def _staircase(P: np.ndarray) -> np.ndarray:
    """The 2-D maxima of ``P`` (rows of obj1, obj2): sorted descending on
    obj1, each row's obj2 above every earlier one's."""
    P = P[np.lexsort((-P[:, 1], -P[:, 0]))]
    step = np.ones(len(P), bool)
    step[1:] = P[1:, 1] > np.maximum.accumulate(P[:-1, 1])
    return P[step]


def _under_staircase(stair: np.ndarray, Q: np.ndarray) -> np.ndarray:
    """(len(Q),) bool — some step is >= the row of ``Q`` in both
    columns.  The steps with obj1 >= q1 are a prefix of ``stair``, and
    the last of them has the prefix's largest obj2."""
    n_ge = np.searchsorted(-stair[:, 0], -Q[:, 0], side="right")
    hit = n_ge > 0
    hit[hit] = stair[n_ge[hit] - 1, 1] >= Q[hit, 1]
    return hit


def _dominated_by(C: np.ndarray, B: np.ndarray) -> np.ndarray:
    """(len(B),) bool — B_j weakly dominated by some C_i (>= everywhere,
    > somewhere; equal rows do not dominate).  Built from per-objective
    2-D comparisons to avoid 3-D broadcast temporaries."""
    ge = np.ones((C.shape[0], B.shape[0]), bool)
    eq = np.ones_like(ge)
    for k in range(C.shape[1]):
        ck = C[:, k, None]
        bk = B[None, :, k]
        ge &= ck >= bk
        eq &= ck == bk
    return (ge & ~eq).any(0)


def nondominated_sort(objectives: np.ndarray, maximize: Sequence[bool],
                      max_fronts: int = 0) -> np.ndarray:
    """NSGA-II fast non-dominated sort: (N,) int rank, 0 = Pareto front.

    Points never ranked (NaN objectives, or beyond ``max_fronts``) get
    rank N (worst)."""
    obj = np.asarray(objectives, np.float64)
    n = obj.shape[0]
    ranks = np.full(n, n, np.int64)
    remaining = ~np.isnan(obj).any(1)
    rank = 0
    while remaining.any():
        if max_fronts and rank >= max_fronts:
            break
        idx = np.nonzero(remaining)[0]
        front = pareto_mask(obj[idx], maximize)
        ranks[idx[front]] = rank
        remaining[idx[front]] = False
        rank += 1
    return ranks


def crowding_distance(objectives: np.ndarray,
                      maximize: Sequence[bool]) -> np.ndarray:
    """NSGA-II crowding distance within one front (larger = lonelier)."""
    M = _as_max(objectives, maximize)
    n, k = M.shape
    if n <= 2:
        return np.full(n, np.inf)
    dist = np.zeros(n)
    for j in range(k):
        order = np.argsort(M[:, j], kind="stable")
        span = M[order[-1], j] - M[order[0], j]
        dist[order[0]] = dist[order[-1]] = np.inf
        if span <= 0:
            continue
        gaps = (M[order[2:], j] - M[order[:-2], j]) / span
        dist[order[1:-1]] += gaps
    return dist


def pareto_front_indices(objectives: np.ndarray, maximize: Sequence[bool]
                         ) -> np.ndarray:
    """Indices of the non-dominated set, best-first by objective 0."""
    mask = pareto_mask(objectives, maximize)
    idx = np.nonzero(mask)[0]
    M = _as_max(objectives[idx], maximize)
    return idx[np.argsort(-M[:, 0], kind="stable")]
