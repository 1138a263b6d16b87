"""Recall / ranking metrics for approximate-kNN evaluation.

The Hydra papers' central lesson is that approximate data-series search
must be judged by *accuracy per unit of data touched*, measured carefully:
ties at the k-th distance boundary must not be scored as misses (any
record at exactly the boundary distance is as correct as the one the
oracle happened to return), and pad sentinel rows (``gid = -1`` /
:data:`repro.core.refine.PAD_DIST`) must be excluded on both sides.

Everything here is pure numpy over ``(dist, gid)`` answer arrays in the
fleet's wire shape — ``[Q, k]`` ascending distance, ``-1``-padded ids.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

__all__ = ["recall_at_k", "mean_average_precision", "frontier_auc",
           "answers_agree", "assert_same_answers", "distance_error",
           "DIST_ULPS"]

# Distances are sqrt(max(‖q‖² − 2q·x + ‖x‖², 0)) in float32: two paths that
# sum the dot in a different order (batch shape, placement, kernel blocking,
# device) agree on the squared distance only to the rounding of its largest
# term, ‖q‖² + ‖x‖².  DIST_ULPS float32 ulps of that bound is the stated
# tolerance; near a self-match it is the whole difference (sqrt of the
# cancellation noise), elsewhere it is far below 1e-4.
DIST_ULPS = 16


def _valid(ids: np.ndarray) -> np.ndarray:
    return ids[ids >= 0]


def answers_agree(dist_a: np.ndarray, gid_a: np.ndarray,
                  dist_b: np.ndarray, gid_b: np.ndarray, *, scale: float,
                  ulps: int = DIST_ULPS) -> Tuple[bool, str]:
    """Whether two exact-ED answers are the same answer.

    Gids must be identical, order and pads included.  Squared distances
    may differ by ``ulps`` float32 ulps of ``scale`` — a bound on
    ‖q‖² + ‖x‖² (``2 * series_len`` for z-normalised series) — which is
    the rounding of the ‖q‖² − 2q·x + ‖x‖² formulation (see
    :data:`DIST_ULPS`).  Pad slots carry the same sentinel on both sides,
    so they compare equal.  Returns ``(ok, reason)``.
    """
    ga, gb = np.asarray(gid_a), np.asarray(gid_b)
    if ga.shape != gb.shape or not np.array_equal(ga, gb):
        bad = int(np.sum(ga != gb)) if ga.shape == gb.shape else -1
        return False, f"gids differ ({bad} slots of {ga.size})"
    err, tol = distance_error(dist_a, dist_b, scale=scale, ulps=ulps)
    if err > tol:
        return False, f"squared distances differ by {err:.3e} > {tol:.3e}"
    return True, "ok"


def distance_error(dist_a: np.ndarray, dist_b: np.ndarray, *, scale: float,
                   ulps: int = DIST_ULPS) -> Tuple[float, float]:
    """``(max |d_a² − d_b²|, ulps·eps32·scale)`` over matching slots — the
    distance half of :func:`answers_agree`, usable where gids may differ
    at exact ties (a float64 reference against a float32 answer)."""
    da = np.asarray(dist_a, np.float64)
    db = np.asarray(dist_b, np.float64)
    err = np.abs(da * da - db * db)
    tol = ulps * float(np.finfo(np.float32).eps) * float(scale)
    return (float(err.max()) if err.size else 0.0), tol


def assert_same_answers(dist_a, gid_a, dist_b, gid_b, *, scale: float,
                        ulps: int = DIST_ULPS) -> None:
    """:func:`answers_agree`, raising ``AssertionError`` with the reason."""
    ok, why = answers_agree(dist_a, gid_a, dist_b, gid_b, scale=scale,
                            ulps=ulps)
    if not ok:
        raise AssertionError(why)


def recall_at_k(approx_ids: np.ndarray, exact_ids: np.ndarray,
                k: Optional[int] = None, *,
                approx_dist: Optional[np.ndarray] = None,
                exact_dist: Optional[np.ndarray] = None,
                tie_tol: float = 1e-5) -> float:
    """Mean fraction of the true k nearest neighbours returned.

    Args:
      approx_ids / exact_ids: ``[Q, >=k]`` id arrays; ``-1`` marks pad
        slots and is excluded on both sides.
      k: evaluate the first ``k`` columns (default: exact answer width).
      approx_dist / exact_dist: when both are given, ties are handled:
        an approximate id *not* in the exact id set still counts as a hit
        if its distance is within ``tie_tol`` of the k-th exact distance —
        the oracle's choice among boundary-equidistant records is
        arbitrary, so any of them is correct.

    Returns the mean over queries with a non-empty exact answer (1.0 when
    no query has one).
    """
    approx_ids = np.asarray(approx_ids)
    exact_ids = np.asarray(exact_ids)
    k = k or exact_ids.shape[1]
    per_query = []
    for i in range(len(exact_ids)):
        truth = _valid(exact_ids[i, :k])
        if truth.size == 0:
            continue
        got = _valid(approx_ids[i, :k])
        hits = np.isin(got, truth).sum()
        if approx_dist is not None and exact_dist is not None:
            boundary = exact_dist[i, :k][exact_ids[i, :k] >= 0].max()
            tied = (~np.isin(got, truth)) \
                & (approx_dist[i, :k][approx_ids[i, :k] >= 0]
                   <= boundary + tie_tol)
            hits = min(int(hits + tied.sum()), truth.size)
        per_query.append(hits / truth.size)
    return float(np.mean(per_query)) if per_query else 1.0


def mean_average_precision(approx_ids: np.ndarray,
                           exact_ids: np.ndarray,
                           k: Optional[int] = None) -> float:
    """MAP@k: order-sensitive quality of the returned ranking.

    Average precision rewards placing true neighbours early: for each
    approximate rank holding a true neighbour, take the precision of the
    prefix up to it, and average over the number of true neighbours.  Pad
    slots (``id < 0``) are skipped without occupying a rank.
    """
    approx_ids = np.asarray(approx_ids)
    exact_ids = np.asarray(exact_ids)
    k = k or exact_ids.shape[1]
    per_query = []
    for i in range(len(exact_ids)):
        truth = set(int(x) for x in _valid(exact_ids[i, :k]))
        if not truth:
            continue
        hits, precisions, rank = 0, [], 0
        for g in approx_ids[i, :k]:
            if g < 0:
                continue
            rank += 1
            if int(g) in truth:
                hits += 1
                precisions.append(hits / rank)
        per_query.append(sum(precisions) / len(truth))
    return float(np.mean(per_query)) if per_query else 1.0


def frontier_auc(points: Sequence[Tuple[float, float]]) -> float:
    """Area under a (cost, recall) frontier, normalised to [0, 1].

    ``points`` are ``(fraction_of_data_scanned, recall)`` pairs from one
    sweep (any order; deduplicated on cost by best recall).  The curve is
    extended flat to cost 1.0 from its last point and starts at
    ``(min_cost, its recall)`` — so AUC rewards reaching high recall at
    *low* cost, the Hydra frontier criterion.  One point yields its recall
    × the covered interval.  Empty input yields 0.
    """
    if not points:
        return 0.0
    best = {}
    for c, r in points:
        c = float(min(max(c, 0.0), 1.0))
        best[c] = max(best.get(c, 0.0), float(r))
    xs = sorted(best)
    # step-function integral (conservative: recall holds until the next
    # measured cost), extended flat to cost 1.0
    auc, prev_x = 0.0, xs[0]
    for i, x in enumerate(xs[1:], 1):
        auc += best[xs[i - 1]] * (x - prev_x)
        prev_x = x
    auc += best[xs[-1]] * (1.0 - prev_x)
    span = 1.0 - xs[0]
    return auc / span if span > 0 else best[xs[-1]]
