"""Percentiles and recall: the arithmetic the metrics share."""
from __future__ import annotations

from typing import Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0–100) by the nearest-rank rule: the
    smallest value with at least ``q`` % of the sample at or below it.
    Nearest rank never interpolates, so the reading is a latency that one
    request actually had."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("percentile of an empty sample")
    rank = int(np.ceil(q / 100.0 * v.size))
    return float(v[max(rank, 1) - 1])


def recall_at_k(approx_ids: np.ndarray, exact_ids: np.ndarray, k: int, *,
                approx_dist: np.ndarray, exact_dist: np.ndarray,
                tie_tol: float) -> np.ndarray:
    """Per-query recall@k with ties at the boundary counted as hits.

    An approximate id outside the exact id set still counts as a hit when
    its distance is within ``tie_tol`` of the k-th exact distance: the
    reference's choice among equidistant records is arbitrary.  Pad slots
    (id -1) are left out on both sides.  Returns ``[Q]`` recalls (queries
    with an empty exact answer read 1.0).
    """
    out = np.ones(len(exact_ids))
    for i in range(len(exact_ids)):
        live_e = exact_ids[i, :k] >= 0
        truth = exact_ids[i, :k][live_e]
        if truth.size == 0:
            continue
        live_a = approx_ids[i, :k] >= 0
        got = approx_ids[i, :k][live_a]
        inside = np.isin(got, truth)
        boundary = exact_dist[i, :k][live_e].max()
        tied = ~inside & (approx_dist[i, :k][live_a] <= boundary + tie_tol)
        out[i] = min(int(inside.sum() + tied.sum()), truth.size) / truth.size
    return out


def tie_tolerance(kth_dist: float, tol_sq: float) -> float:
    """The slack in distance at a boundary ``kth_dist`` that a slack of
    ``tol_sq`` in squared distance implies."""
    return float(np.sqrt(kth_dist * kth_dist + tol_sq) - kth_dist)
