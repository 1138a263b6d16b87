"""How ``correct`` is decided: every answer of the window against the
plain reference (:mod:`benchlib.reference`).

The numbers compared, each against a limit of its configuration's
``limits`` (see PERF.md for the readings each limit was set from):

  * ``dist_err``   — the largest gap, over every returned id of every
    answered request (and of the exhaustive sample), between the squared
    distance the server reported and the float64 Σ(x − q)² of that id.
    The answer says "these ids, at these distances": a wrong id or a
    distance worked out at too low a precision shows here;
  * ``malformed``  — answers that break the answer's shape: ids outside
    the corpus, an id twice, distances out of order, a pad before a live
    slot;
  * ``empty``      — answers with no neighbour at all, although every
    query routes to at least one non-empty partition;
  * ``unanswered`` — requests that never got an answer, or got an error;
  * ``exh_miss``   — in the exhaustive sample (every shard, every
    partition, through the same server), true top-k ids missing from the
    answer, ties at the k-th distance within ``dist_err``'s limit counted
    as found.

Recall of the routed answers is an end-to-end metric, not a check: it is
approximate by design.
"""
from __future__ import annotations

import numpy as np

from benchlib import reference
from benchlib.stats import recall_at_k, tie_tolerance

CHECKS = ("dist_err", "malformed", "empty", "unanswered", "exh_miss")


def malformed_rows(dist: np.ndarray, gid: np.ndarray, n: int) -> np.ndarray:
    """Per answer: whether its ids and distances break the answer shape."""
    live = gid >= 0
    bad = np.any(gid >= n, axis=1)
    # a pad may only follow live slots
    bad |= np.any(~live[:, :-1] & live[:, 1:], axis=1)
    d = np.where(live, dist, np.inf)
    bad |= np.any(d[:, 1:] < d[:, :-1], axis=1)
    srt = np.sort(np.where(live, gid, -1 - np.arange(gid.shape[1])), axis=1)
    bad |= np.any(np.diff(srt, axis=1) == 0, axis=1)
    return bad


def dist_gaps(corpus: np.ndarray, queries: np.ndarray, dist: np.ndarray,
              gid: np.ndarray) -> np.ndarray:
    """|reported d² − float64 d²| of every live slot."""
    true = reference.true_sq_dist(corpus, queries, gid)
    live = (gid >= 0) & np.isfinite(true)
    rep = dist.astype(np.float64) ** 2
    return np.abs(rep - true)[live]


def exhaustive_misses(dist, gid, ref_d2, ref_ids, tol_sq: float) -> int:
    """True top-k ids absent from the answer, boundary ties forgiven."""
    k = ref_ids.shape[1]
    rec = recall_at_k(gid[:, :k], ref_ids, k, approx_dist=dist[:, :k],
                      exact_dist=np.sqrt(ref_d2),
                      tie_tol=_tie(ref_d2, tol_sq))
    return int(round(float(np.sum((1.0 - rec) * k))))


def _tie(ref_d2: np.ndarray, tol_sq: float) -> float:
    kth = float(np.sqrt(np.max(ref_d2[:, -1]))) if ref_d2.size else 0.0
    return tie_tolerance(kth, tol_sq)


def judge(corpus: np.ndarray, *, queries, dist, gid, unanswered: int,
          exh_queries, exh_dist, exh_gid, limits: dict, shards: int = 1,
          devices=None) -> dict:
    """Run the reference, scanning ``corpus`` as ``shards`` shards over
    ``devices`` (:func:`benchlib.reference.exact_topk`), and compare.

    Returns ``{"correct", "checks": {name: {"value", "limit"}},
    "recall": [Q] per-query recall of the routed answers}``.
    """
    k = dist.shape[1]
    tol_sq = float(limits["dist_err"])
    both_d2, both_ids = reference.exact_topk(
        corpus, np.concatenate([queries, exh_queries]), k, shards=shards,
        devices=devices)
    nq = len(queries)
    ref_d2, ref_ids = both_d2[:nq], both_ids[:nq]
    ex_d2, ex_ids = both_d2[nq:], both_ids[nq:]
    tie = _tie(ref_d2, tol_sq)
    recall = recall_at_k(gid, ref_ids, k, approx_dist=dist,
                         exact_dist=np.sqrt(ref_d2), tie_tol=tie)
    gaps = np.concatenate([dist_gaps(corpus, queries, dist, gid),
                           dist_gaps(corpus, exh_queries, exh_dist,
                                     exh_gid)])
    values = {
        "dist_err": float(gaps.max()) if gaps.size else 0.0,
        "malformed": int(malformed_rows(dist, gid, len(corpus)).sum()
                         + malformed_rows(exh_dist, exh_gid,
                                          len(corpus)).sum()),
        "empty": int(np.sum(gid[:, 0] < 0) + np.sum(exh_gid[:, 0] < 0)),
        "unanswered": int(unanswered),
        "exh_miss": exhaustive_misses(exh_dist, exh_gid, ex_d2, ex_ids,
                                      tol_sq),
    }
    checks = {name: {"value": values[name], "limit": limits[name]}
              for name in CHECKS}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return {"correct": bool(correct), "checks": checks, "recall": recall,
            "dist_rms": float(np.sqrt(np.mean(gaps ** 2))) if gaps.size
            else 0.0}
