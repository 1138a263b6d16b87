"""The work a kernel must do, counted from shapes and plans, and the
chip's peaks it is held against.

``refine_bytes`` is the least HBM traffic of the refine stage for a run's
ticks, whatever implements it: every candidate record a tick's routed
plans select — a live slot of a selected partition whose trie tag lies in
a selected node's DFS interval — read once per tick, at ``4n + 8`` bytes
(the series, and its id and norm).  A record that several queries or plan
entries of one tick select counts once, so a kernel that reads a shared
partition once for the whole tick can reach 100 % and no more.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"
PLAN_BLOCK = 256


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a kind not in the table is
    an error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(table)}")
    return table[device_kind]


def _plans(index, queries: np.ndarray, variant: str):
    """The host planner's plans for ``queries`` (the planner entry the
    fleet's host path calls; the fused device pass reproduces its live
    entries), planned in fixed blocks so one program serves every block."""
    import jax.numpy as jnp
    from repro.core.query import plan
    parts, los, his = [], [], []
    for lo in range(0, len(queries), PLAN_BLOCK):
        blk = np.zeros((PLAN_BLOCK, queries.shape[1]), np.float32)
        m = min(PLAN_BLOCK, len(queries) - lo)
        blk[:m] = queries[lo:lo + m]
        p4r, _ = index.featurize(jnp.asarray(blk))
        qp = plan(index, p4r, variant=variant)
        parts.append(np.asarray(qp.sel_part)[:m])
        los.append(np.asarray(qp.sel_lo)[:m])
        his.append(np.asarray(qp.sel_hi)[:m])
    return np.concatenate(parts), np.concatenate(los), np.concatenate(his)


def tick_candidates(sel_part, sel_lo, sel_hi, rec_dfs, rec_gid) -> int:
    """Distinct candidate records of one tick on one shard: the union over
    its plan entries ``(partition, [lo, hi))`` of the live slots whose
    DFS tag falls in the interval."""
    live = sel_part >= 0
    p, lo, hi = sel_part[live], sel_lo[live], sel_hi[live]
    if p.size == 0:
        return 0
    order = np.argsort(p, kind="stable")
    p, lo, hi = p[order], lo[order], hi[order]
    dfs, gid = rec_dfs[p], rec_gid[p]
    hit = (dfs >= lo[:, None]) & (dfs < hi[:, None]) & (gid >= 0)
    starts = np.flatnonzero(np.r_[True, p[1:] != p[:-1]])
    return int(np.logical_or.reduceat(hit, starts, axis=0).sum())


def refine_bytes(fleet, ticks, variant: str) -> int:
    """Least bytes the refine stage reads for ``ticks``, a list of
    ``(queries [Q, n], routed [Q, S])``."""
    if not ticks:
        return 0
    n = ticks[0][0].shape[1]
    allq = np.concatenate([q for q, _ in ticks])
    bounds = np.cumsum([0] + [len(q) for q, _ in ticks])
    total = 0
    for s, shard in enumerate(fleet.shards):
        st = shard.index.store
        rec_dfs, rec_gid = np.asarray(st.rec_dfs), np.asarray(st.rec_gid)
        sp, lo, hi = _plans(shard.index, allq, variant)
        for t, (_, routed) in enumerate(ticks):
            rows = slice(bounds[t], bounds[t + 1])
            r = routed[:, s][:, None]
            total += tick_candidates(np.where(r, sp[rows], -1), lo[rows],
                                     hi[rows], rec_dfs, rec_gid)
    return total * (4 * n + 8)
