"""Dss — Distributed Sequential Scan (paper §VII-A baseline).

The vanilla full-scan solution: compare the query against every record in
parallel and take the exact top-k.  Produces the ground truth (recall = 1.0)
for every benchmark; on a mesh it shards the record dimension over the data
axis (each device scans its shard, then one all-gather merges the top-k).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.distances import squared_l2_pairwise

_INF = jnp.float32(3.4e38)


def exact_knn(queries: jnp.ndarray, data: jnp.ndarray, k: int,
              *, chunk: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact kNN by full scan.

    Args:
      queries: ``[Q, n]``; data: ``[N, n]``; k: answers per query.
      chunk: scan the dataset in chunks of this many rows (0 = single pass) —
        bounds the [Q, N] distance matrix for big N.

    Returns:
      (dist, idx): ``[Q, k]`` ascending true ED + record ids.
    """
    qn = queries.shape[0]
    n_rec = data.shape[0]
    k = min(k, n_rec)
    if not chunk or chunk >= n_rec:
        d2 = squared_l2_pairwise(queries, data)
        neg, idx = jax.lax.top_k(-d2, k)
        return jnp.sqrt(jnp.maximum(-neg, 0.0)), idx

    # streaming scan with a running top-k (the disk-resident formulation)
    best_d = jnp.full((qn, k), _INF)
    best_i = jnp.full((qn, k), -1, dtype=jnp.int32)
    for start in range(0, n_rec, chunk):
        block = jax.lax.dynamic_slice_in_dim(
            data, start, min(chunk, n_rec - start), axis=0)
        d2 = squared_l2_pairwise(queries, block)
        ids = start + jnp.arange(block.shape[0], dtype=jnp.int32)
        cat_d = jnp.concatenate([best_d, d2], axis=-1)
        cat_i = jnp.concatenate([best_i, jnp.tile(ids, (qn, 1))], axis=-1)
        neg, pos = jax.lax.top_k(-cat_d, k)
        best_d = -neg
        best_i = jnp.take_along_axis(cat_i, pos, axis=-1)
    return jnp.sqrt(jnp.maximum(best_d, 0.0)), best_i


def exact_knn_sharded(queries: jnp.ndarray, data: jnp.ndarray, k: int,
                      *, mesh, data_axis: str = "data"):
    """Mesh version: records sharded over ``data_axis``, queries replicated."""
    from jax.sharding import PartitionSpec as P

    def local(q, x):
        d2 = squared_l2_pairwise(q, x)
        neg, idx = jax.lax.top_k(-d2, k)
        base = jax.lax.axis_index(data_axis) * x.shape[0]
        idx = idx + base
        d_all = jax.lax.all_gather(-neg, data_axis, axis=0)
        i_all = jax.lax.all_gather(idx, data_axis, axis=0)
        d = d_all.transpose(1, 0, 2).reshape(q.shape[0], -1)
        i = i_all.transpose(1, 0, 2).reshape(q.shape[0], -1)
        neg2, pos = jax.lax.top_k(-d, k)
        return jnp.sqrt(jnp.maximum(-neg2, 0.0)), jnp.take_along_axis(i, pos, -1)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(P(), P(data_axis)),
                       out_specs=(P(), P()), check_vma=False)
    return fn(queries, data)


def recall(approx_ids: jnp.ndarray, exact_ids: jnp.ndarray) -> float:
    """Def. 4: |S_approx ∩ S_exact| / |S_exact|, averaged over queries."""
    import numpy as np
    a = np.asarray(approx_ids)
    e = np.asarray(exact_ids)
    scores = []
    for i in range(a.shape[0]):
        sa = set(int(v) for v in a[i] if v >= 0)
        se = set(int(v) for v in e[i])
        scores.append(len(sa & se) / max(len(se), 1))
    return float(np.mean(scores))
