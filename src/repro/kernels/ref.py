"""Pure-jnp oracles for every Pallas kernel in this package.

These define the semantics; the kernels must match them (see
tests/test_kernels.py for the shape/dtype sweeps).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def pairwise_l2_ref(q: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Squared ED between every query and every candidate.

    q: [Q, n], x: [C, n] → [Q, C] float32.
    """
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    q2 = jnp.sum(q * q, axis=-1)[:, None]
    x2 = jnp.sum(x * x, axis=-1)[None, :]
    qx = jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(q2 - 2.0 * qx + x2, 0.0)


def qdots_ref(q: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    """Per-query dot products against that query's own candidate rows.

    q: [Q, n], rows: [Q, C, n] → [Q, C] float32.
    """
    return jnp.einsum("qn,qcn->qc", q.astype(jnp.float32),
                      rows.astype(jnp.float32))


def paa_ref(x: jnp.ndarray, segments: int) -> jnp.ndarray:
    """PAA mean-pool.  x: [B, n] → [B, w] float32."""
    b, n = x.shape
    seg = n // segments
    return jnp.mean(x.astype(jnp.float32).reshape(b, segments, seg), axis=-1)


def refine_topk_ref(data, norms, rec_dfs, rec_gid, queries,
                    sel_part, sel_lo, sel_hi, k: int):
    """Dense oracle of the streaming fused refine kernel.

    Same contract as ``repro.kernels.refine_topk.refine_topk`` (plan sorted
    by partition id, pads first): gathers the full ``[Q, MP, cap, n]``
    candidate tensor, masks by DFS interval + segment dedupe, and takes a
    flat top-k — the memory-hungry formulation the kernel streams away.
    Returns ``[Q, k]`` squared ED (+inf pads) and gids (−1 pads).
    """
    q = queries.astype(jnp.float32)
    pid = jnp.maximum(sel_part, 0)
    rows = data[pid].astype(jnp.float32)                    # [Q, MP, cap, n]
    d2 = jnp.maximum(
        jnp.sum(q * q, axis=-1)[:, None, None]
        - 2.0 * jnp.einsum("qn,qmcn->qmc", q, rows,
                           precision=jax.lax.Precision.HIGHEST)
        + norms[pid], 0.0)
    rdfs, rgid = rec_dfs[pid], rec_gid[pid]                 # [Q, MP, cap]

    in_node = (rdfs >= sel_lo[:, :, None]) & (rdfs < sel_hi[:, :, None])
    incl = (rgid >= 0) & in_node & (sel_part >= 0)[:, :, None]
    # earlier same-partition entry covering the record ⇒ duplicate, drop
    same = sel_part[:, None, :] == sel_part[:, :, None]     # [Q, MP, MP']
    earlier = (jnp.arange(sel_part.shape[1])[None, :]
               < jnp.arange(sel_part.shape[1])[:, None])[None]
    cov = (rdfs[:, :, None, :] >= sel_lo[:, None, :, None]) \
        & (rdfs[:, :, None, :] < sel_hi[:, None, :, None])  # [Q, MP, MP', cap]
    dup = jnp.any(cov & (same & earlier)[:, :, :, None], axis=2)
    incl = incl & ~dup

    qn = queries.shape[0]
    flat_d = jnp.where(incl, d2, 3.4e38).reshape(qn, -1)
    flat_g = jnp.where(incl, rgid, -1).reshape(qn, -1)
    if flat_d.shape[-1] < k:
        pad = k - flat_d.shape[-1]
        flat_d = jnp.pad(flat_d, ((0, 0), (0, pad)), constant_values=3.4e38)
        flat_g = jnp.pad(flat_g, ((0, 0), (0, pad)), constant_values=-1)
    neg, idx = jax.lax.top_k(-flat_d, k)
    return -neg, jnp.take_along_axis(flat_g, idx, axis=-1)


def pivot_rank_ref(paa: jnp.ndarray, pivots: jnp.ndarray, m: int) -> jnp.ndarray:
    """Fused pivot distance + top-m prefix extraction.

    paa: [B, w], pivots: [r, w] → [B, m] int32 (ids of m nearest pivots,
    ascending distance, ties toward the lower id).
    """
    paa = paa.astype(jnp.float32)
    pivots = pivots.astype(jnp.float32)
    a2 = jnp.sum(paa * paa, axis=-1, keepdims=True)
    b2 = jnp.sum(pivots * pivots, axis=-1)
    d = jnp.maximum(a2 - 2.0 * (paa @ pivots.T) + b2, 0.0)
    _, idx = jax.lax.top_k(-d, m)
    return idx.astype(jnp.int32)
