"""Similarity metrics of the dual representation — paper Defs. 3, 7, 9–11.

All metrics are expressed as dense linear algebra over bitset/weighted-bitset
rows so they vectorise over millions of objects and shard cleanly under pjit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def euclidean(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """ED (Def. 3) between broadcast-compatible series.  ``[...]``."""
    return jnp.sqrt(jnp.maximum(jnp.sum((x - y) ** 2, axis=-1), 0.0))


def squared_l2_pairwise(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Pairwise squared ED: x ``[Q, n]``, y ``[N, n]`` → ``[Q, N]``.

    Ranking-equivalent to ED; the sqrt is deferred to presentation time.
    """
    x2 = jnp.sum(x * x, axis=-1)[:, None]
    y2 = jnp.sum(y * y, axis=-1)[None, :]
    xy = jnp.matmul(x, y.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(x2 - 2.0 * xy + y2, 0.0)


def overlap_distance(x_onehot: jnp.ndarray, c_onehot: jnp.ndarray,
                     m: int) -> jnp.ndarray:
    """OD (Def. 7): m − |X ∩ Y| for bitset rows.

    Args:
      x_onehot: ``[..., r]`` object bitsets.
      c_onehot: ``[G, r]`` centroid bitsets.
      m: prefix length.
    Returns:
      ``[..., G]`` integer-valued distances in [0, m] (float dtype).
    """
    return m - x_onehot @ c_onehot.T


def total_weight(weights: jnp.ndarray) -> jnp.ndarray:
    """TW (Def. 10) — constant given fixed m and decay."""
    return jnp.sum(weights)


def weight_distance(x_weighted: jnp.ndarray, c_onehot: jnp.ndarray,
                    tw: jnp.ndarray) -> jnp.ndarray:
    """WD (Def. 11): TW − Σ_i W_i·1[pivot_i ∈ centroid].

    Args:
      x_weighted: ``[..., r]`` weighted bitsets (decay weight at pivot id).
      c_onehot:   ``[G, r]``.
      tw: scalar total weight.
    Returns:
      ``[..., G]``.
    """
    return tw - jnp.matmul(x_weighted, c_onehot.T,
                           precision=jax.lax.Precision.HIGHEST)
