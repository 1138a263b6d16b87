"""P⁴ dual signature generation — paper §IV-B Step 2 (Def. 5/6).

Given PAA signatures and the fixed pivot set, each object receives:
  * ``p4_rank`` — the *rank-sensitive* signature P4→: ids of its m nearest
    pivots ordered by ascending distance (the pivot-permutation prefix).
  * ``p4_set``  — the *rank-insensitive* signature P4⇄: the same ids under a
    global (ascending-id ≡ lexicographic) order; semantically a set.

For vectorised distance computations the set signature is materialised as an
r-dim one-hot ("bitset") row, and the rank signature as a *weighted* one-hot
row carrying the decay weights of Def. 9 — both make OD/WD single matmuls.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def pivot_distances(paa: jnp.ndarray, pivots: jnp.ndarray) -> jnp.ndarray:
    """Squared Euclidean distances to every pivot.

    Args:
      paa:    ``[..., w]``.
      pivots: ``[r, w]``.
    Returns:
      ``[..., r]`` squared distances (monotone in ED — ranking-equivalent).
    """
    # |a-b|^2 = |a|^2 - 2ab + |b|^2 ; the -2ab term is the MXU-friendly matmul,
    # at full f32 precision: the ranking decides P4-> and so the partitions.
    a2 = jnp.sum(paa * paa, axis=-1, keepdims=True)
    b2 = jnp.sum(pivots * pivots, axis=-1)
    ab = jnp.matmul(paa, pivots.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(a2 - 2.0 * ab + b2, 0.0)


def rank_signature(paa: jnp.ndarray, pivots: jnp.ndarray, m: int) -> jnp.ndarray:
    """P4→ (Def. 5): ids of the m nearest pivots, nearest first.  ``[..., m]``."""
    d = pivot_distances(paa, pivots)
    # top_k of negated distances == m smallest; ties break toward lower id,
    # which matches a deterministic sort on (distance, id).
    _, idx = jax.lax.top_k(-d, m)
    return idx.astype(jnp.int32)


def set_signature(p4_rank: jnp.ndarray) -> jnp.ndarray:
    """P4⇄ (Def. 6): lexicographic (ascending-id) ordering.  ``[..., m]``."""
    return jnp.sort(p4_rank, axis=-1)


def set_onehot(p4: jnp.ndarray, r: int, dtype=jnp.float32) -> jnp.ndarray:
    """Bitset form of a signature: ``[..., r]`` with 1 at member pivot ids.

    Works for either signature ordering (membership is order-free).
    """
    return jax.nn.one_hot(p4, r, dtype=dtype).sum(axis=-2)


def decay_weights(m: int, kind: str = "exp", lam: float = 0.5,
                  dtype=jnp.float32) -> jnp.ndarray:
    """Pivot weights of Def. 9.

    exp:    W_i = λ^(i-1)                         (i = 1..m)
    linear: W_i = λ·(m-i+1) with λ = 1/m  →  [1, (m-1)/m, ..., 1/m]
    """
    i = jnp.arange(1, m + 1, dtype=dtype)
    if kind == "exp":
        w = lam ** (i - 1.0)
    elif kind == "linear":
        w = (m - i + 1.0) / m
    else:
        raise ValueError(f"unknown decay {kind!r}")
    return w.astype(dtype)


def weighted_onehot(p4_rank: jnp.ndarray, r: int, weights: jnp.ndarray) -> jnp.ndarray:
    """``[..., r]`` row with W_i at the i-th ranked pivot's id (Def. 9).

    This turns the Weight Distance (Def. 11) into a single matmul against the
    centroid bitset matrix.
    """
    oh = jax.nn.one_hot(p4_rank, r, dtype=weights.dtype)          # [..., m, r]
    return jnp.einsum("...mr,m->...r", oh, weights)


def compute_signatures(paa: jnp.ndarray, pivots: jnp.ndarray, m: int):
    """Convenience: (p4_rank, p4_set) for a batch of PAA signatures."""
    p4r = rank_signature(paa, pivots, m)
    return p4r, set_signature(p4r)
