"""The plain reference: exact k nearest neighbours by brute force.

Imports nothing of the program and takes nothing it made: it scans the
benchmark's own copy of the corpus.  Squared distances are
‖q‖² − 2q·x + ‖x‖² with the dot at float32 (``HIGHEST``: six bf16 passes
on a TPU), one query block against one corpus chunk at a time, and an
exact ``top_k`` merged chunk by chunk.

``precision="bf16_3x"`` is the control: the same scan with each dot done
as three bf16 products (hi·hi + hi·lo + lo·hi, what the TPU's ``HIGH``
does), the step below the float32 the configuration states.  It is
written out with ``reduce_precision``, so that it reads the same on every
backend: a bf16 round trip by ``astype`` is an identity XLA may remove.

:func:`true_sq_dist` is the float64 check of an answer's distances: the
directly summed Σ(x − q)² of each returned id.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256
MAX_CHUNK = 262_144


def _dots(q, x, precision: str):
    if precision == "highest":
        return jnp.dot(q, x.T, precision=jax.lax.Precision.HIGHEST)
    if precision == "bf16_3x":
        def bf16(a):
            return jax.lax.reduce_precision(a, exponent_bits=8,
                                            mantissa_bits=7)

        def split(a):
            hi = bf16(a)
            return hi, bf16(a - hi)
        qh, ql = split(q)
        xh, xl = split(x)
        # products of bf16 values are exact in float32 at HIGHEST
        dot = partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
        return dot(qh, xh.T) + dot(qh, xl.T) + dot(ql, xh.T)
    raise ValueError(f"unknown precision {precision!r}")


def _chunking(n: int) -> int:
    chunks = -(-n // MAX_CHUNK)
    if n % chunks:
        raise ValueError(f"{n} corpus rows do not split into {chunks} "
                         "equal chunks")
    return n // chunks


@lru_cache(maxsize=8)
def _programs(precision: str, kk: int):
    """The jitted block scan and merge, built once per (precision, k)."""

    @jax.jit
    def block(x, xn, q):
        qn = jnp.sum(q * q, axis=-1)
        d2 = qn[:, None] - 2.0 * _dots(q, x, precision) + xn[None, :]
        neg, idx = jax.lax.top_k(-d2, kk)
        return -neg, idx

    @jax.jit
    def merge(d_a, i_a, d_b, i_b):
        d = jnp.concatenate([d_a, d_b], axis=1)
        i = jnp.concatenate([i_a, i_b], axis=1)
        # lexicographic (distance, id) so ties keep the lower id
        order = jnp.lexsort((i, d), axis=1)[:, :kk]
        return (jnp.take_along_axis(d, order, axis=1),
                jnp.take_along_axis(i, order, axis=1))

    return block, merge


def exact_topk(corpus: np.ndarray, queries: np.ndarray, k: int, *,
               precision: str = "highest"):
    """``(d2 [Q, k] ascending, ids [Q, k])`` over the whole corpus, ties
    by the lower row id."""
    n = len(corpus)
    chunk = _chunking(n)
    kk = min(k, chunk)
    block, merge = _programs(precision, kk)

    x_dev = jax.device_put(np.asarray(corpus, np.float32))
    xn_dev = jnp.sum(x_dev.astype(jnp.float32) ** 2, axis=-1)
    qn_total = len(queries)
    out_d = np.empty((qn_total, kk), np.float32)
    out_i = np.empty((qn_total, kk), np.int64)
    for lo in range(0, qn_total, QUERY_BLOCK):
        qb = np.zeros((QUERY_BLOCK, corpus.shape[1]), np.float32)
        m = min(QUERY_BLOCK, qn_total - lo)
        qb[:m] = queries[lo:lo + m]
        qj = jnp.asarray(qb)
        best = None
        for c in range(0, n, chunk):
            d, i = block(x_dev[c:c + chunk], xn_dev[c:c + chunk], qj)
            i = i + c
            best = (d, i) if best is None else merge(*best, d, i)
        out_d[lo:lo + m] = np.asarray(best[0])[:m]
        out_i[lo:lo + m] = np.asarray(best[1])[:m]
    del x_dev, xn_dev
    return np.maximum(out_d, 0.0), out_i


def true_sq_dist(corpus: np.ndarray, queries: np.ndarray,
                 ids: np.ndarray) -> np.ndarray:
    """Float64 Σ(x − q)² of every returned id (NaN where the id is a pad
    or out of range)."""
    out = np.full(ids.shape, np.nan)
    ok = (ids >= 0) & (ids < len(corpus))
    safe = np.where(ok, ids, 0)
    step = max(1, 4096 // max(1, ids.shape[1]))
    for lo in range(0, len(ids), step):
        rows = corpus[safe[lo:lo + step]].astype(np.float64)
        diff = rows - queries[lo:lo + step, None, :].astype(np.float64)
        out[lo:lo + step] = np.einsum("qkn,qkn->qk", diff, diff)
    out[~ok] = np.nan
    return out
