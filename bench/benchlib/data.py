"""The corpus of a configuration, and the queries of a run.

The corpus comes from the configuration's fixed key, so every run of a
configuration serves the same data and builds the same index; ``--seed``
draws only the queries and their order.  Copies of the program's
generators (``repro.data.series.random_walk`` and the query perturbation
of ``repro.eval.datasets``), so that a change to the program's copies
cannot change the benchmark's data.  The corpus is made on the device,
one jitted chunk at a time: made whole, 2,000,000 series of length 256
peak at 8.22 GB while the cumulative sum and the normalisation run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def znormalize(x, eps: float = 1e-8):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    sd = jnp.std(x, axis=-1, keepdims=True)
    return (x - mu) / jnp.maximum(sd, eps)


def random_walk(key, num: int, length: int):
    steps = jax.random.normal(key, (num, length), dtype=jnp.float32)
    return znormalize(jnp.cumsum(steps, axis=-1))


GENERATORS = {"random_walk": random_walk}


def root_key(seed: int):
    """A PRNG key from any whole number that fits in 64 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_corpus(recipe: dict, series_len: int, num_series: int) -> list:
    """``recipe["shards"]`` host arrays ``[num_series / shards, n]``, the
    same in every run: they are drawn from the recipe's fixed ``key``,
    never from ``--seed``, so that every seed serves the same index.

    Shard ``s`` draws its rows chunk by chunk under its own key.
    """
    shards = int(recipe["shards"])
    per = num_series // shards
    chunk = min(int(recipe["chunk"]), per)
    if per * shards != num_series or per % chunk:
        raise ValueError(f"{num_series} series do not split into {shards} "
                         f"shards of whole {chunk}-series chunks")
    gen = GENERATORS[recipe["generator"]]

    @jax.jit
    def make_chunk(key):
        return gen(key, chunk, series_len)

    root = root_key(int(recipe["key"]))
    out = []
    for s in range(shards):
        kshard = jax.random.fold_in(root, s)
        arr = np.empty((per, series_len), np.float32)
        for c in range(per // chunk):
            arr[c * chunk:(c + 1) * chunk] = np.asarray(
                make_chunk(jax.random.fold_in(kshard, c)))
        out.append(arr)
    return out


def make_queries(corpus: np.ndarray, count: int, noise: float,
                 seed: int) -> np.ndarray:
    """``count`` distinct corpus rows plus Gaussian noise of ``noise``
    standard deviations, re-z-normalised: near a series, never one."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, 0x9E5])
    rows = rng.choice(len(corpus), size=count, replace=False)
    q = corpus[np.sort(rows)][rng.permutation(count)].astype(np.float64)
    q += noise * rng.standard_normal(q.shape)
    q -= q.mean(axis=1, keepdims=True)
    q /= np.maximum(q.std(axis=1, keepdims=True), 1e-8)
    return q.astype(np.float32)


def run_queries(cfg: dict, traffic: dict, corpus: np.ndarray, seconds: float,
                seed: int):
    """A run's ``(warm-up queries, window queries)``: enough warm-up
    queries for one tick of every live-row count plus one per connection,
    then as many distinct window queries as the traffic may send."""
    from benchlib.traffic import queries_needed
    bs = int(cfg["serving"]["batch_size"])
    n_warm = bs * (bs + 1) // 2 + int(traffic["connections"])
    qs = make_queries(corpus, n_warm + queries_needed(traffic, seconds),
                      float(traffic["query_noise"]), seed)
    return qs[:n_warm], qs[n_warm:]


def exhaustive_pick(seed: int, answered: np.ndarray, size: int) -> np.ndarray:
    """The answered requests, drawn from the seed, that are asked again
    over the exhaustive route."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, 0xE4])
    return np.sort(rng.choice(answered, size=min(size, answered.size),
                              replace=False))
