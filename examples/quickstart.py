"""Quickstart: build a CLIMBER index over data series and run kNN queries.

    PYTHONPATH=src python examples/quickstart.py

Covers the paper's full pipeline at laptop scale: synthetic RandomWalk data
→ CLIMBER-INX construction (PAA → P⁴ dual signatures → groups → trie
partitions) → CLIMBER-kNN-Adaptive queries → recall against the exact scan.
"""
import jax
import numpy as np

from repro.baselines import exact_knn, recall
from repro.core import build_index, knn_query
from repro.data import make_dataset, make_queries
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.config import ClimberConfig


def main():
    enable_compile_cache()
    cfg = ClimberConfig(
        series_len=256,        # n  — raw readings per series
        paa_segments=16,       # w  — PAA word length
        num_pivots=96,         # r  — pivots (paper default is 200 at TB scale)
        prefix_len=10,         # m  — pivot-permutation-prefix length
        capacity=256,          # c  — partition capacity (HDFS-block analogue)
        sample_frac=0.15,      # α  — skeleton sample
        k=50,
        adaptive_factor=4,     # CLIMBER-kNN-Adaptive-4X (paper default)
        candidate_groups=8,
    )

    print("generating 20k RandomWalk series ...")
    data = make_dataset("randomwalk", jax.random.PRNGKey(0), 20_000, 256)
    queries = make_queries(jax.random.PRNGKey(1), data, 16)

    print("building CLIMBER-INX ...")
    index = build_index(jax.random.PRNGKey(2), data, cfg)
    print(f"  groups={index.num_groups} partitions="
          f"{index.forest.num_partitions} trie_nodes={index.forest.num_nodes}")

    print("running CLIMBER-kNN-Adaptive ...")
    dist, gid, plan = knn_query(index, queries, 50, variant="adaptive")

    _, exact_ids = exact_knn(queries, data, 50)
    r = recall(np.asarray(gid), np.asarray(exact_ids))
    touched = float(np.asarray(plan.partitions_touched()).mean())
    frac = touched * index.store.capacity / data.shape[0]
    print(f"  recall@50 = {r:.3f}   partitions touched = {touched:.1f} "
          f"(~{frac:.1%} of the data)")
    assert r > 0.3, "recall sanity floor"
    print("OK")


if __name__ == "__main__":
    main()
