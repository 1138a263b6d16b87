"""Batched kNN serving example: the retrieval-plane engine loop.

    PYTHONPATH=src python examples/serve_knn.py [--batch-size 8]

Builds a small CLIMBER index, submits requests to the ClimberEngine queue,
drains it, and prints per-query metrics plus aggregate queries/sec.
"""
import argparse

import jax
import numpy as np

from repro.core import build_index
from repro.data import make_dataset, make_queries
from repro.serve import ClimberEngine, api
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.config import ClimberConfig


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--variant", default="adaptive")
    args = ap.parse_args()

    cfg = ClimberConfig(series_len=128, paa_segments=16, num_pivots=64,
                        prefix_len=8, capacity=256, sample_frac=0.2,
                        max_centroids=32, k=10, candidate_groups=4,
                        adaptive_factor=4)
    data = make_dataset("randomwalk", jax.random.PRNGKey(0), 8000, 128)
    index = build_index(jax.random.PRNGKey(1), data, cfg)
    queries = np.asarray(make_queries(jax.random.PRNGKey(2), data,
                                      args.requests))

    engine = ClimberEngine(index, config=api.ServingConfig(
        batch_size=args.batch_size, variant=args.variant, k=10))
    tickets = [engine.submit_request(
        api.QueryRequest(series=queries[i], request_id=i))
        for i in range(args.requests)]
    engine.run_until_drained()

    for t in tickets[:4]:
        r = t.result
        print(f"req {r.request_id}: top-3 gids={r.gid[:3].tolist()} "
              f"parts={r.partitions_touched} cands={r.candidates_scanned} "
              f"latency={r.latency_ms:.1f}ms fill={r.batch_fill:.2f}")
    s = engine.stats
    assert all(t.ok for t in tickets)
    print(f"OK — {s.queries} queries in {s.ticks} ticks: "
          f"{s.queries_per_sec:.1f} q/s, "
          f"mean parts={s.mean_partitions_touched:.2f}, "
          f"mean cands={s.mean_candidates_scanned:.0f}")


if __name__ == "__main__":
    main()
