"""Benchmark registry — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Run everything:
    PYTHONPATH=src python -m benchmarks.run
or a subset:
    PYTHONPATH=src python -m benchmarks.run --only fig7,fig12
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.utils.compile_cache import enable_compile_cache

SUITES = {
    "fig7": ("benchmarks.bench_recall", "Fig. 7 recall/time vs baselines"),
    "fig8": ("benchmarks.bench_index_build", "Fig. 8 index construction"),
    "fig9": ("benchmarks.bench_k_sweep", "Fig. 9 K sweep"),
    "fig10": ("benchmarks.bench_pivots", "Fig. 10 pivot-count sweep"),
    "fig11": ("benchmarks.bench_variations", "Fig. 11 variants ablation"),
    "fig12": ("benchmarks.bench_prefix_len", "Fig. 12 prefix-length sweep"),
    "table1": ("benchmarks.bench_memory_systems", "Table I memory-systems"),
    "kernels": ("benchmarks.bench_kernels", "Pallas kernel parity/µbench"),
    "engine": ("benchmarks.bench_query_engine",
               "ClimberEngine queries/sec sweep"),
    "fleet": ("benchmarks.bench_fleet",
              "IndexFleet shards × routing × delta-fill sweep"),
    "serve_net": ("benchmarks.bench_serve_net",
                  "network serving plane qps + tails per concurrency"),
    "recall_frontier": ("benchmarks.bench_recall_frontier",
                        "Hydra-style recall-vs-data-touched frontier"),
    "roofline": ("benchmarks.roofline", "§Roofline table from dry-run"),
}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all",
                    help="comma-separated suite names (default: all)")
    args = ap.parse_args()
    names = list(SUITES) if args.only == "all" else args.only.split(",")

    print("name,us_per_call,derived")
    failures = []
    for name in names:
        mod_name, desc = SUITES[name]
        print(f"# === {name}: {desc} ===")
        t0 = time.perf_counter()
        try:
            mod = __import__(mod_name, fromlist=["run"])
            mod.run()
        except Exception:                       # noqa: BLE001
            traceback.print_exc()
            failures.append(name)
        print(f"# {name} done in {time.perf_counter()-t0:.1f}s")
    if failures:
        print(f"# FAILED suites: {failures}")
        sys.exit(1)


if __name__ == "__main__":
    main()
