"""Network serving example: typed client/server over a fleet.

    PYTHONPATH=src python examples/serve_net.py [--shards 2] [--self-test]
                                                [--routing signature]
                                                [--metrics]

Starts the asyncio :class:`~repro.serve.net.ClimberServer` on a loopback
socket in front of one :class:`~repro.fleet.FleetEngine`, then talks to it
with :class:`~repro.serve.net.ClimberClient`: handshake (``ServerInfo``),
single round trips, a pipelined batch that keeps the double-buffered
admission full, and typed refusals (wrong series shape → ``BAD_REQUEST``).
The example asserts the answers that crossed the socket match calling
``IndexFleet.query`` directly — identical gids, distances to float32
rounding (a served tick and a direct call may batch differently).

``--self-test`` runs the same flow on both routing modes plus an overlap
check (batch N+1 admitted while tick N executes) and exits non-zero on
any mismatch — the localhost smoke the `net` CI job runs.

``--metrics`` dumps the Prometheus page at exit: the net plane's
per-connection ``net.frames_in``/``net.frames_out`` counters and the
client's ``net.rtt_ms`` histogram sit next to the engine's
``serve.latency_ms``.
"""
import argparse
import sys
import time

import jax
import numpy as np

from repro.data import make_dataset, make_queries
from repro.eval.metrics import answers_agree
from repro.fleet import FleetConfig, FleetEngine, IndexFleet
from repro.serve import api
from repro.serve.net import ClimberClient, ServerError, serve_in_thread
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.config import ClimberConfig


def build_fleet(shards: int):
    cfg = ClimberConfig(series_len=128, paa_segments=16, num_pivots=64,
                        prefix_len=8, capacity=256, sample_frac=0.2,
                        max_centroids=32, k=10, candidate_groups=4,
                        adaptive_factor=4)
    per = 1_500
    data = np.asarray(make_dataset("randomwalk", jax.random.PRNGKey(0),
                                   per * shards, 128))
    queries = np.asarray(make_queries(jax.random.PRNGKey(2), data, 12))
    fleet = IndexFleet(FleetConfig(shard_cfg=cfg, fanout=2,
                                   delta_capacity=2_048, auto_compact=False))
    for s in range(shards):
        fleet.add_shard(f"tenant{s}", data[s * per:(s + 1) * per])
    return fleet, queries


def run_mode(fleet, queries, routing: str, batch_size: int) -> bool:
    variant = "exhaustive" if routing == "exhaustive" else "adaptive"
    engine = FleetEngine(fleet, config=api.ServingConfig(
        batch_size=batch_size, k=10, routing=routing, variant=variant))
    server, stop = serve_in_thread(engine)
    try:
        with ClimberClient("127.0.0.1", server.port) as client:
            info = client.info
            print(f"[{routing}] connected to 127.0.0.1:{server.port} — "
                  f"engine={info.engine} shards={info.shards} "
                  f"series_len={info.series_len} k_max={info.k_max} "
                  f"wire v{info.wire_version}")

            res = client.query(queries[0], k=10)
            print(f"[{routing}] one round trip: top-3 gids="
                  f"{res.gid[:3].tolist()} parts={res.partitions_touched} "
                  f"server latency {res.latency_ms:.1f}ms")

            try:
                client.query(np.zeros(13, np.float32))
            except ServerError as exc:
                print(f"[{routing}] typed refusal: {exc.code} "
                      f"({exc.reply.message})")

            t0 = time.perf_counter()
            got = client.query_batch(list(queries), k=10)
            wall = (time.perf_counter() - t0) * 1e3
            print(f"[{routing}] pipelined {len(got)} queries in "
                  f"{wall:.0f}ms wall; overlapped admissions so far: "
                  f"{server.overlap_admissions}")
    finally:
        stop()

    dist, gid, _ = fleet.query(queries, 10, routing=routing,
                               variant=variant)
    same, why = answers_agree(np.stack([r.dist for r in got]),
                              np.stack([r.gid for r in got]),
                              dist.astype(np.float32), gid, scale=2 * 128)
    print(f"[{routing}] socket answers match direct fleet.query: {why}")
    return same and server.overlap_admissions > 0


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--routing", default="signature",
                    choices=["signature", "exhaustive"])
    ap.add_argument("--self-test", action="store_true",
                    help="run both routing modes, assert bit-identity and "
                         "admission overlap, exit non-zero on failure")
    ap.add_argument("--metrics", action="store_true",
                    help="dump the Prometheus page (net.* + serve.*) at exit")
    args = ap.parse_args()

    fleet, queries = build_fleet(args.shards)
    print(f"fleet: {len(fleet.shards)} shards, {fleet.total_records} records")

    modes = ["signature", "exhaustive"] if args.self_test else [args.routing]
    ok = all([run_mode(fleet, queries, m, args.batch_size) for m in modes])

    if args.metrics:
        from repro.obs import REGISTRY, to_prometheus
        print("\n# --- metrics (Prometheus text exposition) ---")
        print(to_prometheus(REGISTRY), end="")

    if args.self_test:
        print("self-test:", "OK" if ok else "FAILED")
        sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
