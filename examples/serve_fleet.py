"""Index-fleet serving example: shards + streaming ingest + lifecycle.

    PYTHONPATH=src python examples/serve_fleet.py [--shards 3] [--mesh]
                                                  [--storage DIR] [--metrics]

Builds a fleet of per-tenant CLIMBER shards, serves a request queue through
one FleetEngine (signature routing fans each query out to a shard subset),
streams fresh records into the delta shard, seals it with ``compact()``
(the INX rebuild runs on the compactor worker thread), and shows that the
answers on the same contents are unchanged.

``--mesh`` attaches a data-axis mesh over every local device, so sealed
shards execute mesh-resident (one shard_map fan-out instead of the
per-shard host loop) — and the example asserts the two placements return
the same answers (identical gids, distances to float32 rounding).

``--storage DIR`` (default: a temp dir) attaches the lifecycle plane's
durable storage: inserts append to the write-ahead log before the delta
scatter, ``save()`` snapshots the sealed shards, and the example simulates
a crash — ``IndexFleet.open`` replays the WAL tail and the restored
answers are asserted unchanged.  Step-by-step commentary:
docs/SERVING.md.
"""
import argparse
import tempfile

import jax
import numpy as np

from repro.data import make_dataset, make_queries
from repro.eval.metrics import assert_same_answers
from repro.fleet import FleetConfig, FleetEngine, IndexFleet
from repro.launch.mesh import make_mesh
from repro.serve import api
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.config import ClimberConfig

SCALE = 2 * 128     # bound on |q|^2 + |x|^2 for z-normalised length 128


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--mesh", action="store_true",
                    help="lay sealed shards out over the local devices and "
                         "serve via the single-shard_map mesh placement")
    ap.add_argument("--storage", default=None,
                    help="durable storage dir (WAL + shard snapshots); "
                         "default: a fresh temp dir")
    ap.add_argument("--metrics", action="store_true",
                    help="dump the Prometheus text exposition page of the "
                         "process metrics registry (repro.obs) at exit")
    args = ap.parse_args()
    storage = args.storage or tempfile.mkdtemp(prefix="fleet-storage-")

    cfg = ClimberConfig(series_len=128, paa_segments=16, num_pivots=64,
                        prefix_len=8, capacity=256, sample_frac=0.2,
                        max_centroids=32, k=10, candidate_groups=4,
                        adaptive_factor=4)
    per = 2_000
    data = np.asarray(make_dataset("randomwalk", jax.random.PRNGKey(0),
                                   per * args.shards, 128))
    queries = np.asarray(make_queries(jax.random.PRNGKey(2), data,
                                      args.requests))

    fleet = IndexFleet(FleetConfig(shard_cfg=cfg, fanout=2,
                                   delta_capacity=2_048, auto_compact=False),
                       storage_dir=storage)
    for s in range(args.shards):
        fleet.add_shard(f"tenant{s}", data[s * per:(s + 1) * per])
    if args.mesh:
        mesh = make_mesh((jax.device_count(),), ("data",))
        fleet.attach_mesh(mesh)     # queries now default to placement="mesh"
    print(f"fleet: {len(fleet.shards)} shards, {fleet.total_records} "
          f"records, placement="
          f"{'mesh (%d devices)' % jax.device_count() if args.mesh else 'host'}")

    # serve a queue through one engine over the whole fleet
    engine = FleetEngine(fleet, config=api.ServingConfig(
        batch_size=args.batch_size, k=10, routing="signature"))
    tickets = [engine.submit_request(
        api.QueryRequest(series=queries[i], request_id=i))
        for i in range(args.requests)]
    engine.run_until_drained()
    r0 = tickets[0].result
    print(f"req 0: top-3 gids={r0.gid[:3].tolist()} "
          f"parts={r0.partitions_touched} latency={r0.latency_ms:.1f}ms")

    # streaming ingest: fresh records are visible immediately
    fresh = np.asarray(make_dataset("randomwalk", jax.random.PRNGKey(9),
                                    512, 128))
    gids = fleet.insert(fresh)
    d, g, _ = fleet.query(fresh[:1], 5, routing="exhaustive")
    print(f"inserted {len(gids)} records (delta occupancy "
          f"{fleet.delta.occupancy}); self-query hit gid {g[0, 0]} "
          f"(expected {gids[0]}) at d={d[0, 0]:.4f}")

    # mesh fan-out is bit-identical to the host-loop oracle
    if args.mesh:
        dh, gh, _ = fleet.query(queries, 10, placement="host")
        dm, gm, _ = fleet.query(queries, 10, placement="mesh")
        assert_same_answers(dh, gh, dm, gm, scale=SCALE)
        print("mesh placement: one shard_map fan-out, same answers as the "
              "host loop (identical gids, distances to float32 rounding)")

    # restart durability: "crash" the process state and replay the WAL —
    # the delta was never snapshotted, yet answers come back bit-identical
    fleet.save()
    d1, g1, _ = fleet.query(queries, 10, routing="exhaustive",
                            variant="exhaustive")
    restored = IndexFleet.open(storage)
    dr, gr, _ = restored.query(queries, 10, routing="exhaustive",
                               variant="exhaustive")
    assert_same_answers(d1, g1, dr, gr, scale=SCALE)
    print(f"restart: WAL tail replayed "
          f"({restored.delta.occupancy} delta records), answers "
          f"unchanged")

    # compaction seals the delta on the worker thread; answers on the same
    # contents don't move, and the WAL segment is truncated once the shard
    # snapshot is durable
    fleet.compact()
    d2, g2, _ = fleet.query(queries, 10, routing="exhaustive",
                            variant="exhaustive")
    assert_same_answers(d1, g1, d2, g2, scale=SCALE)
    print(f"compact(): sealed into {fleet.shards[-1].key}; "
          f"answers unchanged")

    precision = fleet.audit_routing(queries, 10)
    s = fleet.stats
    life = s.lifecycle_snapshot()
    print(f"OK — {s.queries} fleet queries, routing precision "
          f"{precision:.3f}, fan-out savings {s.fanout_savings:.0%}, "
          f"per-shard load {s.per_shard_queries}")
    print(f"lifecycle — compaction {life['compaction_ms']:.0f}ms total, "
          f"pending WAL {life['wal_bytes']} bytes, "
          f"{life['merges']} merges, {life['retired_shards']} retired "
          f"(storage: {storage})")

    if args.metrics:
        # run one short network-plane segment so the page includes the
        # per-connection net.* counters and the client rtt histogram next
        # to the span / engine metrics; the online recall sentinel
        # shadow-samples the served queries and audits them off-path so
        # the fleet.online_recall gauge is live on the page
        from repro.obs import RecallSentinel
        from repro.serve.net import ClimberClient, serve_in_thread
        sentinel = RecallSentinel(fleet, sample_rate=1.0)
        server, stop = serve_in_thread(engine)
        with ClimberClient("127.0.0.1", server.port) as client:
            client.query_batch(list(queries[:4]), k=10)
            sentinel.drain()
            # fetch the page over the admin plane — the same socket the
            # queries rode — exactly what a scrape sidecar would do
            page = client.metrics()
            health = client.health()
        stop()
        print(f"admin health: ready={health['ready']} "
              f"shards={health['shards']} pending={health['pending']} "
              f"spans_dropped={health['spans_dropped']}")
        print(f"sentinel: online recall "
              f"{sentinel.online_recall:.3f} over "
              f"{sentinel.snapshot()['audits']} audits")
        # everything above recorded into the process registry: spans into
        # span.* histograms, fleet/engine counters via collectors, the net
        # segment into net.*, the sentinel's gauge — this is the page a
        # Prometheus scrape of the process would return
        print("\n# --- metrics (Prometheus text exposition) ---")
        print(page, end="")


if __name__ == "__main__":
    main()
