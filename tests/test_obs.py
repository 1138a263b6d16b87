"""Observability plane — registry, histograms, tracer, exporters.

The acceptance contracts from the issue:
  * **histogram quantiles** track ``numpy.percentile`` within the bucket
    quantization bound (growth 1.05 → ≤2.5% relative at the geometric
    midpoint), with exact extremes;
  * **span nesting** stays correct per-thread: a serving thread and the
    background compaction worker interleave spans in the ring without
    corrupting either tree, and one fleet query yields a complete
    admission → plan → refine → merge tree;
  * **exporters** emit the golden Prometheus / JSONL / snapshot formats;
  * **back-compat**: ``FleetStats.snapshot()`` / ``EngineStats.snapshot()``
    keep the exact key sets benchmark artifacts already depend on.
"""
import json
import threading

import jax
import numpy as np
import pytest

from repro.data import make_dataset, make_queries
from repro.fleet import FleetConfig, FleetEngine, IndexFleet
from repro.fleet.fleet import FleetStats
from repro.obs import (REGISTRY, TRACER, MetricsRegistry, SpanTracer,
                       snapshot, spans_jsonl, to_prometheus)
from repro.obs.export import prom_name
from repro.obs.registry import Counter, Gauge, Histogram
from repro.serve import EngineStats, QueryRequest
from repro.utils.config import ClimberConfig

K = 10


def small_cfg() -> ClimberConfig:
    return ClimberConfig(series_len=64, paa_segments=8, num_pivots=32,
                         prefix_len=5, capacity=128, sample_frac=0.3,
                         max_centroids=12, k=K, candidate_groups=4,
                         adaptive_factor=4)


def mkdata(seed: int, n: int) -> np.ndarray:
    return np.asarray(make_dataset("randomwalk", jax.random.PRNGKey(seed),
                                   n, 64))


def mkfleet(**kw) -> IndexFleet:
    fc = dict(shard_cfg=small_cfg(), fanout=1, delta_capacity=4096,
              auto_compact=False)
    fc.update(kw)
    fleet = IndexFleet(FleetConfig(**fc))
    data = mkdata(0, 1600)
    fleet.add_shard("t0", data[:800])
    fleet.add_shard("t1", data[800:])
    return fleet


def span_names(tree: dict) -> set:
    out = {tree["name"]}
    for kid in tree["children"]:
        out |= span_names(kid)
    return out


# ---------------------------------------------------------------------------
# histogram: quantile accuracy, bucket edges, lifecycle
# ---------------------------------------------------------------------------

class TestHistogram:
    @pytest.mark.parametrize("dist", ["lognormal", "uniform", "exp"])
    def test_quantiles_track_numpy(self, dist):
        rng = np.random.default_rng(7)
        vals = {"lognormal": lambda: np.exp(rng.normal(2.0, 1.0, 5000)),
                "uniform": lambda: rng.uniform(0.5, 500.0, 5000),
                "exp": lambda: rng.exponential(30.0, 5000)}[dist]()
        h = Histogram()
        for v in vals:
            h.observe(v)
        # same rank convention as numpy's 'lower' method (rank q·(n−1),
        # no interpolation), so only the bucket quantization differs —
        # at most half a bucket width ≈ growth**0.5 − 1 ≈ 2.47% relative
        for q in (0.10, 0.50, 0.90, 0.95, 0.99):
            exact = float(np.percentile(vals, q * 100, method="lower"))
            assert abs(h.quantile(q) - exact) / exact < 0.026, \
                f"{dist} q={q}: hist {h.quantile(q)} vs numpy {exact}"

    def test_extremes_are_exact(self):
        h = Histogram()
        for v in (3.7, 1.23, 900.5, 42.0):
            h.observe(v)
        assert h.quantile(0.0) == h.min == 1.23
        assert h.quantile(1.0) == h.max == 900.5
        assert h.count == 4 and h.sum == pytest.approx(947.43)

    def test_underflow_overflow_clamp_to_observed(self):
        h = Histogram(lo=1.0, hi=100.0)
        h.observe(0.001)        # below lo → underflow bucket
        h.observe(5000.0)       # above hi → overflow bucket
        assert h.count == 2
        assert h.quantile(0.0) == 0.001 and h.quantile(1.0) == 5000.0

    def test_nan_rejected_empty_zero(self):
        h = Histogram()
        assert h.quantile(0.5) == 0.0 and h.count == 0
        h.observe(float("nan"))
        assert h.count == 0
        with pytest.raises(ValueError):
            h.quantile(1.5)

    def test_reset(self):
        h = Histogram()
        h.observe(10.0)
        h.reset()
        assert h.count == 0 and h.sum == 0.0 and h.quantile(0.5) == 0.0

    def test_percentiles_trio(self):
        h = Histogram()
        for v in range(1, 101):
            h.observe(float(v))
        p = h.percentiles()
        assert sorted(p) == ["p50", "p95", "p99"]
        assert p["p50"] <= p["p95"] <= p["p99"]


# ---------------------------------------------------------------------------
# registry: get-or-create, kind safety, collectors
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_get_or_create_same_object(self):
        reg = MetricsRegistry()
        c = reg.counter("x.q", loop="a")
        c.inc(3)
        assert reg.counter("x.q", loop="a") is c
        assert isinstance(reg.gauge("x.depth"), Gauge)
        assert isinstance(reg.histogram("x.lat"), Histogram)
        # different labels → different series
        assert reg.counter("x.q", loop="b") is not c
        assert reg.counter("x.q", loop="b").value == 0

    def test_kind_mismatch_raises(self):
        reg = MetricsRegistry()
        reg.counter("x.q")
        with pytest.raises(TypeError, match="already registered as Counter"):
            reg.gauge("x.q")
        with pytest.raises(TypeError, match="not Histogram"):
            reg.histogram("x.q")

    def test_counter_monotonic(self):
        c = Counter()
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_collector_scraped_at_read_time(self):
        reg = MetricsRegistry()
        state = {"depth": 1.0}
        reg.add_collector(lambda: {"pool.depth": state["depth"]}, pool="p0")
        assert list(reg.collected()) == [("pool.depth", {"pool": "p0"}, 1.0)]
        state["depth"] = 7.0        # pull-based: next scrape sees the update
        assert list(reg.collected())[0][2] == 7.0

    def test_dead_collector_pruned(self):
        import weakref

        class Owner:
            def vals(self):
                return {"owner.alive": 1.0}

        reg = MetricsRegistry()
        o = Owner()
        ref = weakref.ref(o)
        reg.add_collector(lambda: (lambda s: s.vals() if s else None)(ref()))
        assert len(list(reg.collected())) == 1
        del o
        assert list(reg.collected()) == []       # None → dropped
        assert list(reg.collected()) == []       # and unregistered
        assert len(reg._collectors) == 0

    def test_snapshot_slots(self):
        reg = MetricsRegistry()
        reg.counter("a.n").inc(2)
        reg.gauge("a.g", loop="e0").set(1.5)
        reg.histogram("a.h").observe(4.0)
        snap = reg.snapshot()
        assert snap["counters"] == {"a.n": 2}
        assert snap["gauges"] == {"a.g{loop=e0}": 1.5}
        assert snap["histograms"]["a.h"]["count"] == 1


# ---------------------------------------------------------------------------
# tracer: nesting, ring bound, cross-thread interleaving
# ---------------------------------------------------------------------------

class TestTracer:
    def test_nesting_and_tree(self):
        tr = SpanTracer()
        with tr.span("root", tick=1):
            with tr.span("child.a"):
                with tr.span("leaf"):
                    pass
            with tr.span("child.b"):
                pass
        roots = tr.roots()
        assert [r.name for r in roots] == ["root"]
        tree = tr.tree(roots[0].trace_id)
        assert tree["name"] == "root" and tree["attrs"] == {"tick": 1}
        assert [k["name"] for k in tree["children"]] == ["child.a", "child.b"]
        assert tree["children"][0]["children"][0]["name"] == "leaf"
        # durations nest: parent covers its children
        spans = {s.name: s for s in tr.spans()}
        assert spans["root"].duration_ms >= spans["child.a"].duration_ms

    def test_span_yields_live_measurement(self):
        tr = SpanTracer()
        with tr.span("work") as sp:
            pass
        assert sp.duration_ms >= 0.0
        assert sp.to_dict()["name"] == "work"

    def test_ring_is_bounded(self):
        tr = SpanTracer(capacity=8)
        for i in range(20):
            with tr.span("tick", i=i):
                pass
        spans = tr.spans()
        assert len(spans) == 8
        assert [s.attrs["i"] for s in spans] == list(range(12, 20))

    def test_registry_gets_span_histograms(self):
        reg = MetricsRegistry()
        tr = SpanTracer(registry=reg)
        with tr.span("stage"):
            pass
        h = reg.histogram("span.stage")
        assert h.count == 1

    def test_threads_do_not_corrupt_each_other(self):
        tr = SpanTracer(capacity=100_000)
        barrier = threading.Barrier(4)

        def worker(tag):
            barrier.wait()
            for i in range(200):
                with tr.span(f"outer.{tag}"):
                    with tr.span(f"inner.{tag}"):
                        pass

        threads = [threading.Thread(target=worker, args=(t,), name=f"w{t}")
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        spans = tr.spans()
        assert len(spans) == 4 * 200 * 2
        by_id = {s.span_id: s for s in spans}
        for s in spans:
            if s.name.startswith("inner."):
                parent = by_id[s.parent_id]
                tag = s.name.split(".")[1]
                # every inner span hangs off ITS thread's outer span
                assert parent.name == f"outer.{tag}"
                assert parent.thread == s.thread
                assert s.trace_id == parent.span_id
            else:
                assert s.parent_id is None and s.trace_id == s.span_id

    def test_last_trace_filters_by_root_name(self):
        tr = SpanTracer()
        with tr.span("a"):
            pass
        with tr.span("b"):
            pass
        assert tr.last_trace()["name"] == "b"
        assert tr.last_trace("a")["name"] == "a"
        assert tr.last_trace("nope") is None

    def test_jsonl_event_log(self, tmp_path):
        tr = SpanTracer()
        path = tmp_path / "spans.jsonl"
        tr.attach_jsonl(path)
        with tr.span("outer", rows=3):
            with tr.span("inner"):
                pass
        tr.detach_jsonl()
        lines = [json.loads(l) for l in path.read_text().splitlines()]
        assert [l["name"] for l in lines] == ["inner", "outer"]  # end order
        assert lines[1]["attrs"] == {"rows": 3}
        assert lines[0]["parent_id"] == lines[1]["span_id"]


# ---------------------------------------------------------------------------
# exporters: golden formats
# ---------------------------------------------------------------------------

class TestExporters:
    def test_prometheus_golden_page(self):
        reg = MetricsRegistry()
        reg.counter("serve.queries", loop="e0").inc(12)
        reg.gauge("serve.queue_depth", loop="e0").set(3)
        h = reg.histogram("serve.latency_ms", loop="e0")
        for v in (1.0, 2.0, 3.0, 4.0):
            h.observe(v)
        reg.add_collector(lambda: {"fleet.shards": 2.0}, fleet="f0")
        page = to_prometheus(reg)
        assert page == (
            '# TYPE repro_serve_latency_ms summary\n'
            'repro_serve_latency_ms{loop="e0",quantile="0.5"} '
            + repr(h.quantile(0.5)) + '\n'
            'repro_serve_latency_ms{loop="e0",quantile="0.95"} '
            + repr(h.quantile(0.95)) + '\n'
            'repro_serve_latency_ms{loop="e0",quantile="0.99"} '
            + repr(h.quantile(0.99)) + '\n'
            'repro_serve_latency_ms_count{loop="e0"} 4\n'
            'repro_serve_latency_ms_sum{loop="e0"} 10\n'
            '# TYPE repro_serve_queries_total counter\n'
            'repro_serve_queries_total{loop="e0"} 12\n'
            '# TYPE repro_serve_queue_depth gauge\n'
            'repro_serve_queue_depth{loop="e0"} 3\n'
            '# TYPE repro_fleet_shards gauge\n'
            'repro_fleet_shards{fleet="f0"} 2\n')

    def test_prom_name_sanitizes(self):
        assert prom_name("fleet.query_latency_ms") == \
            "repro_fleet_query_latency_ms"
        assert prom_name("span.compact.seal") == "repro_span_compact_seal"

    def test_spans_jsonl_roundtrip(self):
        tr = SpanTracer()
        with tr.span("q", n=2):
            pass
        doc = spans_jsonl(tr.spans())
        (line,) = doc.strip().splitlines()
        rec = json.loads(line)
        assert rec["name"] == "q" and rec["attrs"] == {"n": 2}
        assert list(rec) == sorted(rec)          # sorted keys: stable diffs

    def test_snapshot_stable_and_prom_named(self):
        reg = MetricsRegistry()
        reg.counter("a.n").inc(1)
        reg.histogram("a.h").observe(2.0)
        s1, s2 = snapshot(reg), snapshot(reg)
        assert s1 == s2
        assert json.dumps(s1, sort_keys=True) == json.dumps(s2,
                                                            sort_keys=True)
        assert "repro_a_n_total" in s1["counters"]
        assert sorted(s1["histograms"]["repro_a_h"]) == \
            ["count", "max", "min", "p50", "p95", "p99", "sum"]

    def test_snapshot_includes_traces(self):
        reg = MetricsRegistry()
        tr = SpanTracer(registry=reg)
        with tr.span("root"):
            with tr.span("leaf"):
                pass
        s = snapshot(reg, tracer=tr)
        assert s["traces"][0]["name"] == "root"
        assert s["traces"][0]["children"][0]["name"] == "leaf"


# ---------------------------------------------------------------------------
# snapshot() back-compat: the dict contracts benchmarks already consume
# ---------------------------------------------------------------------------

class TestSnapshotBackCompat:
    FLEET_KEYS = {
        "queries", "inserts", "compactions", "delta_rebuilds",
        "delta_occupancy", "routed_pairs", "exhaustive_pairs",
        "routing_audits", "routing_overlap", "compaction_ms", "wal_bytes",
        "merges", "retired_shards", "per_shard_queries",
        "per_shard_partitions", "routing_precision", "fanout_savings"}
    ENGINE_KEYS = {
        "queries", "ticks", "total_s", "partitions_touched",
        "candidates_scanned", "plan_cache_hits", "plan_cache_misses",
        "queries_per_sec", "mean_partitions_touched",
        "mean_candidates_scanned", "plan_cache_hit_rate"}

    def test_fleet_stats_keys_unchanged(self):
        assert set(FleetStats().snapshot()) == self.FLEET_KEYS
        assert set(FleetStats().lifecycle_snapshot()) == {
            "compaction_ms", "wal_bytes", "merges", "retired_shards"}

    def test_engine_stats_keys_unchanged(self):
        assert set(EngineStats().snapshot()) == self.ENGINE_KEYS


# ---------------------------------------------------------------------------
# integration: the query path's span tree + metrics, live fleet
# ---------------------------------------------------------------------------

class TestFleetIntegration:
    def test_fleet_query_span_tree_complete(self):
        fleet = mkfleet()
        queries = np.asarray(make_queries(jax.random.PRNGKey(2),
                                          mkdata(0, 1600), 4))
        engine = FleetEngine(fleet, batch_size=4, k=K, routing="exhaustive")
        TRACER.clear()
        engine.run(queries)
        tree = TRACER.last_trace("serve.tick")
        assert tree is not None
        names = span_names(tree)
        # the full admission → plan → refine → merge path, one tree
        assert {"serve.tick", "fleet.query", "fleet.plan", "fleet.refine",
                "fleet.merge"} <= names
        assert "fleet.route" not in names     # exhaustive: no router call
        fq = [c for c in tree["children"] if c["name"] == "fleet.query"]
        assert len(fq) == 1 and fq[0]["attrs"]["placement"] == "host"
        # per-query latency histogram observed one row per live request
        assert engine.latency_hist.count == 4
        assert fleet.query_hist.count == 1

    def test_engine_reset_metrics_clears_fleet_and_histograms(self):
        fleet = mkfleet()
        queries = np.asarray(make_queries(jax.random.PRNGKey(3),
                                          mkdata(0, 1600), 2))
        engine = FleetEngine(fleet, batch_size=2, k=K)
        engine.run(queries)
        assert engine.stats.queries == 2 and fleet.stats.queries >= 1
        engine.reset_metrics()
        assert engine.stats.queries == 0 and fleet.stats.queries == 0
        assert engine.latency_hist.count == 0
        assert fleet.query_hist.count == 0

    def test_ingest_spans(self):
        fleet = mkfleet()
        TRACER.clear()
        fleet.insert(mkdata(5, 32))
        tree = TRACER.last_trace("fleet.insert")
        assert tree is not None
        assert {"delta.scatter"} <= span_names(tree)

    def test_host_plan_cache_hits(self):
        fleet = mkfleet(plan_cache_size=64)
        queries = np.asarray(make_queries(jax.random.PRNGKey(4),
                                          mkdata(0, 1600), 4))
        d1, g1, i1 = fleet.query(queries, K, routing="exhaustive",
                                 placement="host")
        assert i1.plan_cache_hits == 0 and i1.plan_cache_misses > 0
        d2, g2, i2 = fleet.query(queries, K, routing="exhaustive",
                                 placement="host")
        assert i2.plan_cache_misses == 0
        assert i2.plan_cache_hits == i1.plan_cache_misses
        np.testing.assert_array_equal(g1, g2)
        np.testing.assert_array_equal(d1, d2)

    def test_spans_interleave_with_concurrent_compaction(self):
        """The compaction hammer: a query thread serves while the worker
        seals the delta — both span trees come out intact."""
        fleet = mkfleet()
        fleet.insert(mkdata(6, 256))
        queries = np.asarray(make_queries(jax.random.PRNGKey(7),
                                          mkdata(0, 1600), 2))
        fleet.query(queries, K, routing="exhaustive")       # warm the jits
        TRACER.clear()
        stop = threading.Event()
        errors = []

        def hammer():
            try:
                while not stop.is_set():
                    fleet.query(queries, K, routing="exhaustive")
            except Exception as e:                # pragma: no cover
                errors.append(e)

        t = threading.Thread(target=hammer, name="query-hammer")
        t.start()
        try:
            ticket = fleet.compact_async()
            assert ticket.wait(timeout=300)
        finally:
            stop.set()
            t.join()
        assert not errors
        # the compactor's tree: seal → build → swap, on its own thread
        seal = TRACER.last_trace("compact.seal")
        assert seal is not None
        assert {"compact.build", "compact.swap"} <= span_names(seal)
        assert fleet.compaction_hist.count == 1
        # every query tree recorded during the hammer is complete
        spans = TRACER.spans()
        trees = [TRACER.tree(s.trace_id) for s in spans
                 if s.parent_id is None and s.name == "fleet.query"]
        assert trees, "hammer produced no fleet.query roots"
        for tree in trees:
            assert {"fleet.plan", "fleet.refine", "fleet.merge"} <= \
                span_names(tree)
        # no span ever claims a parent on a different thread
        by_id = {s.span_id: s for s in spans}
        for s in spans:
            if s.parent_id is not None and s.parent_id in by_id:
                assert by_id[s.parent_id].thread == s.thread

    def test_prometheus_page_has_fleet_series(self):
        fleet = mkfleet()
        queries = np.asarray(make_queries(jax.random.PRNGKey(8),
                                          mkdata(0, 1600), 2))
        fleet.query(queries, K, routing="exhaustive")
        page = to_prometheus(REGISTRY)
        assert "repro_fleet_query_latency_ms" in page
        assert "repro_span_fleet_query" in page
        assert f'fleet="{fleet.obs_label}"' in page
