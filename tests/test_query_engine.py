"""ClimberEngine + unified query-path tests.

Covers the serving-layer acceptance contract (engine ≡ per-query knn_query,
bit-identical, on every execution backend), the planner registry, budgeted
plan compaction through the public knn_query knob, and refine_sharded ≡
refine on multi-device host meshes including ragged partition counts.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (QueryPlan, build_index, candidates_scanned,
                        compact_plan, default_slot_budget, get_planner,
                        knn_query, plan, plan_knn, planner_names,
                        register_planner)
from repro.core.index import PartitionStore
from repro.core.refine import refine
from repro.data import make_dataset, make_queries
from repro.eval.metrics import assert_same_answers
from repro.serve import ClimberEngine, QueryRequest
from repro.utils.config import ClimberConfig

REPO = Path(__file__).resolve().parents[1]
SCALE = 2 * 64          # bound on |q|^2 + |x|^2 for z-normalised length 64


@pytest.fixture(scope="module")
def small_index():
    cfg = ClimberConfig(series_len=64, paa_segments=8, num_pivots=32,
                        prefix_len=5, capacity=128, sample_frac=0.3,
                        max_centroids=12, k=10, candidate_groups=4,
                        adaptive_factor=4)
    data = make_dataset("randomwalk", jax.random.PRNGKey(0), 3000, 64)
    index = build_index(jax.random.PRNGKey(1), data, cfg)
    queries = np.asarray(make_queries(jax.random.PRNGKey(2), data, 11))
    return index, queries


# ----------------------------------------------------------------------
# Engine ≡ per-query knn_query (acceptance criterion), dense + kernel
# ----------------------------------------------------------------------
class TestEngineParity:
    @pytest.mark.parametrize("variant", ["knn", "adaptive", "od_smallest"])
    def test_dense_bit_identical(self, small_index, variant):
        index, queries = small_index
        engine = ClimberEngine(index, batch_size=4, variant=variant, k=10)
        dist, gid, metrics = engine.run(queries)
        assert len(metrics) == len(queries)
        for i in range(len(queries)):
            d1, g1, _ = knn_query(index, queries[i:i + 1], 10,
                                  variant=variant)
            # a batch of 4 and a batch of 1 sum the dot in different orders
            assert_same_answers(np.asarray(d1)[0], np.asarray(g1)[0],
                                dist[i], gid[i], scale=SCALE)

    @pytest.mark.parametrize("variant", ["knn", "adaptive", "od_smallest"])
    def test_kernel_bit_identical(self, small_index, variant):
        index, queries = small_index
        engine = ClimberEngine(index, batch_size=4, variant=variant, k=10,
                               use_kernel=True)
        dist, gid, _ = engine.run(queries[:6])
        for i in range(6):
            d1, g1, _ = knn_query(index, queries[i:i + 1], 10,
                                  variant=variant, use_kernel=True)
            np.testing.assert_array_equal(np.asarray(g1)[0], gid[i])
            np.testing.assert_array_equal(np.asarray(d1)[0], dist[i])

    def test_batch_size_invariance(self, small_index):
        """The batch a query rides in must not change its answer."""
        index, queries = small_index
        out = {}
        for bs in (1, 3, 8):
            engine = ClimberEngine(index, batch_size=bs, k=10)
            _, out[bs], _ = engine.run(queries)
        np.testing.assert_array_equal(out[1], out[3])
        np.testing.assert_array_equal(out[1], out[8])

    def test_queue_mode_matches_run(self, small_index):
        index, queries = small_index
        engine = ClimberEngine(index, batch_size=4, k=10)
        _, gid, _ = engine.run(queries)
        reqs = [QueryRequest(rid=i, series=queries[i], k=5)
                for i in range(len(queries))]
        for r in reqs:
            engine.submit(r)
        engine.run_until_drained()
        for r in reqs:
            assert r.done and r.metrics is not None
            assert r.metrics.partitions_touched >= 1
            assert r.metrics.candidates_scanned >= r.metrics.partitions_touched
            np.testing.assert_array_equal(r.gid, gid[r.rid][:5])

    def test_rejects_malformed_requests(self, small_index):
        """Admission validates requests so one bad series can't poison a
        batch, and an over-k ask fails loudly instead of silently clamping."""
        index, queries = small_index
        engine = ClimberEngine(index, batch_size=4, k=10)
        with pytest.raises(ValueError, match="series shape"):
            engine.submit(QueryRequest(rid=0, series=queries[0][:7]))
        with pytest.raises(ValueError, match="exceeds the engine"):
            engine.submit(QueryRequest(rid=1, series=queries[0], k=99))
        with pytest.raises(ValueError, match="exceeds the engine"):
            engine.run(queries[:2], k=99)
        with pytest.raises(ValueError, match="batch_size"):
            ClimberEngine(index, batch_size=0)
        assert not engine.queue

    def test_empty_run(self, small_index):
        index, _ = small_index
        engine = ClimberEngine(index, batch_size=4, k=10)
        dist, gid, metrics = engine.run(np.zeros((0, 64), np.float32))
        assert dist.shape == (0, 10) and gid.shape == (0, 10)
        assert metrics == []

    def test_stats_aggregate(self, small_index):
        index, queries = small_index
        engine = ClimberEngine(index, batch_size=4, k=10)
        engine.run(queries)
        s = engine.stats
        assert s.queries == len(queries)
        assert s.queries_per_sec > 0
        assert s.mean_partitions_touched >= 1.0


# ----------------------------------------------------------------------
# Query plan cache (LRU on the P4→ signature prefix)
# ----------------------------------------------------------------------
class TestPlanCache:
    def test_repeat_queries_hit_and_stay_bit_identical(self, small_index):
        index, queries = small_index
        engine = ClimberEngine(index, batch_size=4, k=10)
        d1, g1, _ = engine.run(queries)
        assert engine.stats.plan_cache_misses == len(queries)
        assert engine.stats.plan_cache_hits == 0
        d2, g2, _ = engine.run(queries)          # identical workload: all hit
        assert engine.stats.plan_cache_hits == len(queries)
        assert engine.stats.plan_cache_misses == len(queries)
        np.testing.assert_array_equal(g1, g2)
        np.testing.assert_array_equal(d1, d2)
        assert 0.0 < engine.stats.plan_cache_hit_rate < 1.0

    def test_cached_plan_matches_knn_query(self, small_index):
        """Answers served off the cache equal the uncached oracle."""
        index, queries = small_index
        engine = ClimberEngine(index, batch_size=2, k=10)
        engine.run(queries[:4])
        dist, gid, _ = engine.run(queries[:4])   # fully cached pass
        for i in range(4):
            d1, g1, _ = knn_query(index, queries[i:i + 1], 10)
            assert_same_answers(np.asarray(d1)[0], np.asarray(g1)[0],
                                dist[i], gid[i], scale=SCALE)

    def test_disabled_cache_counts_nothing(self, small_index):
        index, queries = small_index
        engine = ClimberEngine(index, batch_size=4, k=10, plan_cache_size=0)
        engine.run(queries)
        engine.run(queries)
        assert engine.stats.plan_cache_hits == 0
        assert engine.stats.plan_cache_misses == 0
        assert engine.stats.plan_cache_hit_rate == 0.0

    def test_lru_evicts_oldest_signature(self, small_index):
        index, queries = small_index
        engine = ClimberEngine(index, batch_size=1, k=10, plan_cache_size=2)
        engine.run(queries[0:1])
        engine.run(queries[1:2])
        engine.run(queries[2:3])                 # evicts queries[0]
        assert len(engine._plan_cache) == 2
        engine.run(queries[0:1])                 # must miss again
        assert engine.stats.plan_cache_hits == 0
        assert engine.stats.plan_cache_misses == 4
        engine.run(queries[2:3])                 # still resident
        assert engine.stats.plan_cache_hits == 1

    def test_cache_only_keys_live_rows(self, small_index):
        """Zero-padded tail rows of a partial batch must not enter the
        cache or the counters."""
        index, queries = small_index
        engine = ClimberEngine(index, batch_size=8, k=10)
        engine.run(queries[:3])
        assert engine.stats.plan_cache_misses == 3
        assert len(engine._plan_cache) == 3


# ----------------------------------------------------------------------
# Planner registry
# ----------------------------------------------------------------------
class TestPlannerRegistry:
    def test_builtins_registered(self):
        assert {"knn", "adaptive", "od_smallest"} <= set(planner_names())

    def test_unknown_variant_raises(self, small_index):
        index, queries = small_index
        with pytest.raises(KeyError, match="registered"):
            knn_query(index, queries[:1], 5, variant="nope")
        with pytest.raises(KeyError):
            ClimberEngine(index, variant="nope")

    def test_custom_planner_end_to_end(self, small_index):
        index, queries = small_index
        register_planner("knn_alias", plan_knn)
        try:
            d1, g1, qp = knn_query(index, queries[:3], 5, variant="knn_alias")
            d2, g2, _ = knn_query(index, queries[:3], 5, variant="knn")
            np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
            assert get_planner("knn_alias") is plan_knn
            # no lossless bound is knowable for a custom planner: its plans
            # must not be compacted unless a budget is configured
            assert default_slot_budget(index, "knn_alias") is None
            p4r, _ = index.featurize(jnp.asarray(queries[:3]))
            raw = plan_knn(index, p4r)
            assert qp.sel_part.shape == raw.sel_part.shape
        finally:
            from repro.core import query as query_mod
            query_mod._PLANNERS.pop("knn_alias", None)


# ----------------------------------------------------------------------
# Budgeted plan compaction (satellite: compact_plan wired into knn_query)
# ----------------------------------------------------------------------
class TestPlanCompaction:
    def test_default_budget_halves_adaptive_plan(self, small_index):
        index, queries = small_index
        p4r, _ = index.featurize(jnp.asarray(queries))
        raw = get_planner("adaptive")(index, p4r)
        budgeted = plan(index, p4r, variant="adaptive")
        assert budgeted.sel_part.shape[-1] == \
            default_slot_budget(index, "adaptive")
        assert budgeted.sel_part.shape[-1] < raw.sel_part.shape[-1]

    def test_compaction_lossless_paper_default_cap(self, small_index):
        """Regression: the default budget must not drop live entries for the
        paper-default adaptive cap (T=4, Adaptive-4X)."""
        index, queries = small_index
        assert index.cfg.candidate_groups == 4
        assert index.cfg.adaptive_factor == 4
        p4r, _ = index.featurize(jnp.asarray(queries))
        raw = get_planner("adaptive")(index, p4r)
        budgeted = plan(index, p4r, variant="adaptive")
        live_raw = np.asarray((raw.sel_part >= 0).sum(-1))
        live_b = np.asarray((budgeted.sel_part >= 0).sum(-1))
        np.testing.assert_array_equal(live_raw, live_b)
        # and the answers through the public knob are identical
        d1, g1, _ = knn_query(index, queries, 10, max_slots=10**6)
        d2, g2, _ = knn_query(index, queries, 10)
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))

    def test_config_knob(self, small_index):
        """cfg.query_max_slots drives compaction through knn_query."""
        index, queries = small_index
        cfg2 = index.cfg.replace(query_max_slots=4)
        import dataclasses
        index2 = dataclasses.replace(index, cfg=cfg2)
        _, _, qp = knn_query(index2, queries, 10)
        assert qp.sel_part.shape[-1] == 4

    def test_candidates_scanned_counts_distinct(self, small_index):
        index, _ = small_index
        store = index.store
        sel = jnp.asarray([[0, 0, 1, -1]], jnp.int32)
        qp = QueryPlan(sel_part=sel, sel_lo=jnp.zeros_like(sel),
                       sel_hi=jnp.zeros_like(sel),
                       node=jnp.zeros(1, jnp.int32),
                       pathlen=jnp.zeros(1, jnp.int32))
        got = int(candidates_scanned(qp, store)[0])
        want = int(store.count[0]) + int(store.count[1])
        assert got == want


# ----------------------------------------------------------------------
# refine_sharded ≡ refine on host CPU meshes (2 and 4 devices, ragged P)
# ----------------------------------------------------------------------
def _run_subprocess(body: str, n_dev: int, timeout: int = 420) -> dict:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={n_dev}"
        import json
        import jax
        import jax.numpy as jnp
        import numpy as np
        assert jax.device_count() == {n_dev}, jax.device_count()
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return json.loads(res.stdout.strip().splitlines()[-1])


_SHARDED_REFINE_BODY = """
    from repro.core.index import PartitionStore
    from repro.core.refine import refine, refine_sharded
    from repro.distributed import shard_store
    from repro.launch.mesh import make_mesh

    # synthetic ragged store: P=%d partitions (not divisible by %d devices)
    rng = np.random.default_rng(0)
    P, cap, n, Q, MP, k = %d, 12, 32, 5, 9, 7
    data = rng.normal(size=(P, cap, n)).astype(np.float32)
    gid = np.arange(P * cap, dtype=np.int32).reshape(P, cap)
    gid[rng.random((P, cap)) < 0.25] = -1
    dfs = rng.integers(0, 50, size=(P, cap)).astype(np.int32)
    store = PartitionStore(
        data=jnp.asarray(data), norms=jnp.asarray((data ** 2).sum(-1)),
        rec_dfs=jnp.asarray(dfs), rec_gid=jnp.asarray(gid),
        count=jnp.asarray((gid >= 0).sum(1).astype(np.int32)))
    q = jnp.asarray(rng.normal(size=(Q, n)).astype(np.float32))
    sp = jnp.asarray(rng.integers(-1, P, size=(Q, MP)).astype(np.int32))
    lo = rng.integers(0, 40, size=(Q, MP)).astype(np.int32)
    hi = jnp.asarray(lo + rng.integers(0, 30, size=(Q, MP)).astype(np.int32))
    lo = jnp.asarray(lo)

    d1, g1 = refine(store, q, sp, lo, hi, k)
    mesh = make_mesh((%d,), ("data",))
    store_s = shard_store(store, mesh)
    assert store_s.num_partitions %% %d == 0
    d2, g2 = refine_sharded(store_s, q, sp, lo, hi, k, mesh=mesh)
    d3, g3 = refine_sharded(store, q, sp, lo, hi, k, mesh=mesh)  # lazy pad
    print(json.dumps({
        "gid_match": bool(np.array_equal(np.asarray(g1), np.asarray(g2))),
        "dist_match": bool(np.array_equal(np.asarray(d1), np.asarray(d2))),
        "lazy_pad_match": bool(np.array_equal(np.asarray(g2),
                                              np.asarray(g3))),
    }))
"""


@pytest.mark.parametrize("n_dev,P", [(2, 7), (4, 7), (4, 8)])
def test_refine_sharded_matches_refine(n_dev, P):
    out = _run_subprocess(
        _SHARDED_REFINE_BODY % (P, n_dev, P, n_dev, n_dev), n_dev)
    assert out["gid_match"], out
    assert out["dist_match"], out
    assert out["lazy_pad_match"], out


def test_engine_sharded_bit_identical():
    """Acceptance: 2-device sharded engine ≡ dense per-query knn_query."""
    out = _run_subprocess("""
        from repro.utils.config import ClimberConfig
        from repro.core import build_index, knn_query
        from repro.data import make_dataset, make_queries
        from repro.eval.metrics import answers_agree
        from repro.launch.mesh import make_mesh
        from repro.serve import ClimberEngine

        cfg = ClimberConfig(series_len=64, paa_segments=8, num_pivots=32,
                            prefix_len=5, capacity=128, sample_frac=0.3,
                            max_centroids=12, k=10, candidate_groups=4,
                            adaptive_factor=4)
        data = make_dataset("randomwalk", jax.random.PRNGKey(0), 3000, 64)
        index = build_index(jax.random.PRNGKey(1), data, cfg)
        queries = np.asarray(make_queries(jax.random.PRNGKey(2), data, 9))

        mesh = make_mesh((2,), ("data",))
        ok_gid = ok_dist = True
        gid_adaptive = None
        for variant in ("knn", "adaptive", "od_smallest"):
            engine = ClimberEngine(index, batch_size=4, variant=variant,
                                   k=10, mesh=mesh)
            dist, gid, _ = engine.run(queries)
            if variant == "adaptive":
                gid_adaptive = gid
            for i in range(len(queries)):
                d1, g1, _ = knn_query(index, queries[i:i+1], 10,
                                      variant=variant)
                ok_gid &= bool(np.array_equal(np.asarray(g1)[0], gid[i]))
                ok_dist &= answers_agree(np.asarray(d1)[0], gid[i],
                                         dist[i], gid[i], scale=2 * 64)[0]
        # use_kernel composes with the sharded path
        ek = ClimberEngine(index, batch_size=4, variant="adaptive", k=10,
                           mesh=mesh, use_kernel=True)
        dk, gk, _ = ek.run(queries[:4])
        ok_kernel = bool(np.array_equal(gk, gid_adaptive[:4]))
        print(json.dumps({"gid": ok_gid, "dist": ok_dist,
                          "kernel": ok_kernel}))
    """, n_dev=2)
    assert out["gid"] and out["dist"] and out["kernel"], out
