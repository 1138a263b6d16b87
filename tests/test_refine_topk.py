"""Streaming fused refine kernel — parity grid + edge shapes.

The fused kernel (``repro.kernels.refine_topk``) must match the dense
refine path: gids exactly (both sides share the lowest-flat-index
tie-break), distances to fp rounding of the blocked dot, and the
``PAD_DIST``/gid=-1 sentinel convention bit-for-bit wherever fewer than k
candidates exist.  Everything runs in Pallas interpret mode on CPU — the
exact TPU kernel body, executed by the interpreter.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.index import PartitionStore
from repro.core.refine import (PAD_DIST, _sort_by_partition, refine,
                               resolve_use_kernel)
from repro.eval.metrics import assert_same_answers
from repro.kernels import ref
from repro.kernels.refine_topk import (DEFAULT_BLOCK_C, pick_block_c,
                                       refine_topk)

DTOL = dict(rtol=1e-5, atol=1e-5)


def _mkstore(rng, p, cap, n, pad_frac=0.25, dfs_hi=50):
    data = rng.normal(size=(p, cap, n)).astype(np.float32)
    gid = np.arange(p * cap, dtype=np.int32).reshape(p, cap)
    gid[rng.random((p, cap)) < pad_frac] = -1
    dfs = rng.integers(0, dfs_hi, size=(p, cap)).astype(np.int32)
    return PartitionStore(
        data=jnp.asarray(data), norms=jnp.asarray((data ** 2).sum(-1)),
        rec_dfs=jnp.asarray(dfs), rec_gid=jnp.asarray(gid),
        count=jnp.asarray((gid >= 0).sum(1).astype(np.int32)))


def _mkplan(rng, q, mp, p, dfs_hi=50):
    sp = jnp.asarray(rng.integers(-1, p, size=(q, mp)).astype(np.int32))
    lo = rng.integers(0, dfs_hi - 10, size=(q, mp)).astype(np.int32)
    hi = jnp.asarray(lo + rng.integers(0, 30, size=(q, mp)).astype(np.int32))
    return sp, jnp.asarray(lo), hi


def _fused(store, queries, sp, lo, hi, k, **kw):
    """Kernel call with the refine() wrapper conventions applied."""
    ssp, slo, shi = _sort_by_partition(sp, lo, hi)
    d2, gid = refine_topk(store.data, store.norms, store.rec_dfs,
                          store.rec_gid, queries, ssp, slo, shi, k,
                          interpret=True, **kw)
    return np.sqrt(np.asarray(d2)), np.asarray(
        jnp.where(d2 >= 3.4e38, -1, gid))


class TestParityGrid:
    """Acceptance: fused ≡ dense across the Q×slots×cap×k sweep."""

    @pytest.mark.parametrize("q,mp,cap,k,block_c", [
        (1, 1, 8, 1, None),      # degenerate single-everything
        (3, 4, 12, 5, None),
        (5, 9, 12, 7, None),     # multiple entries per partition (dedupe live)
        (2, 6, 33, 20, None),    # cap not a lane multiple
        (4, 3, 16, 10, None),
        (3, 5, 40, 8, 16),       # explicit non-default block (cap % bc != 0)
        (3, 5, 12, 6, 256),      # explicit block far above cap (clamped)
        (2, 8, 40, 150, None),   # k past one lane tile: wider accumulator
    ])
    def test_matches_dense_refine(self, q, mp, cap, k, block_c):
        rng = np.random.default_rng(q * 101 + mp * 7 + cap)
        store = _mkstore(rng, 6, cap, 32)
        queries = jnp.asarray(rng.normal(size=(q, 32)).astype(np.float32))
        sp, lo, hi = _mkplan(rng, q, mp, 6)
        d_ref, g_ref = refine(store, queries, sp, lo, hi, k,
                              use_kernel=False)
        kw = {} if block_c is None else {"block_c": block_c}
        dist, gid = _fused(store, queries, sp, lo, hi, k, **kw)
        np.testing.assert_array_equal(np.asarray(g_ref), gid)
        np.testing.assert_allclose(np.asarray(d_ref), dist, **DTOL)

    @pytest.mark.parametrize("q,mp,cap,k", [(3, 5, 12, 6), (2, 8, 24, 15)])
    def test_matches_ref_oracle(self, q, mp, cap, k):
        """Kernel vs the package's own dense oracle (kernels/ref.py)."""
        rng = np.random.default_rng(q + mp + cap)
        store = _mkstore(rng, 5, cap, 16)
        queries = jnp.asarray(rng.normal(size=(q, 16)).astype(np.float32))
        sp, lo, hi = _mkplan(rng, q, mp, 5)
        ssp, slo, shi = _sort_by_partition(sp, lo, hi)
        d2, gid = refine_topk(store.data, store.norms, store.rec_dfs,
                              store.rec_gid, queries, ssp, slo, shi, k,
                              interpret=True)
        d2_ref, g_ref = ref.refine_topk_ref(
            store.data, store.norms, store.rec_dfs, store.rec_gid,
            queries, ssp, slo, shi, k)
        np.testing.assert_array_equal(np.asarray(g_ref), np.asarray(gid))
        np.testing.assert_allclose(np.asarray(d2_ref), np.asarray(d2),
                                   rtol=1e-4, atol=1e-4)

    def test_refine_use_kernel_flag_routes_to_fused(self):
        """refine(use_kernel=True) is the fused kernel, sentinel included."""
        rng = np.random.default_rng(3)
        store = _mkstore(rng, 4, 12, 16)
        queries = jnp.asarray(rng.normal(size=(3, 16)).astype(np.float32))
        sp, lo, hi = _mkplan(rng, 3, 5, 4)
        d_k, g_k = refine(store, queries, sp, lo, hi, 6, use_kernel=True)
        dist, gid = _fused(store, queries, sp, lo, hi, 6)
        np.testing.assert_array_equal(np.asarray(g_k), gid)
        np.testing.assert_array_equal(np.asarray(d_k), dist)


class TestEdgeShapes:
    """Satellite: cap % block ≠ 0, all-masked plans, pools smaller than k."""

    @pytest.mark.parametrize("cap,block_c", [
        (12, 5),    # ragged last block
        (12, 12),   # exactly one block
        (12, 4),    # even split
        (7, 16),    # block larger than cap (clamped)
    ])
    def test_cap_not_multiple_of_block(self, cap, block_c):
        rng = np.random.default_rng(cap * 31 + block_c)
        store = _mkstore(rng, 5, cap, 16)
        queries = jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32))
        sp, lo, hi = _mkplan(rng, 4, 6, 5)
        d_ref, g_ref = refine(store, queries, sp, lo, hi, 5,
                              use_kernel=False)
        dist, gid = _fused(store, queries, sp, lo, hi, 5, block_c=block_c)
        np.testing.assert_array_equal(np.asarray(g_ref), gid)
        np.testing.assert_allclose(np.asarray(d_ref), dist, **DTOL)

    def test_all_masked_plan(self):
        """Every entry padded / every interval empty → pure PAD output,
        identical to the dense path."""
        rng = np.random.default_rng(0)
        store = _mkstore(rng, 4, 10, 16)
        queries = jnp.asarray(rng.normal(size=(3, 16)).astype(np.float32))
        empty_part = jnp.full((3, 5), -1, jnp.int32)     # all pad entries
        zeros = jnp.zeros((3, 5), jnp.int32)
        live_part = jnp.asarray(
            rng.integers(0, 4, size=(3, 5)).astype(np.int32))
        for sp, lo, hi in [
            (empty_part, zeros, zeros + 10),   # no partition selected
            (live_part, zeros + 7, zeros + 7),  # empty DFS intervals
        ]:
            d_ref, g_ref = refine(store, queries, sp, lo, hi, 5,
                                  use_kernel=False)
            dist, gid = _fused(store, queries, sp, lo, hi, 5)
            np.testing.assert_array_equal(gid, -1)
            np.testing.assert_array_equal(dist, np.float32(PAD_DIST))
            np.testing.assert_array_equal(np.asarray(g_ref), gid)
            np.testing.assert_array_equal(np.asarray(d_ref), dist)

    def test_pool_smaller_than_k(self):
        """cap·slots < k must emit PAD_DIST/gid=-1 exactly like dense."""
        rng = np.random.default_rng(1)
        store = _mkstore(rng, 3, 6, 16, pad_frac=0.5)
        queries = jnp.asarray(rng.normal(size=(2, 16)).astype(np.float32))
        sp, lo, hi = _mkplan(rng, 2, 2, 3)
        k = 40                                  # > 2 slots × 6 cap
        d_ref, g_ref = refine(store, queries, sp, lo, hi, k,
                              use_kernel=False)
        dist, gid = _fused(store, queries, sp, lo, hi, k)
        assert np.all(gid[:, -10:] == -1)       # tail is certainly padded
        np.testing.assert_array_equal(np.asarray(g_ref), gid)
        pads = gid < 0
        np.testing.assert_array_equal(dist[pads], np.float32(PAD_DIST))
        np.testing.assert_allclose(np.asarray(d_ref)[~pads], dist[~pads],
                                   **DTOL)

    def test_duplicate_coverage_dedupe(self):
        """A node and its ancestor both selected: each record must be
        counted once — no duplicate gids, and parity with dense."""
        rng = np.random.default_rng(2)
        store = _mkstore(rng, 4, 12, 16, pad_frac=0.0)
        queries = jnp.asarray(rng.normal(size=(3, 16)).astype(np.float32))
        # same partition selected thrice with nested/overlapping intervals
        sp = jnp.asarray(np.tile([2, 2, 2, 1], (3, 1)).astype(np.int32))
        lo = jnp.asarray(np.tile([0, 5, 10, 0], (3, 1)).astype(np.int32))
        hi = jnp.asarray(np.tile([20, 15, 50, 50], (3, 1)).astype(np.int32))
        d_ref, g_ref = refine(store, queries, sp, lo, hi, 10,
                              use_kernel=False)
        dist, gid = _fused(store, queries, sp, lo, hi, 10)
        np.testing.assert_array_equal(np.asarray(g_ref), gid)
        np.testing.assert_allclose(np.asarray(d_ref), dist, **DTOL)
        for row in gid:
            real = row[row >= 0]
            assert len(set(real.tolist())) == len(real)

    def test_empty_batch_and_empty_plan(self):
        rng = np.random.default_rng(4)
        store = _mkstore(rng, 3, 8, 16)
        d, g = refine_topk(store.data, store.norms, store.rec_dfs,
                           store.rec_gid,
                           jnp.zeros((0, 16), jnp.float32),
                           jnp.zeros((0, 4), jnp.int32),
                           jnp.zeros((0, 4), jnp.int32),
                           jnp.zeros((0, 4), jnp.int32), 5, interpret=True)
        assert d.shape == (0, 5) and g.shape == (0, 5)


class TestEndToEnd:
    def test_knn_query_kernel_parity(self):
        """Fused refine through the full featurize→plan→refine pipeline."""
        from repro.core import build_index, knn_query
        from repro.data import make_dataset, make_queries
        from repro.utils.config import ClimberConfig
        cfg = ClimberConfig(series_len=64, paa_segments=8, num_pivots=32,
                            prefix_len=5, capacity=64, sample_frac=0.3,
                            max_centroids=12, k=10, candidate_groups=4,
                            adaptive_factor=4)
        data = make_dataset("randomwalk", jax.random.PRNGKey(0), 1500, 64)
        index = build_index(jax.random.PRNGKey(1), data, cfg)
        queries = np.asarray(make_queries(jax.random.PRNGKey(2), data, 5))
        for variant in ("knn", "adaptive"):
            d0, g0, _ = knn_query(index, queries, 10, variant=variant,
                                  use_kernel=False)
            d1, g1, _ = knn_query(index, queries, 10, variant=variant,
                                  use_kernel=True)
            # the kernel's blocked dot and the dense einsum round apart;
            # a self-match is 0 on one side and sqrt(noise) on the other
            assert_same_answers(np.asarray(d0), np.asarray(g0),
                                np.asarray(d1), np.asarray(g1), scale=2 * 64)

    def test_backend_default_resolution(self):
        """None resolves to the backend default; explicit flags win."""
        assert resolve_use_kernel(True) is True
        assert resolve_use_kernel(False) is False
        # fused kernel on accelerators, dense oracle elsewhere (CPU CI)
        assert resolve_use_kernel(None) == (jax.default_backend() == "tpu")


class TestBlockAutotune:
    """First autotuning step: BLOCK_C picked at trace time from cap."""

    def test_pick_is_capped_next_pow2(self):
        # a small store is one block of exactly cap slots (a block dim equal
        # to the array dim always lowers on TPU); larger ones stream
        # lane-aligned 512-wide blocks
        assert pick_block_c(1) == 1
        assert pick_block_c(12) == 12
        assert pick_block_c(100) == 100
        assert pick_block_c(512) == DEFAULT_BLOCK_C
        assert pick_block_c(4096) == DEFAULT_BLOCK_C  # streams in 512 blocks
        assert DEFAULT_BLOCK_C % 128 == 0

    @pytest.mark.parametrize("cap", [12, 100, 600])
    def test_auto_block_parity(self, cap):
        """The default (auto) block matches dense — including the small-cap
        case where the single auto block exceeds cap and the tail is
        index-masked."""
        rng = np.random.default_rng(cap)
        store = _mkstore(rng, 5, cap, 16)
        queries = jnp.asarray(rng.normal(size=(3, 16)).astype(np.float32))
        sp, lo, hi = _mkplan(rng, 3, 4, 5)
        d_ref, g_ref = refine(store, queries, sp, lo, hi, 6,
                              use_kernel=False)
        dist, gid = _fused(store, queries, sp, lo, hi, 6)   # block_c=None
        np.testing.assert_array_equal(np.asarray(g_ref), gid)
        np.testing.assert_allclose(np.asarray(d_ref), dist, **DTOL)
