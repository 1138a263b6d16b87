"""The router's compiled signature profile against the op-by-op composition.

``SignatureRouter.signature_profile`` runs ``paa → rank_signature →
weighted_onehot`` as one compiled program per batch shape
(``repro.fleet.router.profile_batch``).  The op-by-op (eager) composition
of the same three functions is the reference: the profile, the scores, the
routing masks and the shard summaries must equal it exactly, at the
paper's widths (n=256, w=16, r=200, m=10) and for every live-row count a
batch of 8 can hold.  A repeated batch shape lowers nothing, even under a
router built from other pivots.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.paa import paa
from repro.core.signatures import rank_signature, weighted_onehot
from repro.data import make_dataset
from repro.fleet import router as router_mod
from repro.fleet.router import SignatureRouter
from repro.obs.profile import install_compile_counter
from repro.utils.config import ClimberConfig

CFG = ClimberConfig()               # n=256, w=16, r=200, m=10
N = CFG.series_len


def series(seed: int, num: int) -> np.ndarray:
    return np.asarray(make_dataset("randomwalk", jax.random.PRNGKey(seed),
                                   num, N), np.float32)


def eager_profile(router: SignatureRouter, x: np.ndarray) -> np.ndarray:
    """The op-by-op composition, one eager dispatch per primitive."""
    z = paa(jnp.asarray(x, dtype=jnp.float32), CFG.paa_segments)
    p4r = rank_signature(z, router.pivots, CFG.prefix_len)
    return np.asarray(weighted_onehot(p4r, router.pivots.shape[0],
                                      router._weights))


def eager_summary(router: SignatureRouter, x: np.ndarray,
                  rows: int) -> np.ndarray:
    prof = sum((eager_profile(router, x[i:i + rows]).sum(axis=0)
                for i in range(0, len(x), rows)),
               np.zeros(CFG.num_pivots, np.float32))
    norm = float(np.linalg.norm(prof))
    return (prof / norm if norm else prof).astype(np.float32)


@pytest.fixture(scope="module")
def router() -> SignatureRouter:
    """A router over six shards of distinct random-walk data."""
    r = SignatureRouter.from_sample(jax.random.PRNGKey(0), series(0, 2000),
                                    CFG)
    for s in range(6):
        r.register(f"s{s}", r.summarize(series(10 + s, 300)))
    return r


@pytest.fixture(scope="module")
def queries() -> np.ndarray:
    return series(99, 8)


@pytest.mark.parametrize("live", range(1, 9))
def test_profile_equals_the_eager_composition(router, queries, live):
    q = queries[:live]
    got = router.signature_profile(q)
    assert isinstance(got, np.ndarray)
    assert got.shape == (live, CFG.num_pivots) and got.dtype == np.float32
    np.testing.assert_array_equal(got, eager_profile(router, q))


@pytest.mark.parametrize("live", [1, 3, 8])
def test_scores_equal_the_eager_composition(router, queries, live):
    q = queries[:live]
    want = eager_profile(router, q) @ np.stack(router._summaries, axis=1)
    np.testing.assert_array_equal(router.score(q), want)


@pytest.mark.parametrize("threshold", [0.0, 0.3, 0.6, 0.9])
def test_route_adaptive_mask_is_unchanged(router, queries, threshold):
    """Through the profile (no ``scores=``) the mask is the one the eager
    scores give."""
    eager = eager_profile(router, queries) @ np.stack(router._summaries,
                                                      axis=1)
    want = router.route_adaptive(queries, threshold, scores=eager)
    got = router.route_adaptive(queries, threshold)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        router.route(queries, 2),
        router.route(queries, 2, scores=eager))


@pytest.mark.parametrize("rows", [64, 250])
def test_summarize_is_unchanged_across_chunks(router, monkeypatch, rows):
    """Chunks of ``SUMMARY_ROWS`` rows, the last one partial."""
    x = series(7, 700)
    monkeypatch.setattr(router_mod, "SUMMARY_ROWS", rows)
    np.testing.assert_array_equal(router.summarize(x),
                                  eager_summary(router, x, rows))


def test_a_repeated_shape_lowers_nothing(router, queries):
    """The pivots and weights are arguments, not constants: a router built
    from another sample reuses the program of each batch shape."""
    counter = install_compile_counter()
    other = SignatureRouter.from_sample(jax.random.PRNGKey(1),
                                        series(1, 2000), CFG)
    q = queries[:5]
    router.signature_profile(q)
    c0 = counter.value
    router.signature_profile(queries[3:8])
    other.signature_profile(q)
    assert counter.value == c0
    router.signature_profile(np.concatenate([q, q]))       # a new shape
    assert counter.value > c0
