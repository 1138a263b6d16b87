"""The distinct-candidate byte count of the refine stage."""
import numpy as np
import pytest

import _benchpaths  # noqa: F401
from benchlib import work


def test_tick_candidates_by_hand():
    # 2 partitions x 4 slots; slot 3 of partition 1 is a pad
    rec_dfs = np.array([[0, 1, 2, 3], [5, 5, 6, 0]])
    rec_gid = np.array([[10, 11, 12, 13], [20, 21, 22, -1]])
    sel_part = np.array([[0, 1, -1], [0, 1, 0]])
    sel_lo = np.array([[0, 5, 0], [2, 6, 3]])
    sel_hi = np.array([[2, 6, 0], [4, 7, 4]])
    # partition 0: [0,2) -> slots 0,1; [2,4) -> 2,3; [3,4) -> 3 (again)
    # partition 1: [5,6) -> slots 0,1; [6,7) -> slot 2; the pad never
    assert work.tick_candidates(sel_part, sel_lo, sel_hi, rec_dfs,
                                rec_gid) == 7


def test_unrouted_and_empty_plans_read_nothing():
    rec = np.zeros((1, 3), int)
    none = np.full((2, 2), -1)
    assert work.tick_candidates(none, none, none, rec, rec) == 0


def test_peaks_table():
    p = work.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("TPU v99")


@pytest.fixture(scope="module")
def toy_fleet():
    import jax
    from repro.fleet import FleetConfig, IndexFleet
    from repro.utils.config import ClimberConfig
    cfg = ClimberConfig(series_len=64, paa_segments=8, num_pivots=16,
                        prefix_len=4, capacity=200, k=10)
    fleet = IndexFleet(FleetConfig(shard_cfg=cfg, auto_compact=False))
    rng = np.random.default_rng(0)
    data = rng.standard_normal((2, 1200, 64)).cumsum(-1).astype(np.float32)
    data = (data - data.mean(-1, keepdims=True)) / data.std(-1,
                                                            keepdims=True)
    for s in range(2):
        fleet.add_shard(f"t{s}", data[s])
    q = data.reshape(-1, 64)[rng.choice(2400, 12, replace=False)]
    del jax
    return fleet, q


def test_refine_bytes_counts_each_tick_candidate_once(toy_fleet):
    """Against a plain loop over every routed plan entry and slot."""
    from repro.core.query import plan
    import jax.numpy as jnp
    fleet, q = toy_fleet
    routed = np.array([[1, 0], [1, 1], [0, 1], [1, 1]] * 3, bool)
    ticks = [(q[:5], routed[:5]), (q[5:], routed[5:])]
    want = 0
    for tq, tr in ticks:
        seen = set()
        for s, shard in enumerate(fleet.shards):
            st = shard.index.store
            dfs, gid = np.asarray(st.rec_dfs), np.asarray(st.rec_gid)
            p4r, _ = shard.index.featurize(jnp.asarray(tq))
            qp = plan(shard.index, p4r, variant="adaptive")
            sp, lo, hi = (np.asarray(x) for x in (qp.sel_part, qp.sel_lo,
                                                  qp.sel_hi))
            for i in range(len(tq)):
                if not tr[i, s]:
                    continue
                for p, a, b in zip(sp[i], lo[i], hi[i]):
                    if p < 0:
                        continue
                    for slot in range(dfs.shape[1]):
                        if gid[p, slot] >= 0 and a <= dfs[p, slot] < b:
                            seen.add((s, int(p), slot))
        want += len(seen)
    got = work.refine_bytes(fleet, ticks, "adaptive")
    assert want > 0
    assert got == want * (4 * 64 + 8)
