"""Percentile and recall arithmetic, and the end-to-end readers,
against hand-computed cases."""
import numpy as np
import pytest

import _benchpaths  # noqa: F401
from benchlib import spec, stats
from benchlib.record import RunRecord


@pytest.mark.parametrize("q,want", [(50, 5.0), (95, 10.0), (90, 9.0),
                                    (10, 1.0), (100, 10.0), (0.1, 1.0)])
def test_nearest_rank_percentile(q, want):
    assert stats.percentile(list(range(10, 0, -1)), q) == want


def test_percentile_of_twenty():
    v = np.arange(1, 21, dtype=float)        # 1..20
    assert stats.percentile(v, 95) == 19.0   # rank ceil(0.95*20) = 19


def test_recall_counts_boundary_ties_and_skips_pads():
    exact = np.array([[1, 2, 3, 4]])
    exact_d = np.array([[0.1, 0.2, 0.3, 0.4]])
    approx = np.array([[1, 2, 9, -1]])
    approx_d = np.array([[0.1, 0.2, 0.4000001, np.inf]])
    r = stats.recall_at_k(approx, exact, 4, approx_dist=approx_d,
                          exact_dist=exact_d, tie_tol=1e-6)
    assert r[0] == pytest.approx(3 / 4)        # 9 ties the 4th; pad misses
    r = stats.recall_at_k(approx, exact, 4, approx_dist=approx_d,
                          exact_dist=exact_d, tie_tol=0.0)
    assert r[0] == pytest.approx(2 / 4)


def test_tie_tolerance():
    assert stats.tie_tolerance(3.0, 7.0) == pytest.approx(1.0)


def _record(**kw):
    base = dict(seconds=10.0, setup_s=60.0, due=np.zeros(0),
                done=np.zeros(0), ok=np.zeros(0, bool), window_end=10.0,
                batch_size=8, tick_secs=np.zeros(0), tick_live=np.zeros(0))
    base.update(kw)
    return RunRecord(**base)


def test_qps_counts_answers_inside_the_window():
    read = spec.load_reader("qps")
    rec = _record(due=np.array([0.0, 1.0, 9.0, 9.5]),
                  done=np.array([0.5, 2.0, 10.5, np.nan]),
                  ok=np.array([True, True, True, False]))
    assert read(rec) == pytest.approx(2 / 10.0)


def test_recall_reader_is_the_mean_and_silent_without_answers():
    read = spec.load_reader("recall")
    assert read(_record(recall=np.array([0.25, 0.5, 1.0]))) == \
        pytest.approx(0.5833333333)
    assert read(_record()) is None
    assert read(_record(recall=np.zeros(0))) is None


def test_tick_readers():
    rec = _record(tick_secs=np.array([0.1, 0.3]),
                  tick_live=np.array([8, 4]))
    assert spec.load_reader("tick_ms.batch")(rec) == pytest.approx(200.0)
    assert spec.load_reader("batch_fill.batch")(rec) == pytest.approx(0.75)
    assert spec.load_reader("batch_fill.batch")(_record()) is None
    assert spec.load_reader("idle_share.batch")(rec) is None
    assert spec.load_reader("refine_topk_roofline")(rec) is None
