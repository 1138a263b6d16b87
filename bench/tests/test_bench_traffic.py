"""The open-loop schedule and the lateness arithmetic."""
import numpy as np
import pytest

import _benchpaths  # noqa: F401
from benchlib import traffic


@pytest.mark.parametrize("rate,seconds", [(40.0, 20.0), (3.5, 2.0),
                                          (250.0, 51.0)])
def test_schedule_count_and_span(rate, seconds):
    due = traffic.open_schedule(rate, seconds, seed=2**33 + 7)
    assert len(due) == round(rate * seconds)
    assert np.all(np.diff(due) > 0)
    assert due[0] > 0 and due[-1] < seconds


def test_every_seed_gets_the_same_gaps_in_another_order():
    a = traffic.open_schedule(40.0, 20.0, seed=1)
    b = traffic.open_schedule(40.0, 20.0, seed=2**40 + 3)
    assert len(a) == len(b)
    assert not np.allclose(a, b)
    # the n arrivals and the window's tail are the same n+1 gaps
    ga, gb = np.diff(np.r_[0.0, a]), np.diff(np.r_[0.0, b])
    full = np.sort(np.r_[ga, 20.0 - a[-1]])
    fullb = np.sort(np.r_[gb, 20.0 - b[-1]])
    np.testing.assert_allclose(full, fullb, rtol=1e-9, atol=1e-12)
    np.testing.assert_array_equal(a, traffic.open_schedule(40.0, 20.0, 1))


def test_schedule_rate_is_poisson_like():
    due = traffic.open_schedule(100.0, 50.0, seed=3)
    gaps = np.diff(due)
    assert abs(gaps.mean() - 0.01) < 0.001
    # exponential gaps: standard deviation close to the mean
    assert 0.8 < gaps.std() / gaps.mean() < 1.2


def test_lateness_summary_by_hand():
    due = np.array([0.0, 1.0, 2.0, 3.0])
    sent = np.array([0.0, 1.002, 1.9, 3.010])     # one early send is 0
    s = traffic.lateness_summary(sent, due)
    assert s["max_ms"] == pytest.approx(10.0)
    assert s["p50_ms"] == pytest.approx(1.0)


def test_queries_needed():
    assert traffic.queries_needed({"mode": "open", "rate_qps": 12.5},
                                  20.0) == 250
    assert traffic.queries_needed({"mode": "closed", "pool": 777},
                                  20.0) == 777


@pytest.mark.parametrize("bad", [
    {"mode": "burst"}, {"mode": "open", "rate_qps": 0, "connections": 1,
                        "query_noise": 0.05},
    {"mode": "closed", "outstanding": 0, "connections": 1, "pool": 5,
     "query_noise": 0.05}])
def test_bad_traffic_is_refused(bad):
    with pytest.raises((ValueError, KeyError)):
        traffic.check_traffic(bad)
