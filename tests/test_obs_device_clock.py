"""Spans on the profiler's clock, admission wait and the compile counter.

  * every tracer span is also a ``jax.profiler`` annotation: a trace
    captured around one served tick on a one-device mesh holds
    ``serve.tick ⊃ fleet.query ⊃ fleet.route`` and
    ``fleet.mesh.query ⊃ fleet.mesh.put → wait → fetch`` on the host plane,
    nested and in that order;
  * ``serve.admit_wait_ms`` observes one wait per executed ticket, from
    admission to the start of its tick;
  * ``jax.lowerings`` rises on a tick of a new batch shape, which the
    tick's ``lowered`` attr shows, and not on a repeated shape.
"""
import time

import jax
import numpy as np
import pytest

from repro.data import make_dataset, make_queries
from repro.fleet import FleetConfig, FleetEngine, IndexFleet
from repro.launch.mesh import make_mesh
from repro.obs import TRACER
from repro.obs.profile import lowerings
from repro.serve import api
from repro.utils.config import ClimberConfig

K = 10
N = 64
HOST_NAMES = ("serve.tick", "fleet.query", "fleet.route", "fleet.mesh.query",
              "fleet.mesh.put", "fleet.mesh.wait", "fleet.mesh.fetch")


def mkdata(seed: int, n: int) -> np.ndarray:
    return np.asarray(make_dataset("randomwalk", jax.random.PRNGKey(seed),
                                   n, N))


@pytest.fixture(scope="module")
def corpus():
    return mkdata(0, 1600)


@pytest.fixture(scope="module")
def engine(corpus):
    cfg = ClimberConfig(series_len=N, paa_segments=8, num_pivots=32,
                        prefix_len=5, capacity=128, sample_frac=0.3,
                        max_centroids=12, k=K, candidate_groups=4,
                        adaptive_factor=4)
    fleet = IndexFleet(FleetConfig(shard_cfg=cfg, fanout=1,
                                   delta_capacity=4096, auto_compact=False))
    fleet.add_shard("t0", corpus[:800])
    fleet.add_shard("t1", corpus[800:])
    fleet.attach_mesh(make_mesh((1,), ("data",)))
    return FleetEngine(fleet, batch_size=4, k=K, routing="adaptive")


def queries(corpus, seed: int, n: int) -> np.ndarray:
    return np.asarray(make_queries(jax.random.PRNGKey(seed), corpus, n))


def serve(engine, qs) -> None:
    for q in qs:
        engine.submit_request(api.QueryRequest(series=q, k=K))
    assert engine.step() == len(qs)


def host_events(logdir) -> list:
    """``(name, start, end)`` of the named host-plane events in the trace
    under ``logdir``, in seconds on the trace's clock."""
    from jax.profiler import ProfileData
    path = sorted(logdir.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in HOST_NAMES:
                    s = e.start_ns * 1e-9
                    out.append((e.name, s, s + e.duration_ns * 1e-9))
    return out


def test_spans_nest_on_the_profiler_clock(engine, corpus, tmp_path):
    serve(engine, queries(corpus, 1, 4))          # compile outside the trace
    with engine.capture_device_trace(tmp_path):
        serve(engine, queries(corpus, 2, 4))
    ev = host_events(tmp_path)
    by = {}
    for name, s, e in ev:
        by.setdefault(name, []).append((s, e))
    assert set(by) == set(HOST_NAMES), sorted(by)
    for name in HOST_NAMES:
        assert len(by[name]) == 1, (name, by[name])

    def inside(child, parent):
        (cs, ce), = by[child]
        (ps, pe), = by[parent]
        return ps <= cs and ce <= pe

    assert inside("fleet.query", "serve.tick")
    assert inside("fleet.route", "fleet.query")
    assert inside("fleet.mesh.query", "fleet.query")
    assert not inside("fleet.route", "fleet.mesh.query")
    stages = ["fleet.mesh.put", "fleet.mesh.wait", "fleet.mesh.fetch"]
    for stage in stages:
        assert inside(stage, "fleet.mesh.query")
    ends = [by[s][0] for s in stages]
    for (_, e0), (s1, _) in zip(ends, ends[1:]):
        assert e0 <= s1                            # put, then wait, then fetch


def test_the_ring_holds_the_honest_mesh_names(engine, corpus):
    TRACER.clear()
    serve(engine, queries(corpus, 3, 4))
    tree = TRACER.last_trace("serve.tick")
    (fq,) = [c for c in tree["children"] if c["name"] == "fleet.query"]
    assert fq["attrs"]["placement"] == "mesh"
    kids = [c["name"] for c in fq["children"]]
    # route → cache probe → the fused pass → cache put → delta merge
    assert kids == ["fleet.route", "fleet.plan_cache", "fleet.mesh.query",
                    "fleet.plan_cache", "fleet.merge"]
    mesh = fq["children"][2]
    assert [c["name"] for c in mesh["children"]] == \
        ["fleet.mesh.put", "fleet.mesh.wait", "fleet.mesh.fetch"]


def test_admission_wait_one_observation_per_ticket(engine, corpus):
    hold = 0.05
    hist = engine.admit_wait_hist
    hist.reset()
    for q in queries(corpus, 4, 3):
        engine.submit_request(api.QueryRequest(series=q, k=K))
    time.sleep(hold)
    assert engine.step() == 3
    assert hist.count == 3
    assert hist.min >= hold * 1e3
    tick = TRACER.last_trace("serve.tick")
    assert tick["attrs"]["wait_ms_max"] == pytest.approx(hist.max)


def test_the_direct_api_records_no_admission_wait(engine, corpus):
    n0 = engine.admit_wait_hist.count
    engine.run(queries(corpus, 5, 4))
    assert engine.admit_wait_hist.count == n0


def test_a_new_batch_shape_is_counted_and_marks_its_tick(engine, corpus):
    first, again = queries(corpus, 6, 2), queries(corpus, 7, 2)
    counter = lowerings()
    c0 = counter.value
    serve(engine, first)                          # two live rows: new shape
    c1 = counter.value
    assert c1 > c0
    assert TRACER.last_trace("serve.tick")["attrs"]["lowered"] == c1 - c0
    serve(engine, again)                          # the same shape again
    assert counter.value == c1
    assert TRACER.last_trace("serve.tick")["attrs"]["lowered"] == 0
