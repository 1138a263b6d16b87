"""The window difference of the program's histograms (``benchlib.program``)
and the per-layer metrics that read it."""
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

import _benchpaths  # noqa: F401
from benchlib import program, spec
from repro.obs import MetricsRegistry, SpanTracer

NEW = ("route_ms.batch", "mesh_host_ms.batch", "admit_wait_ms.batch",
       "serve_tick_ms.batch")


def _tick(tracer, registry, waits, route=True):
    """One served tick: admission waits on two loops' histograms, and a
    routed fleet query with a mesh call."""
    with tracer.span("serve.tick"):
        for i, w in enumerate(waits):
            registry.histogram("serve.admit_wait_ms",
                               loop=f"loop{i % 2}").observe(w)
        if route:
            with tracer.span("fleet.route"):
                pass
        with tracer.span("fleet.mesh.put"):
            pass
        with tracer.span("fleet.mesh.fetch"):
            pass


@pytest.fixture
def plane():
    registry = MetricsRegistry()
    tracer = SpanTracer(registry=registry)
    return tracer, registry, program.TickSnapshots(tracer, registry)


def _run(t0, t1):
    """A RunRecord's window edges, on the harness's monotonic clock."""
    return SimpleNamespace(window_end=t1 - program._PERF_MINUS_MONO,
                           seconds=t1 - t0)


def test_window_sums_over_labels_and_skips_earlier_ticks(plane):
    tracer, registry, rec = plane
    _tick(tracer, registry, [1000.0])              # before the window
    t0 = time.perf_counter()
    _tick(tracer, registry, [10.0, 20.0, 30.0])
    _tick(tracer, registry, [40.0])
    t1 = time.perf_counter()
    _tick(tracer, registry, [5000.0])              # after the window
    delta = rec.window(t0, t1)
    assert delta["serve.admit_wait_ms"] == (4, 100.0)
    assert delta["span.serve.tick"][0] == 2
    assert delta["span.fleet.route"][0] == 2
    run = _run(t0, t1)
    assert program.mean(run, "serve.admit_wait_ms",
                        recorder=rec) == pytest.approx(25.0)
    both = program.mean(run, "span.fleet.mesh.put", "span.fleet.mesh.fetch",
                        per="span.serve.tick", recorder=rec)
    assert both == pytest.approx((delta["span.fleet.mesh.put"][1]
                                  + delta["span.fleet.mesh.fetch"][1]) / 2)


def test_none_where_nothing_moved(plane):
    tracer, registry, rec = plane
    t0 = time.perf_counter()
    assert rec.window(t0, time.perf_counter()) is None     # no tick at all
    _tick(tracer, registry, [], route=False)
    run = _run(t0, time.perf_counter())
    # a program without the router's span, and a tick with no ticket
    assert program.mean(run, "span.fleet.route", recorder=rec) is None
    assert program.mean(run, "serve.admit_wait_ms", recorder=rec) is None
    assert program.mean(run, "span.serve.tick", recorder=rec) > 0.0


def test_the_first_window_tick_counts_from_the_recording_start(plane):
    tracer, registry, rec = plane
    t0 = time.perf_counter()
    _tick(tracer, registry, [7.0])
    assert rec.window(t0, time.perf_counter())["serve.admit_wait_ms"] == \
        (1, 7.0)


def test_readers_read_none_without_ticks():
    run = SimpleNamespace(window_end=time.monotonic() - 3600.0, seconds=1.0)
    for name in NEW:
        assert spec.load_reader(name)(run) is None


def test_traced_rehearsal_reads_the_program_spans(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, str(spec.ROOT / "bench/run.py"), "--workload",
         "rw256-k500-batch", "--rehearse", "--trace", "1", "--seed",
         str(2**35 + 5), "--seconds", "2"], capture_output=True, text=True,
        timeout=600, cwd=tmp_path, env=env)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "rehearsal: correct=True" in res.stderr
    read = res.stderr.rsplit("metrics read: ", 1)[-1]
    for name in NEW:
        assert repr(name) in read, read
