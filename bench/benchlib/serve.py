"""The system under test, built as the configuration says and driven only
through its own entry points: ``IndexFleet`` → ``FleetEngine`` →
``ClimberServer`` on a one-device mesh.

:class:`TickRecorder` is the benchmark's one span: it wraps the engine's
call into ``IndexFleet.query`` on the fleet instance (no program file is
touched) and records, per tick, when it started, how long it took and how
many live rows it had.  In a traced run it also keeps the tick's queries
and routed shards, for the byte count of ``refine_topk_roofline``, and
marks the call with ``TraceAnnotation``s that the trace reduction keys
its idle gaps on.
"""
from __future__ import annotations

import time
from contextlib import nullcontext

import numpy as np

TICK_SPAN = "bench.tick"
QUERY_SPAN = "bench.fleet_query"
ROUTE_SPAN = "bench.route"


class TickRecorder:
    """Host-side record of every tick, from the benchmark's wrapper."""

    def __init__(self, engine, *, keep_queries: bool, annotate: bool):
        self.starts, self.secs, self.live = [], [], []
        self.queries, self.masks = [], []
        self._keep = keep_queries
        fleet = engine.fleet
        query, execute = fleet.query, engine.execute_prepared
        if annotate:
            import jax
            span = jax.profiler.TraceAnnotation
        else:
            span = lambda name: nullcontext()        # noqa: E731

        def timed_query(queries, k=0, **kw):
            t = time.monotonic()
            with span(QUERY_SPAN):
                out = query(queries, k, **kw)
            self.secs.append(time.monotonic() - t)
            self.starts.append(t)
            self.live.append(len(queries))
            if self._keep:
                self.queries.append(np.array(queries))
                self.masks.append(np.array(out[2].routed_mask))
            return out

        fleet.query = timed_query
        engine.execute_prepared = _spanned(span, TICK_SPAN, execute)
        if annotate and fleet.router is not None:
            router = fleet.router
            for name in ("route", "route_adaptive"):
                setattr(router, name, _spanned(span, ROUTE_SPAN,
                                               getattr(router, name)))

    def window(self, t0: float, t1: float) -> np.ndarray:
        """Indices of the ticks that started inside ``[t0, t1)``."""
        s = np.asarray(self.starts)
        return np.nonzero((s >= t0) & (s < t1))[0]


def _spanned(span, name: str, fn):
    def call(*args, **kw):
        with span(name):
            return fn(*args, **kw)
    return call


def build(cfg: dict, shards: list):
    """Build the fleet shard by shard, attach a one-device mesh and wrap it
    in the engine the configuration describes.  The build's seed is the
    configuration's, so every run builds the same index."""
    from repro.fleet import FleetConfig, FleetEngine, IndexFleet
    from repro.launch.mesh import make_mesh
    from repro.serve import api
    from repro.utils.config import ClimberConfig

    fleet = IndexFleet(FleetConfig(shard_cfg=ClimberConfig(**cfg["climber"]),
                                   **cfg["fleet"]))
    for s, data in enumerate(shards):
        fleet.add_shard(f"tenant{s}", data)
    fleet.attach_mesh(make_mesh((1,), ("data",)))
    return FleetEngine(fleet, config=api.ServingConfig(**cfg["serving"]))


def warm_up(engine, queries: np.ndarray):
    """Run one tick of every live-row count the server can form, 1 to
    ``batch_size``: the engine passes only the live rows to the fleet, so
    each count is a program of its own.  The first tick also lays the
    stores out on the mesh.  Returns the queries used and each tick's
    seconds."""
    bs = engine.batch_size
    used, secs = 0, []
    for live in range(1, bs + 1):
        qbatch = np.zeros((bs, engine.series_len), np.float32)
        qbatch[:live] = queries[used:used + live]
        t = time.monotonic()
        engine._execute(qbatch, live)
        secs.append(time.monotonic() - t)
        used += live
    return used, secs


def exhaustive_answers(port: int, engine, queries: np.ndarray):
    """Ask the running server for ``queries`` with every shard and every
    partition scanned (``routing`` and ``variant`` ``"exhaustive"``), the
    route whose answer is the exact top-k."""
    from repro.serve.net import ClimberClient
    saved = engine.routing, engine.variant
    engine.routing, engine.variant = "exhaustive", "exhaustive"
    try:
        with ClimberClient("127.0.0.1", port, timeout=600) as cl:
            res = cl.query_batch(list(queries), engine.k)
    finally:
        engine.routing, engine.variant = saved
    return (np.stack([r.dist for r in res]), np.stack([r.gid for r in res]))
