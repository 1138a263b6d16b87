"""ClimberEngine — batched kNN serving over the CLIMBER index.

The retrieval-plane sibling of the slot-based LLM ``Engine``
(repro.serve.engine): requests are admitted into fixed-shape query batches
so the whole plan→refine pipeline jits once per batch size and every tick
serves a full batch.  One code path covers all execution backends — the
engine resolves its planner by name from the registry
(``repro.core.query``), compacts every plan to the static slot budget, and
executes refine through ``dispatch_refine``, which picks dense /
Pallas-kernel / shard_map-sharded execution from the engine's ``mesh``.

Static-shape adaptation: a tick always runs ``batch_size`` query rows; when
fewer requests are waiting the tail rows are zero-padded and their outputs
dropped.  Planning and refine are row-independent (per-row top_k /
arg-reductions only), so a query's (dist, gid) is bit-identical whichever
batch it rides in — ``run`` on a big batch equals per-query ``knn_query``.

Query plan cache: a plan depends only on the query's P4→ rank signature
(and the frozen index), so the engine memoizes compacted plan rows in a
:class:`PlanCache` LRU keyed on the signature prefix.  The pipeline is
staged as three jits — featurize → plan → refine — and a tick whose live
rows all hit the cache skips the planning stage (assignment-distance
matmuls + trie descent) entirely; any miss re-plans the whole fixed-shape
batch and refreshes every row's cache entry.  Cached rows are exactly a
prior plan stage's output, so caching never changes results.
``EngineStats`` counts per-row hits/misses.  The fleet reuses the same
:class:`PlanCache` for its device plans, prefixing every key with a
*placement epoch* that increments when the sealed shard set changes — the
single-index engine's index is frozen, so its epoch is implicitly 0.

The admission machinery (request queue, fixed-shape ticks, per-query
metrics) lives in :class:`BatchedServingLoop` so other executors — e.g. the
fleet engine (``repro.fleet``) — serve through the identical loop.

Per-query metrics (partitions touched, candidates scanned, latency,
batch fill) ride on every completed request; ``EngineStats`` aggregates
them into the queries/sec numbers the benchmarks report.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
import warnings
import weakref
from collections import OrderedDict
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.index import ClimberIndex
from repro.core.query import candidates_scanned, default_slot_budget, \
    get_planner, plan as plan_queries
from repro.core.refine import dispatch_refine, resolve_use_kernel
from repro.obs import REGISTRY, TRACER, install_compile_counter
from repro.obs.profile import lowerings
from repro.obs.tracer import TraceContext
from repro.serve import api

# distinguishes each serving loop's metric series in the process registry
_LOOP_SEQ = itertools.count()

# the mutable-QueryRequest adapter warns once per process, not per call
_LEGACY_SUBMIT_WARNED = False


class PlanCache:
    """Epoch-aware LRU of per-query plan rows.

    Keys are arbitrary hashables: :class:`ClimberEngine` keys rows on the
    query's rank-signature bytes (its index is frozen — epoch implicitly
    0); the fleet (``repro.fleet``) keys on ``(placement epoch, planner
    variant, raw query bytes)``, so bumping the epoch orphans every entry
    planned against a retired shard layout without an explicit flush —
    stale entries simply age out of the LRU.  ``hits`` / ``misses`` are
    lifetime counters; callers diff them around a lookup burst to
    attribute per-call stats.
    """

    __slots__ = ("size", "hits", "misses", "_rows")

    def __init__(self, size: int):
        self.size = int(size)
        self.hits = 0
        self.misses = 0
        self._rows: OrderedDict = OrderedDict()

    def __len__(self) -> int:
        return len(self._rows)

    def get(self, key):
        """The cached row (refreshing LRU order) or None; counts the
        lookup as a hit or a miss."""
        row = self._rows.get(key)
        if row is None:
            self.misses += 1
            return None
        self.hits += 1
        self._rows.move_to_end(key)
        return row

    def put(self, key, row) -> None:
        """Insert or refresh a row, evicting LRU entries over capacity."""
        if self.size <= 0:
            return
        self._rows[key] = row
        self._rows.move_to_end(key)
        while len(self._rows) > self.size:
            self._rows.popitem(last=False)

    def clear(self) -> None:
        self._rows.clear()


@dataclasses.dataclass
class QueryRequest:
    """One kNN request: a raw series in, (dist, gid) + metrics out.

    .. deprecated::
        This is the *mutable* legacy request the engine writes answers
        back into.  New code should submit the frozen
        :class:`repro.serve.api.QueryRequest` via
        :meth:`BatchedServingLoop.submit_request` and read the immutable
        :class:`repro.serve.api.QueryResult` off the returned
        :class:`QueryTicket`.  ``submit`` keeps accepting this class
        through a thin adapter (one-time ``DeprecationWarning``).
    """

    rid: int
    series: np.ndarray                       # [n] raw query series
    k: int = 0                               # 0 => engine default
    dist: Optional[np.ndarray] = None        # [k] ascending ED
    gid: Optional[np.ndarray] = None         # [k] record ids (−1 pad)
    metrics: Optional["QueryMetrics"] = None
    done: bool = False
    submitted_at: Optional[float] = None     # perf_counter at admission


class QueryTicket:
    """One in-flight admission: a frozen :class:`repro.serve.api.
    QueryRequest` paired with its eventual outcome.

    ``result`` becomes an :class:`repro.serve.api.QueryResult` on
    success or an :class:`repro.serve.api.ErrorReply` on failure; ``done``
    flips atomically last.  Tickets are what the queue, the network
    server's admission buffers, and the executor hand around — the frozen
    request is never mutated.
    """

    __slots__ = ("request", "series", "result", "done", "submitted_at",
                 "legacy", "conn", "trace")

    def __init__(self, request: api.QueryRequest, series: np.ndarray,
                 submitted_at: Optional[float] = None):
        self.request = request
        self.series = series               # validated float32 [n] view
        self.result = None                 # QueryResult | ErrorReply
        self.done = False
        self.submitted_at = submitted_at \
            if submitted_at is not None else time.perf_counter()
        self.legacy: Optional[QueryRequest] = None   # write-back adapter
        self.conn = None                   # net server's delivery handle
        self.trace: Optional[TraceContext] = None    # admitting context

    @property
    def ok(self) -> bool:
        return self.done and isinstance(self.result, api.QueryResult)


@dataclasses.dataclass(frozen=True)
class QueryMetrics:
    partitions_touched: int    # distinct partitions the plan selected
    candidates_scanned: int    # records resident in those partitions
    latency_s: float           # wall time of the tick that served it
    batch_fill: float          # live fraction of that tick's batch


@dataclasses.dataclass
class EngineStats:
    """Aggregate over everything the engine has served."""

    queries: int = 0
    ticks: int = 0
    total_s: float = 0.0
    partitions_touched: float = 0.0          # running sums (means below)
    candidates_scanned: float = 0.0
    plan_cache_hits: int = 0                 # per-row signature-cache hits
    plan_cache_misses: int = 0

    def observe(self, batch_metrics: List[QueryMetrics]) -> None:
        self.ticks += 1
        for m in batch_metrics:
            self.queries += 1
            self.partitions_touched += m.partitions_touched
            self.candidates_scanned += m.candidates_scanned
        if batch_metrics:
            self.total_s += batch_metrics[0].latency_s

    @property
    def queries_per_sec(self) -> float:
        return self.queries / self.total_s if self.total_s else 0.0

    @property
    def mean_partitions_touched(self) -> float:
        return self.partitions_touched / self.queries if self.queries else 0.0

    @property
    def mean_candidates_scanned(self) -> float:
        return self.candidates_scanned / self.queries if self.queries else 0.0

    @property
    def plan_cache_hit_rate(self) -> float:
        n = self.plan_cache_hits + self.plan_cache_misses
        return self.plan_cache_hits / n if n else 0.0

    def snapshot(self) -> dict:
        """Counters + derived rates as one plain dict (for benchmark
        artifacts and operator output, mirroring ``FleetStats.snapshot``)."""
        d = dataclasses.asdict(self)
        d["queries_per_sec"] = self.queries_per_sec
        d["mean_partitions_touched"] = self.mean_partitions_touched
        d["mean_candidates_scanned"] = self.mean_candidates_scanned
        d["plan_cache_hit_rate"] = self.plan_cache_hit_rate
        return d


class BatchedServingLoop:
    """Fixed-shape batch admission shared by every serving executor.

    Subclasses implement :meth:`_execute`, which serves one zero-padded
    ``[batch_size, series_len]`` tick and returns host arrays
    ``(dist, gid, partitions_touched, candidates_scanned, seconds)``.
    """

    def __init__(self, *, series_len: int, batch_size: int, k: int):
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        self.series_len = series_len
        self.batch_size = batch_size
        self.k = k
        self.queue: List[QueryTicket] = []
        self.stats = EngineStats()
        # per-tenant in-flight admissions (the net server's quota hook);
        # finish/fail run on the executor thread, so counts take a lock
        self._tenant_lock = threading.Lock()
        self._tenant_inflight: Dict[str, int] = {}
        # registry wiring: per-instance label so concurrent loops (and
        # benchmark cells building fresh engines) keep distinct series
        self.obs_label = f"{type(self).__name__.lower()}{next(_LOOP_SEQ)}"
        self.latency_hist = REGISTRY.histogram("serve.latency_ms",
                                               loop=self.obs_label)
        self.queue_gauge = REGISTRY.gauge("serve.queue_depth",
                                          loop=self.obs_label)
        self.admit_wait_hist = REGISTRY.histogram("serve.admit_wait_ms",
                                                  loop=self.obs_label)
        install_compile_counter()
        # pull-based stats exposure: the collector holds only a weakref,
        # so EngineStats keeps its exact dataclass shape (snapshot() keys
        # are asserted by tier-1 tests) and dead loops unregister alone
        ref = weakref.ref(self)

        def _collect():
            loop = ref()
            if loop is None:
                return None
            s = loop.stats
            return {"serve.queries": s.queries, "serve.ticks": s.ticks,
                    "serve.queries_per_sec": s.queries_per_sec,
                    "serve.plan_cache_hit_rate": s.plan_cache_hit_rate}

        REGISTRY.add_collector(_collect, loop=self.obs_label)

    def reset_metrics(self) -> None:
        """Zero this loop's aggregate stats and its latency and admission-
        wait histograms (the benchmarks call it between warmup and the
        timed window)."""
        self.stats = EngineStats()
        self.latency_hist.reset()
        self.admit_wait_hist.reset()

    def capture_device_trace(self, log_dir):
        """Opt-in ``jax.profiler`` capture of everything this loop runs
        inside the block (see :func:`repro.obs.profile.device_trace`)."""
        from repro.obs import device_trace
        return device_trace(log_dir)

    def _execute(self, qbatch: np.ndarray, nlive: int):
        raise NotImplementedError

    @contextmanager
    def _tick_span(self, live: int, tickets=None, **attrs):
        """The ``serve.tick`` span of one executed batch.

        For queued ``tickets`` it observes each one's admission wait into
        ``serve.admit_wait_ms``: from ``QueryTicket.submitted_at`` (set
        when the request was admitted) to the start of this tick, with
        the largest as the span's ``wait_ms_max`` attr.  A client's
        ``RETRY_LATER`` back-off happens before admission and is not in
        it.  The ``lowered`` attr counts the programs JAX lowered during
        the tick (``jax.lowerings``): a tick that recompiled shows it.
        """
        counter = lowerings()
        lowered0 = counter.value
        with TRACER.span("serve.tick", loop=self.obs_label, live=live,
                         **attrs) as tick:
            if tickets is not None:
                waits = [(tick.start - t.submitted_at) * 1e3
                         for t in tickets]
                tick.attrs["wait_ms_max"] = max(waits, default=0.0)
                for w in waits:
                    self.admit_wait_hist.observe(w)
            try:
                yield tick
            finally:
                tick.attrs["lowered"] = counter.value - lowered0

    def _after_tick(self) -> None:
        """Hook run after each completed queue tick (between batches — off
        the per-query latency path).  Executors with background upkeep
        override it: the fleet engine runs its lifecycle maintenance here
        (compaction triggers, shard merge/retirement)."""

    # -- typed admission ---------------------------------------------------
    def validate_series(self, series, rid: int = 0) -> np.ndarray:
        """The admission contract: a ``[series_len]`` float32 row or a
        ValueError (so one bad series can't poison a whole batch)."""
        series = np.asarray(series, dtype=np.float32)
        if series.shape != (self.series_len,):
            raise ValueError(f"request {rid}: series shape "
                             f"{series.shape} != ({self.series_len},)")
        return series

    def validate_k(self, k: int, rid: int = 0) -> None:
        if k > self.k:
            raise ValueError(f"request {rid}: k={k} exceeds the "
                             f"engine's static answer size k={self.k}")

    def make_ticket(self, req: api.QueryRequest) -> QueryTicket:
        """Validate a frozen request into an in-flight ticket (counted
        against its tenant's quota) without enqueueing it — the net
        server's admission buffers own ticket placement themselves."""
        series = self.validate_series(req.series, req.request_id)
        self.validate_k(req.k, req.request_id)
        ticket = QueryTicket(req, series)
        # trace handoff: a wire-carried context wins (cross-process); an
        # in-process caller's open span is captured otherwise, so the
        # executor thread's tick can adopt the *admitting* context either
        # way — the tick span then joins the request's trace, not a fresh
        # executor-thread-rooted one
        if req.trace_id:
            ticket.trace = TraceContext(req.trace_id, req.parent_span_id)
        else:
            ticket.trace = TRACER.current_context()
        with self._tenant_lock:
            self._tenant_inflight[req.tenant] = \
                self._tenant_inflight.get(req.tenant, 0) + 1
        return ticket

    def tenant_inflight(self, tenant: str) -> int:
        """Admitted-but-unanswered requests of one tenant (quota hook)."""
        with self._tenant_lock:
            return self._tenant_inflight.get(tenant, 0)

    def _release_tenant(self, ticket: QueryTicket) -> None:
        with self._tenant_lock:
            t = ticket.request.tenant
            n = self._tenant_inflight.get(t, 0) - 1
            if n > 0:
                self._tenant_inflight[t] = n
            else:
                self._tenant_inflight.pop(t, None)

    # -- request-queue serving -------------------------------------------
    def submit_request(self, req: api.QueryRequest) -> QueryTicket:
        """Enqueue a frozen :class:`repro.serve.api.QueryRequest`; the
        returned ticket carries the :class:`repro.serve.api.QueryResult`
        once a tick serves it."""
        ticket = self.make_ticket(req)
        self.queue.append(ticket)
        self.queue_gauge.set(len(self.queue))
        return ticket

    def submit(self, req: QueryRequest) -> QueryTicket:
        """Legacy adapter: enqueue a *mutable* :class:`QueryRequest`.

        Deprecated (one-time warning): wraps the request into the typed
        path and writes ``dist`` / ``gid`` / ``metrics`` / ``done`` back
        into the caller's object when the tick completes, so existing
        call sites keep working unchanged.
        """
        global _LEGACY_SUBMIT_WARNED
        if not _LEGACY_SUBMIT_WARNED:
            _LEGACY_SUBMIT_WARNED = True
            warnings.warn(
                "submit() with the mutable repro.serve.QueryRequest is "
                "deprecated; use submit_request(repro.serve.api."
                "QueryRequest) and read the ticket's QueryResult",
                DeprecationWarning, stacklevel=2)
        series = self.validate_series(req.series, req.rid)
        self.validate_k(req.k, req.rid)
        req.series = series
        if req.submitted_at is None:
            req.submitted_at = time.perf_counter()
        ticket = QueryTicket(
            api.QueryRequest(series=series, k=req.k,
                             request_id=req.rid),
            series, submitted_at=req.submitted_at)
        ticket.legacy = req
        ticket.trace = TRACER.current_context()
        with self._tenant_lock:
            self._tenant_inflight[""] = self._tenant_inflight.get("", 0) + 1
        self.queue.append(ticket)
        self.queue_gauge.set(len(self.queue))
        return ticket

    def prepare_batch(self, tickets: List[QueryTicket]) -> np.ndarray:
        """Assemble validated tickets into the one fixed batch shape —
        featurize-ready, zero-padded — the executor jits against.  This
        is the host half of double buffering: the net server assembles
        batch N+1 here while the executor thread runs batch N."""
        if len(tickets) > self.batch_size:
            raise ValueError(f"{len(tickets)} tickets exceed "
                             f"batch_size={self.batch_size}")
        qbatch = np.zeros((self.batch_size, self.series_len),
                          dtype=np.float32)
        for i, t in enumerate(tickets):
            qbatch[i] = t.series
        return qbatch

    @staticmethod
    def _batch_context(tickets: List[QueryTicket]):
        """The trace context one tick adopts: the first admitted ticket's.

        A batch can mix requests from several traces; the tick span joins
        the first one (its ``traces`` attr counts the distinct ids so the
        others remain discoverable) — every ticket's own result still
        echoes its *own* trace id.
        """
        ids = {t.trace.trace_id for t in tickets if t.trace is not None}
        for t in tickets:
            if t.trace is not None:
                return t.trace, len(ids)
        return None, 0

    def execute_prepared(self, qbatch: np.ndarray,
                         tickets: List[QueryTicket]) -> int:
        """Run one pre-assembled tick and complete its tickets.

        The device half of double buffering: safe to call from a
        dedicated executor thread while the event loop keeps admitting
        into the next batch.  Raises whatever ``_execute`` raises — the
        caller decides whether to fail the tickets
        (:meth:`fail_tickets`) or retry.

        The tick span (and everything under it, including the
        maintenance hook and any compaction it triggers) adopts the
        admitting requests' trace context, so executor-thread spans stay
        in the request's trace instead of rooting their own.
        """
        ctx, ntraces = self._batch_context(tickets)
        with TRACER.adopt(ctx):
            with self._tick_span(len(tickets), tickets,
                                 traces=ntraces) as tick:
                dist, gid, touched, scanned, dt = \
                    self._execute(qbatch, len(tickets))
            self._finish_batch(tickets, dist, gid, touched, scanned, dt,
                               tick_span=tick)
            self._after_tick()
        return len(tickets)

    def fail_tickets(self, tickets: List[QueryTicket],
                     error: api.ErrorReply) -> None:
        """Resolve tickets with a typed refusal (executor fault paths)."""
        for t in tickets:
            t.result = dataclasses.replace(
                error, request_id=t.request.request_id)
            self._release_tenant(t)
            if t.legacy is not None:
                t.legacy.done = True
            t.done = True

    def _finish_batch(self, tickets: List[QueryTicket], dist, gid,
                      touched, scanned, dt: float,
                      tick_span=None) -> None:
        """Complete tickets from one executed tick: typed results, the
        legacy write-back adapter, latency histogram, aggregate stats.
        ``tick_span`` (the finished ``serve.tick``) stamps each result's
        trace echo so a remote client can link answer to server tick."""
        done_at = time.perf_counter()
        fill = len(tickets) / self.batch_size
        metrics = []
        for i, t in enumerate(tickets):
            req = t.request
            kq = req.k or self.k
            qm = QueryMetrics(partitions_touched=int(touched[i]),
                              candidates_scanned=int(scanned[i]),
                              latency_s=dt, batch_fill=fill)
            # arrival-to-answer: queue wait + every tick that ran first
            arrived = t.submitted_at if t.submitted_at is not None \
                else done_at - dt
            latency_ms = (done_at - arrived) * 1e3
            t.result = api.QueryResult(
                request_id=req.request_id,
                dist=dist[i, :kq], gid=gid[i, :kq],
                partitions_touched=qm.partitions_touched,
                candidates_scanned=qm.candidates_scanned,
                latency_ms=latency_ms, batch_fill=fill,
                trace_id=t.trace.trace_id if t.trace is not None else 0,
                parent_span_id=tick_span.span_id
                if tick_span is not None else 0)
            if t.legacy is not None:      # thin adapter: mutate in place
                t.legacy.dist, t.legacy.gid = dist[i, :kq], gid[i, :kq]
                t.legacy.metrics = qm
                t.legacy.done = True
            self._release_tenant(t)
            t.done = True
            metrics.append(qm)
            self.latency_hist.observe(latency_ms)
        self.stats.observe(metrics)

    def step(self) -> int:
        """Serve one batch from the queue; returns #requests completed."""
        if not self.queue:
            return 0
        live = self.queue[:min(self.batch_size, len(self.queue))]
        qbatch = self.prepare_batch(live)
        ctx, ntraces = self._batch_context(live)
        # pop only after the tick succeeds: a device error leaves the
        # queue intact instead of dropping in-flight requests
        with TRACER.adopt(ctx):
            with self._tick_span(len(live), live, traces=ntraces) as tick:
                dist, gid, touched, scanned, dt = \
                    self._execute(qbatch, len(live))
            del self.queue[:len(live)]
            self.queue_gauge.set(len(self.queue))
            self._finish_batch(live, dist, gid, touched, scanned, dt,
                               tick_span=tick)
            self._after_tick()
        return len(live)

    def run_until_drained(self, max_ticks: int = 10_000) -> None:
        for _ in range(max_ticks):
            if not self.step():
                return

    # -- direct batch API -------------------------------------------------
    def run(self, queries, k: int = 0
            ) -> Tuple[np.ndarray, np.ndarray, List[QueryMetrics]]:
        """Serve ``[Q, n]`` queries through fixed-shape ticks.

        Returns ``(dist [Q, k], gid [Q, k], metrics per query)``; results
        are bit-identical to per-query :func:`repro.core.knn_query` with
        the engine's variant/backend (planning and refine are
        row-independent, so batching and tail padding don't change them).
        """
        queries = np.asarray(queries, dtype=np.float32)
        kq = k or self.k
        if kq > self.k:
            raise ValueError(f"k={kq} exceeds the engine's static answer "
                             f"size k={self.k}; build the engine with a "
                             f"larger k")
        qn = queries.shape[0]
        if qn == 0:
            return (np.zeros((0, kq), np.float32),
                    np.full((0, kq), -1, np.int32), [])
        dists, gids, metrics = [], [], []
        for lo in range(0, qn, self.batch_size):
            chunk = queries[lo:lo + self.batch_size]
            pad = self.batch_size - chunk.shape[0]
            nlive = chunk.shape[0]
            if pad:
                chunk = np.concatenate(
                    [chunk, np.zeros((pad, chunk.shape[1]), np.float32)])
            with self._tick_span(nlive):    # no queue: no admission wait
                dist, gid, touched, scanned, dt = \
                    self._execute(chunk, nlive)
            for _ in range(nlive):           # direct API: no queue wait
                self.latency_hist.observe(dt * 1e3)
            dists.append(dist[:nlive, :kq])
            gids.append(gid[:nlive, :kq])
            batch_metrics = [
                QueryMetrics(partitions_touched=int(touched[i]),
                             candidates_scanned=int(scanned[i]),
                             latency_s=dt,
                             batch_fill=nlive / self.batch_size)
                for i in range(nlive)]
            metrics.extend(batch_metrics)
            self.stats.observe(batch_metrics)
        return np.concatenate(dists), np.concatenate(gids), metrics


class ClimberEngine(BatchedServingLoop):
    """Batched, sharded, kernel-first kNN serving loop.

    Args:
      index: a built ClimberIndex.  With ``mesh`` given, the store is laid
        out over the mesh's data axis at construction (ragged partition
        counts are padded), so every tick runs the shard_map refine.
      batch_size: rows per tick — the one static batch shape that jits.
      variant: registered planner name ("knn" | "adaptive" | "od_smallest" |
        "exhaustive" or anything added via ``register_planner``).
      k: default answer size (0 => ``cfg.k``).
      use_kernel: refine implementation — True the streaming fused Pallas
        kernel (masked distance + top-k in one pass), False the dense jnp
        oracle, None (default) the backend default: fused on accelerator
        backends, dense on CPU.
      max_slots: static slot budget for plan compaction (None => the
        lossless ``default_slot_budget`` unless ``cfg.query_max_slots``
        overrides it; stays None — i.e. no compaction — for
        user-registered variants with no knowable lossless bound).
      plan_cache_size: LRU capacity of the signature→plan cache (0 turns
        memoization off; the planning stage then runs every tick).

    All of the above may instead arrive bundled in one
    :class:`repro.serve.api.ServingConfig` via ``config=`` (exclusive
    with the individual kwargs) — the same object the fleet engine and
    the network server consume.  ``mesh`` / ``data_axis`` stay separate:
    they are runtime resources, not serializable configuration.

    The configuration (variant, k, backend, budget, store layout) is baked
    into the compiled pipeline at construction; mutating these attributes
    afterwards has no effect on the cached trace — build a new engine
    instead.
    """

    _CONFIG_KEYS = ("batch_size", "variant", "k", "use_kernel",
                    "max_slots", "plan_cache_size", "trace_ring")

    def __init__(self, index: ClimberIndex, *,
                 config: Optional[api.ServingConfig] = None,
                 mesh=None, data_axis: str = "data", **kwargs):
        cfg = api.resolve_config(config, kwargs, self._CONFIG_KEYS)
        self.config = cfg
        get_planner(cfg.variant)             # fail fast on unknown variants
        if cfg.trace_ring:                   # size the span ring for the
            TRACER.set_capacity(cfg.trace_ring)   # expected serving load
        super().__init__(series_len=index.cfg.series_len,
                         batch_size=cfg.batch_size, k=cfg.k or index.cfg.k)
        self.index = index
        self.variant = cfg.variant
        self.use_kernel = resolve_use_kernel(cfg.use_kernel)
        self.mesh = mesh
        self.data_axis = data_axis
        max_slots = cfg.max_slots
        if max_slots is None:
            max_slots = index.cfg.query_max_slots
        if max_slots is None:
            max_slots = default_slot_budget(index, cfg.variant)
        self.max_slots = max_slots

        self.store = index.store
        if mesh is not None and mesh.shape[data_axis] > 1:
            from repro.distributed.store import shard_store
            self.store = shard_store(index.store, mesh, data_axis=data_axis)

        self.plan_cache_size = cfg.plan_cache_size
        # signature bytes → (sel_part, sel_lo, sel_hi, touched, scanned) rows
        self._plan_cache = PlanCache(cfg.plan_cache_size)

        self._featurize = jax.jit(lambda q: self.index.featurize(q)[0])
        self._plan = jax.jit(self._plan_fn)
        self._refine = jax.jit(self._refine_fn)

    # -- the staged pipeline (featurize → plan → dispatch refine) ---------
    def _plan_fn(self, p4r: jnp.ndarray):
        qp = plan_queries(self.index, p4r, variant=self.variant,
                          max_slots=self.max_slots)
        return (qp.sel_part, qp.sel_lo, qp.sel_hi, qp.partitions_touched(),
                candidates_scanned(qp, self.store))

    def _refine_fn(self, queries, sel_part, sel_lo, sel_hi):
        return dispatch_refine(
            self.store, queries, sel_part, sel_lo, sel_hi, self.k,
            mesh=self.mesh, data_axis=self.data_axis,
            use_kernel=self.use_kernel)

    def _plan_batch(self, p4r: jnp.ndarray, nlive: int):
        """Plan a tick's batch through the signature LRU.

        All live rows cached → assemble the plan on the host and skip the
        planning jit; otherwise plan the whole fixed-shape batch (static
        shapes) and refresh every live row's entry.
        """
        if not self.plan_cache_size:
            return self._plan(p4r)
        cache = self._plan_cache
        p4_host = np.asarray(p4r)            # one transfer for all rows
        keys = [p4_host[i].tobytes() for i in range(nlive)]
        h0, m0 = cache.hits, cache.misses
        rows = [cache.get(kk) for kk in keys]
        self.stats.plan_cache_hits += cache.hits - h0
        self.stats.plan_cache_misses += cache.misses - m0
        if nlive and all(r is not None for r in rows):
            bs = self.batch_size
            mp = rows[0][0].shape[-1]
            sel_part = np.full((bs, mp), -1, np.int32)
            sel_lo = np.zeros((bs, mp), np.int32)
            sel_hi = np.zeros((bs, mp), np.int32)
            touched = np.zeros(bs, np.int32)
            scanned = np.zeros(bs, np.int32)
            for i, r in enumerate(rows):
                sel_part[i], sel_lo[i], sel_hi[i], touched[i], scanned[i] = r
            return (jnp.asarray(sel_part), jnp.asarray(sel_lo),
                    jnp.asarray(sel_hi), touched, scanned)
        out = self._plan(p4r)
        sp, lo, hi, touched, scanned = (np.asarray(x) for x in out)
        for i, kk in enumerate(keys):
            cache.put(kk, (sp[i], lo[i], hi[i], touched[i], scanned[i]))
        return out

    def _execute(self, qbatch: np.ndarray, nlive: int):
        """One fixed-shape tick.  Returns host arrays + wall seconds."""
        t0 = time.perf_counter()
        qb = jnp.asarray(qbatch)
        with TRACER.span("query.featurize"):
            p4r = self._featurize(qb)
        with TRACER.span("query.plan", variant=self.variant):
            sel_part, sel_lo, sel_hi, touched, scanned = \
                self._plan_batch(p4r, nlive)
        with TRACER.span("query.refine"):
            dist, gid = self._refine(qb, jnp.asarray(sel_part),
                                     jnp.asarray(sel_lo),
                                     jnp.asarray(sel_hi))
            jax.block_until_ready(gid)
        dt = time.perf_counter() - t0
        return (np.asarray(dist), np.asarray(gid), np.asarray(touched),
                np.asarray(scanned), dt)
