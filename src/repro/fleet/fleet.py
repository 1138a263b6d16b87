"""IndexFleet — sharded multi-index serving with streaming ingest.

The single two-level CLIMBER index is built once and queried forever; a
serving system needs many of them (per-tenant, per-time-range) plus a place
for data that keeps arriving.  The fleet owns:

  * **sealed shards** — immutable :class:`repro.core.ClimberIndex` instances
    keyed by tenant / time-range, each with a ``global_ids`` map from its
    local record ids to fleet-global ids;
  * a **router** (:class:`repro.fleet.router.SignatureRouter`) that fans a
    query out to a shard subset scored on signature-prefix affinity, with
    exhaustive fan-out as the lossless fallback;
  * a **delta shard** — an append-only index with per-partition capacity
    slack that absorbs ``insert()`` batches through the existing assignment
    path (featurize → group → trie → partition scatter) and is always
    queried, so new records are visible immediately;
  * ``compact()`` — seals the delta into an immutable shard by re-running
    the full CLIMBER-INX build (pivot selection, centroids, partitioning)
    over its contents, preserving global ids, so queries always see one
    consistent fleet view.

Cross-shard fusion goes through :func:`repro.core.merge_topk` with
global-id remapping; per-shard answers carry the :data:`repro.core.PAD_DIST`
sentinel for missing slots, which propagates through every merge.  With
exhaustive routing and the ``"exhaustive"`` planner variant the fleet answer
is bit-identical to a single-index ``knn_query`` over the concatenated data
(both are exact ED top-k computed by the same refine arithmetic).

Placement — where the sealed shards execute:

  * ``placement="host"`` — the lossless oracle: a host loop dispatches each
    sealed shard's ``knn_query`` sequentially and fuses on the host;
  * ``placement="mesh"`` — the sealed stores *and* trie skeletons live
    stacked on the device mesh
    (:class:`repro.fleet.placement.MeshFleetPlacement`) and one
    ``shard_map`` runs the whole query — featurize → trie descent → plan →
    refine → in-order ``merge_topk`` fold — as a single device program
    (planner variants without a registered device twin fall back to host
    planning + the refine-only fan-out).  Bit-identical to the host loop
    (the device planner reproduces the host plans entry-for-entry, same
    refine arithmetic, same merge order); the delta is always queried
    host-side and merged last on both paths.  Device plans are memoized
    per query in an LRU (:class:`repro.serve.knn_engine.PlanCache`) keyed
    on (placement epoch, planner variant, query bytes); the epoch
    increments whenever the sealed shard set or mesh changes
    (``add_shard`` / seal / merge / retire / ``attach_mesh``), so a hit
    can never replay a plan row from a retired layout.  The host loop
    memoizes through the same cache (per-shard rows keyed on
    (``"host"``, epoch, variant, shard slot, query bytes)), so
    ``FleetQueryInfo.plan_cache_hits`` / ``plan_cache_misses`` report on
    both placements identically.

Observability: every query opens a ``fleet.query`` span with
``fleet.plan`` / ``fleet.refine`` / ``fleet.merge`` children (per sealed
shard on the host loop, per device program on the mesh), ingest opens
``fleet.insert → wal.append / delta.scatter``, and the background
compactor opens ``compact.seal → compact.build / compact.swap`` on its
worker thread — see ``repro.obs`` and docs/OBSERVABILITY.md.  Call
latencies land in the ``fleet.query_latency_ms`` registry histogram
(labelled per fleet instance), which is where the benchmarks read their
p50/p99 columns.

``mesh=`` at construction (or :meth:`IndexFleet.attach_mesh`) enables the
mesh path and makes it the default; without a mesh the default stays
``"host"``.

Lifecycle plane (``repro.fleet.lifecycle``) — what makes the fleet survive
a restart and stay healthy over time:

  * **durability** — with a ``storage_dir`` attached, every ``insert``
    batch is appended to a binary write-ahead log *before* the delta
    scatter, and ``compact`` snapshots the sealed shard before truncating
    the WAL segments it came from.  :meth:`IndexFleet.save` /
    :meth:`IndexFleet.open` persist / restore the whole fleet; restart
    replays the WAL tail batch-for-batch (skipping frames a sealed shard
    already covers), so post-restart answers are bit-identical to the
    never-crashed fleet;
  * **background compaction** — ``compact()`` always runs the INX rebuild
    on a worker thread over a frozen delta; queries keep hitting the
    frozen delta until the sealed shard swaps in atomically.
    ``compact_async()`` returns the ticket instead of waiting
    (``FleetConfig.background_compaction`` makes auto-compaction
    non-blocking too);
  * **merge / retirement** — :meth:`IndexFleet.maintenance` applies an
    LSM-style :class:`repro.fleet.lifecycle.merge.MergePolicy`: small
    adjacent sealed shards are merged (rebuild over their concatenated
    records, global ids preserved) and shards past a time horizon are
    retired.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
import weakref
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.index import (ClimberIndex, PartitionStore,
                              _route_full_dataset_jit, build_index,
                              build_store)
from repro.core.query import (candidates_scanned, exhaustive_selection,
                              knn_query, plan)
from repro.core.refine import PAD_DIST, dispatch_refine, merge_topk, refine
from repro.distributed.store import concat_stores
from repro.fleet.router import SignatureRouter
from repro.obs import REGISTRY, TRACER
from repro.serve.knn_engine import PlanCache
from repro.utils.config import ClimberConfig

# distinguishes each fleet's metric series in the process registry
_FLEET_SEQ = itertools.count()


@dataclass(frozen=True)
class FleetConfig:
    """Fleet-level knobs on top of the per-shard :class:`ClimberConfig`."""

    shard_cfg: ClimberConfig
    fanout: int = 2                 # shards the router selects per query
    routing_threshold: float = 0.85  # score-mass cut for routing="adaptive"
                                     # (overridden by a learned
                                     # router.threshold or a per-call arg)
    delta_capacity: int = 4096      # records the delta holds before sealing
    delta_pad: Optional[int] = None  # physical slots per delta partition
                                     # (None => shard_cfg.capacity — full
                                     # capacity slack for in-place appends)
    auto_compact: bool = True       # seal automatically at delta_capacity
    background_compaction: bool = False  # auto-compaction returns before the
                                         # rebuild finishes (ticket-based)
    plan_cache_size: int = 256      # LRU capacity of the per-query plan
                                    # cache (host and mesh placement; 0 = off)
    seed: int = 0


@dataclass
class ShardHandle:
    """One immutable member of the fleet."""

    key: str                        # tenant / time-range label
    index: ClimberIndex
    global_ids: np.ndarray          # [n_shard] local row -> global record id
    sealed: bool = True
    created_at: float = 0.0         # wall-clock seal/registration time
                                    # (drives MergePolicy.retire_after)

    @property
    def num_records(self) -> int:
        return int(self.global_ids.shape[0])


@dataclass
class FleetStats:
    """Aggregate serving/ingest counters for the whole fleet."""

    queries: int = 0
    inserts: int = 0
    compactions: int = 0
    delta_rebuilds: int = 0
    delta_occupancy: int = 0
    routed_pairs: int = 0           # (query, shard) executions actually run
    exhaustive_pairs: int = 0       # what exhaustive fan-out would have run
    routing_audits: int = 0
    routing_overlap: float = 0.0    # running sum of audited precision
    compaction_ms: float = 0.0      # cumulative seal wall time (build+swap)
    wal_bytes: int = 0              # pending WAL bytes (frames not yet sealed)
    merges: int = 0                 # shard pairs merged by maintenance()
    retired_shards: int = 0         # shards aged out by maintenance()
    per_shard_queries: Dict[str, int] = field(default_factory=dict)
    per_shard_partitions: Dict[str, int] = field(default_factory=dict)

    def observe_shard(self, key: str, queries: int, partitions: int) -> None:
        self.per_shard_queries[key] = \
            self.per_shard_queries.get(key, 0) + queries
        self.per_shard_partitions[key] = \
            self.per_shard_partitions.get(key, 0) + partitions

    @property
    def routing_precision(self) -> float:
        """Mean audited recall of routed vs exhaustive fan-out (1.0 = no
        audit has seen the router drop a true neighbour)."""
        return self.routing_overlap / self.routing_audits \
            if self.routing_audits else 1.0

    @property
    def fanout_savings(self) -> float:
        """Fraction of per-shard executions the router skipped."""
        return 1.0 - self.routed_pairs / self.exhaustive_pairs \
            if self.exhaustive_pairs else 0.0

    def lifecycle_snapshot(self) -> dict:
        """Just the lifecycle counters (rides on ``FleetQueryInfo``)."""
        return {"compaction_ms": self.compaction_ms,
                "wal_bytes": self.wal_bytes,
                "merges": self.merges,
                "retired_shards": self.retired_shards}

    def snapshot(self) -> dict:
        d = dataclasses.asdict(self)
        d["routing_precision"] = self.routing_precision
        d["fanout_savings"] = self.fanout_savings
        return d


@dataclass
class FleetQueryInfo:
    """Per-query execution metrics of one fleet query call."""

    partitions_touched: np.ndarray   # [Q] summed over every shard executed
    candidates_scanned: np.ndarray   # [Q]
    routed_mask: np.ndarray          # [Q, S] sealed shards each query hit
    lifecycle: Optional[dict] = None  # FleetStats.lifecycle_snapshot() at
                                      # query time (compaction_ms, wal_bytes,
                                      # merges, retired_shards)
    stage_ms: Optional[dict] = None   # wall-ms per stage of this call:
                                      # plan_ms (host planning / plan-cache
                                      # work), refine_ms (sealed-shard
                                      # execution — on the fused mesh path
                                      # this is the whole device program,
                                      # planning included), merge_ms
                                      # (host-side merge folds + delta)
    plan_cache_hits: int = 0          # per-query plan-cache hits of this
    plan_cache_misses: int = 0        # call (host and mesh placement)


class DeltaShard:
    """Append-only ingest shard with capacity slack.

    Bootstrap: until ``num_pivots`` records exist a CLIMBER index cannot be
    built (pivot selection needs that many samples), so the delta serves
    queries from a single-partition store with an exact scan.  From the
    first rebuild on it is a real ClimberIndex whose partitions carry
    physical slot slack (``delta_pad``); inserts route through the existing
    assignment path and scatter into free slots in place.  A batch that
    overflows its target partition triggers a rebuild (re-running pivot
    selection and partitioning over the accumulated contents).
    """

    def __init__(self, cfg: ClimberConfig, *, pad: Optional[int] = None,
                 seed: int = 0):
        self.cfg = cfg.replace(
            partition_pad=pad if pad is not None else cfg.capacity)
        self._seed = seed
        self.data = np.zeros((0, cfg.series_len), np.float32)
        self.global_ids = np.zeros((0,), np.int32)
        self.index: Optional[ClimberIndex] = None
        self.rebuilds = 0
        self.min_build = cfg.num_pivots

    @property
    def occupancy(self) -> int:
        return int(self.data.shape[0])

    # -- ingest -----------------------------------------------------------
    def insert(self, batch: np.ndarray, gids: np.ndarray) -> None:
        base = self.occupancy
        self.data = np.concatenate([self.data, batch], axis=0)
        self.global_ids = np.concatenate(
            [self.global_ids, gids.astype(np.int32)])
        if self.index is None:
            if self.occupancy >= self.min_build:
                self._rebuild()
            return
        if not self._scatter(batch, base):
            self._rebuild()

    def _rebuild(self) -> None:
        key = jax.random.fold_in(jax.random.PRNGKey(self._seed),
                                 self.occupancy)
        self.index = build_index(key, jnp.asarray(self.data), self.cfg)
        self.rebuilds += 1

    def _scatter(self, batch: np.ndarray, base: int) -> bool:
        """Route a batch through the index's assignment path and append the
        records into free partition slots.  False = some partition is full
        (the caller rebuilds)."""
        idx = self.index
        part, rec_dfs = _route_full_dataset_jit(
            jnp.asarray(batch), idx.pivots, idx.centroid_onehot, idx.trie,
            idx.cfg)
        part = np.asarray(part)
        rec_dfs = np.asarray(rec_dfs)
        store = idx.store
        count = np.asarray(store.count).copy()

        order = np.argsort(part, kind="stable")
        ps = part[order]
        run_start = np.concatenate([[True], ps[1:] != ps[:-1]]) \
            if len(ps) else np.zeros(0, bool)
        first_pos = np.nonzero(run_start)[0]
        run_id = np.cumsum(run_start) - 1
        within = np.arange(len(ps)) - first_pos[run_id]
        slots = count[ps] + within
        if len(slots) and slots.max() >= store.capacity:
            return False

        rows = batch[order].astype(np.float32)
        data_np = np.asarray(store.data).copy()
        norms_np = np.asarray(store.norms).copy()
        dfs_np = np.asarray(store.rec_dfs).copy()
        gid_np = np.asarray(store.rec_gid).copy()
        data_np[ps, slots] = rows
        # same arithmetic as build_store so a later rebuild is bit-identical
        norms_np[ps, slots] = \
            np.sum(rows.astype(np.float64) ** 2, axis=-1).astype(np.float32)
        dfs_np[ps, slots] = rec_dfs[order]
        gid_np[ps, slots] = (base + order).astype(np.int32)
        np.add.at(count, ps, 1)
        new_store = PartitionStore(
            data=jnp.asarray(data_np), norms=jnp.asarray(norms_np),
            rec_dfs=jnp.asarray(dfs_np), rec_gid=jnp.asarray(gid_np),
            count=jnp.asarray(count))
        self.index = dataclasses.replace(idx, store=new_store)
        return True

    def take(self) -> Tuple[np.ndarray, np.ndarray]:
        """Hand the accumulated contents to compaction and reset."""
        out = (self.data, self.global_ids)
        self.data = np.zeros((0, self.cfg.series_len), np.float32)
        self.global_ids = np.zeros((0,), np.int32)
        self.index = None
        return out

    # -- query ------------------------------------------------------------
    def _bootstrap_store(self) -> PartitionStore:
        return build_store(jnp.asarray(self.data),
                           np.zeros(self.occupancy, np.int32),
                           np.zeros(self.occupancy, np.int32), 1)

    def store(self) -> Optional[PartitionStore]:
        if not self.occupancy:
            return None
        return self.index.store if self.index is not None \
            else self._bootstrap_store()

    def query(self, queries: np.ndarray, k: int, *, variant: str,
              use_kernel: Optional[bool] = None):
        """(dist, gid_local, touched, scanned) or None when empty."""
        if not self.occupancy:
            return None
        q = len(queries)
        if self.index is None:
            store = self._bootstrap_store()
            sel = jnp.zeros((q, 1), jnp.int32)
            dist, gid = refine(store, jnp.asarray(queries), sel, sel,
                               sel + 1, k, use_kernel=use_kernel)
            return (np.asarray(dist), np.asarray(gid),
                    np.ones(q, np.int64),
                    np.full(q, self.occupancy, np.int64))
        dist, gid, qp = knn_query(self.index, jnp.asarray(queries), k,
                                  variant=variant, use_kernel=use_kernel)
        return (np.asarray(dist), np.asarray(gid),
                np.asarray(qp.partitions_touched(), np.int64),
                np.asarray(candidates_scanned(qp, self.index.store),
                           np.int64))


@dataclass
class FrozenDelta:
    """A delta frozen for sealing: contents + the WAL segments backing it.

    Built by :meth:`IndexFleet._freeze` under the fleet lock; the build
    runs over ``data``/``global_ids`` off the lock while queries keep
    hitting the frozen :class:`DeltaShard` (still registered as
    ``fleet._sealing``).
    """

    delta: DeltaShard
    frames: List[Tuple[np.ndarray, np.ndarray]]   # (gids, batch) in order
    segs: List[int]                               # WAL segments to drop
    fold: int                                     # build-key fold (shard
                                                  # count at freeze + 17)
    key: str                                      # sealed shard key

    @property
    def data(self) -> np.ndarray:
        return self.delta.data

    @property
    def global_ids(self) -> np.ndarray:
        return self.delta.global_ids


def _frame_nbytes(gids: np.ndarray, batch: np.ndarray) -> int:
    from repro.fleet.lifecycle.wal import _HEADER
    return _HEADER.size + gids.size * 4 + batch.size * 4


class IndexFleet:
    """Several CLIMBER shards + streaming delta behind one query surface."""

    DELTA_KEY = "__delta__"

    def __init__(self, cfg: FleetConfig, *, mesh=None,
                 data_axis: str = "data",
                 storage_dir: Optional[str] = None):
        self.cfg = cfg
        self.shards: List[ShardHandle] = []
        self.router: Optional[SignatureRouter] = None
        self.delta = DeltaShard(cfg.shard_cfg, pad=cfg.delta_pad,
                                seed=cfg.seed + 1)
        self.stats = FleetStats()
        self._next_gid = 0
        self._seal_count = 0
        self._merge_count = 0
        self.mesh = mesh
        self.data_axis = data_axis
        self._placement = None          # lazily built MeshFleetPlacement
        self._placement_epoch = 0       # bumps with every sealed-set change
        self._plan_cache = PlanCache(cfg.plan_cache_size)
        self.merge_policy = None        # default MergePolicy for maintenance
        # -- lifecycle state (repro.fleet.lifecycle) ----------------------
        self._lock = threading.RLock()
        self.wal = None                 # WriteAheadLog when storage attached
        self.storage_dir: Optional[Path] = None
        self._shard_dirs: Dict[str, str] = {}   # shard key -> snapshot slug
        self._frames: List[Tuple[np.ndarray, np.ndarray]] = []  # active delta
        self._delta_segs: List[int] = []        # WAL segments backing it
        self._sealing: Optional[DeltaShard] = None   # frozen mid-compaction
        self._sealing_frames: List[Tuple[np.ndarray, np.ndarray]] = []
        self._sealing_segs: List[int] = []
        self._seal_ticket = None        # in-flight CompactionTicket
        # -- observability (repro.obs) ------------------------------------
        # per-instance label: benchmark cells build fresh fleets and must
        # not share latency series; FleetStats keeps its exact dataclass
        # shape (snapshot() keys are tier-1-tested), so derived rates are
        # exposed through a weakref collector instead of new fields
        self.obs_label = f"fleet{next(_FLEET_SEQ)}"
        self.query_hist = REGISTRY.histogram("fleet.query_latency_ms",
                                             fleet=self.obs_label)
        self.compaction_hist = REGISTRY.histogram("fleet.compaction_ms",
                                                  fleet=self.obs_label)
        # per-query partitions-touched distribution: the live signal the
        # recall-targeted planner calibrates against (repro.eval.target)
        self.touched_hist = REGISTRY.histogram("fleet.partitions_touched",
                                               fleet=self.obs_label)
        # (scores, true-hit counts) pairs recorded by audit_routing(...,
        # record=True); SignatureRouter.learn_threshold consumes them
        self.routing_traces: List[Tuple[np.ndarray, np.ndarray]] = []
        # online recall sentinel (repro.obs.sentinel.RecallSentinel
        # installs itself here); query() hands it each answered batch to
        # shadow-sample — a pure observer, never on the answer path
        self.sentinel = None
        ref = weakref.ref(self)

        def _collect():
            fleet = ref()
            if fleet is None:
                return None
            s = fleet.stats
            return {"fleet.queries": s.queries,
                    "fleet.inserts": s.inserts,
                    "fleet.compactions": s.compactions,
                    "fleet.delta_occupancy": s.delta_occupancy,
                    "fleet.wal_bytes": s.wal_bytes,
                    "fleet.routing_precision": s.routing_precision,
                    "fleet.fanout_savings": s.fanout_savings,
                    "fleet.shards": len(fleet.shards)}

        REGISTRY.add_collector(_collect, fleet=self.obs_label)
        if storage_dir is not None:
            self.attach_storage(storage_dir)

    def reset_metrics(self) -> None:
        """Zero the aggregate stats and this fleet's latency histograms
        (benchmarks call it between warmup and the timed window)."""
        with self._lock:
            self.stats = FleetStats()
            self._refresh_gauges()
        self.query_hist.reset()
        self.compaction_hist.reset()
        self.touched_hist.reset()

    # -- mesh placement ---------------------------------------------------
    def attach_mesh(self, mesh, *, data_axis: str = "data") -> None:
        """Enable mesh-resident execution (and make it the default).

        The sealed stores are stacked and laid out over ``mesh``'s
        ``data_axis`` lazily, on the next ``placement="mesh"`` query, and
        re-laid out whenever the sealed shard set changes.
        """
        with self._lock:
            self.mesh = mesh
            self.data_axis = data_axis
            self._invalidate_placement()

    def _invalidate_placement(self) -> None:
        """Drop the lazy mesh layout and advance the placement epoch.

        Called (under the fleet lock) whenever the sealed shard set or the
        mesh changes — ``add_shard``, seal, lifecycle merge/retire,
        ``attach_mesh``.  The epoch bump also orphans every device-plan
        cache entry keyed on the old layout: plan rows are ``[S_pad, ...]``
        stacks in shard-slot order, so replaying one across a layout change
        would refine against the wrong shards.
        """
        self._placement = None
        self._placement_epoch += 1

    def _resolve_placement(self, placement: Optional[str]) -> str:
        """``None`` → ``"mesh"`` when a mesh is attached, else ``"host"``."""
        if placement is None:
            return "mesh" if self.mesh is not None else "host"
        if placement not in ("host", "mesh"):
            raise ValueError(f"unknown placement {placement!r}; "
                             f"expected 'host' or 'mesh'")
        if placement == "mesh" and self.mesh is None:
            raise ValueError("placement='mesh' needs a mesh: pass mesh= at "
                             "construction or call attach_mesh()")
        return placement

    def _ensure_placement(self):
        from repro.fleet.placement import MeshFleetPlacement
        if self._placement is None:
            self._placement = MeshFleetPlacement(
                self.mesh, self.shards, data_axis=self.data_axis)
        return self._placement

    # -- durable storage --------------------------------------------------
    def attach_storage(self, storage_dir) -> None:
        """Make the fleet durable under ``storage_dir``.

        Opens (or creates) the write-ahead log — subsequent ``insert``
        batches are appended there before the delta scatter — and flushes
        any batches buffered in memory before attachment.  Restoring an
        existing fleet directory goes through :meth:`open` instead; this
        method refuses a WAL that already holds frames (it cannot know
        whether they are in the delta).
        """
        from repro.fleet.lifecycle.snapshot import save_fleet
        from repro.fleet.lifecycle.wal import WriteAheadLog
        with self._lock:
            storage_dir = Path(storage_dir)
            if self.storage_dir is not None:
                if storage_dir != self.storage_dir:
                    raise ValueError(
                        f"fleet already attached to {self.storage_dir}; "
                        f"cannot re-attach to {storage_dir}")
                return
            wal = WriteAheadLog(storage_dir / "wal")
            if wal.replay():
                wal.close()
                raise ValueError(
                    f"{storage_dir} already holds WAL frames; use "
                    f"IndexFleet.open() to restore it")
            self.storage_dir = storage_dir
            self.wal = wal
            # flush memory-buffered batches: the frozen delta's frames get
            # their own (immediately rolled) segment so the segment ↔ delta
            # correspondence holds for the in-flight seal's truncation
            if self._sealing_frames:
                for g, b in self._sealing_frames:
                    self.wal.append(g, b)
                self._sealing_segs = [self.wal.roll()]
            for g, b in self._frames:
                self.wal.append(g, b)
            self._delta_segs = [self.wal.active_segment]
            save_fleet(self, storage_dir)

    def save(self, storage_dir=None) -> Path:
        """Persist the fleet: sealed-shard snapshots + manifest (+ WAL).

        ``storage_dir`` defaults to the attached storage directory; a
        fleet without one is attached first (from then on every insert is
        WAL-durable there).  Returns the directory.  Restore with
        :meth:`open`.
        """
        from repro.fleet.lifecycle.snapshot import save_fleet
        with self._lock:
            if storage_dir is None:
                if self.storage_dir is None:
                    raise ValueError("no storage attached: pass a directory")
                storage_dir = self.storage_dir
            self.attach_storage(storage_dir)
            return save_fleet(self, Path(storage_dir))

    @classmethod
    def open(cls, storage_dir, *, mesh=None,
             data_axis: str = "data") -> "IndexFleet":
        """Restore a fleet saved under ``storage_dir``.

        Sealed shards load from their snapshots (bit-exact arrays), the
        router restores verbatim, and the WAL tail replays batch-for-batch
        into a fresh delta — skipping frames whose global ids a sealed
        shard already covers (the crash window between compact swap and
        WAL truncate).  Replay reproduces the exact insert sequence, so
        the restored delta's rebuild history — and therefore every query
        answer, routed or exhaustive — is bit-identical to the
        never-crashed fleet (``tests/test_fleet_lifecycle.py``).
        """
        from repro.fleet.lifecycle.snapshot import (load_router, load_shard,
                                                    read_manifest)
        from repro.fleet.lifecycle.wal import WriteAheadLog
        storage_dir = Path(storage_dir)
        _recover_wal_rebase(storage_dir)
        manifest = read_manifest(storage_dir)
        shard_cfg = ClimberConfig(**manifest["shard_cfg"])
        cfg = FleetConfig(shard_cfg=shard_cfg, **manifest["fleet"])
        fleet = cls(cfg, mesh=mesh, data_axis=data_axis)
        fleet._seal_count = int(manifest["seal_count"])
        fleet._merge_count = int(manifest["merge_count"])
        for entry in manifest["shards"]:
            handle = load_shard(storage_dir / "shards" / entry["dir"])
            fleet.shards.append(handle)
            fleet._shard_dirs[handle.key] = entry["dir"]
        fleet.router = load_router(storage_dir, manifest, shard_cfg)
        fleet._next_gid = int(manifest["next_gid"])

        # replay the WAL tail in memory-frame mode (storage attaches after,
        # via an atomic rebase, so a replay-time auto-compaction can never
        # drop segments that still hold un-replayed frames)
        wal_dir = storage_dir / "wal"
        frames = []
        if wal_dir.exists():
            wal = WriteAheadLog(wal_dir)
            frames = wal.replay()
            wal.close()
        sealed = np.sort(np.concatenate(
            [s.global_ids for s in fleet.shards])) \
            if fleet.shards else np.zeros(0, np.int32)
        for _seg, gids, batch in frames:
            if len(sealed) and bool(np.isin(gids, sealed).all()):
                continue            # sealed before the crash; already durable
            with fleet._lock:
                fleet._log_frame(gids, batch)
                fleet._ingest(batch, gids)
                fleet._next_gid = max(fleet._next_gid, int(gids.max()) + 1) \
                    if len(gids) else fleet._next_gid
            fleet._maybe_auto_compact()
        fleet._attach_storage_rebased(storage_dir)
        return fleet

    def _attach_storage_rebased(self, storage_dir: Path) -> None:
        """Adopt ``storage_dir`` after a replay: atomically rewrite the WAL
        so it holds exactly the frames still pending in the delta.

        Ordering matters: shards sealed *during* the replay (an
        auto-compaction re-run) exist only in memory until ``save_fleet``
        snapshots them, so the manifest is made durable **before** the old
        WAL — whose frames are their only other copy — is rewritten.  A
        crash before the swap then replays the old WAL against the updated
        manifest (sealed frames skip by gid); a crash during the swap is
        finished by :func:`_recover_wal_rebase`.
        """
        import shutil

        from repro.fleet.lifecycle.snapshot import save_fleet
        from repro.fleet.lifecycle.wal import WriteAheadLog
        with self._lock:
            self.storage_dir = storage_dir
            save_fleet(self, storage_dir)       # replay-sealed shards first
            wal_dir = storage_dir / "wal"
            rebase = storage_dir / "wal.rebase"
            if rebase.exists():
                shutil.rmtree(rebase)
            wal = WriteAheadLog(rebase)
            for g, b in self._frames:
                wal.append(g, b)
            wal.close()
            old = storage_dir / "wal.old"
            if old.exists():
                shutil.rmtree(old)
            if wal_dir.exists():
                wal_dir.rename(old)
            rebase.rename(wal_dir)              # atomic publish
            if old.exists():
                shutil.rmtree(old)
            self.wal = WriteAheadLog(wal_dir)
            self._delta_segs = [self.wal.active_segment]
            self._refresh_gauges()

    # -- membership -------------------------------------------------------
    @property
    def total_records(self) -> int:
        with self._lock:
            sealed = sum(s.num_records for s in self.shards)
            frozen = self._sealing.occupancy if self._sealing else 0
            return sealed + frozen + self.delta.occupancy

    def _ensure_router(self, sample: np.ndarray) -> None:
        """Build the reference pivots once enough rows exist.

        Pivot selection needs ``num_pivots`` distinct samples; until then
        the router stays None and queries fall back to exhaustive fan-out
        (there is at most a bootstrap delta to scan anyway).
        """
        if self.router is None and \
                len(sample) >= self.cfg.shard_cfg.num_pivots:
            self.router = SignatureRouter.from_sample(
                jax.random.PRNGKey(self.cfg.seed),
                sample[: max(4 * self.cfg.shard_cfg.num_pivots, 256)],
                self.cfg.shard_cfg)

    def _build_shard_index(self, data: np.ndarray, fold: int,
                           device=None) -> ClimberIndex:
        """Deterministic INX build for a fleet member (no lock needed).

        The build runs on JAX's default device, so every shard of one shape
        reuses its compiled programs (a TPU compiles per device); the
        finished store then moves to ``device`` when one is given (the
        small skeleton arrays stay uncommitted and follow it into jits).
        """
        build_key = jax.random.fold_in(
            jax.random.PRNGKey(self.cfg.seed), fold)
        index = build_index(build_key, jnp.asarray(data), self.cfg.shard_cfg)
        if device is None:
            return index
        return dataclasses.replace(index,
                                   store=jax.device_put(index.store, device))

    def add_shard(self, key: str, data: np.ndarray,
                  global_ids: Optional[np.ndarray] = None, *,
                  device=None) -> ShardHandle:
        """Build and register an immutable shard over ``data``.

        ``global_ids`` defaults to the next contiguous fleet-global range.
        ``device`` (default: JAX's default device) is where the shard's
        index is kept — a fleet larger than one chip gives each shard the
        chip its mesh slot lives on, so no chip holds the others' stores.
        """
        data = np.asarray(data, dtype=np.float32)
        with self._lock:
            if any(s.key == key for s in self.shards):
                raise ValueError(f"duplicate shard key {key!r}")
            if global_ids is None:
                global_ids = np.arange(
                    self._next_gid, self._next_gid + len(data),
                    dtype=np.int32)
            global_ids = np.asarray(global_ids, dtype=np.int32)
            if len(global_ids):
                self._next_gid = max(self._next_gid,
                                     int(global_ids.max()) + 1)
            fold = len(self.shards) + 17
        index = self._build_shard_index(data, fold, device)
        handle = ShardHandle(key=key, index=index, global_ids=global_ids,
                             created_at=time.time())
        with self._lock:
            self._ensure_router(data)
            self.shards.append(handle)
            self.router.register(key, self.router.summarize(data))
            self._invalidate_placement()    # sealed set changed: re-lay out
            self._persist_shard(handle)
        return handle

    def _persist_shard(self, handle: ShardHandle) -> None:
        """Snapshot one sealed shard + rewrite the manifest (lock held)."""
        if self.storage_dir is None:
            return
        from repro.fleet.lifecycle.snapshot import (save_shard, shard_slug,
                                                    write_manifest)
        slug = shard_slug(handle.key, set(self._shard_dirs.values()))
        save_shard(self.storage_dir / "shards" / slug, handle)
        self._shard_dirs[handle.key] = slug
        write_manifest(self, self.storage_dir)

    # -- streaming ingest -------------------------------------------------
    def _log_frame(self, gids: np.ndarray, batch: np.ndarray) -> None:
        """Record one insert batch: WAL append (the durability point —
        strictly before the delta scatter) + the in-memory frame list."""
        with TRACER.span("wal.append", rows=len(gids),
                         durable=self.wal is not None):
            if self.wal is not None:
                self.wal.append(gids, batch)
            self._frames.append((gids, batch))

    def _ingest(self, batch: np.ndarray, gids: np.ndarray) -> None:
        """Apply one logged batch to the delta (lock held; no WAL write —
        shared by live inserts and WAL replay)."""
        with TRACER.span("delta.scatter", rows=len(batch)):
            before = self.delta.rebuilds
            self.delta.insert(batch, gids)
            # accumulated delta contents, not just this batch: small first
            # batches must not stop the router from ever being built
            self._ensure_router(self.delta.data)
            self.stats.delta_rebuilds += self.delta.rebuilds - before
        self.stats.inserts += len(batch)
        self._refresh_gauges()

    def _refresh_gauges(self) -> None:
        frozen = self._sealing.occupancy if self._sealing else 0
        self.stats.delta_occupancy = self.delta.occupancy + frozen
        self.stats.wal_bytes = sum(
            _frame_nbytes(g, b)
            for g, b in self._frames + self._sealing_frames)

    def _maybe_auto_compact(self) -> None:
        """Seal when the delta crosses capacity (called off the lock so a
        synchronous compact can join an in-flight background ticket)."""
        if not self.cfg.auto_compact:
            return
        with self._lock:
            due = self.delta.occupancy >= max(self.cfg.delta_capacity,
                                              self.delta.min_build)
        if not due:
            return
        if self.cfg.background_compaction:
            self.compact_async()
        else:
            self.compact()

    def insert(self, batch: np.ndarray) -> np.ndarray:
        """Append a ``[B, series_len]`` batch into the streaming delta.

        Returns the assigned fleet-global record ids (``[B] int32``,
        contiguous from the current high-water mark) — the ids later
        queries report in their ``gid`` output.  With storage attached the
        batch is appended to the write-ahead log *before* the delta
        scatter, so an acknowledged insert survives a crash (replayed by
        :meth:`open`).  Records are immediately visible to queries on
        every placement (the delta is always executed host-side).  When
        the delta reaches ``delta_capacity`` and ``auto_compact`` is on,
        it is sealed into an immutable shard (see :meth:`compact`; with
        ``background_compaction`` the seal happens off-thread and insert
        returns immediately).

        Raises ValueError when the batch is not ``[B, series_len]``.
        """
        batch = np.asarray(batch, dtype=np.float32)
        if batch.ndim != 2 or batch.shape[1] != self.cfg.shard_cfg.series_len:
            raise ValueError(f"insert batch shape {batch.shape} != "
                             f"[B, {self.cfg.shard_cfg.series_len}]")
        with TRACER.span("fleet.insert", rows=len(batch)):
            with self._lock:
                gids = np.arange(self._next_gid, self._next_gid + len(batch),
                                 dtype=np.int32)
                self._next_gid += len(batch)
                self._log_frame(gids, batch)
                self._ingest(batch, gids)
            self._maybe_auto_compact()
        return gids

    # -- compaction (freeze → build off-lock → swap) ----------------------
    def _next_seal_key(self) -> str:
        self._seal_count += 1
        while any(s.key == f"sealed:{self._seal_count}"
                  for s in self.shards):
            self._seal_count += 1
        return f"sealed:{self._seal_count}"

    def _freeze(self) -> Optional[FrozenDelta]:
        """Freeze the delta for sealing (lock held by the caller).

        The frozen delta stays registered (queries keep hitting it); a
        fresh delta takes over ingest, and the WAL rolls so the frozen
        segments correspond exactly to the frozen contents.  Returns None
        when the delta is empty; raises when it cannot build an index yet.
        """
        if self._sealing is not None:
            raise RuntimeError("a compaction is already in flight")
        if not self.delta.occupancy:
            return None
        if self.delta.occupancy < self.delta.min_build:
            raise ValueError(
                f"cannot compact {self.delta.occupancy} records: pivot "
                f"selection needs >= {self.delta.min_build}; keep inserting "
                f"or lower shard_cfg.num_pivots")
        frozen = FrozenDelta(delta=self.delta, frames=self._frames,
                             segs=list(self._delta_segs),
                             fold=len(self.shards) + 17,
                             key=self._next_seal_key())
        self._sealing = self.delta
        self._sealing_frames = self._frames
        self._sealing_segs = frozen.segs
        self.delta = DeltaShard(self.cfg.shard_cfg, pad=self.cfg.delta_pad,
                                seed=self.cfg.seed + 1)
        self._frames = []
        if self.wal is not None:
            self.wal.roll()
            self._delta_segs = [self.wal.active_segment]
        else:
            self._delta_segs = []
        self._refresh_gauges()
        return frozen

    def _finish_seal(self, frozen: FrozenDelta,
                     handle: ShardHandle) -> None:
        """Swap the sealed shard in atomically, then reclaim WAL space.

        Snapshot (when storage is attached) happens before the swap; the
        frozen segments are dropped only after the manifest lists the new
        shard, so every kill point leaves a replayable log: frames whose
        gids a sealed shard covers are skipped at replay.
        """
        from repro.fleet.lifecycle.snapshot import save_shard, shard_slug
        with self._lock:
            storage = self.storage_dir
            slug = shard_slug(handle.key, set(self._shard_dirs.values())) \
                if storage is not None else None
        if storage is not None:             # the slow write, off the lock
            save_shard(storage / "shards" / slug, handle)
        with self._lock:
            if storage is None and self.storage_dir is not None:
                # attach_storage() raced the build: it already flushed the
                # frozen frames into a rolled segment, so the snapshot must
                # exist before those segments are dropped below
                storage = self.storage_dir
                slug = shard_slug(handle.key, set(self._shard_dirs.values()))
                save_shard(storage / "shards" / slug, handle)
            self.shards.append(handle)
            self._ensure_router(frozen.data)
            self.router.register(handle.key,
                                 self.router.summarize(frozen.data))
            self._invalidate_placement()
            if storage is not None:
                from repro.fleet.lifecycle.snapshot import write_manifest
                self._shard_dirs[handle.key] = slug
                write_manifest(self, storage)
            self._sealing = None
            self._sealing_frames = []
            segs, self._sealing_segs = self._sealing_segs, []
            self.stats.compactions += 1
            self._refresh_gauges()
        if self.wal is not None and segs:
            self.wal.drop(segs)

    def _abort_seal(self, frozen: FrozenDelta) -> None:
        """Undo a failed seal: fold the frozen contents back into one live
        delta (replaying the logged frames in order) so no buffered insert
        is lost and a later compact retries over everything."""
        with self._lock:
            frames = self._sealing_frames + self._frames
            restored = DeltaShard(self.cfg.shard_cfg, pad=self.cfg.delta_pad,
                                  seed=self.cfg.seed + 1)
            for g, b in frames:
                restored.insert(b, g)
            self.delta = restored
            self._frames = frames
            self._delta_segs = self._sealing_segs + self._delta_segs
            self._sealing = None
            self._sealing_frames = []
            self._sealing_segs = []
            self._refresh_gauges()

    def compact(self) -> Optional[ShardHandle]:
        """Seal the delta into an immutable shard (full INX rebuild).

        The rebuild always runs on a worker thread over a frozen delta —
        queries keep hitting the frozen contents until the sealed shard
        swaps in atomically — and this method waits for it, so the
        synchronous contract is unchanged: global ids are preserved and
        answers on the same contents are bit-identical (tested).  A failed
        build folds the frozen contents back into the live delta, so every
        buffered insert stays queryable.  With storage attached, the
        sealed shard is snapshotted and the manifest rewritten *before*
        the WAL segments are truncated.  Use :meth:`compact_async` for the
        non-blocking ticket.

        Returns the new ShardHandle, or None when the delta is empty;
        raises ValueError when the delta holds fewer than ``num_pivots``
        records (pivot selection needs that many samples).
        """
        ticket = self._seal_ticket
        if ticket is not None:
            ticket.wait()
        ticket = self.compact_async()
        return ticket.wait() if ticket is not None else None

    def compact_async(self):
        """Trigger a background seal; returns a
        :class:`repro.fleet.lifecycle.compactor.CompactionTicket` (or None
        when the delta is empty, or the in-flight ticket when one is
        already running).  Raises like :meth:`compact` when the delta is
        too small to build."""
        from repro.fleet.lifecycle.compactor import \
            start_background_compaction
        return start_background_compaction(self)

    # -- maintenance (LSM merge + retirement) -----------------------------
    def maintenance(self, policy=None, *, now: Optional[float] = None) -> dict:
        """One lifecycle tick: retire aged shards, merge small neighbours.

        ``policy`` defaults to ``self.merge_policy`` (or the
        :class:`repro.fleet.lifecycle.merge.MergePolicy` defaults).  Exact
        answers over the surviving records are unchanged by merging —
        global ids are preserved and the merged shard is rebuilt over the
        concatenated records.  Returns a report dict (``merged``,
        ``retired`` key lists).
        """
        from repro.fleet.lifecycle.merge import run_maintenance
        return run_maintenance(self, policy=policy, now=now)

    # -- query ------------------------------------------------------------
    def _query_sealed_host(self, shards, queries: np.ndarray, k: int,
                           mask: np.ndarray, variant: str,
                           use_kernel: Optional[bool],
                           best_d: np.ndarray, best_g: np.ndarray,
                           touched: np.ndarray, scanned: np.ndarray,
                           stage: dict, epoch: int) -> None:
        """The host-loop oracle: one featurize→plan→refine dispatch per
        sealed shard (the arithmetic of ``knn_query``, staged under
        ``fleet.plan`` / ``fleet.refine`` / ``fleet.merge`` spans so the
        per-stage timers see plan vs refine vs merge), fused on the host
        in shard order (accumulators in place).

        Planning memoizes per (shard, query) through the fleet's
        :class:`PlanCache` under ``("host", epoch, variant, shard slot,
        query bytes)`` — disjoint from the mesh path's 3-tuple keys, and
        epoch-invalidated the same way.  A shard whose routed rows all hit
        assembles the plan on the host and skips its featurize+plan jits;
        cached rows are exactly a prior plan's output, so caching never
        changes results."""
        cache = self._plan_cache if self.cfg.plan_cache_size else None
        for si, shard in enumerate(shards):
            qsel = np.nonzero(mask[:, si])[0]
            if not len(qsel):
                continue
            qj = jnp.asarray(queries[qsel])
            with TRACER.span("fleet.plan", shard=shard.key) as sp_plan:
                keys = rows = None
                if cache is not None:
                    keys = [("host", epoch, variant, si,
                             queries[i].tobytes()) for i in qsel]
                    rows = [cache.get(kk) for kk in keys]
                if rows is not None and all(r is not None for r in rows):
                    nq, mp = len(qsel), rows[0][0].shape[-1]
                    sel_part = np.empty((nq, mp), np.int32)
                    sel_lo = np.empty((nq, mp), np.int32)
                    sel_hi = np.empty((nq, mp), np.int32)
                    pt = np.empty(nq, np.int64)
                    sc = np.empty(nq, np.int64)
                    for i, r in enumerate(rows):
                        sel_part[i], sel_lo[i], sel_hi[i], pt[i], sc[i] = r
                    sel_part, sel_lo, sel_hi = (jnp.asarray(sel_part),
                                                jnp.asarray(sel_lo),
                                                jnp.asarray(sel_hi))
                else:
                    p4r, _ = shard.index.featurize(qj)
                    qp = plan(shard.index, p4r, variant=variant)
                    jax.block_until_ready(qp.sel_part)
                    sel_part, sel_lo, sel_hi = (qp.sel_part, qp.sel_lo,
                                                qp.sel_hi)
                    pt = np.asarray(qp.partitions_touched(), np.int64)
                    sc = np.asarray(
                        candidates_scanned(qp, shard.index.store), np.int64)
                    if cache is not None:
                        sp_np, lo_np, hi_np = (np.asarray(qp.sel_part),
                                               np.asarray(qp.sel_lo),
                                               np.asarray(qp.sel_hi))
                        for i, kk in enumerate(keys):
                            cache.put(kk, (sp_np[i], lo_np[i], hi_np[i],
                                           pt[i], sc[i]))
            with TRACER.span("fleet.refine", shard=shard.key) as sp_ref:
                dist, gid = dispatch_refine(shard.index.store, qj,
                                            sel_part, sel_lo, sel_hi,
                                            k, use_kernel=use_kernel)
                dist, gid = np.asarray(dist), np.asarray(gid)
            with TRACER.span("fleet.merge", shard=shard.key) as sp_mrg:
                gg = np.where(gid >= 0,
                              shard.global_ids[np.maximum(gid, 0)],
                              -1).astype(np.int32)
                md, mg = merge_topk(jnp.asarray(best_d[qsel]),
                                    jnp.asarray(best_g[qsel]),
                                    jnp.asarray(dist), jnp.asarray(gg), k)
                best_d[qsel] = np.asarray(md)
                best_g[qsel] = np.asarray(mg)
            stage["plan_ms"] += sp_plan.duration_ms
            stage["refine_ms"] += sp_ref.duration_ms
            stage["merge_ms"] += sp_mrg.duration_ms
            touched[qsel] += pt
            scanned[qsel] += sc
            self.stats.observe_shard(shard.key, len(qsel), int(pt.sum()))

    def _query_sealed_mesh(self, shards, pl, queries: np.ndarray, k: int,
                           mask: np.ndarray, variant: str,
                           use_kernel: Optional[bool],
                           best_d: np.ndarray, best_g: np.ndarray,
                           touched: np.ndarray, scanned: np.ndarray,
                           stage: dict, epoch: int) -> None:
        """Mesh fan-out, device-resident planning.

        The default path runs featurize → trie descent → plan → refine →
        merge as ONE device program (``MeshFleetPlacement.query``) with
        routing applied as a device-side plan mask; per-query plan rows
        come back and are memoized in the fleet's :class:`PlanCache` under
        ``(placement epoch, variant, query bytes)``.  When every query of
        a batch hits, the plans are assembled on the host and only the
        refine fan-out (``pl.dispatch``) runs.  Planner variants without a
        registered device twin fall back to host planning + refine-only
        dispatch.  All paths are bit-identical to
        :meth:`_query_sealed_host`."""
        if not pl.supports_device_planning(variant):
            self._query_sealed_mesh_hostplan(
                shards, pl, queries, k, mask, variant, use_kernel,
                best_d, best_g, touched, scanned, stage)
            return
        qn = len(queries)
        routed_t = np.zeros((pl.num_slots, qn), dtype=bool)
        routed_t[: len(shards)] = mask.T
        cache = self._plan_cache
        with TRACER.span("fleet.plan", path="mesh") as sp_plan:
            keys = [(epoch, variant, queries[i].tobytes())
                    for i in range(qn)]
            rows = [cache.get(kk) for kk in keys]
            all_hit = bool(qn) and all(r is not None for r in rows)
            if all_hit:
                b = rows[0][0].shape[-1]
                sp = np.empty((pl.num_slots, qn, b), np.int32)
                lo = np.empty((pl.num_slots, qn, b), np.int32)
                hi = np.empty((pl.num_slots, qn, b), np.int32)
                pt_all = np.empty((pl.num_slots, qn), np.int64)
                sc_all = np.empty((pl.num_slots, qn), np.int64)
                for i, r in enumerate(rows):
                    sp[:, i], lo[:, i], hi[:, i], pt_all[:, i], \
                        sc_all[:, i] = r
                spm = np.where(routed_t[:, :, None], sp, -1)
        stage["plan_ms"] += sp_plan.duration_ms
        if all_hit:
            with TRACER.span("fleet.refine", path="mesh") as sp_ref:
                dist, gid = pl.dispatch(queries, spm, lo, hi, k,
                                        use_kernel=use_kernel)
            stage["refine_ms"] += sp_ref.duration_ms
        else:
            # the fused pass plans on device, inseparably from refine
            with TRACER.span("fleet.refine", path="mesh",
                             fused=True) as sp_ref:
                dist, gid, sp, lo, hi, pt_all, sc_all = pl.query(
                    queries, routed_t, k, variant=variant,
                    use_kernel=use_kernel)
            stage["refine_ms"] += sp_ref.duration_ms
            with TRACER.span("fleet.plan", path="mesh") as sp_put:
                for i, kk in enumerate(keys):
                    cache.put(kk, (sp[:, i], lo[:, i], hi[:, i],
                                   pt_all[:, i].astype(np.int64),
                                   sc_all[:, i].astype(np.int64)))
                pt_all = pt_all.astype(np.int64)
                sc_all = sc_all.astype(np.int64)
            stage["plan_ms"] += sp_put.duration_ms
        best_d[:], best_g[:] = dist, gid
        for si, shard in enumerate(shards):
            routed = mask[:, si]
            if not routed.any():        # host loop never executes it either
                continue
            touched += np.where(routed, pt_all[si], 0)
            scanned += np.where(routed, sc_all[si], 0)
            self.stats.observe_shard(shard.key, int(routed.sum()),
                                     int(pt_all[si][routed].sum()))

    def _query_sealed_mesh_hostplan(self, shards, pl, queries: np.ndarray,
                                    k: int, mask: np.ndarray, variant: str,
                                    use_kernel: Optional[bool],
                                    best_d: np.ndarray, best_g: np.ndarray,
                                    touched: np.ndarray,
                                    scanned: np.ndarray,
                                    stage: dict) -> None:
        """Host-planned mesh fallback: plan per shard on the host (each
        shard has its own pivots/trie — cheap), stack the plans to
        ``[S_pad, Q, MP]`` with routing expressed as masked-out rows, and
        run one shard_map that refines every resident shard per device and
        folds the answers in shard order.  Used for planner variants with
        no registered device twin; never cached (plan widths are
        batch-dependent here)."""
        qn = len(queries)
        qj = jnp.asarray(queries)
        with TRACER.span("fleet.plan", path="mesh-hostplan") as sp_plan:
            plans = []
            for si, shard in enumerate(shards):
                if not mask[:, si].any():  # host loop skips unrouted shards:
                    plans.append(None)     # don't plan what won't execute
                    continue
                p4r, _ = shard.index.featurize(qj)
                plans.append(plan(shard.index, p4r, variant=variant))
            if all(qp is None for qp in plans):
                return                  # nothing routed: accumulators stay PAD
            mp = max(int(qp.sel_part.shape[-1]) for qp in plans
                     if qp is not None)
            sp = np.full((pl.num_slots, qn, mp), -1, np.int32)
            lo = np.zeros((pl.num_slots, qn, mp), np.int32)
            hi = np.zeros((pl.num_slots, qn, mp), np.int32)
            for si, (shard, qp) in enumerate(zip(shards, plans)):
                if qp is None:
                    continue
                w = int(qp.sel_part.shape[-1])
                routed = mask[:, si]
                sp[si, :, :w] = np.where(routed[:, None],
                                         np.asarray(qp.sel_part), -1)
                lo[si, :, :w] = np.asarray(qp.sel_lo)
                hi[si, :, :w] = np.asarray(qp.sel_hi)
                pt = np.asarray(qp.partitions_touched(), np.int64)
                touched += np.where(routed, pt, 0)
                scanned += np.where(
                    routed,
                    np.asarray(candidates_scanned(qp, shard.index.store),
                               np.int64), 0)
                self.stats.observe_shard(shard.key, int(routed.sum()),
                                         int(pt[routed].sum()))
        stage["plan_ms"] += sp_plan.duration_ms
        with TRACER.span("fleet.refine", path="mesh-hostplan") as sp_ref:
            dist, gid = pl.dispatch(queries, sp, lo, hi, k,
                                    use_kernel=use_kernel)
        stage["refine_ms"] += sp_ref.duration_ms
        best_d[:], best_g[:] = dist, gid

    def _merge_delta_answer(self, delta: DeltaShard, queries: np.ndarray,
                            k: int, variant: str,
                            use_kernel: Optional[bool],
                            best_d: np.ndarray, best_g: np.ndarray,
                            touched: np.ndarray, scanned: np.ndarray):
        """Fold one delta's (frozen or active) answer into the accumulators
        in place; returns the updated (best_d, best_g)."""
        res = delta.query(queries, k, variant=variant, use_kernel=use_kernel)
        if res is None:
            return best_d, best_g
        dist, gid, dt, dsc = res
        gg = np.where(gid >= 0,
                      delta.global_ids[np.maximum(gid, 0)],
                      -1).astype(np.int32)
        md, mg = merge_topk(jnp.asarray(best_d), jnp.asarray(best_g),
                            jnp.asarray(dist), jnp.asarray(gg), k)
        touched += dt
        scanned += dsc
        self.stats.observe_shard(self.DELTA_KEY, len(queries), int(dt.sum()))
        return np.asarray(md), np.asarray(mg)

    def query(self, queries: np.ndarray, k: int = 0, *,
              routing: str = "signature", variant: str = "adaptive",
              use_kernel: Optional[bool] = None,
              fanout: Optional[int] = None,
              threshold: Optional[float] = None,
              placement: Optional[str] = None
              ) -> Tuple[np.ndarray, np.ndarray, FleetQueryInfo]:
        """Fan out, per-shard kNN, fuse with ``merge_topk``.

        Args:
          queries: ``[Q, n]`` raw query series.
          k: answer size (0 ⇒ ``shard_cfg.k``).
          routing: ``"signature"`` routes each query to the ``fanout``
            best-scoring sealed shards; ``"adaptive"`` sizes the fan-out
            per query by score mass (``SignatureRouter.route_adaptive`` —
            ``threshold`` arg, else the router's learned threshold, else
            ``cfg.routing_threshold``; ``fanout`` then acts as a per-query
            cap); ``"exhaustive"`` executes every shard (lossless
            fan-out).  The delta is always executed.
          threshold: adaptive-routing score-mass cut for this call
            (ignored by the other routing modes).  ``>= 1.0`` is
            bit-identical to exhaustive routing; ``<= 0.0`` degrades to
            top-1.
          variant: per-shard planner variant; ``"exhaustive"`` makes each
            shard exact, so exhaustive routing + exhaustive variant equals
            brute-force over the fleet contents.
          use_kernel: per-shard refine implementation (True = streaming
            fused Pallas kernel, False = dense oracle, None = backend
            default — fused on accelerators, dense on CPU).
          placement: where the sealed shards execute — ``"host"`` (the
            sequential per-shard oracle loop), ``"mesh"`` (one shard_map
            over the device-resident stacked stores; needs an attached
            mesh), or None for the default: ``"mesh"`` when a mesh is
            attached, else ``"host"``.  Both placements return bit-
            identical results; the delta is always executed host-side.

        During a background compaction the frozen delta keeps serving
        (merged between the sealed shards and the live delta), so answers
        over unchanged contents are identical before, during, and after
        the seal.

        Returns:
          (dist ``[Q, k]`` ascending ED, gid ``[Q, k]`` fleet-global ids,
          info).  Rows with fewer than k candidates across the routed
          shards carry the :data:`repro.core.PAD_DIST` sentinel and
          ``gid = -1``.
        """
        if routing not in ("signature", "adaptive", "exhaustive"):
            raise ValueError(f"unknown routing mode {routing!r}")
        placement = self._resolve_placement(placement)
        queries = np.asarray(queries, dtype=np.float32)
        if queries.ndim != 2:
            raise ValueError(f"queries must be [Q, n], got {queries.shape}")
        k = k or self.cfg.shard_cfg.k
        qn = len(queries)
        best_d = np.full((qn, k), PAD_DIST, np.float32)
        best_g = np.full((qn, k), -1, np.int32)
        touched = np.zeros(qn, np.int64)
        scanned = np.zeros(qn, np.int64)
        stage = {"plan_ms": 0.0, "refine_ms": 0.0, "merge_ms": 0.0}

        with TRACER.span("fleet.query", placement=placement,
                         queries=qn) as sp_root:
            # consistent view: shard list + both deltas are captured under
            # the lock; the (slow) sealed-shard execution then runs
            # off-lock.  The captured delta object stays correct even if a
            # freeze/seal re-points ``self.delta`` meanwhile — freezing
            # never mutates it.
            with self._lock:
                shards = list(self.shards)
                sealing = self._sealing
                delta = self.delta
                s = len(shards)
                pl = self._ensure_placement() \
                    if placement == "mesh" and s else None
                epoch = self._placement_epoch
                cache = self._plan_cache
                h0, m0 = cache.hits, cache.misses
                lifecycle = self.stats.lifecycle_snapshot()
                # mask under the same lock: the router registry is only
                # ever resized (seal/merge/retire) while it is held, so the
                # mask width always matches the captured shard list
                if routing == "exhaustive" or self.router is None or s == 0:
                    mask = np.ones((qn, s), dtype=bool)
                elif routing == "adaptive":
                    th = threshold
                    if th is None:
                        th = self.router.threshold
                    if th is None:
                        th = self.cfg.routing_threshold
                    mask = self.router.route_adaptive(
                        queries, float(th), max_fanout=fanout)
                else:
                    mask = self.router.route(queries,
                                             fanout or self.cfg.fanout)

            if s:
                if placement == "mesh":
                    self._query_sealed_mesh(shards, pl, queries, k, mask,
                                            variant, use_kernel, best_d,
                                            best_g, touched, scanned,
                                            stage, epoch)
                else:
                    self._query_sealed_host(shards, queries, k, mask,
                                            variant, use_kernel, best_d,
                                            best_g, touched, scanned,
                                            stage, epoch)

            with TRACER.span("fleet.merge", shard=self.DELTA_KEY) as sp_mrg:
                if sealing is not None:   # frozen mid-compaction: immutable
                    best_d, best_g = self._merge_delta_answer(
                        sealing, queries, k, variant, use_kernel,
                        best_d, best_g, touched, scanned)
                with self._lock:          # live delta: serialize vs inserts
                    best_d, best_g = self._merge_delta_answer(
                        delta, queries, k, variant, use_kernel,
                        best_d, best_g, touched, scanned)
                    self.stats.queries += qn
                    self.stats.routed_pairs += int(mask.sum())
                    self.stats.exhaustive_pairs += qn * s
            stage["merge_ms"] += sp_mrg.duration_ms
        self.query_hist.observe(sp_root.duration_ms)
        for t in touched:
            self.touched_hist.observe(float(t))
        if self.sentinel is not None:
            # shadow-sampling copies (query, answer) pairs aside for the
            # off-path exhaustive audit; it never mutates the arrays it is
            # handed, so served answers are bit-identical with sampling
            # on or off (tests/test_sentinel.py holds this line to it)
            self.sentinel.observe(queries, k, best_d, best_g)
        return best_d, best_g, FleetQueryInfo(
            partitions_touched=touched, candidates_scanned=scanned,
            routed_mask=mask, lifecycle=lifecycle, stage_ms=stage,
            plan_cache_hits=cache.hits - h0,
            plan_cache_misses=cache.misses - m0)

    def scan_exact(self, queries: np.ndarray, k: int = 0, *,
                   use_kernel: Optional[bool] = None, mesh=None
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """Lossless fallback as a *single* refine over the fused store.

        Concatenates every shard store (global-id remapped) and runs one
        exhaustive ``dispatch_refine`` — the fleet answer without any
        per-shard scatter/gather, equal to exhaustive-routing +
        exhaustive-variant :meth:`query`.

        ``mesh`` (default: the fleet's attached mesh, if any) executes the
        union scan sharded over the mesh's data axis via
        ``refine_sharded`` — here the *partition* axis of the union store
        is what shards over the devices, not the shard axis.

        Returns ``(dist [Q, k], gid [Q, k])`` with the usual
        :data:`repro.core.PAD_DIST` / ``gid = -1`` pad sentinel.
        """
        queries = np.asarray(queries, dtype=np.float32)
        k = k or self.cfg.shard_cfg.k
        mesh = mesh if mesh is not None else self.mesh
        with self._lock:
            stores = [s.index.store for s in self.shards]
            gid_maps = [s.global_ids for s in self.shards]
            for delta in (self._sealing, self.delta):
                if delta is None:
                    continue
                dstore = delta.store()
                if dstore is not None:
                    stores.append(dstore)
                    gid_maps.append(delta.global_ids)
        if not stores:
            return (np.full((len(queries), k), PAD_DIST, np.float32),
                    np.full((len(queries), k), -1, np.int32))
        union = concat_stores(stores, gid_maps)
        sel, lo, hi = exhaustive_selection(union.num_partitions,
                                           len(queries))
        dist, gid = dispatch_refine(union, jnp.asarray(queries), sel, lo, hi,
                                    k, mesh=mesh, data_axis=self.data_axis,
                                    use_kernel=use_kernel)
        return np.asarray(dist), np.asarray(gid)

    MAX_ROUTING_TRACES = 4096       # bound on recorded audit traces

    def audit_routing(self, queries: np.ndarray, k: int = 0, *,
                      variant: str = "adaptive",
                      record: bool = False) -> float:
        """Measure routed-mode precision against the exhaustive oracle.

        Returns the mean fraction of the exhaustive fan-out's answers the
        routed fan-out also returned, and folds it into
        ``stats.routing_precision``.

        ``record=True`` additionally appends one ``(scores, true_hits)``
        trace per query to ``self.routing_traces`` — the router's ``[S]``
        shard scores and the count of the exhaustive answer's gids living
        in each sealed shard.  :meth:`calibrate_routing` learns the
        adaptive-routing threshold from these.
        """
        k = k or self.cfg.shard_cfg.k
        _, g_routed, _ = self.query(queries, k, routing="signature",
                                    variant=variant)
        _, g_full, _ = self.query(queries, k, routing="exhaustive",
                                  variant=variant)
        overlaps = []
        for gr, gf in zip(g_routed, g_full):
            truth = set(int(x) for x in gf if x >= 0)
            if not truth:
                continue
            got = set(int(x) for x in gr if x >= 0)
            overlaps.append(len(got & truth) / len(truth))
        precision = float(np.mean(overlaps)) if overlaps else 1.0
        self.stats.routing_audits += 1
        self.stats.routing_overlap += precision
        if record and self.router is not None and self.router.num_shards:
            with self._lock:
                gid_sets = [s.global_ids for s in self.shards]
            scores = self.router.score(queries)            # [Q, S]
            for i, gf in enumerate(g_full):
                valid = gf[gf >= 0]
                hits = np.array([int(np.isin(valid, g).sum())
                                 for g in gid_sets], np.int64)
                self.routing_traces.append((scores[i].copy(), hits))
            del self.routing_traces[:-self.MAX_ROUTING_TRACES]
        return precision

    def calibrate_routing(self, target_recall: float = 0.95) -> float:
        """Learn the adaptive-routing threshold from recorded audit traces
        (``audit_routing(..., record=True)``) and install it on the router.

        Returns the learned threshold (also left on ``router.threshold``,
        where ``routing="adaptive"`` picks it up by default).  Raises if
        there is no router or no trace has been recorded.
        """
        if self.router is None:
            raise RuntimeError("fleet has no router to calibrate")
        if not self.routing_traces:
            raise RuntimeError("no routing traces recorded — call "
                               "audit_routing(..., record=True) first")
        return self.router.learn_threshold(self.routing_traces,
                                           target_recall=target_recall)


def _recover_wal_rebase(storage_dir: Path) -> None:
    """Finish a WAL rebase interrupted by a crash (see
    :meth:`IndexFleet._attach_storage_rebased`): ``wal.rebase`` is only
    renamed into place after it is fully written, so whichever directory
    survives is complete."""
    import shutil
    wal_dir = storage_dir / "wal"
    rebase = storage_dir / "wal.rebase"
    old = storage_dir / "wal.old"
    if not wal_dir.exists() and rebase.exists():
        rebase.rename(wal_dir)          # crash between the two renames
    for leftover in (rebase, old):
        if leftover.exists():
            shutil.rmtree(leftover)
