"""Localized record-level similarity — paper §VI (final refine stage).

Given the partitions + trie-node targets selected by the planner, load the
selected partitions, restrict to records belonging to the targeted trie
node(s) (interval test on the DFS tag — the paper's contiguous node clusters),
compute exact ED against the raw series, and rank for the final top-K.

Execution backends, unified behind :func:`dispatch_refine` (the only entry
point the query layer and the serving engine use):
  * ``refine``          — dense jnp path: gathers the selected rows, masks
    the full ``[Q, slots, cap]`` distance tensor, separate top-k.  The
    parity **oracle** and the CPU default;
  * ``use_kernel=True`` — the streaming fused Pallas kernel
    (``repro.kernels.refine_topk``): one pass per candidate block that
    applies the DFS-interval mask + segment-dedupe predicate inline and
    maintains an online per-query k-best accumulator in VMEM, never
    materializing the ``[Q, slots, cap]`` tensor (or the gathered rows —
    blocks are DMA'd straight from the store via scalar-prefetched
    partition ids).  Validated against the dense oracle; gids match
    exactly under the shared lowest-flat-index tie-break;
  * ``use_kernel=None`` (the default everywhere) — resolves via
    :func:`default_use_kernel`: fused kernel on accelerator backends,
    dense oracle on CPU (where the kernel runs in slow interpret mode);
  * ``refine_sharded``  — shard_map over the data axis: each device scans
    only its local partition shard, produces a local **fused** (or dense)
    top-k, and a single all-gather + merge yields the global answer — the
    TPU analogue of the paper's scatter/gather over HDFS partitions.
    Composes with ``use_kernel``; stores whose partition count is ragged
    over the mesh (``P % n_dev != 0``) are padded via
    ``repro.distributed.pad_store``.

Duplicate-coverage removal (a node and its ancestor both selected) is a
sorted-slot segmented scan: plan entries are sorted by partition id, and a
record is dropped when an earlier entry of the same partition already
included it — O(Q·MP·cap) instead of the former O(Q·MP²·cap) pairwise
einsum over entry pairs.  The fused kernel evaluates the identical
predicate per streamed block, so both backends drop the same records.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.index import PartitionStore

_INF = jnp.float32(3.4e38)

# Sentinel distance of a pad answer (gid = -1): both refine paths emit
# sqrt(_INF) for slots with fewer than k candidates, so consumers that merge
# top-k lists across calls (the fleet) seed their accumulators with this.
PAD_DIST = float(np.sqrt(np.float32(3.4e38)))


def default_use_kernel() -> bool:
    """Backend default for the refine implementation.

    Accelerator backends run the streaming fused kernel (the whole point of
    it — HBM-resident stores, no [Q, slots, cap] materialization); CPU runs
    the dense jnp oracle, where the kernel would only execute in slow
    Pallas interpret mode.
    """
    return jax.default_backend() == "tpu"


def resolve_use_kernel(use_kernel: Optional[bool]) -> bool:
    """``None`` → the backend default; explicit flags are honored as-is."""
    return default_use_kernel() if use_kernel is None else bool(use_kernel)


def _sort_by_partition(sel_part, sel_lo, sel_hi):
    """Stable-sort plan entries by partition id (pads first, ties by entry
    order) so duplicate coverage is detectable by a segmented scan."""
    order = jnp.argsort(sel_part, axis=-1, stable=True)
    take = lambda t: jnp.take_along_axis(t, order, axis=-1)
    return take(sel_part), take(sel_lo), take(sel_hi)


def _dedupe_segments(sel_part, incl):
    """Drop records already included by an earlier same-partition entry.

    ``sel_part`` must be sorted along the entry axis so equal partition ids
    form contiguous segments.  Within a segment, a slot is kept at the first
    entry whose node interval covers it: the exclusive running inclusion
    count since the segment start is zero.
    """
    mp = sel_part.shape[-1]
    pos = jnp.arange(mp)
    seg_new = jnp.concatenate(
        [jnp.ones_like(sel_part[:, :1], bool),
         sel_part[:, 1:] != sel_part[:, :-1]], axis=-1)
    seg_start = jax.lax.cummax(jnp.where(seg_new, pos[None, :], 0), axis=1)
    ex_cum = jnp.cumsum(incl.astype(jnp.int32), axis=1) - incl
    start_cum = jnp.take_along_axis(ex_cum, seg_start[:, :, None], axis=1)
    return incl & ((ex_cum - start_cum) == 0)


def _masked_distances(store: PartitionStore, queries: jnp.ndarray,
                      sel_part: jnp.ndarray, sel_lo: jnp.ndarray,
                      sel_hi: jnp.ndarray):
    """Squared ED of each query against records of its selected partitions.

    The dense formulation (gather + full distance tensor) — the parity
    oracle the fused kernel is validated against.

    Args:
      store: partition store (P partitions × cap slots).
      queries: ``[Q, n]``.
      sel_part: ``[Q, MP]`` partition ids (−1 = unused slot).
      sel_lo / sel_hi: ``[Q, MP]`` DFS interval of the targeting trie node.

    Returns:
      (d2, gid): ``[Q, MP*cap]`` masked squared distances (masked = +inf) and
      the corresponding original record ids.
    """
    sel_part, sel_lo, sel_hi = _sort_by_partition(sel_part, sel_lo, sel_hi)

    q2 = jnp.sum(queries * queries, axis=-1)                    # [Q]
    pid = jnp.maximum(sel_part, 0)                              # clamp pads
    rows = store.data[pid]                                      # [Q, MP, cap, n]
    rows2 = store.norms[pid]                                    # [Q, MP, cap]
    rdfs = store.rec_dfs[pid]
    rgid = store.rec_gid[pid]

    dots = jnp.einsum("qn,qmcn->qmc", queries, rows,
                      precision=jax.lax.Precision.HIGHEST)
    d2 = jnp.maximum(q2[:, None, None] - 2.0 * dots + rows2, 0.0)

    valid = rgid >= 0
    in_node = (rdfs >= sel_lo[:, :, None]) & (rdfs < sel_hi[:, :, None])
    incl = valid & in_node & (sel_part >= 0)[:, :, None]
    incl = _dedupe_segments(sel_part, incl)

    q = queries.shape[0]
    d2 = jnp.where(incl, d2, _INF).reshape(q, -1)
    gid = jnp.where(incl, rgid, -1).reshape(q, -1)
    return d2, gid


def refine(store: PartitionStore, queries: jnp.ndarray, sel_part: jnp.ndarray,
           sel_lo: jnp.ndarray, sel_hi: jnp.ndarray, k: int,
           *, use_kernel: Optional[bool] = None
           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact-ED top-k within the selected (partition, node) targets.

    ``use_kernel=True`` runs the streaming fused Pallas kernel (masked
    distance + online top-k in one pass, nothing of shape [Q, slots, cap]
    materialized); ``False`` the dense jnp oracle; ``None`` the backend
    default (:func:`default_use_kernel`).

    Returns:
      (dist, gid): ``[Q, k]`` ascending ED (not squared) and record ids
      (−1 where fewer than k candidates existed; their distance is the
      :data:`PAD_DIST` sentinel on both paths).
    """
    if resolve_use_kernel(use_kernel):
        from repro.kernels import ops as kernel_ops
        # the device-plan variant owns the partition sort the kernel's
        # scalar-prefetch grid requires, so plans coming straight off a
        # device planner (fleet fused pass) and host-built plans share it
        d2, gid = kernel_ops.fused_refine_topk_device_plan(
            store.data, store.norms, store.rec_dfs, store.rec_gid,
            queries, sel_part, sel_lo, sel_hi, k)
        # under-k slots keep the +inf/-1 accumulator init → PAD_DIST/-1,
        # the same sentinel convention as the dense branch below
        return jnp.sqrt(d2), jnp.where(d2 >= _INF, -1, gid)
    d2, gid = _masked_distances(store, queries, sel_part, sel_lo, sel_hi)
    if d2.shape[-1] < k:        # tiny store: fewer slots than answers asked
        tail = [(0, 0)] * (d2.ndim - 1) + [(0, k - d2.shape[-1])]
        d2 = jnp.pad(d2, tail, constant_values=_INF)
        gid = jnp.pad(gid, tail, constant_values=-1)
    neg, idx = jax.lax.top_k(-d2, k)
    top_gid = jnp.take_along_axis(gid, idx, axis=-1)
    dist = jnp.sqrt(jnp.maximum(-neg, 0.0))
    top_gid = jnp.where(-neg >= _INF, -1, top_gid)
    return dist, top_gid


def merge_topk(dist_a, gid_a, dist_b, gid_b, k: int, *, dedupe: bool = False):
    """Merge two per-query top-k lists into one ``[..., k]`` top-k.

    Args:
      dist_a / dist_b: ``[..., ka]`` / ``[..., kb]`` ascending distances
        (any matching leading batch shape; ka and kb may differ).
      gid_a / gid_b: matching record-id arrays (``-1`` = pad entry).
      k: output answer size.

    Returns:
      (dist ``[..., k]`` ascending, gid ``[..., k]``).  Ties break toward
      input a, then slot order (``jax.lax.top_k`` lowest-index rule) — the
      property the fleet's in-shard-order merge fold relies on for
      bit-identical host/mesh placements.

    Pad entries (``gid = -1``) must carry the :data:`PAD_DIST` sentinel so
    they lose to every real candidate; the sentinel propagates into the
    output wherever fewer than k real candidates exist across both inputs
    (merging a pure-pad list into anything is therefore the identity).

    ``dedupe=False`` (default) assumes the inputs hold disjoint record sets
    — the sharded all-gather reduction and the fleet's sealed shards satisfy
    this — and keeps duplicate gids if the caller violates it.
    ``dedupe=True`` keeps only the best-ranked copy of each gid (ties break
    toward input a, then slot order); it costs O(k²) pairwise compares, so
    reserve it for merges that can legitimately see the same record twice.

    Example — fusing two shards' answers (the second has only one real
    candidate; its pad slot carries the sentinel and loses every merge)::

        >>> import jax.numpy as jnp
        >>> d_a = jnp.asarray([[1.0, 3.0]])
        >>> g_a = jnp.asarray([[10, 11]])
        >>> d_b = jnp.asarray([[2.0, PAD_DIST]])
        >>> g_b = jnp.asarray([[20, -1]])
        >>> dist, gid = merge_topk(d_a, g_a, d_b, g_b, k=3)
        >>> gid.tolist()
        [[10, 20, 11]]
        >>> [round(float(x), 1) for x in dist[0]]
        [1.0, 2.0, 3.0]
    """
    dist = jnp.concatenate([dist_a, dist_b], axis=-1)
    gid = jnp.concatenate([gid_a, gid_b], axis=-1)
    if dedupe:
        # entry j dominates entry i when they carry the same real gid and j
        # ranks strictly better: smaller distance, or equal distance and an
        # earlier slot.  Dominated entries become pads before the top-k.
        same = (gid[..., :, None] == gid[..., None, :]) & \
            (gid[..., None, :] >= 0)
        d_i, d_j = dist[..., :, None], dist[..., None, :]
        n2 = dist.shape[-1]
        earlier = jnp.arange(n2)[None, :] < jnp.arange(n2)[:, None]  # j < i
        dominated = jnp.any(
            same & ((d_j < d_i) | ((d_j == d_i) & earlier)), axis=-1)
        dist = jnp.where(dominated, jnp.float32(PAD_DIST), dist)
        gid = jnp.where(dominated, -1, gid)
    if dist.shape[-1] < k:                   # fewer candidates than asked for
        tail = [(0, 0)] * (dist.ndim - 1) + [(0, k - dist.shape[-1])]
        dist = jnp.pad(dist, tail, constant_values=PAD_DIST)
        gid = jnp.pad(gid, tail, constant_values=-1)
    neg, idx = jax.lax.top_k(-dist, k)
    return -neg, jnp.take_along_axis(gid, idx, axis=-1)


def refine_sharded(store: PartitionStore, queries: jnp.ndarray,
                   sel_part: jnp.ndarray, sel_lo: jnp.ndarray,
                   sel_hi: jnp.ndarray, k: int, *, mesh,
                   data_axis: str = "data",
                   use_kernel: Optional[bool] = None):
    """Distributed refine: local masked scan + local top-k + all-gather merge.

    ``store`` must be sharded over partitions on ``data_axis`` (P → data);
    queries and the plan are replicated.  Partition ids inside ``sel_part``
    are global; each device matches them against its local pid range.  A
    ragged store (``P % n_dev != 0``) is padded with empty partitions first.
    With ``use_kernel`` (the accelerator default) each device runs the
    streaming fused kernel over its local shard, so the per-device top-k is
    produced without materializing any local distance tensor either.
    """
    from jax.sharding import PartitionSpec as P

    use_kernel = resolve_use_kernel(use_kernel)
    n_dev = mesh.shape[data_axis]
    if store.num_partitions % n_dev:
        from repro.distributed.store import shard_store
        store = shard_store(store, mesh, data_axis=data_axis)
    per_dev = store.num_partitions // n_dev

    def local_fn(data, norms, rdfs, rgid, count, q, sp, lo, hi):
        dev = jax.lax.axis_index(data_axis)
        base = dev * per_dev
        local_store = PartitionStore(data=data, norms=norms, rec_dfs=rdfs,
                                     rec_gid=rgid, count=count)
        # global → local partition ids; out-of-range → -1 (skipped locally)
        sp_local = jnp.where((sp >= base) & (sp < base + per_dev),
                             sp - base, -1)
        dist, gid = refine(local_store, q, sp_local, lo, hi, k,
                           use_kernel=use_kernel)
        dist_all = jax.lax.all_gather(dist, data_axis, axis=0)   # [D, Q, k]
        gid_all = jax.lax.all_gather(gid, data_axis, axis=0)
        d = dist_all.transpose(1, 0, 2).reshape(q.shape[0], -1)
        g = gid_all.transpose(1, 0, 2).reshape(q.shape[0], -1)
        d = jnp.where(g >= 0, d, _INF)
        neg, idx = jax.lax.top_k(-d, k)
        g_top = jnp.take_along_axis(g, idx, axis=-1)
        # pad answers carry the same sentinel as the dense path (sqrt(_INF))
        return jnp.where(g_top >= 0, -neg, jnp.sqrt(_INF)), g_top

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(data_axis), P(data_axis), P(data_axis), P(data_axis),
                  P(data_axis), P(), P(), P(), P()),
        out_specs=(P(), P()),
        check_vma=False)
    return fn(store.data, store.norms, store.rec_dfs, store.rec_gid,
              store.count, queries, sel_part, sel_lo, sel_hi)


def dispatch_refine(store: PartitionStore, queries: jnp.ndarray,
                    sel_part: jnp.ndarray, sel_lo: jnp.ndarray,
                    sel_hi: jnp.ndarray, k: int, *, mesh=None,
                    data_axis: str = "data",
                    use_kernel: Optional[bool] = None):
    """Single execution-dispatch layer for the whole query stack.

    Every consumer (``knn_query``, the serving engines, the fleet's exact
    scan) funnels through here, so backend selection lives in exactly one
    place.

    Args:
      store: PartitionStore — replicated, or sharded over ``data_axis``
        when ``mesh`` is given (``repro.distributed.shard_store``).
      queries: ``[Q, n]`` raw series.
      sel_part / sel_lo / sel_hi: ``[Q, MP]`` plan — global partition ids
        (``-1`` = unused slot) and the targeting node's DFS interval.
      k: answer size.
      mesh / data_axis: ``mesh=None`` (or a 1-device data axis) runs the
        single-device path; a multi-device mesh runs the ``refine_sharded``
        shard_map path (local top-k per device + all-gather merge).
      use_kernel: refine implementation on either path — ``True`` the
        streaming fused Pallas kernel, ``False`` the dense jnp oracle,
        ``None`` (default) the backend default via
        :func:`default_use_kernel`: fused on accelerators, dense on CPU.

    Returns:
      (dist, gid): ``[Q, k]`` ascending ED and record ids; rows with fewer
      than k candidates carry :data:`PAD_DIST` and ``gid = -1`` on every
      backend, so outputs merge safely via :func:`merge_topk`.
    """
    if mesh is not None and mesh.shape[data_axis] > 1:
        return refine_sharded(store, queries, sel_part, sel_lo, sel_hi, k,
                              mesh=mesh, data_axis=data_axis,
                              use_kernel=use_kernel)
    return refine(store, queries, sel_part, sel_lo, sel_hi, k,
                  use_kernel=use_kernel)
