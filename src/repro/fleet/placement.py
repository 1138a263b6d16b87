"""Mesh-resident fleet placement — one shard_map instead of S dispatches.

The host-loop fleet query (``IndexFleet.query(placement="host")``) executes
the sealed shards sequentially: S separate ``knn_query`` dispatches, each a
featurize → plan → refine round-trip, fused on the host with ``merge_topk``.
That is the lossless oracle, but it serializes S device round-trips per
query batch — exactly the per-node scan overlap the distributed-series
literature (Odyssey) says a fleet must not give up.

:class:`MeshFleetPlacement` keeps the sealed shards *device-resident*
instead:

  * every sealed shard's :class:`~repro.core.index.PartitionStore` is
    stacked on a new leading shard axis (ragged partition counts / slot
    capacities padded with inert ``rec_gid = -1`` slots, local record ids
    remapped to fleet-global ids at stack time), one device slab at a time
    (:func:`_lay_out_stores`), so no device ever holds more than its own
    shards;
  * every sealed shard's trie skeleton, pivot set and centroid table are
    stacked the same way (:func:`repro.fleet.device_plan.stack_tries` —
    ragged node/edge/group counts padded with inert entries that can never
    match a probe or contribute a partition);
  * the shard axis is padded to a multiple of the mesh's data-axis size
    (empty pad shards / all-inert pad tries — a pad shard is a no-op under
    ``merge_topk``) and sharded over the data axis, so device d owns whole
    shards ``[d·per, (d+1)·per)``;
  * :meth:`query` then runs the WHOLE query — featurize → trie descent →
    plan → budgeted compaction → refine → merge — as ONE jitted shard_map:
    each device featurizes the (replicated) query batch against its
    resident shards' pivots, plans against their stacked skeletons via the
    registered device planner (``repro.core.query.get_device_planner``,
    with a :class:`~repro.core.query.ShardPlanContext` carrying the real
    vs padded counts), refines, and a single ``all_gather`` +
    in-shard-order ``merge_topk`` fold produces the global ``[Q, k]``
    answer.  No host round-trip between planning and refine.

Routing is expressed *in the plan* — a query not routed to a shard gets
that shard's plan row masked to ``-1``, which the refine stage turns into
``PAD_DIST``/``gid = -1`` answers that lose every merge.  Because the
device planner reproduces the host planner's live plan entries in the same
order (ShardPlanContext masking + the shared ``compact_plan``), and the
fold merges shards in the same order the host loop does (shard 0, 1, …,
with the delta merged afterwards on the host), the mesh answer is
bit-identical to the host loop.

:meth:`dispatch` (refine-only, host-stacked plans) remains for plans
computed elsewhere — the fleet's plan-cache hit path and planner variants
without a registered device twin.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro.core import signatures as sig_mod
from repro.core.index import PartitionStore
from repro.core.paa import paa as _paa
from repro.core.query import (QueryPlan, ShardPlanContext, candidates_scanned,
                              compact_plan, default_slot_budget,
                              get_device_planner, get_planner)
from repro.core.refine import (PAD_DIST, merge_topk, refine,
                               resolve_use_kernel)
from repro.fleet.device_plan import ShardView, stack_tries, trie_row


def _lay_out_stores(mesh, data_axis: str, shards,
                    s_pad: int) -> PartitionStore:
    """The sealed stores as one ``[S_pad, P, cap, ...]`` stack sharded over
    ``data_axis``, built one device slab at a time.

    Each device's slab — its whole shards, ragged P/cap padded with inert
    slots (``rec_dfs = rec_gid = -1``, zero rows and norms), local record
    ids remapped to fleet-global ids, pad shards empty — is assembled on
    the host and copied to that device alone, so no device ever holds the
    whole fleet (or a second copy of it) on the way.
    """
    stores = [s.index.store for s in shards]
    pmax = max(st.num_partitions for st in stores)
    cap = max(st.capacity for st in stores)
    n = stores[0].data.shape[-1]
    sharding = NamedSharding(mesh, PS(data_axis))
    pieces = [[] for _ in PartitionStore._fields]
    for dev, idx in sharding.addressable_devices_indices_map(
            (s_pad,)).items():
        lo, hi, _ = idx[0].indices(s_pad)
        m = hi - lo
        slab = PartitionStore(
            data=np.zeros((m, pmax, cap, n), stores[0].data.dtype),
            norms=np.zeros((m, pmax, cap), stores[0].norms.dtype),
            rec_dfs=np.full((m, pmax, cap), -1, np.int32),
            rec_gid=np.full((m, pmax, cap), -1, np.int32),
            count=np.zeros((m, pmax), np.int32))
        for j in range(lo, min(hi, len(shards))):
            st, p, c = stores[j], stores[j].num_partitions, stores[j].capacity
            gid = np.asarray(st.rec_gid)
            gmap = np.asarray(shards[j].global_ids, np.int32)
            slab.data[j - lo, :p, :c] = np.asarray(st.data)
            slab.norms[j - lo, :p, :c] = np.asarray(st.norms)
            slab.rec_dfs[j - lo, :p, :c] = np.asarray(st.rec_dfs)
            slab.rec_gid[j - lo, :p, :c] = np.where(
                gid >= 0, gmap[np.maximum(gid, 0)], -1)
            slab.count[j - lo, :p] = np.asarray(st.count)
        for out, x in zip(pieces, slab):
            out.append(jax.device_put(x, dev))
    return PartitionStore(*[
        jax.make_array_from_single_device_arrays(
            (s_pad,) + arrs[0].shape[1:], sharding, arrs)
        for arrs in pieces])


def _slab_store(data, norms, rdfs, rgid, count) -> PartitionStore:
    """A device's resident shards ``[per, P, ...]`` as ONE store of
    ``per·P`` partitions (a free reshape): shard ``j``'s partition ``p``
    is ``j·P + p`` (:func:`_offset_plan`), so every shard refines straight
    out of the slab — a ``data[j]`` operand would be a copy of the shard."""
    flat = lambda x: x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])
    return PartitionStore(flat(data), flat(norms), flat(rdfs), flat(rgid),
                          flat(count))


def _offset_plan(sel_part, j: int, num_partitions: int):
    """Shard ``j``'s plan partition ids in its device slab (pads stay -1)."""
    return jnp.where(sel_part >= 0, sel_part + j * num_partitions, -1)


class MeshFleetPlacement:
    """Sealed shard stores + skeletons laid out over the mesh, plus the jits.

    Built from the fleet's current sealed shard list; the fleet invalidates
    and rebuilds it whenever that list changes (``add_shard`` /
    ``compact``).  The stacked store and trie tables are device-resident
    *copies* of the shard state — the host copies inside each
    ``ClimberIndex`` stay authoritative for planning oracles and rebuilds.

    Args:
      mesh: a jax Mesh with a ``data_axis`` dimension.
      shards: the fleet's ``ShardHandle`` list (order defines merge order).
      data_axis: mesh axis name the shard axis is laid out over.
    """

    def __init__(self, mesh, shards, *, data_axis: str = "data"):
        if not shards:
            raise ValueError("mesh placement needs at least one sealed shard")
        self.mesh = mesh
        self.data_axis = data_axis
        self.num_shards = len(shards)
        n_dev = mesh.shape[data_axis]
        self.num_slots = -(-len(shards) // n_dev) * n_dev   # S_pad
        shard_put = lambda x: jax.device_put(
            x, NamedSharding(mesh, PS(data_axis)))
        self.store = _lay_out_stores(mesh, data_axis, shards, self.num_slots)

        # ---- device-resident planning inputs (uniform-cfg fleets) -------
        self._indexes = [s.index for s in shards]
        self.cfg = self._indexes[0].cfg
        self._device_plan_ready = all(ix.cfg == self.cfg
                                      for ix in self._indexes)
        if self._device_plan_ready:
            s_pad, pad_n = self.num_slots, self.num_slots - self.num_shards
            tables = stack_tries([ix.trie for ix in self._indexes],
                                 pad_to=s_pad)
            self.tables = jax.tree_util.tree_map(shard_put, tables)
            r, w = self.cfg.num_pivots, self.cfg.paa_segments
            piv = np.zeros((s_pad, r, w), np.float32)
            gmax = int(tables.group_root.shape[-1])
            cent = np.zeros((s_pad, gmax, r), np.float32)
            for j, ix in enumerate(self._indexes):
                piv[j] = np.asarray(ix.pivots)
                c = np.asarray(ix.centroid_onehot)
                cent[j, : c.shape[0]] = c
            g_real = np.array([ix.num_groups for ix in self._indexes]
                              + [1] * pad_n, np.int32)
            t_real = np.maximum(
                np.minimum(self.cfg.candidate_groups, g_real - 1), 1)
            self.pivots = shard_put(jnp.asarray(piv))
            self.centroids = shard_put(jnp.asarray(cent))
            self.t_real = shard_put(jnp.asarray(t_real.astype(np.int32)))
            # static widths of the fused pass
            self._t_static = min(self.cfg.candidate_groups, gmax - 1) or 1
            self._p_static = int(self.store.data.shape[1])
        # (k, use_kernel) -> jitted refine-only shard_map; jit re-traces per
        # Q/MP shape on its own
        self._dispatch: Dict[Tuple, object] = {}
        # (variant, k, use_kernel, B) -> jitted fused featurize→plan→refine
        self._query: Dict[Tuple, object] = {}
        self._plan_widths: Dict[str, int] = {}

    # ------------------------------------------------------------------
    # device-resident planning (the fused pass)
    # ------------------------------------------------------------------
    def supports_device_planning(self, variant: str) -> bool:
        """True when ``variant`` has a registered device planner and the
        fleet's shard configs are uniform (stacked featurize needs one
        pivot-count/segment geometry)."""
        return self._device_plan_ready \
            and get_device_planner(variant) is not None

    def plan_width(self, variant: str) -> int:
        """B — the fused pass's static plan width for ``variant``.

        The max over shards of the width the HOST planner would produce
        after budget resolution (``plan()``'s logic: explicit
        ``cfg.query_max_slots``, else the lossless
        :func:`~repro.core.query.default_slot_budget`) — so a device plan
        row compacted to B holds exactly the host plan's live entries (and
        drops the same ones when the budget is deliberately lossy).
        Shapes come from ``jax.eval_shape`` — no planning is executed.
        """
        b = self._plan_widths.get(variant)
        if b is None:
            widths = []
            for ix in self._indexes:
                spec = jax.ShapeDtypeStruct((1, ix.cfg.prefix_len), jnp.int32)
                shape = jax.eval_shape(
                    lambda p4, ix=ix: get_planner(variant)(ix, p4), spec)
                raw = int(shape.sel_part.shape[-1])
                budget = ix.cfg.query_max_slots
                if budget is None:
                    budget = default_slot_budget(ix, variant)
                widths.append(raw if budget is None else min(budget, raw))
            b = self._plan_widths[variant] = max(widths)
        return b

    def _build_query(self, variant: str, k: int, use_kernel: bool, b: int):
        """Compile the fused featurize→descend→plan→refine→merge pass."""
        axis = self.data_axis
        n_dev = self.mesh.shape[axis]
        per = self.num_slots // n_dev
        s_pad = self.num_slots
        cfg = self.cfg
        planner = get_device_planner(variant)
        t_static, p_static = self._t_static, self._p_static
        m, r, w = cfg.prefix_len, cfg.num_pivots, cfg.paa_segments

        def local_fn(data, norms, rdfs, rgid, count, tab, piv, cent,
                     t_real, q, routed):
            # data…count: [per, ...] this device's resident shards;
            # tab/piv/cent/t_real: their stacked skeletons + planner inputs;
            # routed: [per, Q] fan-out mask.  Queries are replicated.
            # named_scope markers label the fused stages on captured
            # profiler traces (see repro.obs.profile)
            with jax.named_scope("climber.featurize"):
                z = _paa(q, w)                     # shard-independent
            slab = _slab_store(data, norms, rdfs, rgid, count)
            d_l, g_l, sp_l, lo_l, hi_l, pt_l, sc_l = ([] for _ in range(7))
            for j in range(per):                   # static unroll
                with jax.named_scope("climber.plan"):
                    p4r = sig_mod.rank_signature(z, piv[j], m)
                    trie = trie_row(tab, j, num_pivots=r,
                                    num_partitions=p_static)
                    view = ShardView(cfg, cent[j], trie)
                    ctx = ShardPlanContext(
                        num_groups=tab.num_groups[j],
                        num_candidates=t_real[j],
                        num_partitions=tab.num_partitions[j],
                        t_static=t_static, p_static=p_static)
                    qp = planner(view, p4r, ctx)
                    if qp.sel_part.shape[-1] > b:  # live-first, host's drops
                        qp = compact_plan(qp, b)
                    sp, lo, hi = qp.sel_part, qp.sel_lo, qp.sel_hi
                    if sp.shape[-1] < b:
                        pad2 = ((0, 0), (0, b - sp.shape[-1]))
                        sp = jnp.pad(sp, pad2, constant_values=-1)
                        lo, hi = jnp.pad(lo, pad2), jnp.pad(hi, pad2)
                    qp_b = QueryPlan(sel_part=sp, sel_lo=lo, sel_hi=hi,
                                     node=qp.node, pathlen=qp.pathlen)
                    # metrics from the unmasked plan — the host loop
                    # computes them per shard before the routing mask
                    pt_l.append(qp_b.partitions_touched())
                    sc_l.append(candidates_scanned(
                        qp_b, slab._replace(count=count[j])))
                with jax.named_scope("climber.refine"):
                    spm = jnp.where(routed[j][:, None], sp, -1)
                    d, g = refine(slab, q,
                                  _offset_plan(spm, j, data.shape[1]),
                                  lo, hi, k, use_kernel=use_kernel)
                d_l.append(d)
                g_l.append(g)
                sp_l.append(sp)
                lo_l.append(lo)
                hi_l.append(hi)
            with jax.named_scope("climber.merge"):
                d_loc, g_loc = jnp.stack(d_l), jnp.stack(g_l)  # [per, Q, k]
                # one collective: every device sees every shard's top-k
                d_all = jax.lax.all_gather(d_loc, axis, axis=0)
                g_all = jax.lax.all_gather(g_loc, axis, axis=0)
                d_all = d_all.reshape(s_pad, *d_loc.shape[1:])  # shard order
                g_all = g_all.reshape(s_pad, *g_loc.shape[1:])
                # fold in global shard order — the host loop's merge order,
                # so results (incl. tie-breaks) are bit-identical
                best_d = jnp.full(d_loc.shape[1:], PAD_DIST, jnp.float32)
                best_g = jnp.full(g_loc.shape[1:], -1, jnp.int32)
                for s in range(s_pad):
                    best_d, best_g = merge_topk(best_d, best_g,
                                                d_all[s], g_all[s], k)
            return (best_d, best_g, jnp.stack(sp_l), jnp.stack(lo_l),
                    jnp.stack(hi_l), jnp.stack(pt_l), jnp.stack(sc_l))

        fn = jax.shard_map(
            local_fn, mesh=self.mesh,
            in_specs=(PS(axis), PS(axis), PS(axis), PS(axis), PS(axis),
                      PS(axis), PS(axis), PS(axis), PS(axis),
                      PS(), PS(axis)),
            out_specs=(PS(), PS(), PS(axis), PS(axis), PS(axis),
                       PS(axis), PS(axis)),
            check_vma=False)
        return jax.jit(fn)

    def query(self, queries: np.ndarray, routed: np.ndarray, k: int, *,
              variant: str = "adaptive", use_kernel: Optional[bool] = None):
        """ONE device program: featurize → plan → refine → merge, fused.

        Args:
          queries: ``[Q, n]`` raw query series (replicated to every device).
          routed: ``[S_pad, Q]`` bool fan-out mask (pad-shard rows False);
            an unrouted (query, shard) pair gets its plan row masked to
            ``-1`` before refine, exactly like the host-stacked path.
          k: answer size.
          variant: a planner with a registered device twin
            (:meth:`supports_device_planning`).
          use_kernel: per-device refine implementation (None = backend
            default — fused kernel on accelerators, dense oracle on CPU).

        Returns:
          ``(dist [Q, k], gid [Q, k], sel_part, sel_lo, sel_hi
          [S_pad, Q, B], touched [S_pad, Q], scanned [S_pad, Q])`` numpy
          arrays — the answer plus the UNMASKED per-shard plans and plan
          metrics, which the fleet feeds its epoch-keyed plan cache (a
          later hit replays them through :meth:`dispatch` with a fresh
          routing mask).
        """
        if not self.supports_device_planning(variant):
            raise ValueError(
                f"variant {variant!r} has no device planner "
                "(or shard configs are not uniform); use host planning")
        use_kernel = resolve_use_kernel(use_kernel)
        b = self.plan_width(variant)
        key = (variant, k, use_kernel, b)
        fn = self._query.get(key)
        if fn is None:
            fn = self._query[key] = self._build_query(variant, k,
                                                      use_kernel, b)
        st = self.store
        with jax.profiler.TraceAnnotation("fleet.mesh.query"):
            outs = fn(st.data, st.norms, st.rec_dfs, st.rec_gid, st.count,
                      self.tables, self.pivots, self.centroids, self.t_real,
                      jnp.asarray(queries, jnp.float32),
                      jnp.asarray(routed, bool))
            return tuple(np.asarray(o) for o in outs)

    # ------------------------------------------------------------------
    # refine-only fan-out (host-computed / cache-replayed plans)
    # ------------------------------------------------------------------
    def _build_dispatch(self, k: int, use_kernel: bool):
        """Compile the single-collective fan-out for one (shapes, k) combo."""
        axis = self.data_axis
        n_dev = self.mesh.shape[axis]
        per = self.num_slots // n_dev
        s_pad = self.num_slots

        def local_fn(data, norms, rdfs, rgid, count, q, sp, lo, hi):
            # data: [per, P, cap, n] — this device's resident shards;
            # sp/lo/hi: [per, Q, MP] — their (routing-masked) plans.
            local_d, local_g = [], []
            slab = _slab_store(data, norms, rdfs, rgid, count)
            with jax.named_scope("climber.refine"):
                for j in range(per):                 # static unroll
                    d, g = refine(slab, q,
                                  _offset_plan(sp[j], j, data.shape[1]),
                                  lo[j], hi[j], k, use_kernel=use_kernel)
                    local_d.append(d)
                    local_g.append(g)
            with jax.named_scope("climber.merge"):
                d_loc = jnp.stack(local_d)           # [per, Q, k]
                g_loc = jnp.stack(local_g)
                # one collective: every device sees every shard's top-k
                d_all = jax.lax.all_gather(d_loc, axis, axis=0)
                g_all = jax.lax.all_gather(g_loc, axis, axis=0)
                d_all = d_all.reshape(s_pad, *d_loc.shape[1:])  # shard order
                g_all = g_all.reshape(s_pad, *g_loc.shape[1:])
                # fold in global shard order — the host loop's merge order,
                # so results (incl. tie-breaks) are bit-identical
                best_d = jnp.full(d_loc.shape[1:], PAD_DIST, jnp.float32)
                best_g = jnp.full(g_loc.shape[1:], -1, jnp.int32)
                for s in range(s_pad):
                    best_d, best_g = merge_topk(best_d, best_g,
                                                d_all[s], g_all[s], k)
            return best_d, best_g

        fn = jax.shard_map(
            local_fn, mesh=self.mesh,
            in_specs=(PS(axis), PS(axis), PS(axis), PS(axis), PS(axis),
                      PS(), PS(axis), PS(axis), PS(axis)),
            out_specs=(PS(), PS()),
            check_vma=False)
        return jax.jit(fn)

    def dispatch(self, queries: np.ndarray, sel_part: np.ndarray,
                 sel_lo: np.ndarray, sel_hi: np.ndarray, k: int,
                 use_kernel: Optional[bool] = None
                 ) -> Tuple[np.ndarray, np.ndarray]:
        """Run the refine-only fan-out over host-provided stacked plans.

        Args:
          queries: ``[Q, n]`` raw query series (replicated to every device).
          sel_part / sel_lo / sel_hi: ``[S_pad, Q, MP]`` stacked per-shard
            plans; ``sel_part = -1`` marks pad slots *and* (whole rows of)
            queries not routed to that shard.
          k: answer size.
          use_kernel: per-device refine implementation (None = backend
            default — fused kernel on accelerators, dense oracle on CPU).

        Returns:
          (dist ``[Q, k]``, gid ``[Q, k]``): fused over every sealed shard,
          fleet-global ids, ``PAD_DIST``/``-1`` where fewer than k real
          candidates were routed.
        """
        use_kernel = resolve_use_kernel(use_kernel)
        key = (k, use_kernel)
        fn = self._dispatch.get(key)
        if fn is None:
            fn = self._dispatch[key] = self._build_dispatch(k, use_kernel)
        st = self.store
        with jax.profiler.TraceAnnotation("fleet.mesh.dispatch"):
            d, g = fn(st.data, st.norms, st.rec_dfs, st.rec_gid, st.count,
                      jnp.asarray(queries, jnp.float32),
                      jnp.asarray(sel_part, jnp.int32),
                      jnp.asarray(sel_lo, jnp.int32),
                      jnp.asarray(sel_hi, jnp.int32))
            return np.asarray(d), np.asarray(g)
