"""Stacked trie skeletons — device-resident planning inputs for the fleet.

The mesh placement's fused query pass (``MeshFleetPlacement.query``) runs
featurize → descend → plan → refine as ONE device program, which means every
sealed shard's :class:`~repro.core.traversal.TrieDevice` skeleton must live
on the mesh next to its partition store.  Shards are ragged (different node
/ edge / group / partition counts), so the skeletons are padded to
fleet-wide maxima with *inert* entries (:func:`repro.core.traversal.pad_trie`
— int32-max edge keys that no probe can match, an inert node with an empty
DFS interval and no partitions, pad groups rooted at it) and stacked on a
new leading shard axis — the trie analogue of the mesh placement's
stacked stores (``repro.fleet.placement``):

  * :func:`stack_tries`    — ``[TrieDevice] → TrieTables [S, ...]`` (+ pad
    shards up to a mesh-divisible slot count, mirroring ``pad_store``);
  * :func:`trie_row`       — reconstruct one shard's ``TrieDevice`` view
    from the stacked tables *inside* a traced program (the NamedTuple's
    static int fields cannot ride through vmap/shard_map, so the view is
    rebuilt per shard at trace time);
  * :func:`descend_stacked` — batched descent over the shard axis, the
    property-test surface for host↔stacked parity;
  * :class:`ShardView`     — the duck-typed ``ClimberIndex`` stand-in the
    registered device planners (``repro.core.query``) plan against.

Padding can never change a plan: pad edges never match, pad groups descend
to the inert node (size 0, no partitions), pad shards plan only ``-1``
entries — all of which the refine stage already treats as absent.  The
per-shard *real* counts ride alongside as ``[S]`` arrays and become the
traced :class:`~repro.core.query.ShardPlanContext` scalars.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.traversal import TrieDevice, descend, pad_trie


class TrieTables(NamedTuple):
    """Stacked ``[S, ...]`` trie skeletons (an all-array pytree).

    Field-for-field the arrays of :class:`TrieDevice` with a new leading
    shard axis, plus the per-shard real counts.  Every leaf is an array, so
    a TrieTables can be passed straight through jit/shard_map/vmap with a
    leading-axis PartitionSpec — the static ints of TrieDevice
    (``num_pivots``/``num_partitions``) are re-attached by :func:`trie_row`.
    """

    edge_key: jnp.ndarray            # [S, E] int32, pad = int32 max
    edge_child: jnp.ndarray          # [S, E] int32
    has_children: jnp.ndarray        # [S, N] bool
    node_size: jnp.ndarray           # [S, N] float32
    node_depth: jnp.ndarray          # [S, N] int32
    dfs_in: jnp.ndarray              # [S, N] int32
    dfs_out: jnp.ndarray             # [S, N] int32
    part_start: jnp.ndarray          # [S, N + 1] int32
    part_ids_pad: jnp.ndarray        # [S, N, maxP] int32, -1 padded
    group_root: jnp.ndarray          # [S, G] int32, pad groups → inert node
    group_default_part: jnp.ndarray  # [S, G] int32, pad = -1
    num_groups: jnp.ndarray          # [S] int32 — real centroid rows
    num_partitions: jnp.ndarray      # [S] int32 — real partition count

    @property
    def num_slots(self) -> int:
        return int(self.edge_key.shape[0])


def _inert_row(n1: int, emax: int, gmax: int, maxp: int) -> TrieDevice:
    """A whole-shard pad slot: one inert trie that plans nothing."""
    i32max = jnp.iinfo(jnp.int32).max
    return TrieDevice(
        edge_key=jnp.full((emax,), i32max, jnp.int32),
        edge_child=jnp.zeros((emax,), jnp.int32),
        has_children=jnp.zeros((n1,), bool),
        node_size=jnp.zeros((n1,), jnp.float32),
        node_depth=jnp.zeros((n1,), jnp.int32),
        dfs_in=jnp.zeros((n1,), jnp.int32),
        dfs_out=jnp.zeros((n1,), jnp.int32),
        part_start=jnp.zeros((n1 + 1,), jnp.int32),
        part_ids_pad=jnp.full((n1, maxp), -1, jnp.int32),
        group_root=jnp.full((gmax,), n1 - 1, jnp.int32),
        group_default_part=jnp.full((gmax,), -1, jnp.int32),
        num_pivots=0, num_partitions=0)


def stack_tries(tries: Sequence[TrieDevice], *,
                pad_to: Optional[int] = None) -> TrieTables:
    """Stack shard skeletons on a NEW leading shard axis (``S`` first).

    Ragged node/edge/group/partition-list counts are padded to the maxima
    with inert entries (see :func:`repro.core.traversal.pad_trie`); the node
    axis always gains one guaranteed-inert node at the top index, which pad
    groups (and whole pad shards) root at.  ``pad_to`` appends all-inert pad
    shards up to that slot count (``S % n_dev`` raggedness, exactly like
    ``pad_store`` on the stacked stores) — a pad shard's real counts are
    ``num_groups = 1`` / ``num_partitions = 0`` so a masked device planner
    emits only ``-1`` entries for it.

    Args:
      tries: per-shard device skeletons (same ``num_pivots``).
      pad_to: total slot count after padding (>= len(tries)).

    Returns:
      :class:`TrieTables` with every field stacked to ``[S_pad, ...]``.
    """
    tries = list(tries)
    if not tries:
        raise ValueError("stack_tries needs at least one trie")
    pivs = {t.num_pivots for t in tries}
    if len(pivs) != 1:
        raise ValueError(f"tries disagree on num_pivots: {sorted(pivs)}")
    s = len(tries)
    pad_to = s if pad_to is None else pad_to
    if pad_to < s:
        raise ValueError(f"pad_to={pad_to} < {s} shards")
    n1 = max(int(t.has_children.shape[0]) for t in tries) + 1
    emax = max(int(t.edge_key.shape[0]) for t in tries)
    gmax = max(int(t.group_root.shape[0]) for t in tries)
    maxp = max(int(t.part_ids_pad.shape[1]) for t in tries)
    rows = [pad_trie(t, num_nodes=n1, num_edges=emax,
                     max_parts=maxp, num_groups=gmax) for t in tries]
    rows += [_inert_row(n1, emax, gmax, maxp)] * (pad_to - s)
    stacked = [jnp.stack(x) for x in zip(*(r[:11] for r in rows))]
    g_real = np.array([int(t.group_root.shape[0]) for t in tries]
                      + [1] * (pad_to - s), np.int32)
    p_real = np.array([t.num_partitions for t in tries]
                      + [0] * (pad_to - s), np.int32)
    return TrieTables(*stacked, num_groups=jnp.asarray(g_real),
                      num_partitions=jnp.asarray(p_real))


def trie_row(tables: TrieTables, j, *, num_pivots: int,
             num_partitions: int = 0) -> TrieDevice:
    """Shard ``j``'s TrieDevice view of the stacked tables.

    Usable inside a traced program (``j`` may be a python int into local
    shard_map slices); the static int fields are re-attached from the
    caller's config, which is what keeps TrieDevice out of vmapped pytrees.
    """
    return TrieDevice(
        edge_key=tables.edge_key[j], edge_child=tables.edge_child[j],
        has_children=tables.has_children[j], node_size=tables.node_size[j],
        node_depth=tables.node_depth[j], dfs_in=tables.dfs_in[j],
        dfs_out=tables.dfs_out[j], part_start=tables.part_start[j],
        part_ids_pad=tables.part_ids_pad[j],
        group_root=tables.group_root[j],
        group_default_part=tables.group_default_part[j],
        num_pivots=num_pivots, num_partitions=num_partitions)


def descend_stacked(tables: TrieTables, p4_rank: jnp.ndarray,
                    group: jnp.ndarray, *, num_pivots: int):
    """Batched descent over the shard axis (vmapped ``descend``).

    Args:
      tables: stacked skeletons ``[S, ...]``.
      p4_rank: ``[S, ..., m]`` rank signatures (per-shard pivots differ, so
        the caller featurizes per shard).
      group: ``[S, ...]`` group ids.

    Returns:
      (node, pathlen, parent), each ``[S, ...]`` — row ``s`` identical to
      ``descend(tries[s], p4_rank[s], group[s])`` on the unstacked skeleton
      (the parity property ``tests/test_device_plan.py`` checks).
    """
    def one(tab: TrieTables, p4, grp):
        trie = TrieDevice(*tab[:11], num_pivots=num_pivots, num_partitions=0)
        return descend(trie, p4, grp)
    return jax.vmap(one)(tables, p4_rank, group)


class ShardView:
    """Duck-typed ``ClimberIndex`` stand-in for planning on device.

    The registered planners only touch ``index.cfg``, ``index.trie`` and
    ``index.centroid_onehot`` (plus ``index.store.num_partitions``, which
    the device path replaces with ``ShardPlanContext.p_static``), so a view
    of one shard's padded rows is all a device planner needs.
    """

    __slots__ = ("cfg", "centroid_onehot", "trie")

    def __init__(self, cfg, centroid_onehot: jnp.ndarray, trie: TrieDevice):
        self.cfg = cfg
        self.centroid_onehot = centroid_onehot
        self.trie = trie

    @property
    def num_groups(self) -> int:
        return int(self.centroid_onehot.shape[0])
