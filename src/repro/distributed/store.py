"""PartitionStore layout over the data axis of a device mesh.

The CLIMBER store is the TPU analogue of the paper's HDFS blocks: a dense
``[P, cap, n]`` array plus per-record masks.  For distributed query execution
(`repro.core.refine.refine_sharded`) every store field must be sharded over
its leading partition axis so each device scans only its local shard.  These
helpers make that layout a one-liner:

  * :func:`store_pspecs`  — the PartitionSpec tree (every field: ``P(data)``);
  * :func:`pad_store`     — pad the leading axis up to a multiple of the
    axis size (a ragged count would otherwise be silently truncated by the
    per-device split); padding slots carry ``rec_gid = -1`` so they can
    never match;
  * :func:`shard_store`   — pad + ``device_put`` with NamedShardings;
  * :func:`store_to_arrays` / :func:`store_from_arrays` — the bit-exact
    host-array wire format the fleet's shard snapshots
    (``repro.fleet.lifecycle.snapshot``) serialize through.

Global partition ids are preserved: padding appends empty partitions at the
end, and planners only ever emit real partition ids, so a padded store is
query-for-query equivalent to the unpadded one.

The same helpers serve two layouts:

  * **partition-sharded** (single index): each field's leading axis is P,
    so every device scans a slice of one index's partitions
    (``refine_sharded``);
  * **shard-stacked** (fleet): the fleet's mesh placement
    (``repro.fleet.placement``) stacks whole shard stores on a NEW leading
    shard axis ``S`` (ragged P/cap padded with inert slots, local gids
    remapped to fleet-global) and shards that axis — each device then
    owns whole indexes.
    The trie skeletons ride the same layout through the sibling helper
    :func:`repro.fleet.device_plan.stack_tries` (``[S, ...]`` padded trie
    tables next to the ``[S, ...]`` stacked stores), which is what lets the
    placement plan on device instead of looping shards on the host.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro.core.index import PartitionStore


def store_pspecs(data_axis: str = "data") -> PartitionStore:
    """PartitionSpec per store field: everything shards its leading P axis."""
    return PartitionStore(
        data=PS(data_axis), norms=PS(data_axis), rec_dfs=PS(data_axis),
        rec_gid=PS(data_axis), count=PS(data_axis))


def pad_store(store: PartitionStore, multiple: int) -> PartitionStore:
    """Append empty partitions so ``P % multiple == 0`` (no-op when it is).

    Padded slots are inert: ``rec_gid``/``rec_dfs`` are −1 (never a live
    record, never inside a node interval) and no planner emits their ids.
    """
    pad = (-store.num_partitions) % multiple
    if pad == 0:
        return store
    tail = lambda x: ((0, pad),) + ((0, 0),) * (x.ndim - 1)
    return PartitionStore(
        data=jnp.pad(store.data, tail(store.data)),
        norms=jnp.pad(store.norms, tail(store.norms)),
        rec_dfs=jnp.pad(store.rec_dfs, tail(store.rec_dfs),
                        constant_values=-1),
        rec_gid=jnp.pad(store.rec_gid, tail(store.rec_gid),
                        constant_values=-1),
        count=jnp.pad(store.count, tail(store.count)))


def _pad_caps(store: PartitionStore, cap: int,
              gid_map=None) -> PartitionStore:
    """Pad slot capacity to ``cap`` with inert slots; optionally remap the
    store's local record ids to global ids (``gid_map[n_local] -> gid``)."""
    pad = cap - store.capacity
    slot = lambda x, cv=0: jnp.pad(
        x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2),
        constant_values=cv)
    gid = slot(store.rec_gid, -1)
    if gid_map is not None:
        gmap = jnp.asarray(np.asarray(gid_map, dtype=np.int32))
        gid = jnp.where(gid >= 0, gmap[jnp.maximum(gid, 0)], -1)
    return PartitionStore(
        data=slot(store.data), norms=slot(store.norms),
        rec_dfs=slot(store.rec_dfs, -1), rec_gid=gid, count=store.count)


def concat_stores(stores, gid_maps=None) -> PartitionStore:
    """Fuse several shard stores into one union store along the P axis.

    The fleet's lossless full-scan fallback executes one ``dispatch_refine``
    over this union instead of a per-shard scatter/gather.  Slot capacities
    are padded to the fleet-wide max with inert slots (``rec_gid = -1``), so
    a fused scan touches exactly the union of live records.

    Args:
      stores: sequence of PartitionStore (same series_len).
      gid_maps: optional per-store ``[n_i]`` arrays mapping the store's local
        record ids to global ids; identity (with no offset) when omitted —
        pass maps whenever the shards' local id spaces overlap.
    """
    stores = list(stores)
    if not stores:
        raise ValueError("concat_stores needs at least one store")
    cap = max(s.capacity for s in stores)
    padded = [_pad_caps(s, cap, None if gid_maps is None else gid_maps[i])
              for i, s in enumerate(stores)]
    return PartitionStore(*[jnp.concatenate(x, axis=0)
                            for x in zip(*padded)])


def shard_store(store: PartitionStore, mesh, *,
                data_axis: str = "data") -> PartitionStore:
    """Lay the store out over ``data_axis``: pad P, then place each field."""
    store = pad_store(store, mesh.shape[data_axis])
    specs = store_pspecs(data_axis)
    return PartitionStore(*[
        jax.device_put(x, NamedSharding(mesh, s))
        for x, s in zip(store, specs)])


def store_to_arrays(store: PartitionStore, prefix: str = "store_"):
    """Host-array dict of every store field (the snapshot wire format).

    Keys are ``f"{prefix}{field}"`` so several stores (or a store plus
    other arrays) can share one ``npz``.  Inverse of
    :func:`store_from_arrays`; the round trip is bit-exact, which is what
    makes a restored shard's answers bit-identical
    (``repro.fleet.lifecycle.snapshot``).
    """
    return {prefix + name: np.asarray(getattr(store, name))
            for name in PartitionStore._fields}


def store_from_arrays(arrays, prefix: str = "store_") -> PartitionStore:
    """Rebuild a device-resident store from :func:`store_to_arrays` output."""
    return PartitionStore(*[jnp.asarray(arrays[prefix + name])
                            for name in PartitionStore._fields])
