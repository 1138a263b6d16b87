"""Dry-run of the PAPER'S OWN technique at production scale.

Lowers + compiles the two distributed CLIMBER steps on the 16×16 (and
2×16×16) mesh with ShapeDtypeStruct data — no allocation:

  * ``index_build_step`` — §V Step 4: PAA → P⁴ signatures → Algorithm-1
    group assignment → trie routing, for every record (sharded over all
    non-model axes; embarrassingly parallel, zero collectives expected);
  * ``query_step``      — §VI: featurise queries → OD/WD planning → trie
    descent → sharded masked-ED refine + all-gather top-k merge.

Scale: 128M series × 256 readings (the paper's 200GB-class RandomWalk
regime at c=3000 partition capacity), r=200 pivots, m=10 prefix, K=500,
50 queries per batch — the paper's §VII defaults.

Writes artifacts/dryrun/climber_{build,query}_{mesh}.json.
"""
from __future__ import annotations

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro.core import (ClimberIndex, PartitionStore, build_forest,
                        plan_adaptive)
from repro.core.query import compact_plan
from repro.core.index import _route_full_dataset
from repro.core.refine import refine
from repro.core.traversal import TrieDevice
from repro.launch.mesh import make_production_mesh
from repro.utils import roofline as RL
from repro.utils.config import ClimberConfig

ART = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

CFG = ClimberConfig(series_len=256, paa_segments=16, num_pivots=200,
                    prefix_len=10, capacity=3000, sample_frac=0.01,
                    max_centroids=512, k=500, candidate_groups=8,
                    adaptive_factor=4)
N_SERIES = 128_000_000
N_QUERIES = 50


def synthetic_skeleton(cfg: ClimberConfig, num_groups: int = 256,
                       sample: int = 60_000, seed: int = 0):
    """Host-built skeleton with realistic shape statistics (trace-time only)."""
    rng = np.random.default_rng(seed)
    sigs = np.stack([rng.choice(cfg.num_pivots, cfg.prefix_len, replace=False)
                     for _ in range(sample)]).astype(np.int32)
    freqs = rng.integers(1, 50, size=sample)
    groups = rng.integers(0, num_groups, size=sample)
    forest = build_forest(sigs, freqs, groups, num_groups, cfg.num_pivots,
                          capacity=float(cfg.capacity),
                          sample_frac=cfg.sample_frac)
    trie = TrieDevice.from_forest(forest)
    onehot = np.zeros((num_groups, cfg.num_pivots), np.float32)
    for g in range(1, num_groups):
        onehot[g, rng.choice(cfg.num_pivots, cfg.prefix_len, replace=False)] = 1
    return forest, trie, jnp.asarray(onehot)


def _mesh_and_axes(multi_pod: bool):
    mesh = make_production_mesh(multi_pod=multi_pod)
    shard_axes = tuple(mesh.axis_names)          # all axes shard the records
    return mesh, shard_axes


def lower_build_step(multi_pod: bool):
    """§V Step 4 at scale: every record → (partition, dfs tag).

    Expressed with shard_map (each worker routes only its block — the exact
    Spark-executor semantics): left to GSPMD, the one-hot/top-k pipeline got
    partitioned with a full [N, r] replication (100 GB/device of involuntary
    all-gather).  Manual sharding pins every intermediate to the record
    shard; the step is embarrassingly parallel with zero collectives.
    """
    mesh, axes = _mesh_and_axes(multi_pod)
    forest, trie, onehot = synthetic_skeleton(CFG)
    data = jax.ShapeDtypeStruct((N_SERIES, CFG.series_len), jnp.float32)
    data_sh = NamedSharding(mesh, PS(axes, None))
    out_sh = NamedSharding(mesh, PS(axes))
    pivots = jnp.zeros((CFG.num_pivots, CFG.paa_segments), jnp.float32)

    def local_route(x):
        return _route_full_dataset(x, pivots, onehot, trie, CFG)

    def step(x):
        return jax.shard_map(local_route, mesh=mesh,
                             in_specs=PS(axes, None),
                             out_specs=(PS(axes), PS(axes)),
                             check_vma=False)(x)

    jitted = jax.jit(step, in_shardings=(data_sh,),
                     out_shardings=(out_sh, out_sh))
    return jitted.lower(data), mesh, forest


def lower_query_step(multi_pod: bool):
    """§VI at scale: plan + sharded masked-ED refine + top-k merge."""
    mesh, axes = _mesh_and_axes(multi_pod)
    forest, trie, onehot = synthetic_skeleton(CFG)
    n_dev = mesh.devices.size
    p_total = ((N_SERIES // CFG.capacity) // n_dev) * n_dev
    cap = CFG.capacity

    index = ClimberIndex(
        cfg=CFG,
        pivots=jnp.zeros((CFG.num_pivots, CFG.paa_segments), jnp.float32),
        centroid_onehot=onehot, forest=forest, trie=trie, store=None)

    sds = jax.ShapeDtypeStruct
    store_sds = PartitionStore(
        data=sds((p_total, cap, CFG.series_len), jnp.float32),
        norms=sds((p_total, cap), jnp.float32),
        rec_dfs=sds((p_total, cap), jnp.int32),
        rec_gid=sds((p_total, cap), jnp.int32),
        count=sds((p_total,), jnp.int32))
    store_sh = PartitionStore(
        *[NamedSharding(mesh, PS(axes, *([None] * (len(s.shape) - 1))))
          for s in store_sds])
    q_sds = sds((N_QUERIES, CFG.series_len), jnp.float32)
    rep = NamedSharding(mesh, PS())
    per_dev = p_total // n_dev

    def query_step(store, queries):
        p4r_q, _ = index.featurize(queries)
        # compact the slot axis: the refine gather is Q×slots×cap×n bytes,
        # so the static 2T×maxP padding must not reach the gather
        plan = compact_plan(plan_adaptive(index, p4r_q), 16)

        def local_fn(data, norms, rdfs, rgid, count, q, sp, lo, hi):
            # flat device id over all shard axes
            dev = 0
            for a in axes:
                dev = dev * mesh.shape[a] + jax.lax.axis_index(a)
            base = dev * per_dev
            local = PartitionStore(data=data, norms=norms, rec_dfs=rdfs,
                                   rec_gid=rgid, count=count)
            sp_l = jnp.where((sp >= base) & (sp < base + per_dev),
                             sp - base, -1)
            dist, gid = refine(local, q, sp_l, lo, hi, CFG.k)
            d_all = jax.lax.all_gather(dist, axes, axis=0, tiled=False)
            g_all = jax.lax.all_gather(gid, axes, axis=0, tiled=False)
            d = d_all.transpose(1, 0, 2).reshape(q.shape[0], -1)
            g = g_all.transpose(1, 0, 2).reshape(q.shape[0], -1)
            d = jnp.where(g >= 0, d, 3.4e38)
            neg, idx = jax.lax.top_k(-d, CFG.k)
            return -neg, jnp.take_along_axis(g, idx, axis=-1)

        fn = jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(PS(axes), PS(axes), PS(axes), PS(axes), PS(axes),
                      PS(), plan_spec, plan_spec, plan_spec),
            out_specs=(PS(), PS()), check_vma=False)
        return fn(store.data, store.norms, store.rec_dfs, store.rec_gid,
                  store.count, queries, plan.sel_part, plan.sel_lo,
                  plan.sel_hi)

    plan_spec = PS()
    jitted = jax.jit(query_step, in_shardings=(store_sh, rep),
                     out_shardings=(rep, rep))
    return jitted.lower(store_sds, q_sds), mesh, forest


def run(kind: str, multi_pod: bool) -> dict:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    lowered, mesh, forest = (lower_build_step(multi_pod) if kind == "build"
                             else lower_query_step(multi_pod))
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    coll = RL.collective_bytes(compiled.as_text())

    n_dev = mesh.devices.size
    if kind == "build":
        # useful work: one pass over every record (PAA+pivot dots dominate)
        useful_flops = N_SERIES * (CFG.series_len                 # PAA
                                   + 2 * CFG.paa_segments * CFG.num_pivots)
        useful_bytes = N_SERIES * CFG.series_len * 4
    else:
        # useful work: ED refine over the selected partitions
        sel_rows = N_QUERIES * 8 * CFG.capacity
        useful_flops = 2 * sel_rows * CFG.series_len
        useful_bytes = sel_rows * CFG.series_len * 4

    report = RL.RooflineReport(
        arch="climber", shape=kind, mesh=mesh_name,
        flops_per_device=float(cost.get("flops", 0.0)),
        bytes_per_device=float(cost.get("bytes accessed", 0.0)),
        coll_bytes_per_device=float(sum(coll.values())),
        coll_breakdown=coll,
        model_flops_per_device=useful_flops / n_dev,
        model_bytes_per_device=useful_bytes / n_dev,
        peak_memory_bytes=float(mem.temp_size_in_bytes
                                + mem.argument_size_in_bytes
                                + mem.output_size_in_bytes))
    res = {"status": "ok", "num_devices": n_dev,
           "partitions": forest.num_partitions,
           "memory": {
               "argument_bytes": int(mem.argument_size_in_bytes),
               "output_bytes": int(mem.output_size_in_bytes),
               "temp_bytes": int(mem.temp_size_in_bytes)},
           **report.to_dict()}
    print(f"[climber-{kind} × {mesh_name}] "
          f"args={mem.argument_size_in_bytes/2**30:.2f}GiB/dev "
          f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB/dev "
          f"flops/dev={report.flops_per_device:.3g} "
          f"coll/dev={report.coll_bytes_per_device/1e6:.1f}MB "
          f"bottleneck={report.bottleneck} frac={report.roofline_fraction:.3f}")
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--kind", default="both", choices=["build", "query", "both"])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    args = ap.parse_args()
    kinds = ["build", "query"] if args.kind == "both" else [args.kind]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    ART.mkdir(parents=True, exist_ok=True)
    for kind in kinds:
        for multi in meshes:
            res = run(kind, multi)
            name = f"climber_{kind}_{'2x16x16' if multi else '16x16'}.json"
            (ART / name).write_text(json.dumps(res, indent=2))


if __name__ == "__main__":
    main()
