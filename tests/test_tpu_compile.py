"""Compile-only checks for a described TPU v5e (2x2 topology) — no chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached: what Mosaic refuses here (block shapes off the
(8, 128) tiling, unsupported vector ops, too much VMEM) would be refused on
the chip.  Interpret-mode tests cannot see any of it.  The refine tests
compile the served refine path at the paper's widths (n=256, capacity 3000,
Q=32, K=10 and K=500, the planner's width MP=16 and the exhaustive width
MP=P) and assert the program holds the Mosaic kernel (``tpu_custom_call``);
the router's signature profile compiles as one program for a batch of 8 and
for a ``summarize`` chunk.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and the multi-worker test
run gives this whole file to one worker.  The persistent compilation cache
is off around these compiles — an entry written for a described chip cannot
be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as PS,
                          SingleDeviceSharding)

N, CAP, Q, P = 256, 3000, 32, 97     # P partitions: a 200,000-series shard


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """The kernel wrappers pick interpret mode from the (CPU) backend; a
    compile for the described chip needs the Mosaic lowering."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _store_specs(sharding, p=P):
    f32, i32 = jnp.float32, jnp.int32
    return [jax.ShapeDtypeStruct((p, CAP, N), f32, sharding=sharding),
            jax.ShapeDtypeStruct((p, CAP), f32, sharding=sharding),
            jax.ShapeDtypeStruct((p, CAP), i32, sharding=sharding),
            jax.ShapeDtypeStruct((p, CAP), i32, sharding=sharding)]


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k", [10, 500])
@pytest.mark.parametrize("mp", [16, P])
def test_refine_topk_compiles(topo, k, mp):
    from repro.kernels.refine_topk import refine_topk
    one = SingleDeviceSharding(topo.devices[0])
    plan = jax.ShapeDtypeStruct((Q, mp), jnp.int32, sharding=one)
    compiled = jax.jit(refine_topk, static_argnames=("k",)).lower(
        *_store_specs(one),
        jax.ShapeDtypeStruct((Q, N), jnp.float32, sharding=one),
        plan, plan, plan, k=k).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("k", [10, 500])
def test_fused_refine_device_plan_compiles(topo, on_tpu, k):
    """The wrapper the served path calls (unsorted device plan)."""
    from repro.kernels import ops
    one = SingleDeviceSharding(topo.devices[0])
    plan = jax.ShapeDtypeStruct((Q, P), jnp.int32, sharding=one)
    compiled = jax.jit(ops.fused_refine_topk_device_plan,
                       static_argnames=("k",)).lower(
        *_store_specs(one),
        jax.ShapeDtypeStruct((Q, N), jnp.float32, sharding=one),
        plan, plan, plan, k=k).compile()
    _assert_kernel(compiled)


def test_mesh_fused_pass_compiles(topo, on_tpu):
    """featurize → plan → refine → all-gather merge as one shard_map over
    the 2x2 mesh, one 97-partition shard per chip, at K=500."""
    from repro.data import make_dataset
    from repro.fleet import FleetConfig, IndexFleet
    from repro.launch.mesh import make_mesh
    from repro.utils.config import ClimberConfig

    fleet = IndexFleet(FleetConfig(shard_cfg=ClimberConfig(),
                                   auto_compact=False))
    for s in range(4):
        fleet.add_shard(f"t{s}", np.asarray(make_dataset(
            "randomwalk", jax.random.PRNGKey(s), 1000, N)))
    fleet.attach_mesh(make_mesh((1,), ("data",)))
    pl = fleet._ensure_placement()
    pl.mesh = Mesh(np.asarray(topo.devices), ("data",))
    pl._p_static = P
    sh = NamedSharding(pl.mesh, PS("data"))
    spec = lambda x, shape=None: jax.ShapeDtypeStruct(
        x.shape if shape is None else shape, x.dtype, sharding=sh)
    fn = pl._build_query("adaptive", 500, True, 16)
    st = pl.store
    compiled = fn.lower(
        spec(st.data, (4, P, CAP, N)), spec(st.norms, (4, P, CAP)),
        spec(st.rec_dfs, (4, P, CAP)), spec(st.rec_gid, (4, P, CAP)),
        spec(st.count, (4, P)), jax.tree_util.tree_map(spec, pl.tables),
        spec(pl.pivots), spec(pl.centroids), spec(pl.t_real),
        jax.ShapeDtypeStruct((8, N), jnp.float32,
                             sharding=NamedSharding(pl.mesh, PS())),
        jax.ShapeDtypeStruct((4, 8), jnp.bool_, sharding=sh)).compile()
    _assert_kernel(compiled)
    assert "all-gather" in compiled.as_text()
    # one shard's store per chip, nothing else of size resident
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    assert per_chip < 2 * P * CAP * N * 4


@pytest.mark.parametrize("rows", [8, 1 << 16])
def test_router_profile_compiles(topo, rows):
    """The router's signature profile as one program: a batch of 8 queries
    (routing) and a full chunk of ``summarize``, whose ``[rows, m, r]``
    one-hot the compiled program does not hold."""
    from repro.fleet.router import SUMMARY_ROWS, profile_batch
    from repro.utils.config import ClimberConfig
    cfg = ClimberConfig()
    assert rows in (8, SUMMARY_ROWS)
    one = SingleDeviceSharding(topo.devices[0])
    f32 = jnp.float32
    compiled = profile_batch.lower(
        jax.ShapeDtypeStruct((rows, cfg.series_len), f32, sharding=one),
        jax.ShapeDtypeStruct((cfg.num_pivots, cfg.paa_segments), f32,
                             sharding=one),
        jax.ShapeDtypeStruct((cfg.prefix_len,), f32, sharding=one),
        segments=cfg.paa_segments, m=cfg.prefix_len).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= rows * cfg.num_pivots * 4
    assert mem.temp_size_in_bytes < rows * cfg.prefix_len * cfg.num_pivots * 4
