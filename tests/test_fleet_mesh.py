"""Mesh-resident fleet fan-out acceptance tests.

The acceptance contract: ``IndexFleet.query(placement="mesh")`` — the
single-shard_map fan-out over device-resident stacked shard stores — is
**bit-identical** to the host-loop oracle (``placement="host"``) on 1/2/4
device CPU meshes, for routed and exhaustive fan-out, with a shard count
that does not divide the mesh (S=3), and with a live delta.

Multi-device runs happen in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the parent jax is
already initialised with 1 device); the 1-device mesh cases run in-process.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import make_dataset, make_queries
from repro.eval.metrics import assert_same_answers
from repro.fleet import FleetConfig, FleetEngine, IndexFleet
from repro.launch.mesh import make_mesh
from repro.utils.config import ClimberConfig

REPO = Path(__file__).resolve().parents[1]
K = 10
SCALE = 2 * 64          # bound on |q|^2 + |x|^2 for z-normalised length 64

SETUP = """
    from repro.data import make_dataset, make_queries
    from repro.eval.metrics import answers_agree
    from repro.fleet import FleetConfig, IndexFleet
    from repro.launch.mesh import make_mesh
    from repro.utils.config import ClimberConfig

    cfg = ClimberConfig(series_len=64, paa_segments=8, num_pivots=32,
                        prefix_len=5, capacity=128, sample_frac=0.3,
                        max_centroids=12, k=10, candidate_groups=4,
                        adaptive_factor=4)
    data = np.asarray(make_dataset("randomwalk", jax.random.PRNGKey(0),
                                   1800, 64))
    queries = np.asarray(make_queries(jax.random.PRNGKey(2),
                                      jnp.asarray(data), 5))
    fleet = IndexFleet(FleetConfig(shard_cfg=cfg, fanout=2,
                                   auto_compact=False))
    for i in range(3):                      # S=3: ragged on 2 and 4 devices
        fleet.add_shard(f"t{i}", data[i * 600:(i + 1) * 600])
    fleet.insert(np.asarray(make_dataset("randomwalk",
                                         jax.random.PRNGKey(5), 80, 64)))
"""


def small_cfg() -> ClimberConfig:
    return ClimberConfig(series_len=64, paa_segments=8, num_pivots=32,
                         prefix_len=5, capacity=128, sample_frac=0.3,
                         max_centroids=12, k=K, candidate_groups=4,
                         adaptive_factor=4)


def run_subprocess(body: str, timeout: int = 600) -> dict:
    """Run SETUP + ``body`` on 8 host devices; body prints one JSON line."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax
        import jax.numpy as jnp
        import numpy as np
        assert jax.device_count() == 8, jax.device_count()
    """) + textwrap.dedent(SETUP) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def fleet_setup():
    cfg = small_cfg()
    data = np.asarray(make_dataset("randomwalk", jax.random.PRNGKey(0),
                                   1800, 64))
    queries = np.asarray(make_queries(jax.random.PRNGKey(2),
                                      jnp.asarray(data), 5))
    fleet = IndexFleet(FleetConfig(shard_cfg=cfg, fanout=2,
                                   auto_compact=False))
    for i in range(3):
        fleet.add_shard(f"t{i}", data[i * 600:(i + 1) * 600])
    fleet.insert(np.asarray(make_dataset("randomwalk",
                                         jax.random.PRNGKey(5), 80, 64)))
    return fleet, queries


class TestSingleDeviceMesh:
    def test_mesh_bit_identical_to_host(self, fleet_setup):
        """1-device mesh: results and per-query metrics match the oracle
        exactly, routed and exhaustive."""
        fleet, queries = fleet_setup
        fleet.attach_mesh(make_mesh((1,), ("data",)))
        try:
            for routing in ("exhaustive", "signature"):
                for variant in ("adaptive", "exhaustive"):
                    dh, gh, ih = fleet.query(queries, K, routing=routing,
                                             variant=variant,
                                             placement="host")
                    dm, gm, im = fleet.query(queries, K, routing=routing,
                                             variant=variant,
                                             placement="mesh")
                    assert_same_answers(dh, gh, dm, gm, scale=SCALE)
                    np.testing.assert_array_equal(ih.partitions_touched,
                                                  im.partitions_touched)
                    np.testing.assert_array_equal(ih.candidates_scanned,
                                                  im.candidates_scanned)
                    np.testing.assert_array_equal(ih.routed_mask,
                                                  im.routed_mask)
        finally:
            fleet.mesh = None
            fleet._placement = None

    def test_default_placement_follows_mesh(self, fleet_setup):
        """placement=None resolves to mesh iff a mesh is attached."""
        fleet, queries = fleet_setup
        d_host, g_host, _ = fleet.query(queries, K)     # no mesh → host
        fleet.attach_mesh(make_mesh((1,), ("data",)))
        try:
            d_mesh, g_mesh, _ = fleet.query(queries, K)  # mesh default
            assert_same_answers(d_host, g_host, d_mesh, g_mesh, scale=SCALE)
        finally:
            fleet.mesh = None
            fleet._placement = None

    def test_engine_mesh_matches_host(self, fleet_setup):
        fleet, queries = fleet_setup
        mesh = make_mesh((1,), ("data",))
        try:
            eng_m = FleetEngine(fleet, batch_size=4, k=K, mesh=mesh,
                                placement="mesh", routing="exhaustive")
            dm, gm, _ = eng_m.run(queries)
            eng_h = FleetEngine(fleet, batch_size=4, k=K, placement="host",
                                routing="exhaustive")
            dh, gh, _ = eng_h.run(queries)
            np.testing.assert_array_equal(gm, gh)
            np.testing.assert_array_equal(dm, dh)
        finally:
            fleet.mesh = None
            fleet._placement = None

    def test_scan_exact_uses_attached_mesh(self, fleet_setup):
        fleet, queries = fleet_setup
        d0, g0 = fleet.scan_exact(queries, K)
        fleet.attach_mesh(make_mesh((1,), ("data",)))
        try:
            d1, g1 = fleet.scan_exact(queries, K)
            np.testing.assert_array_equal(g0, g1)
            np.testing.assert_array_equal(d0, d1)
        finally:
            fleet.mesh = None
            fleet._placement = None

    def test_compact_invalidates_placement(self):
        """Sealing the delta changes the sealed set: the next mesh query
        must see the new shard (re-laid-out placement), and stay identical
        to the host loop."""
        cfg = small_cfg()
        data = np.asarray(make_dataset("randomwalk", jax.random.PRNGKey(3),
                                       1200, 64))
        queries = np.asarray(make_queries(jax.random.PRNGKey(4),
                                          jnp.asarray(data), 4))
        fleet = IndexFleet(FleetConfig(shard_cfg=cfg, auto_compact=False),
                           mesh=make_mesh((1,), ("data",)))
        fleet.add_shard("t0", data[:600])
        fleet.add_shard("t1", data[600:])
        fleet.query(queries, K, placement="mesh")   # placement built (S=2)
        assert fleet._placement is not None and \
            fleet._placement.num_shards == 2
        fleet.insert(np.asarray(make_dataset("randomwalk",
                                             jax.random.PRNGKey(6), 64, 64)))
        fleet.compact()
        dm, gm, _ = fleet.query(queries, K, routing="exhaustive",
                                placement="mesh")
        assert fleet._placement.num_shards == 3
        dh, gh, _ = fleet.query(queries, K, routing="exhaustive",
                                placement="host")
        np.testing.assert_array_equal(gm, gh)
        np.testing.assert_array_equal(dm, dh)


class TestPlacementValidation:
    def test_mesh_placement_without_mesh_raises(self, fleet_setup):
        fleet, queries = fleet_setup
        with pytest.raises(ValueError, match="mesh"):
            fleet.query(queries, K, placement="mesh")

    def test_unknown_placement_raises(self, fleet_setup):
        fleet, queries = fleet_setup
        with pytest.raises(ValueError, match="placement"):
            fleet.query(queries, K, placement="gpu")
        with pytest.raises(ValueError, match="placement"):
            FleetEngine(fleet, placement="gpu")


class TestMultiDeviceMesh:
    def test_2_and_4_device_bit_identity(self):
        """Acceptance: mesh fan-out ≡ host loop on 2- and 4-device meshes,
        S=3 shards (S % n_dev != 0 on both), routed + exhaustive, with a
        live delta."""
        out = run_subprocess("""
            oracle = {}
            for routing in ("exhaustive", "signature"):
                d, g, info = fleet.query(queries, 10, routing=routing,
                                         variant="adaptive",
                                         placement="host")
                oracle[routing] = (d, g, info)

            results = {}
            for n_dev in (2, 4):
                fleet.attach_mesh(make_mesh((n_dev,), ("data",)))
                for routing in ("exhaustive", "signature"):
                    dm, gm, im = fleet.query(queries, 10, routing=routing,
                                             variant="adaptive",
                                             placement="mesh")
                    dh, gh, ih = oracle[routing]
                    results[f"{n_dev}/{routing}"] = bool(
                        answers_agree(dm, gm, dh, gh, scale=2 * 64)[0]
                        and np.array_equal(im.partitions_touched,
                                           ih.partitions_touched)
                        and np.array_equal(im.candidates_scanned,
                                           ih.candidates_scanned))
                # padded shard slots: S=3 rounds up to a multiple of n_dev
                results[f"{n_dev}/slots"] = fleet._placement.num_slots
            print(json.dumps(results))
        """)
        for key in ("2/exhaustive", "2/signature", "4/exhaustive",
                    "4/signature"):
            assert out[key], f"mesh != host at {key}: {out}"
        assert out["2/slots"] == 4 and out["4/slots"] == 4, out

    def test_4_device_exhaustive_variant_and_scan(self):
        """Exact mode end-to-end on 4 devices: mesh fan-out with the
        exhaustive planner ≡ host loop ≡ sharded scan_exact."""
        out = run_subprocess("""
            dh, gh, _ = fleet.query(queries, 10, routing="exhaustive",
                                    variant="exhaustive", placement="host")
            fleet.attach_mesh(make_mesh((4,), ("data",)))
            dm, gm, _ = fleet.query(queries, 10, routing="exhaustive",
                                    variant="exhaustive", placement="mesh")
            ds, gs = fleet.scan_exact(queries, 10)
            print(json.dumps({
                "mesh": bool(np.array_equal(dm, dh)
                             and np.array_equal(gm, gh)),
                "scan": bool(np.array_equal(ds, dh)
                             and np.array_equal(gs, gh)),
            }))
        """)
        assert out["mesh"], out
        assert out["scan"], out
