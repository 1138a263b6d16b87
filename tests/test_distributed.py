"""Multi-device integration tests.

These run in subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8
(the parent process already initialised jax with 1 device).  They exercise
real SPMD semantics: sharded train steps match single-device training,
sharded CLIMBER queries match local queries, checkpoints reshard elastically,
and the compressed cross-pod all-reduce preserves gradient direction.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def run_subprocess(body: str, timeout: int = 420) -> dict:
    """Run `body` (which must print a final JSON line) on 8 host devices."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax
        import jax.numpy as jnp
        import numpy as np
        assert jax.device_count() == 8, jax.device_count()
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return json.loads(res.stdout.strip().splitlines()[-1])


class TestShardedTraining:
    def test_sharded_step_matches_local(self):
        out = run_subprocess("""
            from repro.configs import get_config
            from repro.models import Model
            from repro.train.optimizer import AdamW, constant_lr
            from repro.train.train_step import make_train_step, shard_train_step
            from repro.launch.mesh import make_mesh
            from repro.data.tokens import TokenPipeline

            cfg = get_config("internlm2-1.8b", smoke=True)
            pipe = TokenPipeline(cfg, 8, 32, seed=1)
            batch = pipe.batch_at(0)
            opt = AdamW(lr=constant_lr(1e-3))

            # local (single-logical-device semantics)
            model_l = Model(cfg)
            params = model_l.init(jax.random.PRNGKey(0))
            state = opt.init(params)
            fn_l = jax.jit(make_train_step(model_l, opt, kv_chunk=32))
            p1, s1, m1 = fn_l(params, state, batch)

            # sharded on a (4, 2) mesh
            mesh = make_mesh((4, 2), ("data", "model"))
            model_s = Model(cfg, mesh=mesh, batch_axes=("data",))
            shapes = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
            fn_s, (psh, osh, bsh) = shard_train_step(
                model_s, opt, mesh, shapes, kv_chunk=32, donate=False)
            params_s = jax.device_put(params, psh)
            state_s = jax.device_put(state, osh)
            batch_s = jax.device_put(batch, bsh)
            p2, s2, m2 = fn_s(params_s, state_s, batch_s)

            d = abs(float(m1["loss"]) - float(m2["loss"]))
            # compare a couple of updated weights
            w1 = np.asarray(p1["embed"]["out"], np.float32)
            w2 = np.asarray(jax.device_get(p2["embed"]["out"]), np.float32)
            print(json.dumps({
                "loss_delta": d,
                "w_delta": float(np.max(np.abs(w1 - w2))),
                "loss": float(m1["loss"]),
            }))
        """)
        assert out["loss_delta"] < 5e-2, out
        assert out["w_delta"] < 5e-2, out

    def test_microbatched_matches_plain(self):
        out = run_subprocess("""
            from repro.configs import get_config
            from repro.models import Model
            from repro.train.optimizer import AdamW, constant_lr
            from repro.train.train_step import make_train_step
            from repro.data.tokens import TokenPipeline

            cfg = get_config("mamba2-780m", smoke=True)
            pipe = TokenPipeline(cfg, 8, 32, seed=2)
            batch = pipe.batch_at(0)
            opt = AdamW(lr=constant_lr(1e-3))
            model = Model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            state = opt.init(params)
            f1 = jax.jit(make_train_step(model, opt, kv_chunk=32))
            f4 = jax.jit(make_train_step(model, opt, kv_chunk=32,
                                         microbatches=4))
            _, _, m1 = f1(params, state, batch)
            _, _, m4 = f4(params, state, batch)
            print(json.dumps({"l1": float(m1["loss"]),
                              "l4": float(m4["loss"])}))
        """)
        assert abs(out["l1"] - out["l4"]) < 5e-2, out


class TestShardedClimber:
    def test_sharded_refine_matches_local(self):
        out = run_subprocess("""
            from repro.utils.config import ClimberConfig
            from repro.core import build_index, knn_query, plan_adaptive
            from repro.core.refine import refine, refine_sharded
            from repro.data import make_dataset, make_queries
            from repro.launch.mesh import make_mesh
            from jax.sharding import NamedSharding, PartitionSpec as P

            cfg = ClimberConfig(series_len=64, paa_segments=8, num_pivots=32,
                                prefix_len=5, capacity=128, sample_frac=0.3,
                                max_centroids=12, k=10, candidate_groups=4)
            data = make_dataset("randomwalk", jax.random.PRNGKey(0), 4000, 64)
            index = build_index(jax.random.PRNGKey(1), data, cfg)
            q = make_queries(jax.random.PRNGKey(2), data, 8)

            dist_l, gid_l, plan = knn_query(index, q, 10)

            mesh = make_mesh((8,), ("data",))
            # pad partitions to a multiple of 8 and shard the store
            import jax.numpy as jnp
            store = index.store
            P_total = store.num_partitions
            pad = (-P_total) % 8
            def padp(x):
                return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            from repro.core.index import PartitionStore
            store_p = PartitionStore(*[padp(getattr(store, f))
                                       for f in store._fields])
            sh = NamedSharding(mesh, P("data"))
            store_s = PartitionStore(*[jax.device_put(x, sh) for x in store_p])
            p4r_q, _ = index.featurize(q)
            plan = plan_adaptive(index, p4r_q)
            dist_s, gid_s = refine_sharded(
                store_s, q, plan.sel_part, plan.sel_lo, plan.sel_hi, 10,
                mesh=mesh)
            match = float((np.sort(np.asarray(gid_l), -1)
                           == np.sort(np.asarray(gid_s), -1)).mean())
            print(json.dumps({"match": match}))
        """)
        assert out["match"] > 0.99, out

    def test_sharded_exact_scan_matches(self):
        out = run_subprocess("""
            from repro.baselines import exact_knn, exact_knn_sharded
            from repro.data import make_dataset
            from repro.launch.mesh import make_mesh

            data = make_dataset("sift", jax.random.PRNGKey(0), 4096, 64)
            q = data[:6]
            d1, i1 = exact_knn(q, data, 9)
            mesh = make_mesh((8,), ("data",))
            d2, i2 = exact_knn_sharded(q, data, 9, mesh=mesh)
            same = all(set(np.asarray(a)) == set(np.asarray(b))
                       for a, b in zip(i1, i2))
            print(json.dumps({"same": bool(same)}))
        """)
        assert out["same"], out


class TestElasticity:
    def test_checkpoint_reshards_to_smaller_mesh(self):
        out = run_subprocess("""
            import tempfile
            from repro.configs import get_config
            from repro.models import Model
            from repro.train.checkpoint import save_checkpoint, restore_checkpoint
            from repro.train.train_step import make_state_shardings
            from repro.train.optimizer import AdamW, constant_lr
            from repro.launch.mesh import make_mesh

            cfg = get_config("internlm2-1.8b", smoke=True)
            opt = AdamW(lr=constant_lr(1e-3))

            mesh8 = make_mesh((4, 2), ("data", "model"))
            model8 = Model(cfg, mesh=mesh8, batch_axes=("data",))
            psh8, _ = make_state_shardings(mesh8, model8)
            params = jax.device_put(model8.init(jax.random.PRNGKey(0)), psh8)

            with tempfile.TemporaryDirectory() as d:
                save_checkpoint(d, 3, params)
                # "pod loss": bring up a (2, 2) mesh — 4 surviving devices
                mesh4 = make_mesh((2, 2), ("data", "model"))
                model4 = Model(cfg, mesh=mesh4, batch_axes=("data",))
                psh4, _ = make_state_shardings(mesh4, model4)
                restored, step, _ = restore_checkpoint(d, params,
                                                       shardings=psh4)
                w0 = np.asarray(jax.device_get(params["embed"]["tok"]),
                                np.float32)
                w1 = np.asarray(jax.device_get(restored["embed"]["tok"]),
                                np.float32)
                ok = bool(np.array_equal(w0, w1)) and step == 3
                nshards = len(restored["embed"]["tok"].sharding.device_set)
            print(json.dumps({"ok": ok, "devices": nshards}))
        """)
        assert out["ok"] and out["devices"] == 4, out


class TestCompressedAllReduce:
    def test_cross_pod_ef_allreduce(self):
        out = run_subprocess("""
            from jax.sharding import PartitionSpec as P
            from repro.distributed.compression import (ef_allreduce_tree,
                                                       init_error_tree)
            from repro.launch.mesh import make_mesh

            mesh = make_mesh((8,), ("pod",))
            g_global = jax.random.normal(jax.random.PRNGKey(0), (8, 256))
            true_mean = np.asarray(g_global).mean(0)

            def f(g, e):
                return ef_allreduce_tree({"w": g}, {"w": e}, "pod")

            fn = jax.shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                               out_specs=(P("pod"), P("pod")), check_vma=False)
            red, err = fn(g_global, jnp.zeros((8, 256)))
            got = np.asarray(red["w"])[0]
            rel = float(np.abs(got - true_mean).max()
                        / (np.abs(true_mean).max() + 1e-9))
            print(json.dumps({"rel_err": rel}))
        """)
        assert out["rel_err"] < 0.05, out
