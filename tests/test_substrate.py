"""Substrate tests: optimizer, checkpoint (+elastic restore), fault
tolerance, gradient compression, data pipeline, serving engine."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # not in the container; vendored fallback
    from _hypothesis_fallback import given, settings, st

from repro.configs import get_config
from repro.data.tokens import TokenPipeline
from repro.distributed.compression import (compression_ratio,
                                           dequantize_int8, ef_allreduce_tree,
                                           init_error_tree, quantize_int8)
from repro.models import Model
from repro.train import (AdamW, WatchdogPolicy, constant_lr, latest_step,
                         plan_remesh, prune_checkpoints, restore_checkpoint,
                         run_with_recovery, save_checkpoint, warmup_cosine)


class TestOptimizer:
    def test_adamw_reduces_quadratic(self):
        opt = AdamW(lr=constant_lr(0.1), weight_decay=0.0)
        params = {"w": jnp.array([3.0, -2.0])}
        state = opt.init(params)
        for _ in range(200):
            grads = {"w": 2 * params["w"]}
            params, state, _ = opt.update(grads, state, params)
        assert float(jnp.abs(params["w"]).max()) < 0.05

    def test_grad_clip(self):
        opt = AdamW(lr=constant_lr(0.1), clip_norm=1.0)
        params = {"w": jnp.zeros(3)}
        state = opt.init(params)
        _, _, stats = opt.update({"w": jnp.full(3, 1e6)}, state, params)
        assert float(stats["grad_norm"]) > 1e5   # reported pre-clip

    def test_warmup_cosine_shape(self):
        sched = warmup_cosine(1e-3, warmup=10, total=100)
        lrs = [float(sched(jnp.int32(s))) for s in range(100)]
        assert lrs[0] < lrs[9] <= 1e-3 + 1e-9
        assert lrs[99] < lrs[50] < lrs[12]

    def test_moments_match_param_tree(self):
        cfg = get_config("internlm2-1.8b", smoke=True)
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        opt = AdamW(lr=constant_lr(1e-3))
        state = opt.init(params)
        assert (jax.tree_util.tree_structure(state.m)
                == jax.tree_util.tree_structure(params))


class TestCheckpoint:
    def _tree(self, key):
        k1, k2 = jax.random.split(key)
        return {"a": jax.random.normal(k1, (4, 8)),
                "nested": {"b": jax.random.normal(k2, (3,)),
                           "step": jnp.int32(7)}}

    def test_roundtrip(self):
        with tempfile.TemporaryDirectory() as d:
            tree = self._tree(jax.random.PRNGKey(0))
            save_checkpoint(d, 5, tree, extra={"note": "x"})
            restored, step, extra = restore_checkpoint(d, tree)
            assert step == 5 and extra["note"] == "x"
            for a, b in zip(jax.tree_util.tree_leaves(tree),
                            jax.tree_util.tree_leaves(restored)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_latest_and_prune(self):
        with tempfile.TemporaryDirectory() as d:
            tree = self._tree(jax.random.PRNGKey(1))
            for s in (1, 2, 3, 4):
                save_checkpoint(d, s, tree)
            assert latest_step(d) == 4
            prune_checkpoints(d, keep=2)
            assert latest_step(d) == 4
            with pytest.raises(Exception):
                restore_checkpoint(d, tree, step=1)

    def test_atomicity_no_partial_dir_visible(self):
        with tempfile.TemporaryDirectory() as d:
            tree = self._tree(jax.random.PRNGKey(2))
            save_checkpoint(d, 9, tree)
            names = os.listdir(d)
            assert names == ["step_00000009"], names  # no .tmp left behind

    def test_shape_mismatch_rejected(self):
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, 1, {"a": jnp.zeros((2, 2))})
            with pytest.raises(ValueError):
                restore_checkpoint(d, {"a": jnp.zeros((3, 3))})


class TestFaultTolerance:
    def test_watchdog_flags_stragglers(self):
        w = WatchdogPolicy(warmup_steps=3, multiplier=2.0, min_deadline_s=0.0)
        for _ in range(10):
            w.record(1.0)
        assert not w.is_straggler(1.5)
        assert w.is_straggler(3.0)

    def test_plan_remesh(self):
        assert plan_remesh(256) == (16, 16)
        assert plan_remesh(255) == (15, 16)   # one dead chip drops a TP group
        assert plan_remesh(15) is None

    def test_recovery_restores_and_completes(self):
        calls = {"fails": 0}
        completed = []
        saved = {"step": 0}

        def step_fn(step):
            if step == 5 and calls["fails"] < 2:
                calls["fails"] += 1
                raise RuntimeError("simulated preemption")
            completed.append(step)
            return {}

        def save(step):
            saved["step"] = step

        def restore():
            return saved["step"]

        final = run_with_recovery(step_fn, start_step=0, num_steps=10,
                                  save_fn=save, restore_fn=restore,
                                  checkpoint_every=2, max_retries=3)
        assert final == 10
        assert calls["fails"] == 2
        assert 9 in completed

    def test_recovery_gives_up_after_max_retries(self):
        def step_fn(step):
            raise RuntimeError("hard failure")

        with pytest.raises(RuntimeError):
            run_with_recovery(step_fn, start_step=0, num_steps=3,
                              save_fn=lambda s: None,
                              restore_fn=lambda: 0, max_retries=2)


class TestCompression:
    def test_quantize_roundtrip_error_bounded(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 3
        q, scale = quantize_int8(x)
        err = np.abs(np.asarray(dequantize_int8(q, scale) - x))
        assert err.max() <= float(scale) / 2 + 1e-6

    def test_ratio(self):
        tree = {"w": jnp.zeros((128, 128))}
        assert compression_ratio(tree) < 0.26

    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_property_quantize_idempotent_signs(self, seed):
        x = jax.random.normal(jax.random.PRNGKey(seed), (64,))
        q, scale = quantize_int8(x)
        deq = np.asarray(dequantize_int8(q, scale))
        big = np.abs(np.asarray(x)) > float(scale)
        assert np.all(np.sign(deq[big]) == np.sign(np.asarray(x)[big]))

    def test_error_feedback_mean_preserved_over_steps(self):
        """EF accumulates: the *running sum* of compressed reductions tracks
        the running sum of true means (the EF-SGD guarantee)."""
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        if jax.device_count() < 1:
            pytest.skip("no devices")
        mesh = make_mesh((1,), ("pod",))

        grads = {"w": jax.random.normal(jax.random.PRNGKey(3), (1, 64))}
        err = init_error_tree({"w": jnp.zeros((1, 64))})

        def f(g, e):
            return ef_allreduce_tree(g, e, "pod")

        fn = jax.shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                           out_specs=(P("pod"), P("pod")), check_vma=False)
        total_reduced = jnp.zeros(64)
        for _ in range(10):
            red, err = fn(grads, err)
            total_reduced = total_reduced + red["w"][0]
        true_total = grads["w"][0] * 10
        # EF guarantee: cumulative error stays bounded by one quantisation step
        q, scale = quantize_int8(grads["w"][0])
        assert float(jnp.abs(total_reduced - true_total).max()) \
            <= float(scale) + 1e-5


class TestPipeline:
    def test_deterministic_and_resumable(self):
        cfg = get_config("internlm2-1.8b", smoke=True)
        pipe = TokenPipeline(cfg, 8, 16, seed=3)
        a = pipe.batch_at(7)
        b = pipe.batch_at(7)
        np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                      np.asarray(b["tokens"]))
        c = pipe.batch_at(8)
        assert not np.array_equal(np.asarray(a["tokens"]),
                                  np.asarray(c["tokens"]))

    def test_host_slice_consistent(self):
        cfg = get_config("internlm2-1.8b", smoke=True)
        pipe = TokenPipeline(cfg, 8, 16, seed=3)
        part = pipe.batch_at(5, lo=0, hi=4)
        assert part["tokens"].shape == (4, 17)

    def test_family_extras(self):
        for arch in ("whisper-large-v3", "llama-3.2-vision-90b"):
            cfg = get_config(arch, smoke=True)
            pipe = TokenPipeline(cfg, 2, 8)
            b = pipe.batch_at(0)
            if cfg.family == "encdec":
                assert b["frames"].shape == (2, 8, cfg.d_model)
            if cfg.family == "vlm":
                assert b["image_embeds"].shape == (
                    2, cfg.num_image_tokens, cfg.d_model)


class TestTrainDriver:
    def test_loss_decreases_and_resumes(self, tmp_path):
        # "periodic" token data has next-token-predictable structure, so
        # the loss trend is real learning rather than noise around the
        # entropy floor (the old "uniform" mode made this flaky: random
        # tokens have nothing to learn and the trend was a coin flip).
        # Seed 0 at lr=3e-3 / 24 steps drops ~0.7 nats on CPU.
        from repro.launch.train import train
        ckpt = str(tmp_path / "ck")
        _, losses = train("internlm2-1.8b", smoke=True, steps=24, batch=4,
                          seq=32, ckpt_dir=ckpt, checkpoint_every=12,
                          lr=3e-3, kv_chunk=32, seed=0,
                          data_mode="periodic")
        assert losses[-1] < losses[0]
        assert latest_step(ckpt) == 24
        # resume continues from the checkpoint
        _, losses2 = train("internlm2-1.8b", smoke=True, steps=4, batch=4,
                           seq=32, ckpt_dir=ckpt, checkpoint_every=100,
                           lr=3e-3, kv_chunk=32, seed=0,
                           data_mode="periodic")
        assert len(losses2) == 4


class TestEngine:
    def test_batched_serving_drains(self):
        from repro.serve import Engine, Request
        cfg = get_config("internlm2-1.8b", smoke=True)
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        eng = Engine(model, params, slots=2, max_len=48)
        rng = np.random.default_rng(0)
        for rid in range(4):
            eng.submit(Request(rid=rid,
                               prompt=rng.integers(0, cfg.vocab_size, 8,
                                                   dtype=np.int32),
                               max_new_tokens=5))
        reqs = list(eng.queue)
        eng.run_until_drained(max_ticks=200)
        assert not eng.queue
        for r in reqs:
            assert r.done and len(r.generated) >= 5
