"""A cell on the chips it names, rehearsed on four CPU devices: the fleet
laid out shard by shard over a four-device mesh, the reference scanned
shard by shard, the refine roofline per chip, and a four-chip cell end to
end.

Multi-device runs are subprocesses that set ``XLA_FLAGS`` before JAX is
imported, each with its own compile cache directory.
"""
import json
import shutil
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import pytest

import _benchpaths  # noqa: F401
from benchlib import spec, trace
from test_bench_rehearse import SEED, _env

CHIPS = 4
PRELUDE = f"""
import json, sys
sys.path[:0] = [{str(spec.ROOT / "src")!r}, {str(spec.BENCH)!r}]
import jax
import numpy as np
assert len(jax.devices()) == {CHIPS}, jax.devices()
"""


def _on_four_devices(body: str, tmp_path) -> dict:
    """Run ``body`` on four CPU devices; its last line of output is JSON."""
    env = _env(tmp_path)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={CHIPS}"
    res = subprocess.run(
        [sys.executable, "-c", PRELUDE + textwrap.dedent(body)],
        capture_output=True, text=True, timeout=600, cwd=tmp_path, env=env)
    assert res.returncode == 0, res.stderr[-4000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def _four_shard_config() -> dict:
    """``rw256-paper`` as four shards of its one-chip size."""
    bm = spec.load_benchmark()
    cfg = spec.load_config(bm, "rw256-paper")
    return dict(cfg, num_series=CHIPS * cfg["num_series"],
                corpus=dict(cfg["corpus"], shards=CHIPS))


@pytest.fixture(scope="module")
def four_shard_fleet(tmp_path_factory):
    """``serve.build`` of the four-shard configuration at its rehearsal
    size on four devices: where each shard's index and mesh slab live, and
    the exhaustive route's answers through the server beside the
    per-shard reference's."""
    tmp_path = tmp_path_factory.mktemp("fleet")
    cfg = _four_shard_config()
    return _on_four_devices(f"""
        from benchlib import data, reference, serve, spec
        from repro.eval.metrics import answers_agree
        from repro.serve.net import serve_in_thread
        cfg, traffic = spec.rehearsal(json.loads({json.dumps(cfg)!r}),
                                      spec.load_traffic("rw256-k500-batch"))
        n, k = cfg["climber"]["series_len"], cfg["serving"]["k"]
        shards = data.make_corpus(cfg["corpus"], n, cfg["num_series"])
        corpus = np.concatenate(shards)
        engine = serve.build(cfg, shards, {CHIPS})
        fleet = engine.fleet
        index_on = [[str(d) for d in sh.index.store.data.devices()]
                    for sh in fleet.shards]
        store = fleet._ensure_placement().store
        live = {{str(s.device): int(np.asarray(s.data).sum())
                for s in store.count.addressable_shards}}
        slabs = {{str(s.device): list(range(*s.index[0].indices(
                     store.data.shape[0])))
                 for s in store.data.addressable_shards}}
        queries = data.make_queries(corpus, 6, 0.05, {SEED})
        server, stop = serve_in_thread(engine)
        try:
            dist, gid = serve.exhaustive_answers(server.port, engine,
                                                 queries)
        finally:
            stop()
        ref_d2, ref_ids = reference.exact_topk(
            corpus, queries, k, shards=len(shards),
            devices=jax.devices()[:{CHIPS}])
        ok, why = answers_agree(dist, gid, np.sqrt(ref_d2), ref_ids,
                                scale=2 * n)
        print(json.dumps({{
            "devices": [str(d) for d in jax.devices()],
            "per": len(shards[0]), "index_on": index_on, "live": live,
            "slabs": slabs, "k": int(gid.shape[1]), "agree": ok,
            "why": why}}))
    """, tmp_path)


def test_build_puts_each_shard_on_its_own_device(four_shard_fleet):
    out = four_shard_fleet
    devices = out["devices"]
    assert out["index_on"] == [[d] for d in devices]
    assert out["slabs"] == {d: [j] for j, d in enumerate(devices)}
    assert out["live"] == {d: out["per"] for d in devices}


def test_exhaustive_answers_equal_the_per_shard_reference(four_shard_fleet):
    out = four_shard_fleet
    assert out["k"] == 500
    assert out["agree"], out["why"]


def test_per_shard_scan_equals_the_single_scan(tmp_path):
    """On a corpus whose row count the whole-corpus chunking refuses: four
    shards of 20,000 rows, with the chunk cut as 8,000,000 rows meet the
    262,144-row chunk (31 chunks, 8,000,000 % 31 = 16).  The single scan
    runs in chunks of the length each shard is cut into, so both compute
    every distance with one program and near ties fall alike."""
    out = _on_four_devices("""
        from benchlib import data, reference
        from repro.eval.metrics import answers_agree
        n, per, k = 64, 20_000, 500
        corpus = np.asarray(data.random_walk(jax.random.PRNGKey(3),
                                             4 * per, n))
        queries = data.make_queries(corpus, 16, 0.05, 9)
        reference.MAX_CHUNK = 2500
        one_d2, one_ids = reference.exact_topk(corpus, queries, k)
        reference.MAX_CHUNK = 2621
        try:
            reference._chunking(len(corpus))
            refused = False
        except ValueError:
            refused = True
        d2, ids = reference.exact_topk(corpus, queries, k, shards=4,
                                       devices=jax.devices())
        ok, why = answers_agree(np.sqrt(d2), ids, np.sqrt(one_d2), one_ids,
                                scale=2 * n)
        print(json.dumps({"refused": refused, "agree": ok, "why": why,
                          "chunk": reference._chunking(per),
                          "shape": list(ids.shape)}))
    """, tmp_path)
    assert out["refused"]
    assert out["chunk"] == 2500
    assert out["shape"] == [16, 500]
    assert out["agree"], out["why"]


@pytest.mark.parametrize("chips", [1, CHIPS])
def test_roofline_is_per_chip(chips):
    """The trace's kernel time is a mean over the chips, so the bytes of
    every shard are held against every chip's bandwidth."""
    read = spec.load_reader("refine_topk_roofline")
    summary = trace.TraceSummary(
        window_s=1.0, busy_s=0.5, chips=chips,
        family_s={"climber_refine_topk": 0.25}, top_ops=[], idle_gaps=[])
    run = SimpleNamespace(trace=summary, refine_bytes=lambda: 4 * 10**9,
                          device_kind="TPU v5 lite")
    all_chips = 100.0 * (4e9 / 819e9) / 0.25
    assert read(run) == pytest.approx(all_chips / chips, rel=1e-12)


def test_rehearsal_of_a_four_chip_cell(tmp_path):
    """A checkout whose ``BENCHMARK.json`` adds a four-chip cell of four
    shards: its rehearsal lays the fleet over four CPU devices and reads
    correct."""
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(spec.ROOT / "src")
    bm = spec.load_benchmark()
    base = next(c for c in bm["configs"] if c["name"] == "rw256-paper")
    (root / "bench/configs/rw256x4.json").write_text(
        json.dumps(dict(_four_shard_config(), name="rw256x4")))
    bm["configs"].append(dict(base, name="rw256x4",
                              file="bench/configs/rw256x4.json"))
    bm["workloads"].append({
        "name": "rw256x4-k500-batch", "config": "rw256x4",
        "traffic": "rw256-k500-batch", "chips": CHIPS,
        "why": "one rw256-paper shard per chip"})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    res = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rw256x4-k500-batch",
         "--rehearse", "--seed", str(SEED), "--seconds", "2"],
        capture_output=True, text=True, timeout=600, cwd=root,
        env=_env(tmp_path))
    assert res.returncode == 0, res.stderr[-4000:]
    err = res.stderr
    assert f"cpu cpu x{CHIPS} (REHEARSAL" in err, err[-4000:]
    assert f"over {CHIPS} chip(s)" in err
    assert "rehearsal: correct=True" in err, err[-4000:]
    assert "failed=0" in err
    assert "compilations inside the window: 0" in err
