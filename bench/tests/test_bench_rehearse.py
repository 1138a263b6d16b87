"""Each cell end to end at its rehearsal size on the CPU, sound and with
the timed path broken underneath.

Every run is a subprocess with its own compile cache directory, so the
checkout's ``.jax_cache`` is never written by these tests.
"""
import os
import subprocess
import sys

import pytest

import _benchpaths  # noqa: F401
from benchlib import spec

ROOT = spec.ROOT
CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]
SEED = 2**35 + 11          # larger than 32 signed bits hold


def _env(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("XLA_FLAGS", None)
    return env


def _bench(tmp_path, *args, timeout=600):
    return subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), *args],
        capture_output=True, text=True, timeout=timeout, cwd=tmp_path,
        env=_env(tmp_path))


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_of_each_cell(tmp_path, cell):
    res = _bench(tmp_path, "--workload", cell, "--rehearse", "--seed",
                 str(SEED), "--seconds", "2")
    assert res.returncode == 0, res.stderr[-4000:]
    assert res.stdout.strip() == "", "a rehearsal printed a metrics line"
    err = res.stderr
    assert "rehearsal: correct=True" in err, err[-4000:]
    assert "compilations inside the window: 0" in err
    assert "failed=0" in err
    # the checks are the last lines, each beside its limit
    tail = [ln for ln in err.strip().splitlines()][-6:-1]
    assert [ln.split()[2].rstrip(":") for ln in tail] == \
        ["dist_err", "malformed", "empty", "unanswered", "exh_miss"]


def test_traced_rehearsal_reads_the_per_layer_metrics(tmp_path):
    res = _bench(tmp_path, "--workload", CELLS[0], "--rehearse",
                 "--trace", "1", "--seed", "3", "--seconds", "2")
    assert res.returncode == 0, res.stderr[-4000:]
    assert "rehearsal: correct=True" in res.stderr
    assert "batch_fill.batch" in res.stderr and "tick_ms.batch" in res.stderr


def test_without_a_tpu_no_line_and_nonzero_exit(tmp_path):
    res = _bench(tmp_path, "--workload", CELLS[0], "--seed", "1",
                 "--seconds", "1", timeout=300)
    assert res.returncode != 0
    assert res.stdout.strip() == ""


def test_directory_with_only_the_benchmark_fails(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--rehearse"], capture_output=True,
        text=True, timeout=300, cwd=tmp_path, env=_env(tmp_path))
    assert res.returncode != 0
    assert res.stdout.strip() == ""
