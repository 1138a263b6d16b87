"""Persistent compilation cache location (repro.utils.compile_cache)."""
import os
import random
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.utils import compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_default_is_checkout_dot_jax_cache(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.CHECKOUT == REPO
    assert compile_cache.cache_dir() == REPO / ".jax_cache"


def test_env_var_wins(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.cache_dir() == tmp_path


@pytest.mark.parametrize("use_env", [True, False])
def test_entries_land_only_in_the_cache_dir(tmp_path, use_env):
    """A compile through the helper writes entries where cache_dir() says
    and nowhere else: the env dir when set (the checkout's cache untouched),
    else the checkout's ``.jax_cache``."""
    default = REPO / ".jax_cache"
    before = set(os.listdir(default)) if default.exists() else set()
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    env.pop(compile_cache.ENV_VAR, None)
    if use_env:
        env[compile_cache.ENV_VAR] = str(tmp_path)
    # a program no earlier run compiled, so the entry is new either way
    salt = 1.0 + random.random()
    code = textwrap.dedent(f"""
        import jax, jax.numpy as jnp
        from repro.utils.compile_cache import enable_compile_cache
        print(enable_compile_cache())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.cos(x) * {salt!r})(jnp.ones(7)).block_until_ready()
    """)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env=env)
    assert res.returncode == 0, res.stderr
    after = set(os.listdir(default)) if default.exists() else set()
    if use_env:
        assert res.stdout.split()[0] == str(tmp_path)
        assert any(n.endswith("-cache") for n in os.listdir(tmp_path))
        assert after == before
    else:
        assert res.stdout.split()[0] == str(default)
        assert any(n.endswith("-cache") for n in after - before)
