"""Where the entry points keep JAX's persistent compilation cache."""
from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch.compile_cache import ENV_VAR, enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_env_var_wins_and_receives_the_entries(monkeypatch, tmp_path,
                                               restore_cache_config):
    env_dir = tmp_path / "cache"
    monkeypatch.setenv(ENV_VAR, str(env_dir))
    checkout = REPO / ".jax_cache"
    before = sorted(checkout.iterdir()) if checkout.exists() else []
    assert enable_compile_cache() == str(env_dir)
    assert jax.config.jax_compilation_cache_dir == str(env_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.arange(7.0)).block_until_ready()
    assert any(env_dir.iterdir())
    after = sorted(checkout.iterdir()) if checkout.exists() else []
    assert after == before


def test_checkout_directory_without_env(monkeypatch, restore_cache_config):
    monkeypatch.delenv(ENV_VAR, raising=False)
    path = enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
