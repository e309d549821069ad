"""The persistent compile cache lives in exactly one directory:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``."""
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.utils.compile_cache import DEFAULT_DIR, enable_compile_cache


@pytest.fixture()
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    compilation_cache.reset_cache()
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_default_dir_is_the_checkout_root():
    root = Path(__file__).resolve().parents[1]
    assert DEFAULT_DIR == root / ".jax_cache"
    gitignore = (root / ".gitignore").read_text().split()
    assert ".jax_cache/" in gitignore


def test_unset_env_uses_the_checkout_dir(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)


def test_env_dir_gets_every_entry(monkeypatch, tmp_path, restore_cache_config):
    cache = tmp_path / "cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    assert enable_compile_cache() == str(cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.arange(7.0)).block_until_ready()
    assert any(cache.iterdir())
