"""Where ``use_persistent_cache`` puts JAX's compilation cache."""

from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_setting():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_unset_env_uses_fixed_checkout_path(monkeypatch, cache_dir_setting):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    used = compile_cache.use_persistent_cache()
    assert used == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == used
    assert compile_cache.use_persistent_cache() == used


def test_env_is_left_to_jax(monkeypatch, tmp_path, cache_dir_setting):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_persistent_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_checkout_cache_is_gitignored():
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
