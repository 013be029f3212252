"""Where the entry points' persistent compilation cache lands."""
from __future__ import annotations

import os

import jax
import pytest

from repro import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, cache_dir_config,
                                            tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_uses_the_fixed_ignored_checkout_dir(monkeypatch,
                                                        cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == path
    assert path == os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == path  # fixed, not per run
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
