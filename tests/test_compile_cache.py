"""Where the entry points keep JAX's persistent compilation cache."""

import os

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def cache_dir_config():
    """Restore JAX's cache directory after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_variable_is_left_to_jax(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got == os.path.join(checkout, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    # fixed: calling again never moves it
    assert compile_cache.enable_compile_cache() == got
