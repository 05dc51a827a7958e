"""The persistent compilation cache is placed from outside, or fixed."""
import os

import jax
import pytest

from repro.configs import backend


@pytest.fixture
def cache_config():
    """Restore the process's cache directory after the test."""
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_environment_cache_dir_wins(cache_config, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert backend.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # set no dir


def test_cache_dir_is_fixed_inside_the_checkout(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert backend.compile_cache_dir() == want
    assert backend.use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
