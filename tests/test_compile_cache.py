"""Where the entry points put JAX's persistent compilation cache."""

from __future__ import annotations

from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_config():
    """Leave the persistent cache as it was for later tests in the worker."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_env_var_is_left_to_jax(monkeypatch, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == "/cache/from/env"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_dir_in_checkout(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.use_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.use_compile_cache() == path
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
