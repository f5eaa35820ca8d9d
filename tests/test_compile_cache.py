"""Where the entry points put JAX's persistent compilation cache."""

import pathlib

import jax
import pytest

from repro import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Restore the cache directory a test may set."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_environment_variable_wins(cache_config, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


def test_default_is_gitignored_dir_in_checkout(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.DEFAULT_DIR == ROOT / ".jax_cache"
    path = compile_cache.use_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def test_installed_package_leaves_cache_off(cache_config, monkeypatch):
    """Outside a checkout there is no fixed place of its own: no cache."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR", None)
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == was
