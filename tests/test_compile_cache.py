"""Placement of JAX's persistent compilation cache by the entry points."""
import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.launch import compile_cache


@pytest.fixture
def cache_dir_config():
    """Restore the process-wide cache directory after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_env_var_wins_and_nothing_else_is_set(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/shared/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.place() == "/some/shared/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_var_places_cache_in_repo(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.place()
    assert got == str(compile_cache.REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    # <repo>/.jax_cache: next to src/, fixed (no temp name, pid or time)
    assert compile_cache.REPO_CACHE_DIR.name == ".jax_cache"
    assert (compile_cache.REPO_CACHE_DIR.parent / "src" / "repro").is_dir()
    assert compile_cache.place() == got
