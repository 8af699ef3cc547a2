"""Placement of JAX's persistent compilation cache by the entry points."""
import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.launch import compile_cache


@pytest.fixture
def cache_dir_config():
    """Restore the process-wide cache directory after the test."""
    was = jax.config.jax_compilation_cache_dir
    was_meta = jax.config.jax_compilation_cache_include_metadata_in_key
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      was_meta)
    cc.reset_cache()


def test_env_var_wins_and_nothing_else_is_set(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/shared/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.place() == "/some/shared/cache"
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("env", ["/some/shared/cache", None])
def test_cache_key_includes_metadata(monkeypatch, cache_dir_config, env):
    """A step that differs only in its op names (the phase scopes) must
    not load the other one's executable: its profile would show stale
    names."""
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    compile_cache.place()
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def test_unset_env_var_places_cache_in_repo(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.place()
    assert got == str(compile_cache.REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    # <repo>/.jax_cache: next to src/, fixed (no temp name, pid or time)
    assert compile_cache.REPO_CACHE_DIR.name == ".jax_cache"
    assert (compile_cache.REPO_CACHE_DIR.parent / "src" / "repro").is_dir()
    assert compile_cache.place() == got
