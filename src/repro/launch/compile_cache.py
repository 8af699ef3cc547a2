"""Where JAX keeps its persistent compilation cache.

Call :func:`place` once, before the first compile, from every entry point
(``chip_smoke.py``, ``examples/``).  The cache directory is part of the
cache key, so it is a fixed path — never a temp name, a pid or a time.
"""
from __future__ import annotations

import os
import pathlib

#: ``<repo>/.jax_cache`` — this file is ``<repo>/src/repro/launch/``.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def place() -> str:
    """Directory of the persistent compilation cache, set if unset.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here; otherwise the cache goes to
    ``<repo>/.jax_cache``.

    Either way the cache key includes the program's metadata: without it
    a step whose ops gained or lost a ``lags/<phase>`` scope loads the
    executable compiled before, and a profile shows that one's op names.
    """
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
