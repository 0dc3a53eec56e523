"""Persistent compilation cache at a place that can be set from outside.

Entry points call ``use_compile_cache()`` first thing in ``main()`` —
never at import. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and nothing is changed here. Otherwise the cache goes to the
fixed directory ``.jax_cache/`` at the root of the checkout (listed in
``.gitignore``): the directory is part of the cache key, so it is never
derived from a temporary directory, a process id or the time.
"""
from __future__ import annotations

import os
import pathlib

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``).
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
