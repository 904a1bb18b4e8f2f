"""JAX's persistent compilation cache for the entry points.

A process that compiles the full-width serve step pays for it once per
cache directory, not once per run. ``JAX_COMPILATION_CACHE_DIR``, when set,
is read by JAX itself and wins; otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (git-ignored). The path is part of what makes a
later run hit, so it is never derived from a temporary name, a process id
or the time.
"""
from __future__ import annotations

import os
import pathlib

#: the checkout's root: src/repro/launch/compile_cache.py -> four levels up
DEFAULT_CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[3]
                        / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
