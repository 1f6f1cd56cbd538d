"""Where JAX keeps its persistent compilation cache.

Every entry point (``bench/run.py`` through its harness,
``repro.launch.serve``, ``repro.launch.model_serve``) calls
:func:`use_compile_cache` before it compiles anything.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and this sets
  no other directory.
- Otherwise the cache lives at one fixed path inside the checkout,
  ``<repo>/.jax_cache`` (git-ignored).  The path is part of the cache
  key, so it never depends on a temp name, a pid or the time.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
