"""JAX's persistent compilation cache, at one fixed place.

``enable_compile_cache()`` is called by every entry point that compiles the
detection path (``chip_smoke.py``, ``repro.launch.serve``,
``examples/quickstart.py``) before it compiles anything:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing else is
  set in code.
* unset: the cache goes to ``<checkout>/.jax_cache`` (gitignored).  The
  path is fixed — never temporary, per-process or per-run — because a
  cache that moves never hits.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
