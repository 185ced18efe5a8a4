"""JAX's persistent compilation cache, placed for the entry points.

A cold process spends most of a short run compiling; the persistent cache
lets the next process with the same programs skip that.  The cache key
includes its directory, so the directory must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX already reads it; nothing else
  is set here, so entries land there and nowhere else;
* unset — ``<checkout>/.jax_cache``, a fixed path inside the checkout
  (listed in ``.gitignore``).

Call :func:`enable` at the start of an entry point (the solve and serve
CLIs, ``benchmarks/run.py``, ``chip_smoke.py``), never on import: library
users and the tests decide for themselves.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

# src/repro/utils/compile_cache.py -> the checkout root
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
