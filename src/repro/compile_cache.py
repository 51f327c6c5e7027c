"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, the benchmarks) call ``enable_compile_cache``
once at start; importing this module changes nothing.  The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it — use it and set
  nothing else.
* otherwise: the fixed ``<checkout>/.jax_cache`` (ignored by git).  The path
  is part of the cache key, so it never derives from a temp name, a pid or
  the time.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
