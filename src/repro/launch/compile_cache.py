"""Where JAX keeps its persistent compilation cache.

One helper, :func:`enable_compile_cache`, called from the ``main()`` of
each entry point (``launch/mine.py``, ``launch/serve_motifs.py``,
``benchmarks/run.py``, ``chip_smoke.py``) and never at import:

* when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
  code sets no other directory;
* otherwise the cache goes to the fixed path ``<checkout>/.jax_cache``
  (listed in ``.gitignore``).  A cache path is part of the cache's key, so
  it is never built from a temporary name, a process id or the time.
"""

from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the repository checkout: src/repro/launch/ -> three levels up
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
