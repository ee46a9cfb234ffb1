"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, the benchmarks, the examples) call
:func:`use_compile_cache` once, before their first compile; importing
``repro`` never does.  The cache's path is part of what it is keyed on,
so it is always a fixed directory, never one named after a temp dir, a
pid or a time.
"""

from __future__ import annotations

import os

#: The repository checkout this package runs from (``src/repro/..``).
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that directory
    itself and nothing is set here.  Otherwise the cache goes to
    ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
