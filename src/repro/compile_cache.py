"""Where JAX keeps its persistent compilation cache.

A cache hit needs the same directory as the run that wrote it (the path
is part of the key), so the directory is fixed: ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it — JAX reads that variable itself and this
module sets nothing else — and otherwise ``.jax_cache/`` at the root of
the checkout.  Entry points call ``enable_compile_cache()`` from their
``main()``; importing a module never places the cache.
"""
from __future__ import annotations

import os

#: the checkout's root (this file is ``src/repro/compile_cache.py``)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
