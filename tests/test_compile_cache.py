"""Where JAX's persistent compilation cache goes: the environment's
``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` in the
checkout."""
import os

import jax

from repro import compile_cache


def test_environment_cache_dir_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the code sets no other directory
    assert jax.config.jax_compilation_cache_dir == before


def test_default_cache_dir_is_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(compile_cache.REPO_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
