"""Where the entry points put JAX's persistent compile cache."""
import os

import jax

from repro.launch import compile_cache


def test_env_dir_wins_and_nothing_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_under_the_repo(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        # Same path on every call: no temp name, pid or time in it.
        assert compile_cache.enable_compile_cache() == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        cc.reset_cache()
