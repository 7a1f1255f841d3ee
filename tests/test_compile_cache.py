"""The persistent compilation cache sits where the launchers say it does."""
import os

import jax

from repro.launch.compile_cache import use_compile_cache

CHECKOUT = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))


def test_env_cache_dir_is_left_alone(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_cache_dir_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache()
        assert path == os.path.join(CHECKOUT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert use_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
