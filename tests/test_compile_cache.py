"""The persistent compilation cache follows JAX_COMPILATION_CACHE_DIR when
it is set, and otherwise sits at one fixed place in the checkout."""
import os

import jax

from repro.launch import compile_cache

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_var_stands(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == first
        second = compile_cache.enable_compile_cache()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert first == second == os.path.join(CHECKOUT, ".jax_cache")
