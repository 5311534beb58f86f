"""The compile-cache rule (``utils/compile_cache.py``): a directory
placed from outside wins and nothing else is set; otherwise one fixed
path under the checkout."""
import os

import jax

from autodist_tpu.utils import compile_cache


def test_outside_placement_wins_and_sets_nothing(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_one_fixed_path_under_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.enable_compile_cache()
        assert compile_cache.enable_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    assert first == os.path.join(repo, ".jax_cache")
