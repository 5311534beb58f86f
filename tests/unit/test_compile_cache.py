"""The compile-cache rule (``utils/compile_cache.py``): a directory
placed from outside wins and no other is set; otherwise one fixed path
under the checkout.  Either way the key covers the ops' metadata (that
the scopes survive a shared cache is ``test_program_spans.py``'s)."""
import os

import jax
import pytest

from autodist_tpu.utils import compile_cache

KEY_SETTINGS = {"jax_compilation_cache_include_metadata_in_key": True,
                "jax_traceback_in_locations_limit": 1}


@pytest.fixture(autouse=True)
def key_settings_put_back():
    before = {n: getattr(jax.config, n) for n in KEY_SETTINGS}
    yield
    for n, v in before.items():
        jax.config.update(n, v)


@pytest.mark.parametrize("placed", ["/some/dir", None])
def test_the_key_covers_what_a_trace_reads(monkeypatch, placed):
    before = jax.config.jax_compilation_cache_dir
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        compile_cache.enable_compile_cache()
        assert {n: getattr(jax.config, n)
                for n in KEY_SETTINGS} == KEY_SETTINGS
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


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
