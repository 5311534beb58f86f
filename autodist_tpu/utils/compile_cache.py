"""Where jax keeps compiled programs between runs.

Every chip run that starts with no compiled code pays the whole
compilation again (BERT-base's train step alone is ~40 s on a v5e), so
the entry points — ``chip_smoke.py``, ``bench.py``, the ``examples/`` —
call :func:`enable_compile_cache` first thing.  One rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself; this module
  sets nothing, so whoever placed the cache from outside keeps it.
* otherwise: ``<checkout>/.jax_cache`` — a fixed path derived from this
  file's location.  The path is part of the cache key's environment, so
  it never comes from ``tempfile``, a pid or the clock: a directory that
  moves never hits.
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
