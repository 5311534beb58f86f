"""Where jax keeps compiled programs between runs.

Every chip run that starts with no compiled code pays the whole
compilation again (BERT-base's train step alone is ~40 s on a v5e), so
the entry points — ``chip_smoke.py``, ``benchmark/run.py``, the
``examples/`` — call :func:`enable_compile_cache` first thing.  One rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself; this module
  sets no directory, so whoever placed the cache from outside keeps it.
* otherwise: ``<checkout>/.jax_cache`` — a fixed path derived from this
  file's location.  The path is part of the cache key's environment, so
  it never comes from ``tempfile``, a pid or the clock: a directory that
  moves never hits.

Either way the key covers what a profiler trace reads.  jax leaves op
metadata (the ``named_scope`` path, the source line) out of the cache
key by default, so a program compiled before its ops wore the scopes of
``autodist_tpu.telemetry.SCOPES`` would be a cache *hit* for the scoped
one and come back without them: every scope share of a traced run would
read "unscoped", silently.  :func:`enable_compile_cache` therefore puts
the metadata into the key, and cuts each location to the op's own frame
(``jax_traceback_in_locations_limit=1``) so that the key follows the
lines of the model code and not those of every caller.  Not
``jax_include_full_tracebacks_in_locations=False``, which reads like the
same thing: under it jax 0.9.0 moves the scope path out of ``op_name``
(``"dot_general"`` where it was ``"jit(f)/attention/dot_general"``) and
the trace finds no scope at all.
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
