"""Run manifest + provenance: the identity stamp of a measurement.

One schema for what used to live in two places: the git-SHA/jax-version
record of every BENCH_r*.json row (written by a since-deleted script) and
``examples/pipeline_train.py``'s hand-rolled ``step_times.json``.  A
hardware window's numbers must stay interpretable months later — the
manifest records exactly which code and stack produced them.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import Optional

# Env vars worth recording: the launch-config plane that changes what a
# run measures.
_MANIFEST_ENV = (
    "AUTODIST_TPU_WORKER", "AUTODIST_TPU_STRATEGY_ID",
    "AUTODIST_TPU_NUM_PROCESSES", "AUTODIST_TPU_PROCESS_ID",
    "AUTODIST_TPU_GENERATION", "AUTODIST_TPU_ASYNC_COLLECTIVES",
    "AUTODIST_TPU_TELEMETRY", "AUTODIST_TPU_TELEMETRY_SAMPLE",
    "JAX_PLATFORMS", "XLA_FLAGS",
)

_provenance_cache: dict[str, dict] = {}


def provenance(repo_root: Optional[str] = None, refresh: bool = False) -> dict:
    """Identity stamp: git SHA + jax/jaxlib/python versions (the exact
    keys the BENCH_r*.json rows carry — ``git_sha``/``jax``/
    ``jaxlib`` — so BENCH record consumers keep working).  Cached per
    root: the answer cannot change within a process, but different
    callers may stamp different checkouts."""
    root = repo_root or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if root in _provenance_cache and not refresh:
        return dict(_provenance_cache[root])
    rec: dict = {}
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=root,
            capture_output=True, text=True, timeout=10).stdout.strip()
        rec["git_sha"] = sha or None
    except (OSError, subprocess.SubprocessError):
        rec["git_sha"] = None
    try:
        import jax

        rec["jax"] = getattr(jax, "__version__", None)
    except ImportError:  # pragma: no cover - jax is a hard dep
        rec["jax"] = None
    try:
        import jaxlib

        rec["jaxlib"] = getattr(jaxlib, "__version__", None)
    except ImportError:  # pragma: no cover
        rec["jaxlib"] = None
    rec["python"] = sys.version.split()[0]
    _provenance_cache[root] = rec
    return dict(rec)


def build_manifest(annotations: Optional[dict] = None,
                   telemetry: Optional[dict] = None) -> dict:
    """The run-manifest dict ``Telemetry.flush`` writes as
    ``manifest.json``: provenance + launch env + run annotations."""
    manifest = {
        "kind": "manifest",
        "created_unix": time.time(),
        "pid": os.getpid(),
        "argv": list(sys.argv),
        "provenance": provenance(),
        "env": {k: os.environ[k] for k in _MANIFEST_ENV
                if k in os.environ},
    }
    if annotations:
        manifest["run"] = dict(annotations)
    if telemetry:
        manifest["telemetry"] = dict(telemetry)
    return manifest
