"""Test harness: a simulated 8-device CPU mesh.

The reference tested multi-worker semantics against real TF servers over
SSH (SURVEY.md §4); this build exploits what the reference lacked — a
simulated mesh — so multi-"host" semantics are unit-testable without
hardware.
"""
import os

# Must run before the first jax backend initialization.
_flag = "--xla_force_host_platform_device_count=8"
if _flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

import ast

import pytest

# Modules that only work against real TPU silicon (or its libraries).
# A test module importing one of these at top level could crash
# during CPU collection, so every
# test in such a module must be tier-2 (``slow``); collection itself
# fails otherwise, naming the offenders.  Static top-level imports only:
# an import buried inside a function is the test's own runtime gate.
TPU_ONLY_IMPORT_PREFIXES = (
    "jax.experimental.pallas.tpu",
    "jax.experimental.mosaic",
    "jax._src.pallas.mosaic",
    "pltpu",
    "libtpu",
    "torch_xla",
    # the repo's own Pallas-kernel modules: CPU runs them in interpret
    # mode, which is minutes-per-test — tier-2 by policy
    "autodist_tpu.ops.flash_attention",
)


def _iter_module_level(node):
    """AST nodes outside function bodies (a buried import is the test's
    own runtime gate, not a collection hazard)."""
    for child in ast.iter_child_nodes(node):
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
            continue
        yield child
        yield from _iter_module_level(child)


def _tpu_only_imports(path: str) -> set:
    try:
        tree = ast.parse(open(path).read(), filename=path)
    except (OSError, SyntaxError):
        return set()
    found = set()
    for node in _iter_module_level(tree):
        names = []
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.module:
            names = [node.module] + [f"{node.module}.{a.name}"
                                     for a in node.names]
        for name in names:
            for prefix in TPU_ONLY_IMPORT_PREFIXES:
                if name == prefix or name.startswith(prefix + "."):
                    found.add(prefix)
    return found


def pytest_collection_modifyitems(config, items):
    cache: dict = {}
    offenders: dict = {}
    for item in items:
        path = str(getattr(item, "fspath", ""))
        if not path:
            continue
        if path not in cache:
            cache[path] = _tpu_only_imports(path)
        if cache[path] and item.get_closest_marker("slow") is None:
            offenders.setdefault(path, set()).update(cache[path])
    if offenders:
        lines = [f"  {p}: imports {sorted(mods)} but has unmarked tests"
                 for p, mods in sorted(offenders.items())]
        raise pytest.UsageError(
            "TPU-only imports in tier-1 test modules (mark the tests "
            "@pytest.mark.slow or move the import into the test):\n"
            + "\n".join(lines))
