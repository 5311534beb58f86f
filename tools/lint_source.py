"""AST repo lint: raw collectives must route through the policy layer.

PR 8 gave every collective boundary a per-collective precision slot —
but only because each lowering routes its collectives through the
sanctioned wrappers (``parallel/tensor.py``'s ``precision_scope``
primitives, ``kernel/``'s ``zero3_gather``/quantize/compressor
helpers).  A new lowering calling ``lax.psum`` / ``lax.all_gather`` /
``lax.psum_scatter`` directly would silently bypass the policy (and the
cost model's wire accounting), so this linter forbids raw calls outside
the sanctioned modules:

* ``autodist_tpu/parallel/tensor.py`` — the precision primitives
* ``autodist_tpu/kernel/`` — the quantize/compressor/gather layer

A deliberate exception (a collective that is *not* a policied data
boundary — e.g. the pipeline's pipe-axis role reductions) carries an
inline pragma on the call line or the line above::

    gp = lax.psum(g, pipe_axis)  # lint: allow-raw-collective — <why>

Violations are ``ADT201`` diagnostics (file:line); rc 1 on any.
Tier-1 runs this over ``autodist_tpu/`` so the rule holds for every
future lowering.

    python tools/lint_source.py            # lint autodist_tpu/
    python tools/lint_source.py --check    # CI spelling (compact)
"""
from __future__ import annotations

import argparse
import ast
import json
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

# Raw collective calls that must route through the policy layer.
FORBIDDEN = ("psum", "all_gather", "psum_scatter")

# Modules allowed to touch lax collectives directly (repo-relative,
# forward slashes; directories end with "/").
ALLOWED = ("autodist_tpu/parallel/tensor.py",
           "autodist_tpu/kernel/")

PRAGMA = "lint: allow-raw-collective"

FIX = ("route through autodist_tpu.parallel.tensor (precision_scope "
       "primitives) or kernel/ helpers (zero3_gather, quantize), or "
       f"annotate '# {PRAGMA} — <reason>' for a non-policied boundary")


def _lax_aliases(tree: ast.AST) -> tuple[dict, set]:
    """Every local spelling of a forbidden collective in this module:
    ``(bare_names, module_aliases)`` where ``bare_names`` maps a local
    name to the collective it binds (``from jax.lax import psum as p``)
    and ``module_aliases`` holds every name bound to the lax module
    (``from jax import lax``, ``import jax.lax as jl``)."""
    bare: dict[str, str] = {}
    modules: set[str] = {"lax"}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            if node.module in ("jax.lax", "jax._src.lax"):
                for a in node.names:
                    if a.name in FORBIDDEN:
                        bare[a.asname or a.name] = a.name
            elif node.module == "jax":
                for a in node.names:
                    if a.name == "lax":
                        modules.add(a.asname or "lax")
        elif isinstance(node, ast.Import):
            for a in node.names:
                # `import jax.lax as jl` -> jl.psum; the un-aliased
                # `import jax.lax` form calls jax.lax.psum, which the
                # attribute-chain branch below already catches.
                if a.name == "jax.lax" and a.asname:
                    modules.add(a.asname)
    return bare, modules


def _is_lax_collective(node: ast.Call, bare: dict, modules: set):
    """``lax.psum(...)`` / ``jax.lax.psum(...)`` / aliased-module /
    from-imported spellings of a forbidden collective; returns the
    dotted name or None."""
    fn = node.func
    if isinstance(fn, ast.Name) and fn.id in bare:
        return bare[fn.id]
    if not isinstance(fn, ast.Attribute) or fn.attr not in FORBIDDEN:
        return None
    base = fn.value
    if isinstance(base, ast.Name) and base.id in modules:
        return f"{base.id}.{fn.attr}"
    if isinstance(base, ast.Attribute) and base.attr == "lax":
        return f"jax.lax.{fn.attr}"
    return None


def lint_file(path: str, rel: str) -> list:
    """ADT201 diagnostics for one file (empty = clean)."""
    from autodist_tpu.analysis.diagnostics import Diagnostic

    try:
        source = open(path).read()
        tree = ast.parse(source, filename=path)
    except (OSError, SyntaxError) as e:
        return [Diagnostic("ADT201", f"unparseable: {e}", where=rel)]
    lines = source.splitlines()
    bare, modules = _lax_aliases(tree)
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _is_lax_collective(node, bare, modules)
        if name is None:
            continue
        ln = node.lineno
        context = " ".join(lines[max(ln - 2, 0):ln])
        if PRAGMA in context:
            continue
        out.append(Diagnostic(
            "ADT201",
            f"raw {name}() in a lowering module bypasses the "
            "per-collective precision policy",
            where=f"{rel}:{ln}", fix=FIX, rule="no_raw_collective"))
    return out


def lint_tree(root: str) -> list:
    """Lint every .py under ``root`` (package-relative allowlist)."""
    diags = []
    root = os.path.abspath(root)
    repo = os.path.dirname(root)
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, repo).replace(os.sep, "/")
            if any(rel == a or (a.endswith("/") and rel.startswith(a))
                   for a in ALLOWED):
                continue
            diags.extend(lint_file(path, rel))
    return diags


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="forbid raw lax collectives outside the policy "
                    "layer (ADT201)")
    ap.add_argument("--root", default=None,
                    help="package root to lint (default: the repo's "
                         "autodist_tpu/)")
    ap.add_argument("--json", metavar="PATH", default=None)
    ap.add_argument("--check", action="store_true",
                    help="CI spelling: compact output, same rc")
    args = ap.parse_args(argv)
    root = args.root or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "autodist_tpu")
    diags = lint_tree(root)
    if args.json:
        with open(args.json, "w") as f:
            json.dump([d.to_dict() for d in diags], f, indent=1)
    if diags:
        for d in diags:
            print(d)
        print(f"{len(diags)} raw-collective violation(s)")
        return 1
    if not args.check:
        print(f"source lint clean ({root})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
