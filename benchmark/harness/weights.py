"""Weights from the seed, on the device, in one jitted call.

A shape tree (nested dicts of ``(shape, dtype)``) becomes arrays: a leaf
named ``scale`` (or ending ``_scale``) is ones, one named ``bias`` (or
ending ``_bias``) zeros, every other normal x ``std`` (the configuration's
``initializer_range``) under a key folded from the seed and the leaf's
name.  The program and the plain
reference are both handed what this makes; neither makes weights.
"""
from __future__ import annotations

import zlib

def _leaves(tree, prefix=()):
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            yield from _leaves(v, prefix + (k,))
        else:
            yield prefix + (k,), v


def _unflatten(items):
    out: dict = {}
    for path, v in items:
        d = out
        for k in path[:-1]:
            d = d.setdefault(k, {})
        d[path[-1]] = v
    return out


def seeded_fill(shape_tree: dict, seed: int, std: float) -> dict:
    """Arrays for ``shape_tree`` from ``seed`` (any whole number: it is
    folded to 31 bits), on jax's default device."""
    import jax
    import jax.numpy as jnp

    leaves = list(_leaves(shape_tree))

    def make(key):
        out = []
        for path, (shape, dtype) in leaves:
            last = path[-1]
            if last == "scale" or last.endswith("_scale"):
                arr = jnp.ones(shape, dtype)
            elif last == "bias" or last.endswith("_bias"):
                arr = jnp.zeros(shape, dtype)
            else:
                k = jax.random.fold_in(
                    key, zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF)
                arr = (jax.random.normal(k, shape, jnp.float32)
                       * std).astype(dtype)
            out.append(arr)
        return out

    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    arrays = jax.jit(make)(key)
    return _unflatten(zip((p for p, _ in leaves), arrays))


def host_rng(seed: int, stream: str):
    """A numpy generator for host-side inputs, keyed by the seed and the
    name of what it draws, so two streams never share draws."""
    import numpy as np

    return np.random.default_rng([seed % (2 ** 63), zlib.crc32(stream.encode())])
