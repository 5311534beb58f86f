"""Faults planted under ``brumby-14b-base``'s program, each a context
manager, and a command that reads one through ``tools/readings.py`` at
the cell's own size, so that ``PERF.md`` can say which limit sees it:

    python benchmark/tools/planted_retention.py --plant gate_after_write \
        --workload brumby-14b-base.closed-loop-16-decode-heavy \
        --seeds 1 [--seconds 20]

``benchmark/tests/test_brumby_14b_base.py`` and
``tests/unit/test_retention_block.py`` plant the same five under the
rehearsal (the stale state is ``tools/planted.py``'s).  One plant a
process: a program traced sound stays sound.
"""
from __future__ import annotations

import contextlib
import os
import sys
from unittest import mock

TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, TOOLS)
from planted import stale_state  # noqa: E402


@contextlib.contextmanager
def _around_the_recurrence(before=None, after=None):
    """Both entry points of the recurrence — a prompt's chunked form and
    a decode step through the cache manager's seam — with ``before(q, k,
    v, g) -> (q, k, v, g)`` on their operands and ``after(y, state, q, k)
    -> (y, state)`` on their results (``q``, ``k`` as they came)."""
    from autodist_tpu.models import pipeline_lm as lm
    from autodist_tpu.serving import kv_cache

    before = before or (lambda *operands: operands)
    after = after or (lambda y, state, q, k: (y, state))
    chunked, advance = lm.retention_chunked, \
        kv_cache.DenseLayout.advance_retention
    with mock.patch.object(
            lm, "retention_chunked",
            lambda q, k, v, g, state, **kw: after(
                *chunked(*before(q, k, v, g), state, **kw), q, k)), \
        mock.patch.object(
            kv_cache.DenseLayout, "advance_retention",
            lambda self, q, k, v, g, state, layer: after(
                *advance(self, *before(q, k, v, g), state, layer), q, k)):
        yield


def state_bf16():
    """The state and its normaliser are rounded to bf16 after every
    decode step and after the prompt's pass."""
    import jax

    narrow = lambda a: jax.lax.reduce_precision(a, exponent_bits=8,
                                                mantissa_bits=7)
    return _around_the_recurrence(
        after=lambda y, state, q, k: (y, tuple(map(narrow, state))))


def gate_after_write():
    """A position's gate decays its own write too: ``S_t = exp(gamma_t)
    (S_{t-1} + phi(k_t) v_t^T)``.  ``phi`` is quadratic, so the key is
    scaled by ``exp(gamma_t / 2)``."""
    import jax.numpy as jnp

    return _around_the_recurrence(
        before=lambda q, k, v, g: (q, k * jnp.exp(g / 2)[..., None], v, g))


def wrong_group():
    """Query head ``i`` reads key/value head ``i % kv_heads`` where the
    model says ``i // (heads / kv_heads)``: the heads go in sorted by
    ``i % kv_heads`` and their outputs come back to their places (the
    heads' axis is the last but one of q and of y alike)."""
    import numpy as np

    def order(q, k):
        n, kv = q.shape[-2], k.shape[-2]
        return np.asarray(sorted(range(n), key=lambda i: (i % kv, i)))

    return _around_the_recurrence(
        before=lambda q, k, v, g: (q[..., order(q, k), :], k, v, g),
        after=lambda y, state, q, k: (
            y[..., np.argsort(order(q, k)), :], state))


@contextlib.contextmanager
def no_rotary():
    """q and k reach the recurrence unrotated."""
    from autodist_tpu.models import pipeline_lm as lm

    with mock.patch.object(lm, "rope", lambda x, *a, **kw: x):
        yield


PLANTS = {f.__name__: f for f in (state_bf16, gate_after_write, wrong_group,
                                  no_rotary, stale_state)}


def main(argv=None) -> int:
    # readings.py from beside this file, the program from the checkout
    sys.path.insert(1, os.path.dirname(os.path.dirname(TOOLS)))
    import readings

    argv = list(sys.argv[1:] if argv is None else argv)
    at = argv.index("--plant")
    with PLANTS[argv[at + 1]]():
        return readings.main(argv[:at] + argv[at + 2:])


if __name__ == "__main__":
    sys.exit(main())
