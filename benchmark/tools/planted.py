"""Faults planted under ``qwen3-next-80b-a3b``'s program, each a context
manager, and a command that reads one through ``tools/readings.py`` at
the cell's own size, so that ``PERF.md`` can say which limit sees it:

    python benchmark/tools/planted.py --plant stale_state \
        --workload qwen3-next-80b-a3b.closed-loop-32-decode-heavy \
        --seeds 1 [--seconds 20]

``benchmark/tests/test_qwen3_next.py`` plants the same three under the
rehearsal.  One plant a process: a program traced sound stays sound.
"""
from __future__ import annotations

import contextlib
import os
import sys
from unittest import mock

TOOLS = os.path.dirname(os.path.abspath(__file__))


@contextlib.contextmanager
def stale_state():
    """The prefill leaves the slot's recurrent state as its previous
    occupant left it."""
    from autodist_tpu.serving import kv_cache

    real = kv_cache.write_state
    with mock.patch.object(
            kv_cache, "write_state",
            lambda arrays, layer, new, slot=None: tuple(arrays)
            if slot is not None else real(arrays, layer, new, slot)):
        yield


@contextlib.contextmanager
def no_conv_tail():
    """Every decode step's convolution sees zeros where the last three
    inputs were."""
    import jax.numpy as jnp

    from autodist_tpu.serving import kv_cache

    real = kv_cache.read_state

    def no_tail(arrays, layer, slot=None):
        conv, ssm = real(arrays, layer, slot)
        return jnp.zeros_like(conv), ssm

    with mock.patch.object(kv_cache, "read_state", no_tail):
        yield


@contextlib.contextmanager
def share_offset():
    """The program takes the arrays it holds for the experts one past
    the share's first; the configuration and the reference say 0."""
    from autodist_tpu.models.transformer import RoutedFFNSpec

    real = RoutedFFNSpec.__init__

    def off_by_one(self, *a, **kw):
        real(self, *a, **kw)
        object.__setattr__(self, "first_expert", self.first_expert + 1)

    with mock.patch.object(RoutedFFNSpec, "__init__", off_by_one):
        yield


PLANTS = {f.__name__: f for f in (stale_state, no_conv_tail, share_offset)}


def main(argv=None) -> int:
    # readings.py from beside this file, the program from the checkout
    sys.path[:0] = [TOOLS, os.path.dirname(os.path.dirname(TOOLS))]
    import readings

    argv = list(sys.argv[1:] if argv is None else argv)
    at = argv.index("--plant")
    name = argv[at + 1]
    with PLANTS[name]():
        return readings.main(argv[:at] + argv[at + 2:])


if __name__ == "__main__":
    sys.exit(main())
