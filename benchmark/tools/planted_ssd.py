"""Faults planted under ``granite-4.0-h-micro``'s program, each a context
manager, and a command that reads one through ``tools/readings.py`` at
the cell's own size, so that ``PERF.md`` can say which limit sees it:

    python benchmark/tools/planted_ssd.py --plant no_skip \
        --workload granite-4.0-h-micro.closed-loop-64-long-decode \
        --seeds 1 [--seconds 20]

``benchmark/tests/test_granite_4_0_h_micro.py`` and
``tests/unit/test_ssd_block.py`` plant the same under the rehearsal (the
stale state is ``tools/planted.py``'s).  One plant a process: a program
traced sound stays sound.
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
def stale_tail():
    """The prefill writes the slot's matrices and leaves its convolution
    tail as the previous occupant left it."""
    from autodist_tpu.serving import kv_cache

    real = kv_cache.write_state

    def keep_tail(arrays, layer, new, slot=None):
        if slot is None or len(arrays) < 2:
            return real(arrays, layer, new, slot)
        return (arrays[0], *real(arrays[1:], layer, new[1:], slot))

    with mock.patch.object(kv_cache, "write_state", keep_tail):
        yield


@contextlib.contextmanager
def no_skip():
    """``D x`` never joins the read-out: the mixer sees ``D = 0``."""
    import jax.numpy as jnp

    from autodist_tpu.models import pipeline_lm as lm

    real = lm.ssd_attention

    def without(cfg, chunk, x, state, **kw):
        mixer = dict(chunk["linear_attention"])
        mixer["D"] = jnp.zeros_like(mixer["D"])
        return real(cfg, dict(chunk, linear_attention=mixer), x, state, **kw)

    with mock.patch.object(lm, "ssd_attention", without):
        yield


@contextlib.contextmanager
def norm_before_gate():
    """The read-out is normed and THEN gated."""
    import jax
    import jax.numpy as jnp

    from autodist_tpu.models import pipeline_lm as lm

    def swapped(y, z, scale, groups, eps):
        g = y.reshape(*y.shape[:-1], groups, -1)
        o = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
        return o.reshape(y.shape) * scale * jax.nn.silu(z)

    with mock.patch.object(lm, "gated_group_norm", swapped):
        yield


@contextlib.contextmanager
def no_softplus():
    """``Delta = dt + dt_bias``, no softplus (while the program's mixer is
    traced: the reference keeps its own)."""
    import jax

    from autodist_tpu.models import pipeline_lm as lm

    real = lm.ssd_attention

    def without(*a, **kw):
        with mock.patch.object(jax.nn, "softplus", lambda x: x):
            return real(*a, **kw)

    with mock.patch.object(lm, "ssd_attention", without):
        yield


@contextlib.contextmanager
def _respec(**changes):
    """Every ``BlockSpec`` built inside comes out with ``changes``."""
    from autodist_tpu.models.transformer import BlockSpec

    real = BlockSpec.__init__

    def changed(self, *a, **kw):
        real(self, *a, **kw)
        for name, value in changes.items():
            object.__setattr__(self, name, value)

    with mock.patch.object(BlockSpec, "__init__", changed):
        yield


def residual_one():
    """Every sub-block's output is added unscaled."""
    return _respec(residual_multiplier=1.0)


def head_scale():
    """The attention layers scale their scores by ``head_dim ** -0.5``."""
    return _respec(softmax_scale=None)


def rotary():
    """The attention layers rotate q and k (rotate-half at theta 1e4)."""
    return _respec(positions="rope")


def no_embedding_multiplier():
    """The embedding's rows enter the stream as they are."""
    return _respec(embedding_multiplier=1.0)


@contextlib.contextmanager
def state_bf16():
    """The matrices are rounded to bf16 after every decode step and
    after the prompt's pass."""
    import jax

    from autodist_tpu.models import pipeline_lm as lm
    from autodist_tpu.serving import kv_cache

    chunked, advance = lm.ssd_chunked, kv_cache.DenseLayout.advance_ssd

    def narrowed(y, state):
        return y, jax.lax.reduce_precision(state, exponent_bits=8,
                                           mantissa_bits=7)

    with mock.patch.object(
            lm, "ssd_chunked",
            lambda *a, **kw: narrowed(*chunked(*a, **kw))), \
        mock.patch.object(
            kv_cache.DenseLayout, "advance_ssd",
            lambda self, *a, **kw: narrowed(*advance(self, *a, **kw))):
        yield


PLANTS = {f.__name__: f for f in (
    stale_state, stale_tail, no_skip, norm_before_gate, no_softplus,
    residual_one, head_scale, rotary, no_embedding_multiplier, state_bf16)}


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
