"""Faults planted under ``ling-3.0-flash``'s program, each a context
manager, and a command that reads one through ``tools/readings.py`` at
the cell's own size, so that ``PERF.md`` can say which limit sees it:

    python benchmark/tools/planted_hybrid_latent.py --plant scalar_gate \
        --workload ling-3.0-flash.closed-loop-96-long-decode \
        --seeds 1 [--seconds 20]

``benchmark/tests/test_ling_3_0_flash.py`` and
``tests/unit/test_hybrid_latent_block.py`` plant the same seven under the
rehearsal (the stale state and the share offset are ``tools/planted.py``'s).
One plant a process: a program traced sound stays sound.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import sys
from unittest import mock

TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, TOOLS)
from planted import share_offset, stale_state  # noqa: E402


@contextlib.contextmanager
def scalar_gate():
    """Every row of a head's state decays by the mean of its channels'
    log decays: one gate a head where the model has one a key channel."""
    from autodist_tpu.models import pipeline_lm as lm
    from autodist_tpu.serving import kv_cache

    def mean_of(g):
        return g.mean(-1, keepdims=True) + 0.0 * g

    chunked, advance = lm.gated_delta_chunked, \
        kv_cache.DenseLayout.advance_state
    with mock.patch.object(
            lm, "gated_delta_chunked",
            lambda q, k, v, g, *a, **kw: chunked(q, k, v, mean_of(g), *a,
                                                 **kw)), \
        mock.patch.object(
            kv_cache.DenseLayout, "advance_state",
            lambda self, q, k, v, g, *a, **kw: advance(
                self, q, k, v, mean_of(g), *a, **kw)):
        yield


@contextlib.contextmanager
def groups_unlimited():
    """The router takes its 8 a token from all 512 outputs: no group is
    left out."""
    from autodist_tpu.models.transformer import RoutedFFNSpec

    real = RoutedFFNSpec.__init__

    def one_group(self, *a, **kw):
        real(self, *a, **kw)
        object.__setattr__(self, "groups", 1)
        object.__setattr__(self, "groups_kept", 1)

    with mock.patch.object(RoutedFFNSpec, "__init__", one_group):
        yield


@contextlib.contextmanager
def correction_weighs():
    """The chosen experts are weighted by their scores WITH the
    correction, which the model adds to choose and never to weigh."""
    import jax
    import jax.numpy as jnp

    from autodist_tpu.parallel import moe

    real = moe._route

    def route(x, router_w, top_k, renormalise=True, **rule):
        experts, _, kept = real(x, router_w, top_k, renormalise, **rule)
        s = jax.nn.sigmoid(jnp.matmul(
            x.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)) \
            + rule["correction"].astype(jnp.float32)
        w = jnp.take_along_axis(s, experts, axis=-1)
        return experts, w / w.sum(-1, keepdims=True) * rule["scale"], kept

    with mock.patch.object(moe, "_route", route):
        yield


@contextlib.contextmanager
def no_head_gate():
    """A latent-attention layer's heads reach the output projection
    without their gates."""
    from autodist_tpu.models import pipeline_lm as lm

    real = lm._latent_output

    def ungated(cfg, chunk, x, out):
        plain = dataclasses.replace(cfg, block=dataclasses.replace(
            cfg.block, attn_gate=False))
        return real(plain, chunk, x, out)

    with mock.patch.object(lm, "_latent_output", ungated):
        yield


@contextlib.contextmanager
def row_short():
    """A decode step attends the cached rows one position short: the row
    it has just written is not read."""
    import jax.numpy as jnp

    from autodist_tpu.kernel.pallas import flash_decode
    from autodist_tpu.serving import kv_cache

    composed, fused = kv_cache.cached_attention, \
        flash_decode.flash_decode_latent_layer
    short = lambda lengths: jnp.maximum(lengths - 1, 0)
    with mock.patch.object(
            kv_cache, "cached_attention",
            lambda q, k, v, lengths, **kw: composed(q, k, v, short(lengths),
                                                    **kw)), \
        mock.patch.object(
            flash_decode, "flash_decode_latent_layer",
            lambda lengths, *a, **kw: fused(short(lengths), *a, **kw)):
        yield


PLANTS = {f.__name__: f for f in (scalar_gate, stale_state, groups_unlimited,
                                  correction_weighs, no_head_gate, row_short,
                                  share_offset)}


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
