"""Faults planted under ``deepseek-v2-lite``'s program, each a context
manager, and a command that reads one through ``tools/readings.py`` at
the cell's own size, so that ``PERF.md`` can say which limit sees it:

    python benchmark/tools/planted_latent.py --plant unrotated_key \
        --workload deepseek-v2-lite.closed-loop-64-long-decode \
        --seeds 1 [--seconds 20]

``benchmark/tests/test_deepseek_v2_lite.py`` plants the same five under
the rehearsal (the share offset is ``tools/planted.py``'s).  One plant a
process: a program traced sound stays sound.
"""
from __future__ import annotations

import contextlib
import os
import sys
from unittest import mock

TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, TOOLS)
from planted import share_offset  # noqa: E402


@contextlib.contextmanager
def unrotated_key():
    """The positional key, the one head every query head shares, reaches
    the row it is cached in without its rotation."""
    from autodist_tpu.models import pipeline_lm as lm

    real = lm.rope

    def rope(x, *a, **kw):
        return x if x.shape[2] == 1 else real(x, *a, **kw)

    with mock.patch.object(lm, "rope", rope):
        yield


@contextlib.contextmanager
def latent_unnormed():
    """The latent is cached as the down-projection left it, before its
    norm."""
    from autodist_tpu.models import pipeline_lm as lm

    real = lm._latent_inputs

    def inputs(cfg, chunk, x, positions):
        rank = cfg.block.latent.kv_rank
        with mock.patch.object(
                lm, "_rms_norm", lambda t, *a, **kw: t
                if t.shape[-1] == rank else real_norm(t, *a, **kw)):
            return real(cfg, chunk, x, positions)

    real_norm = lm._rms_norm
    with mock.patch.object(lm, "_latent_inputs", inputs):
        yield


@contextlib.contextmanager
def no_mscale():
    """The softmax scale without YaRN's ``m ** 2``."""
    from autodist_tpu.models.transformer import BlockSpec

    plain = property(lambda self: (self.latent.nope_dim
                                   + self.latent.rope_dim) ** -0.5)
    with mock.patch.object(BlockSpec, "latent_softmax_scale", plain):
        yield


@contextlib.contextmanager
def renormalised():
    """The top-k weights renormalised to sum 1, which the configuration
    says they are not."""
    from autodist_tpu.models.transformer import RoutedFFNSpec

    real = RoutedFFNSpec.__init__

    def renorm(self, *a, **kw):
        real(self, *a, **kw)
        object.__setattr__(self, "renormalise", True)

    with mock.patch.object(RoutedFFNSpec, "__init__", renorm):
        yield


PLANTS = {f.__name__: f for f in (unrotated_key, latent_unnormed, no_mscale,
                                  renormalised, share_offset)}


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
