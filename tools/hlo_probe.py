"""HLO-structural proof of the framework's performance claims — on CPU.

Every "we emit fewer/better collectives" claim must be checkable
without a chip.  This probe lowers real train-step programs with
``jax.jit(...).lower(...).compile()`` on simulated CPU meshes and
asserts collective *counts and kinds* in the optimized HLO text.

This module is now a thin back-compat shim: the facts layer lives in
:mod:`autodist_tpu.analysis.facts`, the memoized program corpus in
:mod:`autodist_tpu.analysis.programs`, the declarative rules in
:mod:`autodist_tpu.analysis.program_rules`, and the probes themselves —
identical names, reports, and pass/fail behavior — in
:mod:`autodist_tpu.analysis.probes`.  The same engine also powers
``tools/lint_strategy.py``, which sweeps the ENTIRE AutoStrategy zoo
(plan lint + program lint) instead of these eight hand-picked programs.

* ``probe_steps_per_loop`` — ``run_steps``'s k-step program is ONE HLO
  module whose scan is a ``while`` loop with the *same* collective
  counts as the single-step program.
* ``probe_single_replica`` — a 1-device program contains zero
  cross-device collectives (the allreduce bypass).
* ``probe_pipeline_tp`` — tensor_parallel=2 adds the per-stage
  Megatron activation all-reduces on top of the tp=1 program.
* ``probe_collective_matmul`` — the latency-hiding decomposition
  removes every monolithic model-axis all-reduce without re-fusion.
* ``probe_vocab_parallel`` — the vocab-sharded program materializes no
  full-vocab buffer anywhere.
* ``probe_quantized`` — the per-collective precision policy narrows
  exactly the policied boundaries' wire dtypes.
* ``probe_decode`` — the serving decode window is buffer-clean,
  in-place, and one fused dispatch per K tokens.
* ``probe_prefill`` — the serving prefill computes and writes, in place,
  only the one row a dispatch admits.
* ``probe_zero3`` — ZeRO-3 stores parameters only as shards across the
  step boundary, gathering per layer on demand.

Run as a script for a JSON report::

    JAX_PLATFORMS=cpu python tools/hlo_probe.py            # all probes
    JAX_PLATFORMS=cpu python tools/hlo_probe.py --json out.json
    JAX_PLATFORMS=cpu python tools/hlo_probe.py --probe single_replica
"""
from __future__ import annotations

import argparse
import json
import os
import sys

if __name__ == "__main__":  # simulated mesh before the first jax import
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flag = "--xla_force_host_platform_device_count=8"
    if flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from autodist_tpu.analysis.facts import (buffers_with_dim,  # noqa: E402,F401
                                         buffers_with_dim_repeated,
                                         collective_counts,
                                         collective_wire, compiled_text,
                                         convert_counts,
                                         dynamic_update_slices,
                                         entry_signature,
                                         large_copies_with_dim,
                                         narrowed_collective_counts,
                                         nonscalar_all_reduces)
from autodist_tpu.analysis.probes import (PROBES,  # noqa: E402,F401
                                          probe_collective_matmul,
                                          probe_decode,
                                          probe_pipeline_tp,
                                          probe_prefill,
                                          probe_quantized,
                                          probe_single_replica,
                                          probe_steps_per_loop,
                                          probe_vocab_parallel,
                                          probe_zero3, run_probes)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="HLO-structural proof of collective claims (CPU mesh)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the report to this file (machine-"
                         "readable provenance)")
    ap.add_argument("--probe", action="append", choices=sorted(PROBES),
                    help="run only these probes (repeatable; default all)")
    args = ap.parse_args(argv)
    report, failed = run_probes(args.probe)
    text = json.dumps(report, indent=2)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text + "\n")
    print(text)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
