"""Record, on the chip, the two small traces that
``tests/test_program_trace.py`` reduces: the serving engine behind its
batcher and the BERT training runner, both at the toy size of the files'
``rehearsal`` groups, a few rounds / windows each, under the profiler
options and the ``bench/window`` annotation a traced cell uses (its edges
are kept as ``lo`` / ``hi``).  The host
spans and the scopes in them are the program's own.  Writes
``<out>/serve.trace.json`` and ``<out>/train.trace.json`` (a
``trace_reduce.Trace`` whose op events carry the framework op name as
their category) and, beside each, what the reduction gave when it was cut
(``.expected.json``).

    python benchmark/tools/record_program_trace.py chiprun_out/program
    cp chiprun_out/program/*.json benchmark/testdata/program/
"""
from __future__ import annotations

import glob
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

SERVE_CELL, TRAIN_CELL = "gpt2-large-postln.closed-loop", "bert-base-mlm.1chip"
ROUNDS, WINDOWS, SEED = 4, 3, 7


def _cell(name: str):
    from harness import loader

    spec = loader.benchmark_spec()
    cell = loader.find_cell(spec, name)
    return (loader.sized(loader.config_of(spec, cell), True),
            loader.sized(loader.traffic_of(cell), True),
            loader.load_module("reference", cell["config"]))


def _profile(out: str, body) -> str:
    """Run ``body`` inside a profiled ``bench/window``; the xplane."""
    from harness import window

    with window.profiled(out, True) as log_dir:
        with window.annotate("window"):
            body()
    return sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]


def _write(out: str, xplane: str, pattern: str) -> None:
    from harness import program_trace as pt, trace_reduce as tr

    trace = pt.read_xplane(xplane)
    lo, hi = tr.annotated_window(tr.read_xplane(xplane))
    with open(out + ".trace.json", "w") as f:
        json.dump(trace.to_json(), f, separators=(",", ":"))
    scopes = pt.scope_seconds(trace, pattern, lo, hi, pt.vocabulary())
    expected = {
        "program": pattern, "lo": lo, "hi": hi,
        "busy_s": tr.busy_seconds(trace, lo, hi),
        "idle_by_span": pt.idle_by_span(trace, lo, hi),
        "runs": scopes["runs"],
        "by_scope": sorted([s, d, t] for (s, d), t
                           in scopes["by_scope"].items()),
    }
    with open(out + ".expected.json", "w") as f:
        json.dump(expected, f, indent=1)
    print(out, json.dumps(expected, indent=1))


def record_serving(out: str) -> None:
    from harness import loader, traffic as traffic_gen, weights

    cfg, mix, ref = _cell(SERVE_CELL)
    builder = loader.load_module("builders", cfg["builder"])
    params = weights.seeded_fill(ref.param_shapes(cfg), SEED,
                                 cfg["initializer_range"])
    engine, batcher = builder.build_serving(cfg, params)
    stream = traffic_gen.RequestStream(mix, cfg["vocab_size"], SEED)
    seen = 0

    def one_round():
        nonlocal seen
        batcher.step()
        for _ in range(len(batcher.completions) - seen):
            prompt, asked = stream.next()
            batcher.submit(prompt, max_new_tokens=asked)
        seen = len(batcher.completions)

    for _ in range(mix["callers"]):
        prompt, asked = stream.next()
        batcher.submit(prompt, max_new_tokens=asked)
    for _ in range(6):          # both programs compiled, slots staggered
        one_round()
    out = os.path.join(out, "serve")
    xplane = _profile(out, lambda: [one_round() for _ in range(ROUNDS)])
    _write(out, xplane, cfg["trace_programs"]["decode"])


def record_training(out: str) -> None:
    import jax
    import numpy as np

    from autodist_tpu import stack_steps
    from harness import loader, weights

    cfg, traffic, ref = _cell(TRAIN_CELL)
    builder = loader.load_module("builders", cfg["builder"])
    params = weights.seeded_fill(ref.param_shapes(cfg), SEED,
                                 cfg["initializer_range"])
    runner, _ = builder.build_training(cfg, traffic, params, 1)
    rng = weights.host_rng(SEED, "batches")
    k = traffic["steps_per_window"]
    windows = [stack_steps([builder.make_batch(
        rng, cfg, traffic, traffic["sequences_per_chip"])
        for _ in range(k)]) for _ in range(2)]
    for w in windows:
        jax.block_until_ready(runner.run_steps(w))

    def body():
        pending = None
        for i in range(WINDOWS):
            m = runner.run_steps(windows[i % 2])
            if pending is not None:
                np.asarray(pending["loss"])
            pending = m
        np.asarray(pending["loss"])

    out = os.path.join(out, "train")
    _write(out, _profile(out, body), cfg["trace_programs"]["window"])
    runner.close()


def main(out: str) -> int:
    os.makedirs(out, exist_ok=True)
    record_serving(out)
    record_training(out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
