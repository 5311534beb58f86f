"""Read, on the chip and at a cell's own size, the numbers its limits are
set from: what the comparison gives for the sound program over many
seeds, and for the control (the reference computed in the next lower
precision, put in the program's place) on a few.  One process.

    python benchmark/tools/readings.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2 --control int8 [--seconds 20]

Not part of a benchmark run; ``PERF.md`` records what it printed.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run as bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from harness import loader

    ctx0 = bench.make_context(args.workload, 0, args.seconds, False,
                              args.rehearse)
    runner = loader.load_module("runners", ctx0.traffic["kind"])
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    worst: dict = {}
    for seed in seeds:
        ctx = dataclasses.replace(ctx0, seed=seed)
        got = runner.readings(
            ctx, seed, args.control if seed in controls else "", bench.say)
        for side, rows in got.items():
            for name, value, limit, ok, note in rows:
                bench.say(f"[reading] seed={seed} {side} {name}={value:.6g} "
                          f"limit={limit:.6g} ok={ok} {note}")
                worst.setdefault((side, name), []).append(value)
    for (side, name), values in sorted(worst.items()):
        pick = max if side == "sound" else min
        bench.say(f"[summary] {side} {name}: "
                  f"{'largest' if side == 'sound' else 'smallest'} "
                  f"{pick(values):.6g} over {len(values)} seeds; all "
                  f"{[float(f'{v:.4g}') for v in values]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
