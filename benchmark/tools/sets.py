"""Make, on the chip, the sets of runs a bound is set from: one cell, the
same seeds in every set, each run a process of its own as the driver's
are, and the spread of every number by the contract's rule.

    python benchmark/tools/sets.py --workload <cell> --seeds 1,2,3,4,5,6 \
        [--sets 2] [--seconds <s>] [--warm-seed 7] [--out chiprun_out/sets]

``--warm-seed`` first makes one run on a seed of its own, outside the
sets, so that no run of a set compiles.  A spread is (Q3 - Q1) / median by
``statistics.quantiles(n=4)``; beside it stands the spread with the run
farthest from the median left out, which is what the driver's check of
tightness reads.  Every end-to-end metric is reduced; beside
``serve_tokens_per_s`` stands the rate the same window gives by the
tokens of the requests that completed in it (``[count]
tokens_generated`` over the window's length, which is the delivered
tokens over the rate reported).  Without ``--seconds`` a run lasts
``BENCHMARK.json``'s ``run_seconds``.  This process never touches jax:
the chip belongs to the run.

Not part of a benchmark run; ``PERF.md`` records what it printed.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def without_farthest(values) -> list:
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def one_run(workload: str, seed: int, seconds, log) -> dict:
    """One run in a process of its own; its numbers by name."""
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
           workload, "--seed", str(seed), "--trace", "0"]
    if seconds is not None:
        cmd += ["--seconds", str(seconds)]
    done = subprocess.run(cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          text=True)
    log.write(f"$ {' '.join(cmd)}\n{done.stdout}\n[stderr]\n"
              f"{done.stderr[-4000:]}\n")
    log.flush()
    if done.returncode != 0:
        raise SystemExit(f"run on seed {seed} exited {done.returncode}:\n"
                         f"{done.stderr[-2000:]}")
    lines = done.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    out = {k: v["value"] for k, v in line["metrics"].items()}
    counts = {}
    for text in lines:
        if text.startswith("[count] "):
            name, _, value = text[len("[count] "):].partition(" = ")
            counts[name] = float(value)
    rate = out.get("serve_tokens_per_s")
    if rate:
        elapsed = counts["tokens_delivered_in_window"] / rate
        out["completed_requests_tokens_per_s"] = (
            counts["tokens_generated"] / elapsed)
    out.update(correct=line["correct"], seed=seed,
               memory_peak_bytes=line["device"]["memory_peak_bytes"],
               checks={k: v["value"] for k, v in line["checks"].items()},
               counts=counts)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--warm-seed", type=int, default=None)
    ap.add_argument("--out", default=os.path.join(BENCH, "out", "sets"))
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    os.makedirs(args.out, exist_ok=True)
    stem = os.path.join(args.out, args.workload)
    with open(stem + ".log", "w") as log:
        if args.warm_seed is not None:
            warm = one_run(args.workload, args.warm_seed, args.seconds, log)
            print(f"[warm] seed={args.warm_seed} correct={warm['correct']} "
                  f"setup_s={warm['setup_s']:.1f}", flush=True)
        sets = []
        for k in range(args.sets):
            sets.append([])
            for seed in seeds:
                r = one_run(args.workload, seed, args.seconds, log)
                sets[-1].append(r)
                print(f"[run] set={k} " + " ".join(
                    f"{name}={value}" for name, value in r.items()
                    if name not in ("counts", "checks")) + " checks="
                    + json.dumps(r["checks"]), flush=True)
    with open(stem + ".json", "w") as f:
        json.dump({"workload": args.workload, "seconds": args.seconds,
                   "seeds": seeds, "sets": sets}, f, indent=1)
    names = [n for n, v in sets[0][0].items() if isinstance(v, float)]
    for name in names:
        for k, runs in enumerate(sets):
            values = [r[name] for r in runs]
            print(f"[spread] {name} set={k} median="
                  f"{statistics.median(values):.6g} spread="
                  f"{100 * spread(values):.3f}% farthest_left_out="
                  f"{100 * spread(without_farthest(values)):.3f}% values="
                  f"{[float(f'{v:.6g}') for v in values]}", flush=True)
    bad = [r["seed"] for runs in sets for r in runs if not r["correct"]]
    print(f"[sets] {args.sets} x {len(seeds)} runs of {args.workload}; "
          f"not correct: {bad or 'none'}", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
