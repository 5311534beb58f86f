"""What untraced runs of one serving cell say of their own rounds and their
own start-up: N runs, each a process of its own with the profiler off and
a telemetry directory of its own (``AUTODIST_TPU_TELEMETRY_DIR``, which
makes the program flush its spans and instruments when it exits), and
beside each run's numbers the ``[rounds]`` and ``[startup]`` lines that
``tools/telemetry_report.py`` reduces from what the run left.

    python benchmark/tools/rounds.py --workload <cell> --seeds 1,2,3 \
        [--seconds <s>] [--out chiprun_out/rounds]

Last come the medians and spreads of the set — a spread is (Q3 - Q1) /
median by ``statistics.quantiles(n=4)`` — and, for the run that served
the fewest tokens a second, each part of its round against the set's
median: a run that lies far off says there whether its decode part, its
own part, a few flagged rounds or a compile carried the time.  The
seconds before the program's telemetry was imported are taken against
this tool's own clock where it starts the run.  This process never
touches jax: the chip belongs to the run.

Not part of a benchmark run; ``PERF.md`` records what it printed.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import re
import shutil
import statistics
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SETUP_LINE = re.compile(r"^\[setup\] weights_s=([\d.]+) engine_s=([\d.]+)")
PARTS = ("serve_tokens_per_s", "round_ms_p50", "round_ms_p95",
         "decode_ms_p50", "prefill_ms_a_row_p50", "own_ms_p50", "rounds",
         "slow_rounds", "slow_excess_ms", "compiles", "setup_s",
         "programs_s")


def report_tool():
    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(ROOT, "tools", "telemetry_report.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def one_run(tool, workload: str, seed: int, seconds, out: str, log) -> dict:
    """One untraced run in a process of its own; its result line's
    metrics, its rounds and its start-up, by name."""
    run_dir = os.path.join(out, workload, str(seed))
    shutil.rmtree(run_dir, ignore_errors=True)
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
           workload, "--seed", str(seed), "--trace", "0"]
    if seconds is not None:
        cmd += ["--seconds", str(seconds)]
    launched = time.time()
    done = subprocess.run(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=dict(os.environ, AUTODIST_TPU_TELEMETRY_DIR=run_dir))
    log.write(f"$ {' '.join(cmd)}\n{done.stdout}\n[stderr]\n"
              f"{done.stderr[-4000:]}\n")
    log.flush()
    if done.returncode != 0:
        raise SystemExit(f"run on seed {seed} exited {done.returncode}:\n"
                         f"{done.stderr[-2000:]}")
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    print(f"[run] seed={seed} " + lines[-1], flush=True)
    out_row = {k: v["value"] for k, v in result["metrics"].items()}
    out_row.update(seed=seed, correct=result["correct"])
    problems = tool.check_schema(run_dir)
    print(f"[check] seed={seed} telemetry_report --check: "
          f"{problems or 'schema OK'}", flush=True)
    with open(os.path.join(run_dir, "trace.json")) as f:
        spans = json.load(f)["traceEvents"]
    records = tool.load_jsonl(os.path.join(run_dir, "metrics.jsonl"))
    rounds = tool.rounds_summary(spans, records)
    print(tool.rounds_line(rounds), flush=True)
    for r in rounds["slow"]:
        print(f"[slow_round] seed={seed} "
              + json.dumps({k: v for k, v in r.items() if k != "kind"}),
              flush=True)
    startup = tool.startup_summary(records)
    account = next(r for r in records if r["kind"] == "startup")
    setup = next((m for m in map(SETUP_LINE.match, lines) if m), None)
    more = {"setup_s": out_row["setup_s"],
            "before_import_s": account["import_wall_s"] - launched}
    if setup:
        more.update(weights_s=float(setup[1]), runner_engine_s=float(setup[2]))
    print(tool.startup_line(startup, **more), flush=True)
    out_row.update({k: v for k, v in rounds.items() if k != "slow"},
                   **more, **startup, schema=problems)
    return out_row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default=os.path.join(BENCH, "out", "rounds"))
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    tool = report_tool()
    os.makedirs(os.path.join(args.out, args.workload), exist_ok=True)
    stem = os.path.join(args.out, args.workload)
    with open(stem + ".log", "w") as log:
        runs = [one_run(tool, args.workload, seed, args.seconds, args.out,
                        log) for seed in seeds]
    with open(stem + ".json", "w") as f:
        json.dump({"workload": args.workload, "seconds": args.seconds,
                   "runs": runs}, f, indent=1)
    names = [n for n in PARTS if all(r.get(n) is not None for r in runs)]
    for name in names:
        values = [float(r[name]) for r in runs]
        tail = f" spread={100 * spread(values):.3f}%" \
            if len(values) > 1 and statistics.median(values) else ""
        print(f"[set] {name} median={statistics.median(values):.6g}{tail} "
              f"values={[float(f'{v:.6g}') for v in values]}", flush=True)
    slowest = min(runs, key=lambda r: r["serve_tokens_per_s"])
    print(f"[slowest] seed={slowest['seed']} " + " ".join(
        f"{n}={float(slowest[n]):.6g}"
        f"({float(slowest[n]) / m - 1:+.2%})" if m else
        f"{n}={float(slowest[n]):.6g}"
        for n in names
        for m in [statistics.median(float(r[n]) for r in runs)]),
        flush=True)
    bad = [r["seed"] for r in runs if not r["correct"] or r["schema"]]
    print(f"[rounds.py] {len(runs)} runs of {args.workload}; not correct or "
          f"failing --check: {bad or 'none'}", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
