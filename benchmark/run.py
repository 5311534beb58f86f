"""Run one cell of the benchmark once, in this process, on this machine.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

builds the cell's system, warms every shape the window will use, measures
for ``--seconds``, checks the outputs against the plain reference, and
prints as its last line one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` in a traced run),
then ``checks``: every number ``correct`` compared, beside its limit (the
same numbers are the last lines on standard error).
``--trace 0`` reports the cell's end-to-end metrics with the profiler
off; ``--trace 1`` profiles a short steady slice and reports its
per-layer metrics.  Without a TPU, with fewer chips than the cell asks
for, or on a device without published peaks it exits non-zero and prints
no result.  ``--rehearse`` runs the same code at the toy size of the
files' ``rehearsal`` groups on the CPU and prints counts only.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by its name in ``BENCHMARK.json``
(``benchmark/README.md``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402


@dataclasses.dataclass
class Context:
    """What a runner is handed: the cell as data, and where it runs."""

    spec: dict
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    devices: list
    peaks: dict
    out_dir: str
    t_start: float = T_START

    @property
    def chips(self) -> int:
        return len(self.devices)


def say(msg: str) -> None:
    print(msg, flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy size on the CPU; prints counts only")
    return ap.parse_args(argv)


def prepare_process(rehearse: bool, chips: int, out_dir: str) -> None:
    """Settle, before jax starts, where it runs and where it caches."""
    from harness.loader import ROOT

    sys.path.insert(0, ROOT)
    # libtpu logs under a fixed /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(out_dir, "tpu_logs"))
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={chips}")
    import jax

    if rehearse:
        jax.config.update("jax_platforms", "cpu")
    else:
        from autodist_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        # every program, however quick to compile, so that the second
        # run of a cell in a checkout compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # The program logs and serializes strategies under a fixed
    # /tmp/autodist_tpu; a run writes inside its checkout only.
    from autodist_tpu import const

    work = os.path.join(out_dir, "work")
    const.DEFAULT_WORKING_DIR = work
    const.DEFAULT_STRATEGY_DIR = os.path.join(work, "strategies")
    const.DEFAULT_TRACE_DIR = os.path.join(work, "traces")
    const.DEFAULT_LOG_DIR = os.path.join(work, "logs")


def make_context(workload: str, seed: int, seconds, trace: bool,
                 rehearse: bool) -> Context:
    """The cell as data and the devices it runs on.  Raises
    ``BenchmarkError`` for an unknown cell and ``NoChip`` where the
    accelerator the cell asks for is not there."""
    from harness import device, loader

    spec = loader.benchmark_spec()
    cell = loader.find_cell(spec, workload)
    config = loader.sized(loader.config_of(spec, cell), rehearse)
    traffic = loader.sized(loader.traffic_of(cell), rehearse)
    out_dir = os.path.join(loader.OUT_DIR, cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    prepare_process(rehearse, cell["chips"], out_dir)
    import jax

    if rehearse:
        devices, peaks = jax.devices()[:cell["chips"]], {}
    else:
        devices, peaks = device.require_chips(cell["chips"])
    return Context(
        spec=spec, cell=cell, config=config, traffic=traffic, seed=seed,
        seconds=float(spec["run_seconds"] if seconds is None else seconds),
        trace=trace, rehearse=rehearse, devices=devices, peaks=peaks,
        out_dir=out_dir)


def main(argv=None) -> int:
    args = parse_args(argv)
    from harness import device, loader

    try:
        ctx = make_context(args.workload, args.seed, args.seconds,
                           bool(args.trace), args.rehearse)
    except device.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    except loader.BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    spec, cell, traffic, devices = ctx.spec, ctx.cell, ctx.traffic, ctx.devices
    seconds = ctx.seconds
    say(f"[cell] {cell['name']} config={cell['config']} "
        f"traffic={cell['traffic']} kind={traffic['kind']} "
        f"chips={cell['chips']} seed={args.seed} seconds={seconds} "
        f"trace={args.trace} device={device.describe(devices)}")
    runner = loader.load_module("runners", traffic["kind"])
    result = runner.run(ctx, say)

    if args.rehearse:
        say("[rehearsal] counts only; a CPU run gives no time, rate or "
            "share of the device")
        print(json.dumps({"rehearsal": True, "correct": result["correct"],
                          "counts": result["counts"],
                          "checks": compared(result["checks"])}))
        return 0 if result["correct"] else 1

    section = "per_layer" if ctx.trace else "end_to_end"
    metrics = {}
    for m in loader.metrics_of(spec, section, cell["name"]):
        if ctx.trace:
            value = loader.load_module("metrics", m["name"]).read(
                result["record"])
        else:
            value = result["end_to_end"].get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = dict(device.describe(devices), **result["memory"])
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": dev}
    if ctx.trace:
        dev["busy_s"] = result["record"]["busy_s"]
        dev["window_s"] = result["record"]["window_s"]
        line["breakdown"] = result["record"]["breakdown"]
    for name, value in sorted(result.get("counts", {}).items()):
        say(f"[count] {name} = {value}")
    line["checks"] = compared(result["checks"])
    print(json.dumps(line), flush=True)
    return 0


def compared(checks: list) -> dict:
    """Every number ``correct`` compared, beside its limit, where the
    record of a run at fault keeps it: printed here as the last lines on
    standard error, and returned for the end of the result's line."""
    for name, value, limit, ok, _ in checks:
        print(f"[check] {name}: {value:.6g} (limit {limit:.6g}) -> "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr, flush=True)
    return {name: {"value": float(value), "limit": float(limit)}
            for name, value, limit, _, _ in checks}


if __name__ == "__main__":
    sys.exit(main())
