"""Shared benchmark harness (≙ reference ``examples/benchmark/utils/``:
absl flags system + benchmark logger + ``TimeHistory`` meter).

Provides the common flag set, a JSON-lines benchmark logger, and the
timed training loop all benchmark drivers share.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Callable, Optional

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))  # repo root when run as a script


def base_parser(description: str) -> argparse.ArgumentParser:
    """Common flags (≙ ``utils/flags/_base.py``/``_performance.py``)."""
    ap = argparse.ArgumentParser(description=description)
    ap.add_argument("--strategy", default="AllReduce",
                    help="strategy builder name (AllReduce, PS, "
                         "PSLoadBalancing, PartitionedPS, Parallax, ZeRO, ...)")
    ap.add_argument("--batch-size", type=int, default=None,
                    help="global batch size (default: per-model)")
    ap.add_argument("--train-steps", type=int, default=30)
    ap.add_argument("--warmup-steps", type=int, default=2)
    ap.add_argument("--log-steps", type=int, default=10,
                    help="steps between throughput reports (TimeHistory)")
    ap.add_argument("--steps-per-loop", type=int, default=None,
                    help="steps fused into one device dispatch per report "
                         "window (default: --log-steps; 1 = legacy "
                         "per-step loop with per-step latency stats)")
    ap.add_argument("--chunk-size", type=int, default=None,
                    help="allreduce bucketing chunk size (default: per-model)")
    ap.add_argument("--benchmark-log-dir", default=None,
                    help="write benchmark JSON lines here")
    ap.add_argument("--preset", choices=["tiny", "full"], default="full",
                    help="tiny = smoke-test sizes for CPU")
    return ap


class BenchmarkLogger:
    """JSON-lines metric logger (≙ ``utils/logs/logger.py``)."""

    def __init__(self, log_dir: Optional[str] = None):
        self._f = None
        if log_dir:
            os.makedirs(log_dir, exist_ok=True)
            self._f = open(os.path.join(log_dir, "metric.log"), "a")

    def log_metric(self, name: str, value, unit: str = "", step: int = 0,
                   extras: Optional[dict] = None):
        record = {"name": name, "value": float(value), "unit": unit,
                  "timestamp": time.time(), "step": step,
                  **(extras or {})}
        line = json.dumps(record)
        print(line)
        if self._f:
            self._f.write(line + "\n")
            self._f.flush()

    def close(self):
        if self._f:
            self._f.close()


def run_benchmark(runner, make_batch: Callable[[int], dict], *,
                  batch_size: int, train_steps: int, warmup_steps: int,
                  log_steps: int, logger: BenchmarkLogger,
                  flops_per_example: Optional[float] = None,
                  peak_flops: Optional[float] = None,
                  steps_per_loop: Optional[int] = None,
                  static_data: bool = False) -> dict:
    """Timed training loop with windowed examples/sec reports
    (≙ ``TimeHistory``: examples/sec = batch_size × log_steps / elapsed,
    reference ``examples/benchmark/imagenet.py:84-140``).

    When the runner supports :meth:`run_steps`, each report window runs
    as ONE fused device dispatch of ``steps_per_loop`` (default
    ``log_steps``) steps — host dispatch cost and the fencing round-trip
    are paid once per window instead of once per step.  Pass ``steps_per_loop=1`` to
    force the legacy per-step loop (per-step latency percentiles).
    Every window reuses one executable shape: warmup is one fused
    window, and ``train_steps`` is measured in ``train_steps //
    steps_per_loop`` whole windows.

    On the per-step path batches ride the prefetching
    :class:`~autodist_tpu.data.DataLoader` (host→HBM transfer overlaps
    compute) and each timed step is fenced by fetching a metric scalar
    (a value that depends on the step's execution)."""
    import jax

    def fence(metrics):
        leaf = np.asarray(next(iter(metrics.values())))
        return float(leaf if leaf.ndim == 0 else leaf[-1])

    fused = steps_per_loop != 1 and hasattr(runner, "run_steps")
    if fused:
        # One executable shape for warmup and every window: k is capped
        # by train_steps so a tiny run is not inflated to a full
        # log_steps window, and the warmup dispatch (which is also the
        # compile) replaces warmup_steps — it is always exactly k steps.
        from autodist_tpu import stack_steps

        k = min(int(steps_per_loop or log_steps), train_steps)
        windows = max(train_steps // k, 1)
        if windows * k != train_steps:
            print(f"# fused loop measures {windows * k} of "
                  f"{train_steps} requested steps ({windows} whole "
                  f"windows of {k}); pass --steps-per-loop 1 for exact "
                  "per-step counts", flush=True)

        def stacked(i0):
            return stack_steps([make_batch(i0 + j) for j in range(k)])

        # Static-source fast path: drivers that feed a constant batch
        # declare it (static_data=True), so one window serves warmup and
        # every timed window — placed on device ONCE instead of
        # re-transferring an identical stack per window.
        static = static_data
        if static and hasattr(runner, "place_steps"):
            data = runner.place_steps(stacked(0))
        else:
            data = stacked(0)

        fence(runner.run_steps(data))   # compile + warmup window
        # Fence the *state* too: the donated-state update can outlive
        # the metrics buffers and must not bleed into the timed window.
        state = getattr(runner, "state", None)
        if state is not None:
            float(np.asarray(state["step"]))
        times = []
        if not static:
            data = stacked(k)
        for w in range(windows):
            t0 = time.perf_counter()
            metrics = runner.run_steps(data)
            if not static and w + 1 < windows:
                # Build the next window while the device runs this one
                # (the dispatch above is async until the fence): the
                # fused path's substitute for the DataLoader's prefetch.
                data = stacked(k * (w + 2))
            fence(metrics)
            dt = time.perf_counter() - t0
            times.append(dt)
            logger.log_metric("examples_per_sec", batch_size * k / dt,
                              "examples/s", step=k * (w + 1))
        mean_s = float(np.sum(times)) / (windows * k)
        summary = {
            "examples_per_sec": batch_size / mean_s,
            "step_ms_mean": mean_s * 1e3,
            # Deliberately NOT step_ms_p50: that key is the per-step
            # path's true per-step percentile; a window-derived stat
            # under the same name would corrupt cross-run comparisons.
            "step_ms_window_p50": float(np.percentile(times, 50) / k * 1e3),
            "steps_per_loop": k,
            "steps_measured": windows * k,
        }
        if flops_per_example and peak_flops:
            summary["mfu"] = (summary["examples_per_sec"]
                              * flops_per_example / peak_flops)
        logger.log_metric("examples_per_sec_final",
                          summary["examples_per_sec"], "examples/s",
                          step=windows * k,
                          extras={kk: v for kk, v in summary.items()
                                  if kk != "examples_per_sec"})
        return summary

    from autodist_tpu.data import DataLoader

    loader = iter(DataLoader(make_batch, runner.mesh, buffer_size=2,
                             num_batches=warmup_steps + train_steps))
    for step in range(warmup_steps):
        runner.step(next(loader))
    # Fence the *state*, not just metrics: the donated-state update can
    # outlive the metrics buffers and must not bleed into the timed window.
    state = getattr(runner, "state", None)
    if state is not None:
        float(np.asarray(state["step"]))

    times = []
    window_start = time.perf_counter()
    for step in range(train_steps):
        t0 = time.perf_counter()
        metrics = runner.step(next(loader))
        fence(metrics)
        times.append(time.perf_counter() - t0)
        if (step + 1) % log_steps == 0:
            elapsed = time.perf_counter() - window_start
            logger.log_metric("examples_per_sec",
                              batch_size * log_steps / elapsed, "examples/s",
                              step=step + 1)
            window_start = time.perf_counter()

    mean_s = float(np.mean(times))
    summary = {
        "examples_per_sec": batch_size / mean_s,
        "step_ms_mean": mean_s * 1e3,
        "step_ms_p50": float(np.percentile(times, 50) * 1e3),
    }
    if flops_per_example and peak_flops:
        summary["mfu"] = summary["examples_per_sec"] * flops_per_example / peak_flops
    logger.log_metric("examples_per_sec_final", summary["examples_per_sec"],
                      "examples/s", step=train_steps,
                      extras={k: v for k, v in summary.items()
                              if k != "examples_per_sec"})
    return summary
