"""Share of the device's busy time that lies inside runs of the prefill
program, in the traced slice (layer: serving engine)."""
from harness import trace_reduce
from harness.stats import measure


def read(rec):
    trace, lo, hi = rec["trace"], rec["lo"], rec["hi"]
    inside = total = 0.0
    for d in trace.devices.values():
        total += measure(trace_reduce.busy_intervals(d, lo, hi))
        for run in trace_reduce.module_runs(d, rec["programs"]["prefill"],
                                            lo, hi):
            inside += measure(trace_reduce.busy_intervals(d, run.start,
                                                          run.end))
    return 100.0 * inside / total if total else None
