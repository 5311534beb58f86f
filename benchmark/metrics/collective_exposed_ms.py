"""Per step, the time inside all-reduce / reduce-scatter / all-gather /
collective-permute operations during which nothing else ran on that
device (layer: lowering).  0 on one chip, the control."""
from harness import trace_reduce


def read(rec):
    span = trace_reduce.runs_window(rec["trace"], rec["program"],
                                    rec["lo"], rec["hi"])
    if span is None:
        return None
    lo, hi, runs = span
    exposed = trace_reduce.exposed_collective_seconds(rec["trace"], lo, hi)
    return exposed / (runs * rec["steps_per_run"]) * 1e3
