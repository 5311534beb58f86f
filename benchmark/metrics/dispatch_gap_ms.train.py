"""Mean device-idle gap between one ``run_steps`` window's program and
the next (layer: runner).  What the host's dispatch and feed cost the
chip."""
from harness import trace_reduce


def read(rec):
    gaps = trace_reduce.gaps_between_runs_seconds(
        rec["trace"], rec["program"], rec["lo"], rec["hi"])
    return sum(gaps) / len(gaps) * 1e3 if gaps else None
