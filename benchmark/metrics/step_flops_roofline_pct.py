"""Model FLOPs of a step over what the chips could do in the time they
were busy for it (layer: kernels).  Near the end-to-end MFU when the
device is never idle; the distance to 100 is the step's own fusions."""
from harness import trace_reduce


def read(rec):
    span = trace_reduce.runs_window(rec["trace"], rec["program"],
                                    rec["lo"], rec["hi"])
    if span is None:
        return None
    lo, hi, runs = span
    busy_per_step = trace_reduce.busy_seconds(rec["trace"], lo, hi) \
        / (runs * rec["steps_per_run"])
    flops = rec["flops"].train_flops_per_step(rec["cfg"], rec["traffic"],
                                              rec["chips"])
    peak = rec["peaks"]["bf16_flops_per_s"] * rec["chips"]
    return 100.0 * flops / (busy_per_step * peak)
