"""The least time the chip could take for one decode step — the bytes it
must read (every weight once, the live keys and values) over the HBM
peak — against the device time one step of the decode program took
(layer: kernels).  Decode is bound by bandwidth, so bytes, not FLOPs."""
from harness import trace_reduce


def read(rec):
    trace, lo, hi = rec["trace"], rec["lo"], rec["hi"]
    runs = [r for d in trace.devices.values() for r in
            trace_reduce.module_runs(d, rec["programs"]["decode"], lo, hi)]
    if not runs or not rec["decodes"]:
        return None
    steps = rec["cfg"]["serving"]["decode_steps"]
    step_s = sum(r.end - r.start for r in runs) / len(runs) / steps \
        * trace_reduce.NS
    live = sum(d[2] for d in rec["decodes"]) / len(rec["decodes"])
    least_s = rec["flops"].decode_step_bytes(rec["cfg"], live) \
        / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
