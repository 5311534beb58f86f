"""The least time the chip could take for the dense decode-attention
kernel's calls in the traced slice — the bytes they must move (the live
blocks of K and V, rounded to the kernel's block, and the rows written)
over the HBM peak — against the device time of the ops that wear the
kernel's ``adtk_flash_decode`` marker inside the runs of the decode
program (layer: kernels).  The kernel is bound by bandwidth, so bytes.

The blocks are the program's own count (``serve/kv_blocks_attended``,
which the batcher advances by what each window's steps read, from the
lengths it holds); the rows written are the runner's (active slots x
steps of every decode dispatch).  Nothing to read where the decode
program runs no such kernel or the program keeps no such counter."""
import bisect

from harness import program_trace, trace_reduce
from harness.stats import measure

MARKER = "adtk_flash_decode"
COUNTER = "serve/kv_blocks_attended"


def _counter(name):
    """The program's counter since the window opened (the runner resets
    the program's telemetry there), or ``None``."""
    try:
        from autodist_tpu import telemetry

        for m in telemetry.get().registry.snapshot():
            if m["name"] == name and m["kind"] == "counter":
                return float(m["value"])
    except Exception:       # a program without this telemetry
        pass
    return None


def _block_len(max_len):
    """The kernel's block for a lane of ``max_len`` positions: the unit
    of the program's counter."""
    try:
        from autodist_tpu.kernel.pallas.flash_decode import decode_block_len
    except ImportError:
        return None
    return decode_block_len(max_len)


def kernel_seconds(trace, pattern, lo, hi):
    """``(own device seconds of the marked ops, runs)`` inside the runs
    of ``pattern`` that lie in ``[lo, hi]``, mean over the devices."""
    total, runs_n = 0.0, 0
    for d in trace.devices.values():
        runs = trace_reduce.module_runs(d, pattern, lo, hi)
        runs_n += len(runs)
        starts = [r.start for r in runs]
        for ev, own in trace_reduce.self_intervals(d.ops):
            i = bisect.bisect_right(starts, ev.start) - 1
            if i < 0 or ev.end > runs[i].end + program_trace.ROUNDING_NS:
                continue
            if MARKER in ev.name or MARKER in ev.category:
                total += measure(own)
    n = len(trace.devices)
    return total / n * trace_reduce.NS, runs_n // n


def read(rec):
    cfg, decodes = rec["cfg"], rec["decodes"]
    if not decodes or not hasattr(rec["flops"],
                                  "decode_attention_kernel_bytes"):
        return None
    trace = program_trace._read_cached(program_trace.xplane_path(rec))
    kernel_s, runs = kernel_seconds(trace, rec["programs"]["decode"],
                                    rec["lo"], rec["hi"])
    blocks = _counter(COUNTER)
    block_len = _block_len(cfg["serving"]["max_len"])
    if not kernel_s or not runs or not blocks or not block_len:
        return None
    steps = cfg["serving"]["decode_steps"]
    rows = sum(d[1] for d in decodes) * steps
    # the counter and the runner saw every dispatch of the window; the
    # trace holds the runs that lie wholly inside the slice
    share = runs / len(decodes)
    least_s = rec["flops"].decode_attention_kernel_bytes(
        cfg, blocks * share, rows * share, block_len) \
        / rec["peaks"]["hbm_bytes_per_s"]
    print(f"[kernel] {MARKER}: {kernel_s:.6f} s of own device time in "
          f"{runs} runs of {len(decodes)} dispatches; {blocks:.0f} blocks "
          f"of {block_len} attended and {rows} rows written per cache "
          f"layer; least {least_s:.6f} s", flush=True)
    return 100.0 * least_s / kernel_s
