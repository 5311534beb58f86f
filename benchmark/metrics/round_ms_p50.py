"""Median length of a scheduler round since the window opened: the
program's histogram ``serve/round_ms``, one observation a
``ContinuousBatcher.step`` (layer: batcher).  Nothing to read where the
program keeps no such histogram.

``read`` also prints what the program's round account and start-up
account say of the slice, through ``tools/telemetry_report.py``'s own
reducers: a ``[rounds]`` line (how many rounds, how long, the medians of
their decode, prefill-a-row and own parts, compile events, rounds flagged
slow), a ``[startup]`` line (what the process did before the window), and
a ``[round]`` line for each of the three longest rounds, joined to the
trace by the ``round`` argument of its ``serve/step`` span: the share of
the span in which the device was busy says whether the step itself ran
long or the device waited on the host.
"""
import importlib.util
import os
import sys
import traceback

from harness import loader, program_trace, trace_reduce as tr
from harness.stats import measure

HISTOGRAM = "serve/round_ms"


def instruments():
    """``{name: snapshot}`` of the program's instruments since the window
    opened (the runner resets the program's telemetry there); ``None``
    for a program without this telemetry."""
    try:
        from autodist_tpu import telemetry

        return {m["name"]: m for m in telemetry.get().registry.snapshot()}
    except Exception:
        return None


def events(kind: str) -> list:
    """The program's typed records of ``kind`` since the window opened."""
    from autodist_tpu import telemetry

    return [r for r in telemetry.get().step_records() if r["kind"] == kind]


def report_tool():
    """``tools/telemetry_report.py`` of this checkout, if it knows the
    round account (a tree from before it does not)."""
    path = os.path.join(loader.ROOT, "tools", "telemetry_report.py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location("telemetry_report", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module if hasattr(module, "rounds_summary") else None


def traced_rounds(path: str) -> dict:
    """``{round: (start ns, end ns)}`` of the ``serve/step`` spans in the
    xplane's host planes, by the ordinal each carries as an argument."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name != "serve/step":
                    continue
                ordinal = dict(e.stats).get("round")
                if ordinal is not None:
                    out[int(ordinal)] = (float(e.start_ns), float(
                        e.start_ns + e.duration_ns))
    return out


def busy_pct(trace, lo: float, hi: float) -> float:
    """Share of ``[lo, hi]`` in which an op ran, mean over the devices."""
    per = [measure(tr.busy_intervals(d, lo, hi))
           for d in trace.devices.values()]
    return 100.0 * sum(per) / len(per) / (hi - lo)


def report(rec) -> None:
    """The ``[rounds]``, ``[startup]`` and ``[round]`` lines."""
    from autodist_tpu import telemetry

    tool = report_tool()
    if tool is None:
        return
    tel = telemetry.get()
    spans = tel.chrome_trace()["traceEvents"]
    records = tel.step_records() + tel.registry.snapshot() \
        + [tel.startup_record()]
    summary = tool.rounds_summary(spans, records)
    if summary is None:
        return
    print(tool.rounds_line(summary), flush=True)
    started = getattr(sys.modules.get("__main__"), "T_START", None)
    more = {} if started is None else {
        "before_import_s": records[-1]["import_perf_s"] - started}
    print(tool.startup_line(tool.startup_summary(records), **more),
          flush=True)
    path = program_trace.xplane_path(rec)
    where = traced_rounds(path)
    trace = program_trace._read_cached(path)
    slow = {r["round"]: r for r in summary["slow"]}
    steps = sorted((e for e in spans if e["name"] == "serve/step"),
                   key=lambda e: -e["dur"])[:3]
    for e in steps:
        a = e["args"]
        at = where.get(a["round"])
        busy = f"{busy_pct(trace, *at):.2f}" if at else "not in the trace"
        kids = slow.get(a["round"], {}).get("children_ms")
        print(f"[round] round={a['round']} round_ms={e['dur'] * 1e-3:.3f} "
              f"decode_ms={a['decode_ms']:.3f} "
              f"prefill_ms={a['prefill_ms']:.3f} own_ms={a['own_ms']:.3f} "
              f"admitted={a['admitted']} compiles={a['compiles']} "
              f"device_busy_pct={busy} flagged_slow={a['round'] in slow}"
              + (f" children_ms={kids}" if kids else ""), flush=True)


def read(rec):
    got = (instruments() or {}).get(HISTOGRAM)
    if not got or got.get("p50") is None:
        return None
    try:
        report(rec)
    except Exception:     # the lines are a reading aid, not the metric
        traceback.print_exc()
    return got["p50"]
