"""Own device time of the ops that wear one of the program's scopes, or
carry a kernel's name, inside the runs of one compiled program in a
traced slice: what the readers of a configuration's own per-layer
metrics (``metrics/decode_moe_pct.py`` and its neighbours) share.

``program_trace.scope_of`` charges an op to the FIRST scope of its path,
which is right for a table that must add up to 100; a scope that lies
inside another (``state_update`` in ``linear_attention``,
``moe_experts`` in ``moe``) is found here by any component of the path.
The TPU compiler lowers ``jax.lax.ragged_dot`` to a kernel of its own
whose ops keep no framework path at all (``ragged-dot-none``,
``ragged-dot-metadata``): they are found by that name.
"""
from __future__ import annotations

import bisect

from harness import program_trace, trace_reduce as tr
from harness.stats import measure

GROUPED_MATMUL = "ragged-dot"


def wears(ev, scope: str, kernel: str = "") -> bool:
    """Whether the op event's framework path has ``scope`` among its
    components, or (``kernel`` given) its name carries ``kernel``."""
    parts = (program_trace._WRAPPERS.sub("", p)
             for p in ev.category.rstrip(":").split("/"))
    if scope in parts:
        return True
    return bool(kernel) and (kernel in ev.name or kernel in ev.category)


def _ops_in_runs(trace, rec: dict, pattern: str):
    """``(runs, [(op event, its own time)])`` of the ops inside the runs
    of ``pattern`` that lie in the record's slice, over every device."""
    runs_n, ops = 0, []
    for d in trace.devices.values():
        runs = tr.module_runs(d, pattern, rec["lo"], rec["hi"])
        runs_n += len(runs)
        starts = [r.start for r in runs]
        for ev, own in tr.self_intervals(d.ops):
            i = bisect.bisect_right(starts, ev.start) - 1
            if i < 0 or ev.end > runs[i].end + program_trace.ROUNDING_NS:
                continue
            ops.append((ev, measure(own)))
    return runs_n, ops


def own_seconds(rec: dict, pattern: str, scope: str, kernel: str = ""):
    """``(seconds of the ops wearing scope, seconds of all ops, runs)``
    inside the runs of ``pattern`` that lie in the record's slice, own
    time, mean over the devices; ``None`` where the program declares no
    such scope or no run lies in the slice."""
    vocab = program_trace.vocabulary()
    if vocab is None or scope not in vocab:
        return None
    trace = program_trace._read_cached(program_trace.xplane_path(rec))
    runs_n, ops = _ops_in_runs(trace, rec, pattern)
    total = sum(t for _, t in ops)
    worn = sum(t for ev, t in ops if wears(ev, scope, kernel))
    n = len(trace.devices)
    if not runs_n or total <= 0:
        return None
    return worn / n * tr.NS, total / n * tr.NS, runs_n // n


def report(rec: dict, top_n: int = 12) -> None:
    """Print, once a run, where each of the record's programs spent its
    device time: ``program_trace``'s table by first scope (``[scope]``),
    and the ``top_n`` ops by own time inside the program's runs
    (``[ops]``) — the grouped matmul among them, which no scope holds."""
    trace = program_trace._read_cached(program_trace.xplane_path(rec))
    for pattern in rec["programs"].values():
        program_trace.scope_report(rec, pattern)
        by_op: dict = {}
        runs_n, ops = _ops_in_runs(trace, rec, pattern)
        for ev, t in ops:
            key = (ev.name.split(" ")[-1],
                   "/".join(ev.category.rstrip(":").split("/")[-3:]))
            by_op[key] = by_op.get(key, 0.0) + t
        if not runs_n:
            continue
        total = sum(by_op.values())
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:top_n]
        print(f"[ops] {pattern}: {runs_n} runs, "
              f"{total * tr.NS / runs_n * 1e3:.3f} ms of device time a run; "
              f"largest by shape and path, % of it: "
              f"{[[k[0], k[1], round(100 * v / total, 2)] for k, v in top]}",
              flush=True)


def counter(name: str):
    """The program's counter since the window opened (the runner resets
    the program's telemetry there), or ``None`` where it keeps none of
    that name."""
    from autodist_tpu import telemetry

    for m in telemetry.get().registry.snapshot():
        if m["name"] == name and m["kind"] == "counter":
            return float(m["value"])
    return None


def roofline_pct(rec: dict, scope: str, kernel: str, count: str,
                 least_bytes) -> float:
    """The least time the chip could take to move ``least_bytes(n)``
    over the HBM peak, ``n`` the program's counter ``count`` cut to the
    share of the window's decode dispatches whose runs lie in the slice,
    against the own time of the ops wearing ``scope`` inside the decode
    program's runs.  ``None`` where either is missing."""
    decodes = rec["decodes"]
    got = own_seconds(rec, rec["programs"]["decode"], scope, kernel)
    if not decodes or got is None or not got[0]:
        return None
    n = counter(count)
    if not n:
        return None
    worn_s, _, runs = got
    # the counter saw every dispatch of the window; the trace holds the
    # runs that lie wholly inside the slice
    share = runs / len(decodes)
    least_s = least_bytes(n * share) / rec["peaks"]["hbm_bytes_per_s"]
    print(f"[kernel] {scope}: {worn_s:.6f} s of own device time in {runs} "
          f"runs of {len(decodes)} dispatches; {count} = {n:.0f}; least "
          f"{least_s:.6f} s", flush=True)
    return 100.0 * least_s / worn_s
