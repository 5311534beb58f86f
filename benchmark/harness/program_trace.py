"""What the program says about itself inside a traced run's xplane.

Two things the program writes reach the profiler's trace on the device's
clock, and this module reads both:

* its host spans: every ``telemetry.span`` is a ``TraceAnnotation`` of
  the same name (``serve/*``, ``engine/*``, ``runner/*``), so the time
  the device sat idle can be charged to what the program was doing;
* its scopes: the ops of a compiled program wear the names of
  ``autodist_tpu.telemetry.SCOPES`` (``jax.named_scope``) in the
  framework op name the TPU plane keeps for each op (``tf_op``:
  ``jit(decode)/while/body/attention/dot_general:``), so device time can
  be charged to a part of this system under a name no recompile changes.

``jax.profiler.ProfileData`` gives events with their own stats only; an
op's framework name is a stat of its event *metadata*, which the binding
does not show.  ``op_names`` therefore reads that one table straight
from the protobuf wire format (a few thousand entries; the events, which
are many, still come through ``ProfileData``).

A program without the vocabulary (a tree from before it, which the
driver lays these files over) has nothing to read: the readers give
``None`` there.  A program that has it and a trace whose ops carry none
of the scopes the program itself applies is an error: a compilation
cache handed back executables compiled before the scopes existed.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re

from harness import loader, trace_reduce as tr
from harness.stats import measure

SPAN_PREFIXES = ("serve/", "engine/", "runner/")
UNSCOPED = "unscoped"
# names a flax module gives its ops on its own (SelfAttention(name=
# "attention"), MlpBlock(name="mlp")): an executable compiled before the
# program's scopes existed wears them too, so they prove nothing
FLAX_NAMED = ("attention", "mlp")
UNANNOTATED = "host:unannotated"
_WRAPPERS = re.compile(r"^(?:[\w.\-]+\()+|\)+$")
_PROGRAM_ID = re.compile(r"\((-?\d+)\)$")
ROUNDING_NS = 2.0     # events are picoseconds rounded to whole ns


def vocabulary():
    """The program's scope names; ``None`` where it declares none."""
    try:
        from autodist_tpu.telemetry import SCOPES
    except ImportError:
        return None
    return tuple(SCOPES)


# --------------------------------------------------------------------- #
# the xplane: events through ProfileData, op names from the wire
# --------------------------------------------------------------------- #
def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(number, value)`` of a protobuf message's fields: an int for a
    varint, a ``memoryview`` for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield number, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(view):
    key, value = 0, b""
    for number, v in _fields(view):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def op_names(xspace: bytes, stat: str = "tf_op") -> dict:
    """``{plane: {(program id, event name): framework op name}}`` of the
    device planes, from the planes' ``event_metadata`` (xplane.proto:
    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
    .stat_metadata = 5; XEventMetadata.name = 2, .stats = 5;
    XStat.metadata_id = 1, .uint64_value = 3, .int64_value = 4,
    .str_value = 5, .ref_value = 7; XStatMetadata.name = 2).  The
    program id is as the "XLA Modules" line prints it in a run's name."""
    out = {}
    for number, plane in _fields(memoryview(xspace)):
        if number != 1:
            continue
        name, events, stats = "", [], {}
        for n, v in _fields(plane):
            if n == 2:
                name = _text(v)
            elif n == 4:
                events.append(_map_entry(v)[1])
            elif n == 5:
                key, meta = _map_entry(v)
                stats[key] = next((_text(x) for k, x in _fields(meta)
                                   if k == 2), "")
        if not tr.DEVICE_PLANE.match(name):
            continue
        wanted = {k for k, v in stats.items() if v in (stat, "program_id")}
        table = out.setdefault(name, {})
        for meta in events:
            ev_name, found = "", {}
            for n, v in _fields(meta):
                if n == 2:
                    ev_name = _text(v)
                elif n == 5:
                    st = dict(_fields(v))
                    if st.get(1) in wanted:
                        found[stats[st[1]]] = st
            if stat not in found:
                continue
            st = found[stat]
            op = _text(st[5]) if 5 in st else stats.get(st.get(7), "")
            pid = found.get("program_id", {})
            pid = pid.get(3, pid.get(4, 0))
            for signed in (pid, pid - (1 << 64)):
                table[(str(signed), ev_name)] = op
    return out


def read_xplane(path: str) -> tr.Trace:
    """A ``trace_reduce.Trace`` whose op events carry the framework op
    name as their ``category`` and whose host list holds the program's
    own spans."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    names = op_names(raw)
    data = ProfileData.from_serialized_xspace(raw)
    devices, host = {}, []
    for plane in data.planes:
        if tr.DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            modules = [tr.Event(e.name, float(e.start_ns),
                                float(e.start_ns + e.duration_ns))
                       for e in lines[tr.MODULES_LINE].events] \
                if tr.MODULES_LINE in lines else []
            ops = _named_ops(lines[tr.OPS_LINE].events, modules,
                             names.get(plane.name, {})) \
                if tr.OPS_LINE in lines else []
            devices[plane.name] = tr.Device(ops, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        host.append(tr.Event(
                            e.name, float(e.start_ns),
                            float(e.start_ns + e.duration_ns)))
    host.sort(key=lambda e: e.start)
    return tr.Trace(devices, host)


def _named_ops(events, modules: list, table: dict) -> list:
    """The op events of one device, each with the op name that ``table``
    holds for its HLO line in the program whose run covers it."""
    runs = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in runs]
    ids = [(_PROGRAM_ID.search(m.name) or [None, ""])[1] for m in runs]
    seen: dict = {}      # (program id, hlo line) -> (short name, op name)
    out = []
    for e in events:
        start, end = float(e.start_ns), float(e.start_ns + e.duration_ns)
        i = bisect.bisect_right(starts, start) - 1
        key = (ids[i] if i >= 0 and end <= runs[i].end + ROUNDING_NS
               else "", e.name)
        if key not in seen:
            seen[key] = (tr.short_name(key[1]), table.get(key, ""))
        out.append(tr.Event(seen[key][0], start, end, seen[key][1]))
    return out


def xplane_path(rec: dict) -> str:
    """The newest xplane of the cell that the record's configuration and
    traffic mix name."""
    spec = loader.benchmark_spec()
    cells = [c["name"] for c in spec["workloads"]
             if c["config"] == rec["cfg"]["name"]
             and c["traffic"] == rec["traffic"]["name"]]
    if len(cells) != 1:
        raise loader.BenchmarkError(
            f"{len(cells)} cells run {rec['cfg']['name']!r} under "
            f"{rec['traffic']['name']!r}")
    paths = sorted(glob.glob(os.path.join(
        loader.OUT_DIR, cells[0], "profile", "plugins", "profile", "*",
        "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no xplane under benchmark/out/{cells[0]}")
    return paths[-1]


@functools.lru_cache(maxsize=1)
def _read_cached(path: str) -> tr.Trace:
    """A run's xplane, parsed once per process."""
    return read_xplane(path)


# --------------------------------------------------------------------- #
# device idle time by the program's innermost span
# --------------------------------------------------------------------- #
def idle_by_span(trace: tr.Trace, lo: float, hi: float) -> dict:
    """What ``trace_reduce.idle_seconds_by_host_span`` gives (each
    device's idle time charged to the shortest host span that covers it,
    ``host:unannotated`` where none does; seconds, mean over the
    devices), computed segment by segment between the spans' edges: a
    serving slice holds some 10^5 gaps between ops, and that function
    walks all of them once for every span."""
    spans = sorted((e for e in trace.host if e.end > lo and e.start < hi),
                   key=lambda e: e.end - e.start)
    edges = sorted({lo, hi} | {min(max(t, lo), hi) for e in spans
                               for t in (e.start, e.end)})
    total: dict = {}
    for d in trace.devices.values():
        gaps = tr.idle_gaps(d, lo, hi)
        starts = [g[0] for g in gaps]
        upto = [0.0]
        for s, e in gaps:
            upto.append(upto[-1] + e - s)

        def idle_before(t):
            i = bisect.bisect_right(starts, t)
            return upto[i] - (max(gaps[i - 1][1] - t, 0.0) if i else 0.0)

        for a, b in zip(edges, edges[1:]):
            t = idle_before(b) - idle_before(a)
            if t <= 0:
                continue
            name = next((e.name for e in spans
                         if e.start <= a and e.end >= b), UNANNOTATED)
            total[name] = total.get(name, 0.0) + t
    n = len(trace.devices)
    return {k: v / n * tr.NS for k, v in total.items()}


def span_table(trace: tr.Trace, lo: float, hi: float):
    """``{"idle": idle seconds by span, "spans": {name: (count, mean
    ms)}}`` for the program's spans inside ``[lo, hi]``; ``None`` where
    it wrote none there."""
    inside: dict = {}
    for e in trace.host:
        if e.start >= lo and e.end <= hi:
            inside.setdefault(e.name, []).append(e.end - e.start)
    if not inside:
        return None
    return {"idle": idle_by_span(trace, lo, hi),
            "spans": {k: (len(v), sum(v) / len(v) * 1e-6)
                      for k, v in sorted(inside.items())}}


def idle_shares(idle: dict, window_s: float) -> dict:
    """Idle seconds by span as shares of the slice, by the spans' layer
    (``serve/*``, ``engine/*``, ``runner/*``) and ``host:unannotated``:
    together the device's whole idle share."""
    return {key: 100.0 * sum(v for k, v in idle.items()
                             if k.startswith(key.rstrip("*"))) / window_s
            for key in [p + "*" for p in SPAN_PREFIXES] + [UNANNOTATED]}


def span_report(rec: dict):
    """``span_table`` of the record's run, printed once as a ``[span]``
    line."""
    return _span_report(xplane_path(rec), rec["lo"], rec["hi"])


@functools.lru_cache(maxsize=4)
def _span_report(path: str, lo: float, hi: float):
    table = span_table(_read_cached(path), lo, hi)
    if table is None:
        print("[span] the program wrote no serve/*, engine/* or runner/* "
              "span inside the slice", flush=True)
        return None
    window_s = (hi - lo) * tr.NS
    shares = idle_shares(table["idle"], window_s)
    spans = {k: (n, round(ms, 4)) for k, (n, ms) in table["spans"].items()}
    print(f"[span] device idle by the program's innermost span, % of the "
          f"{window_s:.3f} s slice: "
          f"{ {k: round(v, 4) for k, v in shares.items()} } sum "
          f"{sum(shares.values()):.4f}; seconds by span: "
          f"{ {k: round(v, 6) for k, v in sorted(table['idle'].items())} }; "
          f"spans (count, mean ms): {spans}", flush=True)
    return table


def idle_pct(rec: dict, prefix: str):
    """Device idle time inside the own time of the spans whose name
    starts with ``prefix``, as a share of the slice."""
    table = span_report(rec)
    if table is None:
        return None
    return idle_shares(table["idle"], rec["window_s"])[prefix + "*"]


# --------------------------------------------------------------------- #
# device time by scope inside the runs of one program
# --------------------------------------------------------------------- #
def scope_of(op_name: str, vocab) -> tuple:
    """``(scope, direction)`` of a framework op name: the first name of
    the vocabulary among its path components, transforms unwrapped
    (``transpose(jvp(lm_head))``), and ``"bwd"`` when a component is a
    transpose, the backward pass's mark."""
    scope, direction = UNSCOPED, "fwd"
    for part in op_name.rstrip(":").split("/"):
        if "transpose(" in part:
            direction = "bwd"
        if scope == UNSCOPED and _WRAPPERS.sub("", part) in vocab:
            scope = _WRAPPERS.sub("", part)
    return scope, direction


def scope_seconds(trace: tr.Trace, pattern: str, lo: float, hi: float,
                  vocab) -> dict:
    """Own device time of every ``(scope, direction)`` inside the runs of
    the programs matching ``pattern`` that lie in ``[lo, hi]``, seconds,
    mean over the devices; ``runs`` counts them and ``unscoped_ops``
    keeps the own time of each op without a scope, by name."""
    by_scope, unscoped, runs_n = {}, {}, 0
    for d in trace.devices.values():
        runs = tr.module_runs(d, pattern, lo, hi)
        runs_n += len(runs)
        starts = [r.start for r in runs]
        for ev, own in tr.self_intervals(d.ops):
            i = bisect.bisect_right(starts, ev.start) - 1
            if i < 0 or ev.end > runs[i].end + ROUNDING_NS:
                continue
            t = measure(own)
            key = scope_of(ev.category, vocab)
            by_scope[key] = by_scope.get(key, 0.0) + t
            if key[0] == UNSCOPED:
                unscoped[ev.name] = unscoped.get(ev.name, 0.0) + t
    n = len(trace.devices)
    return {"runs": runs_n // n,
            "by_scope": {k: v / n * tr.NS for k, v in by_scope.items()},
            "unscoped_ops": {k: v / n * tr.NS for k, v in unscoped.items()}}


def scope_table(trace: tr.Trace, pattern: str, lo: float, hi: float,
                vocab):
    """``{"runs", "device_s", "pct": {scope: {"fwd": %, "bwd": %}},
    "unscoped_ops": [[name, %], ..]}`` of the device time of the runs of
    ``pattern`` in ``[lo, hi]``, ``unscoped`` among the scopes, so that
    they add up to 100.  ``None`` where no run lies in the slice; an
    error where the runs' ops carry no scope that only the program's own
    ``telemetry.scope`` gives."""
    got = scope_seconds(trace, pattern, lo, hi, vocab)
    total = sum(got["by_scope"].values())
    if not got["runs"] or total <= 0:
        return None
    if all(scope in FLAX_NAMED + (UNSCOPED,)
           for scope, _ in got["by_scope"]):
        raise RuntimeError(
            f"no op of the {got['runs']} runs of {pattern} carries a scope "
            f"that the program itself applies "
            f"({[s for s in vocab if s not in FLAX_NAMED]}): the "
            f"executable was compiled before the scopes "
            f"existed and came back from jax's persistent compilation "
            f"cache (JAX_COMPILATION_CACHE_DIR, or <checkout>/.jax_cache), "
            f"whose key leaves op metadata out unless "
            f"autodist_tpu.utils.compile_cache.enable_compile_cache() has "
            f"put it in; clear that cache or let the entry point call it")
    pct = {s: {"fwd": 0.0, "bwd": 0.0} for s in tuple(vocab) + (UNSCOPED,)}
    for (scope, direction), t in got["by_scope"].items():
        pct[scope][direction] = 100.0 * t / total
    return {"runs": got["runs"], "device_s": total, "pct": pct,
            "unscoped_ops": [[k, 100.0 * v / total] for k, v in
                             tr.top(got["unscoped_ops"], 4)]}


def scope_report(rec: dict, pattern: str):
    """``scope_table`` of the record's run, printed once as a ``[scope]``
    line; ``None`` also where the program declares no scopes."""
    return _scope_report(xplane_path(rec), pattern, rec["lo"], rec["hi"])


@functools.lru_cache(maxsize=4)
def _scope_report(path: str, pattern: str, lo: float, hi: float):
    vocab = vocabulary()
    if vocab is None:
        print("[scope] the program declares no scopes "
              "(autodist_tpu.telemetry.SCOPES)", flush=True)
        return None
    table = scope_table(_read_cached(path), pattern, lo, hi, vocab)
    if table is None:
        print(f"[scope] no run of {pattern} inside the slice", flush=True)
        return None
    pct = {s: (round(v["fwd"], 3), round(v["bwd"], 3))
           for s, v in table["pct"].items()}
    print(f"[scope] {pattern}: {table['runs']} runs, "
          f"{table['device_s']:.6f} s of own device time; % by scope "
          f"(fwd, bwd): {pct} sum "
          f"{sum(v['fwd'] + v['bwd'] for v in table['pct'].values()):.3f}; "
          f"largest unscoped ops: "
          f"{[[k, round(v, 3)] for k, v in table['unscoped_ops']]}",
          flush=True)
    return table


def scope_pct(rec: dict, pattern: str, scope: str):
    """Share of the device time of ``pattern``'s runs that ops of
    ``scope`` took, forward and backward together."""
    table = scope_report(rec, pattern)
    if table is None:
        return None
    return sum(table["pct"][scope].values())
