"""From a profiler trace to numbers: the one reduction every PR uses.

The JAX profiler writes an ``.xplane.pb``; ``read_xplane`` turns it into
a :class:`Trace` — per device the operations and the executed programs
("XLA Ops" and "XLA Modules" lines), and the host's annotations
(``jax.profiler.TraceAnnotation`` spans, which share the trace's clock).
Everything below works on that plain structure, so it is checked against
a small recorded trace (``benchmark/testdata``) without a chip.

Times are nanoseconds on the trace's clock; results are seconds.
"""
from __future__ import annotations

import dataclasses
import json
import re

from harness.stats import clip, interval_union, measure, subtract

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|reduce-scatter|all-gather|collective-permute|"
    r"all-to-all|collective-broadcast)")
NS = 1e-9
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def short_name(text: str) -> str:
    """The TPU trace names an operation by its whole HLO line
    (``%fusion.5 = f32[8,128]{...} fusion(...)``): keep the name and the
    first result shape, ``fusion.5 f32[8,128]``."""
    head, sep, rest = text.partition(" = ")
    name = head.lstrip("%")
    if sep:
        m = _SHAPE.search(rest)
        if m:
            name += " " + m.group(0)
    return name


def opcode(text: str) -> str:
    """The HLO opcode of a trace event's line (``all-reduce`` for
    ``%psum.34 = f32[8]{0} all-reduce(...)``: jax names the instruction
    after its own primitive, the opcode says what the chip runs)."""
    m = _OPCODE.search(text.partition(" = ")[2])
    return m.group(1) if m else ""


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float
    category: str = ""     # the HLO opcode, where the trace gives it


@dataclasses.dataclass
class Device:
    ops: list          # Events of the "XLA Ops" line(s); may nest
    modules: list      # Events of the "XLA Modules" line: one per run


@dataclasses.dataclass
class Trace:
    devices: dict      # plane name -> Device
    host: list         # annotation Events of the host's threads

    def to_json(self) -> dict:
        ev = lambda es: [[e.name, e.start, e.end, e.category] for e in es]
        return {"devices": {n: {"ops": ev(d.ops), "modules": ev(d.modules)}
                            for n, d in self.devices.items()},
                "host": ev(self.host)}

    @classmethod
    def from_json(cls, data: dict) -> "Trace":
        ev = lambda rows: [Event(*r) for r in rows]
        return cls({n: Device(ev(d["ops"]), ev(d["modules"]))
                    for n, d in data["devices"].items()}, ev(data["host"]))


def load_json(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))


def read_xplane(path: str, host_prefix: str = "bench/") -> Trace:
    """The device planes' op and module events and the host events whose
    name starts with ``host_prefix`` (the runners' own annotations)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                into = ops if line.name == OPS_LINE else modules
                for e in line.events:
                    into.append(Event(short_name(e.name), float(e.start_ns),
                                      float(e.start_ns + e.duration_ns),
                                      opcode(e.name)))
            devices[plane.name] = Device(ops, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(host_prefix):
                        host.append(Event(
                            e.name, float(e.start_ns),
                            float(e.start_ns + e.duration_ns)))
    host.sort(key=lambda e: e.start)
    return Trace(devices, host)


# --------------------------------------------------------------------- #
# windows
# --------------------------------------------------------------------- #
def annotated_window(trace: Trace, name: str = "bench/window"):
    """``(lo, hi)`` of the host annotation that brackets the slice."""
    for e in trace.host:
        if e.name == name:
            return e.start, e.end
    raise ValueError(f"the trace has no host annotation {name!r}")


def module_runs(device: Device, pattern: str, lo: float, hi: float) -> list:
    """The runs of programs whose name matches ``pattern`` that lie
    wholly inside ``[lo, hi]``, in time order."""
    rx = re.compile(pattern)
    return sorted((m for m in device.modules
                   if rx.search(m.name) and m.start >= lo and m.end <= hi),
                  key=lambda m: m.start)


# --------------------------------------------------------------------- #
# busy, idle, op time
# --------------------------------------------------------------------- #
def busy_intervals(device: Device, lo: float, hi: float) -> list:
    """Merged intervals in which any operation ran on the device."""
    return interval_union(clip([[e.start, e.end] for e in device.ops],
                               lo, hi))


def busy_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Mean over the devices of the time an operation ran."""
    per = [measure(busy_intervals(d, lo, hi)) for d in trace.devices.values()]
    return sum(per) / len(per) * NS


def self_intervals(events: list) -> list:
    """``(event, [intervals])``: each event's own time, the part of its
    interval that no event nested inside it covers.  A ``while`` that
    holds a scan's fusions keeps only the gaps between them."""
    out = []
    order = sorted(events, key=lambda e: (e.start, -e.end))
    stack: list = []      # (event, children intervals)

    def close(upto: float):
        while stack and stack[-1][0].end <= upto:
            ev, kids = stack.pop()
            own = subtract([[ev.start, ev.end]], interval_union(kids))
            out.append((ev, own))
            if stack:
                stack[-1][1].append([ev.start, ev.end])

    for ev in order:
        close(ev.start)
        stack.append((ev, []))
    close(float("inf"))
    return out


def op_seconds_by_name(trace: Trace, lo: float, hi: float) -> dict:
    """Own time of each operation name, mean over the devices."""
    total: dict = {}
    for d in trace.devices.values():
        for ev, own in self_intervals(d.ops):
            t = measure(clip(own, lo, hi))
            if t:
                total[ev.name] = total.get(ev.name, 0.0) + t
    n = len(trace.devices)
    return {k: v / n * NS for k, v in total.items()}


def is_collective(ev: Event) -> bool:
    return bool(COLLECTIVE.match(ev.category or ev.name))


def exposed_collective_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Time inside collective operations during which no other operation
    ran on that device, mean over the devices."""
    per = []
    for d in trace.devices.values():
        coll, rest = [], []
        for ev, own in self_intervals(d.ops):
            (coll if is_collective(ev) else rest).extend(own)
        exposed = subtract(interval_union(clip(coll, lo, hi)),
                           interval_union(clip(rest, lo, hi)))
        per.append(measure(exposed))
    return sum(per) / len(per) * NS


def idle_gaps(device: Device, lo: float, hi: float) -> list:
    return subtract([[lo, hi]], busy_intervals(device, lo, hi))


def idle_seconds_by_host_span(trace: Trace, lo: float, hi: float) -> dict:
    """Each device's idle time, charged to the innermost host annotation
    that covers it (``host:unannotated`` where none does); mean over the
    devices.  Says what the host was doing while the chip waited."""
    spans = sorted((e for e in trace.host if e.name != "bench/window"),
                   key=lambda e: e.end - e.start)     # innermost first
    total: dict = {}
    for d in trace.devices.values():
        left = idle_gaps(d, lo, hi)
        for sp in spans:
            inside = clip(left, sp.start, sp.end)
            t = measure(inside)
            if t:
                total[sp.name] = total.get(sp.name, 0.0) + t
                left = subtract(left, interval_union(inside))
        rest = measure(left)
        if rest:
            total["host:unannotated"] = total.get("host:unannotated",
                                                  0.0) + rest
    n = len(trace.devices)
    return {k: v / n * NS for k, v in total.items()}


def gaps_between_runs_seconds(trace: Trace, pattern: str, lo: float,
                              hi: float) -> list:
    """Device-idle time between each run of a program and the next, all
    devices' gaps in one list."""
    gaps = []
    for d in trace.devices.values():
        runs = module_runs(d, pattern, lo, hi)
        for a, b in zip(runs, runs[1:]):
            idle = subtract([[a.end, b.start]],
                            busy_intervals(d, a.end, b.start))
            gaps.append(measure(idle) * NS)
    return gaps


def top(named_seconds: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(named_seconds.items(),
                                      key=lambda kv: -kv[1])[:n]]


def runs_window(trace: Trace, pattern: str, lo: float, hi: float):
    """``(lo, hi, n)``: the span from the start of the first to the end
    of the last complete run of a program inside the slice, and how many
    runs each device made in it (the fewest, if they differ)."""
    per = [module_runs(d, pattern, lo, hi) for d in trace.devices.values()]
    if not per or not all(per):
        return None
    return (min(r[0].start for r in per), max(r[-1].end for r in per),
            min(len(r) for r in per))
