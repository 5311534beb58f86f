"""Metrics registry: counters, gauges, histograms.

The counterpart of the reference's ``TimeHistory`` meter and ad-hoc
per-run printouts (SURVEY.md §5.1), generalized: any subsystem registers
a named instrument once and updates it on the hot path; the registry
snapshots to JSONL lines (one ``{"kind": ..., "name": ..., ...}`` object
per line) and renders a human-readable summary.  Instruments are
process-wide and thread-safe — the AsyncPS server thread and the step
loop update the same registry.
"""
from __future__ import annotations

import threading
from typing import Optional

import numpy as np

# Histogram sample cap: beyond it new observations still update count /
# sum / min / max but stop being retained for percentiles (the summary
# reports how many were dropped).  Keeps a million-step run's registry
# bounded.
HISTOGRAM_CAP = 65536


class Counter:
    """Monotonic event count (``asyncps/push``, ``bench/retries``...)."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._value = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        return self._value

    def snapshot(self) -> dict:
        return {"kind": "counter", "name": self.name, "value": self._value}


class Gauge:
    """Last-write-wins scalar (HBM in use, MFU, examples/sec)."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._value: Optional[float] = None
        self._lock = threading.Lock()

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    @property
    def value(self) -> Optional[float]:
        return self._value

    def snapshot(self) -> dict:
        return {"kind": "gauge", "name": self.name, "value": self._value}


class Histogram:
    """Distribution of observations (step latency, SSP gate waits)."""

    __slots__ = ("name", "_values", "_count", "_sum", "_min", "_max",
                 "_lock")

    def __init__(self, name: str):
        self.name = name
        self._values: list[float] = []
        self._count = 0
        self._sum = 0.0
        self._min: Optional[float] = None
        self._max: Optional[float] = None
        self._lock = threading.Lock()

    def observe(self, value: float, count: int = 1) -> None:
        """``count`` observations of ``value`` (a fused window's tokens
        share one latency: one call, not one a token)."""
        value = float(value)
        if count < 1:
            return
        with self._lock:
            self._count += count
            self._sum += value * count
            self._min = value if self._min is None else min(self._min, value)
            self._max = value if self._max is None else max(self._max, value)
            room = HISTOGRAM_CAP - len(self._values)
            if room > 0:
                self._values.extend([value] * min(count, room))

    @property
    def count(self) -> int:
        return self._count

    def percentile(self, q: float) -> Optional[float]:
        with self._lock:
            if not self._values:
                return None
            return float(np.percentile(np.asarray(self._values), q))

    def snapshot(self) -> dict:
        with self._lock:
            vs = np.asarray(self._values) if self._values else None
        out = {"kind": "histogram", "name": self.name, "count": self._count,
               "sum": self._sum, "min": self._min, "max": self._max,
               "mean": (self._sum / self._count) if self._count else None,
               "p50": float(np.percentile(vs, 50)) if vs is not None else None,
               "p99": float(np.percentile(vs, 99)) if vs is not None else None}
        if self._count > len(self._values):
            out["samples_dropped"] = self._count - len(self._values)
        return out


class NullInstrument:
    """The disabled path's stand-in for every instrument kind: all
    updates are no-ops, all reads are empty.  A single shared instance —
    the zero-overhead-when-disabled contract is that call sites hold no
    per-call allocation or state."""

    __slots__ = ()
    name = "<disabled>"
    value = None
    count = 0

    def inc(self, n: int = 1) -> None:
        pass

    def set(self, value: float) -> None:
        pass

    def observe(self, value: float, count: int = 1) -> None:
        pass

    def percentile(self, q: float) -> None:
        return None

    def snapshot(self) -> dict:
        return {}


NULL_INSTRUMENT = NullInstrument()


class MetricsRegistry:
    """Name → instrument map; get-or-create, kind-checked."""

    def __init__(self):
        self._instruments: dict[str, object] = {}
        self._lock = threading.Lock()

    def _get(self, name: str, cls):
        with self._lock:
            inst = self._instruments.get(name)
            if inst is None:
                inst = self._instruments[name] = cls(name)
            elif not isinstance(inst, cls):
                raise TypeError(
                    f"metric {name!r} already registered as "
                    f"{type(inst).__name__}, requested {cls.__name__}")
            return inst

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str) -> Histogram:
        return self._get(name, Histogram)

    def snapshot(self) -> list[dict]:
        """One JSONL-ready dict per instrument, name-sorted."""
        with self._lock:
            insts = sorted(self._instruments.items())
        return [inst.snapshot() for _, inst in insts]

    def summary_lines(self) -> list[str]:
        """Human-readable one-liner per instrument."""
        lines = []
        for snap in self.snapshot():
            if snap["kind"] == "histogram":
                mean = snap["mean"]
                lines.append(
                    f"{snap['name']}: n={snap['count']}"
                    + (f" mean={mean:.6g} p50={snap['p50']:.6g} "
                       f"p99={snap['p99']:.6g}" if mean is not None else ""))
            else:
                lines.append(f"{snap['name']}: {snap['value']}")
        return lines
