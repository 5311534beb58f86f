"""Process-wide telemetry: spans, metrics, per-step records, sinks.

The reference AutoDist's observability was chrome-trace timelines per
``session.run`` (``runner.py:64-75``), graph-stage snapshots, and the
``TimeHistory`` meter; this module unifies that tier for the TPU build:

* :meth:`Telemetry.span` — nested timing spans (``with
  telemetry.span("compile"):``) exported as chrome-trace JSON
  (``chrome://tracing`` / Perfetto load it directly).  Every span is
  also a ``jax.profiler.TraceAnnotation`` of the same name, so while a
  profiler session runs it lands in the trace's host plane, on the
  clock of the device ops — the one way the program writes host spans.
* counters / gauges / histograms (:mod:`autodist_tpu.telemetry.metrics`)
  flushed to a JSONL sink plus a human-readable summary.
* per-step records (step latency, examples, metrics) with a sampling
  knob, flushed to the same JSONL sink.

Config plane (see :mod:`autodist_tpu.const`):

* ``AUTODIST_TPU_TELEMETRY=0`` disables everything: ``span()`` returns a
  shared no-op context manager, instruments are a shared null object, no
  files are ever written.  Default is ON (cheap: in-memory, bounded).
* ``AUTODIST_TPU_TELEMETRY_DIR`` — flush destination (also settable via
  :func:`configure`); without a directory, telemetry stays in-memory.
  A process that runs with the variable set also flushes once when it
  exits, so a run that never calls :func:`flush` leaves its files.
* ``AUTODIST_TPU_TELEMETRY_SAMPLE=N`` — keep every Nth per-step record.
"""
from __future__ import annotations

import atexit
import json
import os
import threading
import time
from collections import deque
from typing import Optional

from autodist_tpu import const
from autodist_tpu.telemetry import account, tracing
from autodist_tpu.telemetry.metrics import (NULL_INSTRUMENT, MetricsRegistry)

# In-memory caps (the default-on-cheap contract): spans and step records
# are held in rings of these lengths, so an unbounded loop cannot grow
# the process with observability data and a service that has run for
# hours still holds its newest spans — the ones an operator looking for
# a stall wants.  What fell out of a ring is counted as dropped.
MAX_SPANS = 20000
MAX_STEP_RECORDS = 100000


class _NullSpan:
    """Shared no-op context manager for the disabled path — ``span()``
    returns this exact singleton, so a disabled run leaves no wrapper
    object behind per call."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


NULL_SPAN = _NullSpan()


_TraceAnnotation = None


def _trace_annotation(name: str, args: dict):
    """The span as a ``jax.profiler.TraceAnnotation`` carrying its
    scalar args (one TraceMe level check when no profiler session
    runs).  jax is imported on the first span, not with this module:
    the tools that read telemetry files never enter one."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation as _TraceAnnotation
    return _TraceAnnotation(name, **{
        k: v for k, v in args.items()
        if isinstance(v, (bool, int, float, str))})


class Span:
    """One timed region; nesting is tracked per thread so the chrome
    trace shows parent/child stacks."""

    __slots__ = ("name", "args", "_tel", "_t0", "_tid", "_annotation")

    def __init__(self, tel: "Telemetry", name: str, args: dict):
        self._tel = tel
        self.name = name
        self.args = args

    def set(self, **attrs) -> "Span":
        """Attach attributes after entry (e.g. a lowering kind resolved
        mid-region); they land in the trace event's ``args``."""
        self.args.update(attrs)
        return self

    def __enter__(self):
        self._tid = threading.get_ident()
        self._tel._span_stack().append(self.name)
        self._annotation = _trace_annotation(self.name, self.args)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        stack = self._tel._span_stack()
        if stack and stack[-1] == self.name:
            stack.pop()
        self._tel._record_span(self, self._t0, t1, self._tid,
                               depth=len(stack),
                               parent=stack[-1] if stack else None)
        return False


class Telemetry:
    """The process-wide recorder.  Use the module-level functions in
    :mod:`autodist_tpu.telemetry` rather than instantiating directly."""

    def __init__(self, out_dir: Optional[str] = None,
                 sample: Optional[int] = None,
                 enabled: Optional[bool] = None):
        self.enabled = (const.ENV.AUTODIST_TPU_TELEMETRY.val
                        if enabled is None else enabled)
        self.out_dir = (out_dir or const.ENV.AUTODIST_TPU_TELEMETRY_DIR.val
                        or None)
        self.sample = (sample if sample is not None
                       else const.ENV.AUTODIST_TPU_TELEMETRY_SAMPLE.val)
        self.registry = MetricsRegistry()
        self._lock = threading.Lock()
        self._local = threading.local()
        self._spans: deque[dict] = deque(maxlen=MAX_SPANS)
        self._spans_dropped = 0
        self._steps: deque[dict] = deque(maxlen=MAX_STEP_RECORDS)
        self._steps_dropped = 0
        self._steps_seen = 0
        self._annotations: dict = {}
        # chrome-trace timestamps: wall-clock epoch anchored once, deltas
        # from the monotonic clock (wall time can step mid-run).
        self._epoch_wall_us = time.time() * 1e6
        self._epoch_perf = time.perf_counter()

    # ---------------- spans ------------------------------------------- #
    def _span_stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, **args):
        if not self.enabled:
            return NULL_SPAN
        if "trace_id" not in args and "trace_ids" not in args:
            tid = tracing.current_trace_id()
            if tid is not None:
                args["trace_id"] = tid
        return Span(self, name, args)

    def _record_span(self, span: Span, t0: float, t1: float, tid: int,
                     depth: int, parent: Optional[str] = None):
        event = {"name": span.name, "ph": "X", "pid": os.getpid(),
                 "tid": tid,
                 "ts": self._epoch_wall_us + (t0 - self._epoch_perf) * 1e6,
                 "dur": (t1 - t0) * 1e6}
        if span.args:
            event["args"] = {k: _jsonable(v) for k, v in span.args.items()}
        if depth:
            event.setdefault("args", {}).update(depth=depth, parent=parent)
        with self._lock:
            if len(self._spans) == MAX_SPANS:
                self._spans_dropped += 1
            self._spans.append(event)

    def spans_since(self, t_perf: float, prefix: str = "") -> list[dict]:
        """The held spans that began at or after ``t_perf`` (a
        ``time.perf_counter`` reading) and whose name starts with
        ``prefix``, newest first: a walk back from the ring's tail that
        stops at the first span that ended before ``t_perf``, for a
        caller that wants the children of a region it has just timed."""
        since_us = self._epoch_wall_us + (t_perf - self._epoch_perf) * 1e6
        out = []
        with self._lock:
            for event in reversed(self._spans):
                if event["ts"] + event["dur"] < since_us:
                    break
                if event["ts"] >= since_us \
                        and event["name"].startswith(prefix):
                    out.append(event)
        return out

    # ---------------- metrics ----------------------------------------- #
    def counter(self, name: str):
        return self.registry.counter(name) if self.enabled \
            else NULL_INSTRUMENT

    def gauge(self, name: str):
        return self.registry.gauge(name) if self.enabled else NULL_INSTRUMENT

    def histogram(self, name: str):
        return self.registry.histogram(name) if self.enabled \
            else NULL_INSTRUMENT

    # ---------------- per-step records -------------------------------- #
    def record_step(self, step: int, duration_s: float, *,
                    examples: Optional[int] = None,
                    steps: int = 1, **extra) -> bool:
        """One training-step (or fused-window: ``steps=k``) record.
        The JSONL record is subject to the sampling knob (returns
        whether it was kept); the ``step/duration_s`` histogram sees
        every call regardless, so percentiles stay exact under
        sampling."""
        if not self.enabled:
            return False
        self.registry.histogram("step/duration_s").observe(
            float(duration_s) / max(steps, 1))
        with self._lock:
            self._steps_seen += 1
            if self.sample > 1 and (self._steps_seen - 1) % self.sample:
                return False
            rec = {"kind": "step", "step": int(step),
                   "duration_ms": float(duration_s) * 1e3}
            if steps != 1:
                rec["steps"] = int(steps)
            if examples is not None:
                rec["examples"] = int(examples)
            for k, v in extra.items():
                rec[k] = _jsonable(v)
            self._append_record(rec)
        return True

    def _append_record(self, rec: dict) -> None:
        """Under the lock: the newest record in, the oldest out of a
        full ring and counted."""
        if len(self._steps) == MAX_STEP_RECORDS:
            self._steps_dropped += 1
        self._steps.append(rec)

    def record_event(self, kind: str, **fields) -> bool:
        """One typed event record on the JSONL sink (``kind`` other than
        the reserved ``"step"`` — e.g. the serving path's per-request
        ``"serve"`` records).  Events share the step records' retention
        cap but not the sampling knob: a request-level record is already
        aggregated, so dropping every Nth would lose requests, not
        resolution."""
        if not self.enabled:
            return False
        if kind == "step":
            raise ValueError("use record_step for step records")
        rec = {"kind": str(kind)}
        for k, v in fields.items():
            rec[k] = _jsonable(v)
        # The same wall-anchored timestamp spans carry: what lets the
        # trace stitcher fold typed records into the merged timeline as
        # causally-ordered instant events.
        rec.setdefault("ts_us", self._epoch_wall_us
                       + (time.perf_counter() - self._epoch_perf) * 1e6)
        if "trace_id" not in rec:
            tid = tracing.current_trace_id()
            if tid is not None:
                rec["trace_id"] = tid
        with self._lock:
            self._append_record(rec)
        return True

    def step_records(self) -> list[dict]:
        with self._lock:
            return list(self._steps)

    # ---------------- manifest / annotations -------------------------- #
    def annotate(self, **kv):
        """Attach run-level facts (mesh, config, argv...) to the
        manifest."""
        if not self.enabled:
            return
        with self._lock:
            self._annotations.update(
                {k: _jsonable(v) for k, v in kv.items()})

    def manifest(self) -> dict:
        """The run manifest: provenance (git SHA, jax/jaxlib versions —
        the identity stamp of every measurement record) plus
        run-level annotations and telemetry bookkeeping."""
        from autodist_tpu.telemetry import records

        with self._lock:
            ann = dict(self._annotations)
            book = {"spans": len(self._spans),
                    "spans_dropped": self._spans_dropped,
                    "step_records": len(self._steps),
                    "steps_seen": self._steps_seen,
                    "step_records_dropped": self._steps_dropped,
                    "sample": self.sample}
        return records.build_manifest(annotations=ann, telemetry=book)

    def startup_record(self) -> dict:
        """The process's account (:func:`account.startup`) as a record,
        with ``run_started_s``: how long after the package's import this
        recorder was created — the last ``reset()``, so what the account
        holds beyond this run's ``compile/*`` instruments happened in
        those seconds."""
        return dict(account.startup(), kind="startup",
                    run_started_s=self._epoch_perf - account.T_IMPORT)

    # ---------------- sinks ------------------------------------------- #
    def chrome_trace(self) -> dict:
        with self._lock:
            events = list(self._spans)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def summary(self) -> str:
        lines = [f"telemetry summary (pid {os.getpid()})"]
        with self._lock:
            lines.append(f"  spans: {len(self._spans)} "
                         f"(dropped {self._spans_dropped})")
            lines.append(f"  step records: {len(self._steps)} of "
                         f"{self._steps_seen} seen (sample={self.sample})")
        for line in self.registry.summary_lines():
            lines.append("  " + line)
        return "\n".join(lines)

    def flush(self, out_dir: Optional[str] = None) -> dict:
        """Write every sink and return ``{artifact: path}``.

        Artifacts: ``trace.json`` (chrome trace), ``metrics.jsonl``
        (per-step records, instrument snapshots, then the process's
        ``kind="startup"`` account, one object per line),
        ``manifest.json``, ``summary.txt``.  A no-op (returns
        ``{}``) when disabled or when no directory is configured — the
        disabled path never writes files.
        """
        if not self.enabled:
            return {}
        out_dir = out_dir or self.out_dir
        if not out_dir:
            return {}
        os.makedirs(out_dir, exist_ok=True)
        paths = {}

        trace_path = os.path.join(out_dir, "trace.json")
        with open(trace_path, "w") as f:
            json.dump(self.chrome_trace(), f)
        paths["trace"] = trace_path

        jsonl_path = os.path.join(out_dir, "metrics.jsonl")
        with open(jsonl_path, "w") as f:
            for rec in self.step_records():
                f.write(json.dumps(rec) + "\n")
            for snap in self.registry.snapshot():
                f.write(json.dumps(snap) + "\n")
            f.write(json.dumps(self.startup_record()) + "\n")
        paths["metrics"] = jsonl_path

        manifest_path = os.path.join(out_dir, "manifest.json")
        with open(manifest_path, "w") as f:
            json.dump(self.manifest(), f, indent=1)
        paths["manifest"] = manifest_path

        summary_path = os.path.join(out_dir, "summary.txt")
        with open(summary_path, "w") as f:
            f.write(self.summary() + "\n")
        paths["summary"] = summary_path
        return paths


def _jsonable(v):
    """Best-effort JSON coercion for span/record attributes (numpy
    scalars, tuples, device arrays)."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    try:
        import numpy as np

        if isinstance(v, np.integer):
            return int(v)
        if isinstance(v, np.floating):
            return float(v)
        arr = np.asarray(v)
        if arr.ndim == 0:
            return arr.item()
        if arr.size <= 16:
            return arr.tolist()
    except Exception:
        pass
    return str(v)


# ---------------- process-wide singleton ------------------------------- #
_singleton: Optional[Telemetry] = None
_singleton_lock = threading.Lock()


def get() -> Telemetry:
    global _singleton
    if _singleton is None:
        with _singleton_lock:
            if _singleton is None:
                _singleton = Telemetry()
    return _singleton


def _flush_at_exit() -> None:
    """Leave the live recorder's files where ``AUTODIST_TPU_TELEMETRY_DIR``
    says, if it says anything: a directory given in code
    (:func:`configure`) belongs to a caller who flushes when they mean
    to."""
    out_dir = const.ENV.AUTODIST_TPU_TELEMETRY_DIR.val
    if out_dir and _singleton is not None:
        _singleton.flush(out_dir)


atexit.register(_flush_at_exit)


def configure(out_dir: Optional[str] = None, sample: Optional[int] = None,
              enabled: Optional[bool] = None) -> Telemetry:
    """Adjust the live singleton (flush destination, sampling, on/off)."""
    tel = get()
    if out_dir is not None:
        tel.out_dir = out_dir
    if sample is not None:
        tel.sample = max(int(sample), 1)
    if enabled is not None:
        tel.enabled = bool(enabled)
    return tel


def reset() -> Telemetry:
    """Discard all recorded state and re-read the env config (tests; a
    fresh run in a reused process)."""
    global _singleton
    with _singleton_lock:
        _singleton = Telemetry()
    return _singleton
