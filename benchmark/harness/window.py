"""What every runner's measured window shares: the count of compilations
inside it, and the profiled slice of a traced run."""
from __future__ import annotations

import contextlib
import glob
import os
import shutil

from harness import trace_reduce


class CompileCounter:
    """Counts jax's compile events while ``active``: tracing, lowering
    and backend compilation all report under ``/jax/core/compile/``, and
    a window that has warmed its shapes sees none of them."""

    def __init__(self):
        import jax.monitoring

        self.active = False
        self.events: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if self.active and "/jax/core/compile/" in name:
            self.events.append(name)

    @contextlib.contextmanager
    def counting(self):
        self.active = True
        try:
            yield self
        finally:
            self.active = False

    @property
    def count(self) -> int:
        return len(self.events)


class GcTimer:
    """Times Python's garbage collections while ``active`` (a pause of the
    one thread that drives the window is a pause of the served path)."""

    def __init__(self):
        import gc

        self.active = False
        self.collections = 0
        self.seconds = 0.0
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        import time

        if not self.active:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.seconds += time.perf_counter() - self._t
            self.collections += 1
            self._t = None

    @contextlib.contextmanager
    def timing(self):
        self.active = True
        try:
            yield self
        finally:
            self.active = False

    def __str__(self):
        return (f"{self.collections} python gc passes, "
                f"{self.seconds * 1e3:.1f} ms")


def settle_heap() -> None:
    """Collect what set-up left and move the survivors out of the
    collector's reach, so that a pass inside the window scans the
    window's own objects and not the traced programs' millions."""
    import gc

    gc.collect()
    gc.freeze()


def annotate(name: str, **kw):
    """A host span on the profiler's clock (a no-op when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation("bench/" + name, **kw)


@contextlib.contextmanager
def profiled(out_dir: str, enabled: bool):
    """Profile the body into ``out_dir/profile`` when ``enabled``; the
    body brackets its steady slice with ``annotate("window")``."""
    if not enabled:
        yield None
        return
    import jax

    log_dir = os.path.join(out_dir, "profile")
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # the runners annotate what matters
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


def reduce_profile(log_dir: str) -> dict:
    """The slice's trace and the numbers every traced line carries:
    ``busy_s``, ``window_s`` and the ``breakdown``."""
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"the profiler wrote no xplane under {log_dir}")
    trace = trace_reduce.read_xplane(paths[-1])
    if not trace.devices:
        raise RuntimeError("the trace holds no TPU device plane")
    lo, hi = trace_reduce.annotated_window(trace)
    busy = trace_reduce.busy_seconds(trace, lo, hi)
    if busy <= 0:
        raise RuntimeError("no operation ran on the device in the slice")
    return {
        "trace": trace, "lo": lo, "hi": hi,
        "busy_s": busy, "window_s": (hi - lo) * trace_reduce.NS,
        "breakdown": {
            "device_ops": trace_reduce.top(
                trace_reduce.op_seconds_by_name(trace, lo, hi)),
            "idle_gaps": trace_reduce.top(
                trace_reduce.idle_seconds_by_host_span(trace, lo, hi)),
        },
    }
