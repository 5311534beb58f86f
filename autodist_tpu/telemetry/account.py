"""The compile and start-up account: what jax says it compiled, and when.

One ``jax.monitoring`` listener (:func:`watch_compiles`, installed once
by whatever builds programs: ``ServingEngine`` and ``DistributedRunner``
bracket their construction with :func:`constructing` /
:func:`constructed`) sums jax's own compile events twice:

* into the live recorder's run-scoped instruments — histograms
  ``compile/trace_s``, ``compile/lower_s``, ``compile/backend_s``,
  ``compile/cache_retrieval_s`` and counters ``compile/cache_hits``,
  ``compile/cache_misses`` — which ``telemetry.reset()`` discards with
  the rest of a run;
* into the process's own account, :func:`startup`, which ``reset()``
  leaves alone: it describes the process, as its start time does.

What happened before a run's recorder was created (a benchmark's set-up
before its window) is the account less the run-scoped instruments.

``backend_s`` is jax's ``backend_compile_duration``, which times
``compile_or_get_cached`` whole: on a cache hit it *is* the retrieval
(deserialise and load), so ``cache_retrieval_s`` is a part of it and the
seconds a process spent on its programs are ``trace_s + lower_s +
backend_s``.
"""
from __future__ import annotations

import threading
import time

T_IMPORT = time.perf_counter()
T_IMPORT_WALL = time.time()

# jax's duration events -> the account's key (the run-scoped histogram is
# ``compile/<key>``)
DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
}
# jax's plain events -> the account's key (the counter ``compile/<key>``)
COUNTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
COMPILE_PATH = "/jax/core/compile/"

_lock = threading.Lock()
_account = dict.fromkeys((*DURATIONS.values(), "engine_s", "runner_s",
                          "engine_built_s", "runner_built_s"), 0.0)
_account.update(dict.fromkeys((*COUNTS.values(), "compile_events"), 0))
_watching = False


def _live():
    """The live recorder, or ``None`` while telemetry is disabled."""
    from autodist_tpu.telemetry import core

    tel = core.get()
    return tel if tel.enabled else None


def _on_duration(name: str, secs: float, **kw) -> None:
    key = DURATIONS.get(name)
    if key is None:
        return
    tel = _live()
    if tel is None:
        return
    with _lock:
        _account[key] += secs
        if name.startswith(COMPILE_PATH):
            _account["compile_events"] += 1
    tel.histogram("compile/" + key).observe(secs)


def _on_event(name: str, **kw) -> None:
    key = COUNTS.get(name)
    if key is None:
        return
    tel = _live()
    if tel is None:
        return
    with _lock:
        _account[key] += 1
    tel.counter("compile/" + key).inc()


def watch_compiles() -> None:
    """Install the listener; every call after the first does nothing."""
    global _watching
    with _lock:
        if _watching:
            return
        _watching = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def compile_events() -> int:
    """Tracing, lowering and backend-compile events of this process so
    far (what a window that has warmed its shapes sees none of)."""
    return _account["compile_events"]


def constructing(what: str) -> tuple:
    """Called where the construction of ``what`` (``engine``,
    ``runner``) begins; the stamp it returns goes to
    :func:`constructed`.  It installs the listener, so whatever builds
    programs is watched while it does."""
    watch_compiles()
    return what, time.perf_counter(), _account[what + "_s"]


def constructed(stamp: tuple) -> None:
    """Add the seconds since ``stamp`` was taken to its kind's
    construction seconds, less what others of the kind added meanwhile:
    one built inside another's construction (a speculative draft's
    engine) is part of the outer one's seconds, not counted twice.
    ``<kind>_built_s`` keeps when, in seconds since the import, the
    newest one stood: an engine older than the live recorder set its
    gauges in a recorder that a ``reset()`` has discarded."""
    what, since, already = stamp
    if _live() is None:
        return
    now = time.perf_counter()
    with _lock:
        inner = _account[what + "_s"] - already
        _account[what + "_s"] += now - since - inner
        _account[what + "_built_s"] = now - T_IMPORT


def startup() -> dict:
    """The process's account so far: seconds since this package was
    imported (``import_wall_s`` is that moment on the wall clock,
    ``import_perf_s`` on ``time.perf_counter``, for a caller that holds
    stamps of its own), the compile seconds and counts jax reported, the
    seconds spent constructing engines and runners, and when the newest
    of each stood (``engine_built_s``, ``runner_built_s``; 0 for none)."""
    with _lock:
        out = dict(_account)
    out.update(since_import_s=time.perf_counter() - T_IMPORT,
               import_perf_s=T_IMPORT, import_wall_s=T_IMPORT_WALL)
    return out
