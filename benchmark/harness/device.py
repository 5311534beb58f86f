"""The chip a run is on: found or refused, never substituted."""
from __future__ import annotations

from harness.loader import BenchmarkError, read_json


class NoChip(BenchmarkError):
    """The accelerator the cell asks for is not there."""


def peaks_of(kind: str) -> dict:
    table = read_json("harness", "peaks.json")
    if kind not in table:
        raise NoChip(f"device_kind {kind!r} is not in harness/peaks.json "
                     f"(it has {[k for k in table if k != 'source']}); "
                     "add its published peaks with their source")
    return table[kind]


def require_chips(chips: int):
    """The ``chips`` first TPU devices and their peaks; raises
    :class:`NoChip` on any other backend, on fewer chips than the cell
    asks for, or on a ``device_kind`` without published peaks."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"jax found platform {devices[0].platform!r}, not a "
                     "TPU; the benchmark has no CPU fallback "
                     "(--rehearse prints counts at a toy size)")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; jax sees "
                     f"{len(devices)}")
    return devices[:chips], peaks_of(devices[0].device_kind)


def describe(devices) -> dict:
    """The ``device`` object of a result line: what jax reports."""
    import jax

    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": jax.device_count()}


def memory_held(devices, say=None) -> dict:
    """What the fullest of ``devices`` holds, read while the window's
    programs are still loaded and its state alive.

    The TPU runtime keeps two counts side by side.  ``bytes_in_use`` is
    what the allocator handed out: arrays (weights, optimizer state, the
    KV cache, batches).  ``bytes_reserved`` is the space a loaded
    program's temporaries (activations, scratch) were given when the
    program was loaded, at the bottom of memory, for as long as it stays
    loaded ("Error loading program ...: Attempting to reserve 13.66G at
    the bottom of memory" is that reservation failing); no allocation
    can use it.  ``peak_bytes_in_use`` counts the first kind only.

    ``memory_peak_bytes`` is ``peak_bytes_in_use`` plus ``bytes_reserved``
    of the same reading: the allocator's peak over the reservation that
    stood while it was reached.  The parts go into the line beside it
    (``memory_in_use_peak_bytes``, the on-chip-measurement guide's
    reading, and ``memory_reserved_bytes``).  All 0 where the backend
    keeps no such count, as the CPU."""
    best = {"memory_peak_bytes": 0, "memory_in_use_peak_bytes": 0,
            "memory_reserved_bytes": 0}
    for d in devices:
        stats = d.memory_stats() or {}
        if say is not None and stats:
            say(f"[device] at the window's end {d}: {dict(stats)}")
        in_use = int(stats.get("peak_bytes_in_use", 0))
        reserved = int(stats.get("bytes_reserved", 0))
        if in_use + reserved >= best["memory_peak_bytes"]:
            best = {"memory_peak_bytes": in_use + reserved,
                    "memory_in_use_peak_bytes": in_use,
                    "memory_reserved_bytes": reserved}
    return best
