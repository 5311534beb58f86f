"""Prompt tokens admitted over the positions the prefill program
computed, both since the window opened (layer: serving engine).  The
positions are the program's own count (``engine/prefill_positions``:
rows dispatched x the length each spans, padding included); the prompt
tokens are the benchmark's own requests.  Nothing to read where the
program keeps no such counter."""
COUNTER = "engine/prefill_positions"


def _positions():
    """The program's counter since the window opened (the runner resets
    the program's telemetry there), or ``None``."""
    try:
        from autodist_tpu import telemetry

        for m in telemetry.get().registry.snapshot():
            if m["name"] == COUNTER and m["kind"] == "counter":
                return float(m["value"])
    except Exception:       # a program without this telemetry
        pass
    return None


def read(rec):
    positions = _positions()
    if not positions:
        return None
    return 100.0 * sum(p[2] for p in rec["prefills"]) / positions
