"""Seconds jax spent on the process's programs before the window opened:
tracing, lowering and the backend's compile-or-retrieve (jax's
``backend_compile_duration`` times ``compile_or_get_cached`` whole, so a
program that came from the compilation cache counts its retrieval there
and nowhere else), by the program's own account ``telemetry.startup()``
less its run-scoped ``compile/*`` histograms, which hold what happened
since the runner reset the telemetry at the window's opening (layer:
serving engine).  Nothing to read where the program keeps no account."""
from harness import loader

PARTS = ("trace_s", "lower_s", "backend_s")


def read(rec):
    try:
        from autodist_tpu import telemetry

        account = telemetry.startup()
    except (ImportError, AttributeError):   # a program without the account
        return None
    since = loader.load_module("metrics", "round_ms_p50").instruments()
    return sum(account[k] - since.get("compile/" + k, {}).get("sum", 0.0)
               for k in PARTS)
