"""Share of the traced slice in which the device sat idle while the
batcher's own code ran: idle time inside the own time of the program's
``serve/*`` spans (``serve/evict``, ``serve/admit``, ``serve/distribute``
and what ``serve/step``, ``serve/prefill`` and ``serve/decode`` keep for
themselves), each gap charged to the innermost span (layer: batcher)."""
from harness import program_trace


def read(rec):
    return program_trace.idle_pct(rec, "serve/")
