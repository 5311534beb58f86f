"""Share of the traced slice in which the device sat idle while the
engine's host code ran: idle time inside the program's ``engine/*`` spans
(``stage``, ``dispatch``, ``fetch``, ``register`` of prefill and decode),
each gap charged to the innermost span (layer: serving engine).  With
``batcher_idle_pct`` and the time outside every program span it adds up
to ``device_idle_pct.serve``."""
from harness import program_trace


def read(rec):
    return program_trace.idle_pct(rec, "engine/")
