"""Share of the decode program's device time that the ops wearing the
program's ``kv_write`` scope took: own time inside the runs of
``jit_decode`` in the traced slice (layer: kv cache)."""
from harness import program_trace


def read(rec):
    return program_trace.scope_pct(rec, rec["programs"]["decode"],
                                   "kv_write")
