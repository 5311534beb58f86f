"""Share of the decode program's device time that the ops wearing the
program's ``attention`` scope took (qkv projection through output
projection, without the cache write): own time inside the runs of
``jit_decode`` in the traced slice (layer: kernels)."""
from harness import program_trace


def read(rec):
    return program_trace.scope_pct(rec, rec["programs"]["decode"],
                                   "attention")
