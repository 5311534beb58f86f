"""Share of the decode program's device time that ops with no scope of
the program's vocabulary took: what the compiler added (copies, the
loop's own bookkeeping) and what the program leaves unnamed (the layer
norms); own time inside the runs of ``jit_decode`` (layer: kernels)."""
from harness import program_trace


def read(rec):
    return program_trace.scope_pct(rec, rec["programs"]["decode"],
                                   program_trace.UNSCOPED)
