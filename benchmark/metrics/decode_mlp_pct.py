"""Share of the decode program's device time that the ops wearing the
program's ``mlp`` scope took (the gated FFN: gate and up, SiLU, down —
two thirds of a layer's weight bytes, read once per loop step): own time
inside the runs of ``jit_decode`` in the traced slice (layer: kernels)."""
from harness import program_trace


def read(rec):
    return program_trace.scope_pct(rec, rec["programs"]["decode"], "mlp")
