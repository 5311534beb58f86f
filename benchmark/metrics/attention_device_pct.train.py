"""Share of the training window program's device time that the ops
wearing the ``attention`` scope took, forward and backward together (the
``[scope]`` line prints them apart): own time inside the runs of
``jit_scanned`` in the traced slice (layer: kernels).

The backward share holds more than attention's own arithmetic: XLA fuses
each Adam update into the backward fusion that makes its gradient, and a
fusion wears one op name, so the updates of the attention weights are
counted here (and those of the FFN weights under ``mlp``), not under
``optimizer``."""
from harness import program_trace


def read(rec):
    return program_trace.scope_pct(rec, rec["program"], "attention")
