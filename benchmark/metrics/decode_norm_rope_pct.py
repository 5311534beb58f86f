"""Share of the decode program's device time that the ops wearing the
program's ``norm`` and ``rope`` scopes took (a looped sandwich block's
four RMSNorms and two rotations a layer application, and the final norm
that closes each loop step: small launch-bound ops on a few rows): own
time inside the runs of ``jit_decode`` in the traced slice (layer:
kernels).  Nothing to read where the program declares neither scope."""
from harness import program_trace


def read(rec):
    table = program_trace.scope_report(rec, rec["programs"]["decode"])
    if table is None or not {"norm", "rope"} <= table["pct"].keys():
        return None
    return sum(sum(table["pct"][s].values()) for s in ("norm", "rope"))
