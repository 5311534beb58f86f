"""Share of the prefill programs' device time that the ops wearing the
program's ``state_update`` scope took — a prompt's pass through the
recurrence in its chunked form, which leaves the slot the state its
decode steps start from: the delta rule's solve a chunk, power
retention's expansion of every key and query to its symmetric square —
own time inside the runs of ``jit_prefill`` in the traced slice (layer:
kernels).  Nothing to read where the program declares no such scope or
no prefill ran in the slice."""
from harness import scoped_ops


def read(rec):
    got = scoped_ops.own_seconds(rec, rec["programs"]["prefill"],
                                 "state_update")
    return None if got is None else 100.0 * got[0] / got[1]
