"""Share of the decode program's device time that the router took: the
ops wearing the program's ``moe_route`` scope (the scores of every
expert, the groups kept where the router keeps groups, the top-k and its
weights), which lies inside ``moe_experts``; own time inside the runs of
``jit_decode`` in the traced slice (layer: kernels).  Nothing to read
where the program declares no such scope."""
from harness import scoped_ops


def read(rec):
    got = scoped_ops.own_seconds(rec, rec["programs"]["decode"],
                                 "moe_route")
    return None if got is None else 100.0 * got[0] / got[1]
