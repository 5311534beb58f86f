"""Share of the decode program's device time that the ops wearing the
program's ``state_conv`` scope took — a state-space layer's causal
convolution over ``[x | B | C]``: the tail read out of the cache
manager's array, shifted and written back, the taps, the bias and the
SiLU, all outside the state-step kernel; what a later kernel that takes
the convolution in would remove — own time inside the runs of
``jit_decode`` in the traced slice (layer: kernels).  Nothing to read
where the program declares no such scope."""
from harness import scoped_ops


def read(rec):
    got = scoped_ops.own_seconds(rec, rec["programs"]["decode"],
                                 "state_conv")
    return None if got is None else 100.0 * got[0] / got[1]
