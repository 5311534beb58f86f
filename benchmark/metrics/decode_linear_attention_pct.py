"""Share of the decode program's device time that the ops wearing the
program's ``linear_attention`` scope took (a gated-DeltaNet layer's
projections, its convolution, the recurrent state read, decayed and
written back, the gated norm and the output projection): own time inside
the runs of ``jit_decode`` in the traced slice (layer: kernels).  Nothing
to read where the program declares no such scope."""
from harness import scoped_ops


def read(rec):
    got = scoped_ops.own_seconds(rec, rec["programs"]["decode"],
                                 "linear_attention")
    return None if got is None else 100.0 * got[0] / got[1]
