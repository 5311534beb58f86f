"""Share of the decode program's device time that the ops wearing the
program's ``latent_attention`` scope took (a latent-attention mixer's
down-projection, the latent's norm, rotary, the up-projection's two
halves folded into the query and the output, the read of the cached
rows, the output projection): own time inside the runs of ``jit_decode``
in the traced slice (layer: kernels).  Nothing to read where the program
declares no such scope."""
from harness import scoped_ops


def read(rec):
    got = scoped_ops.own_seconds(rec, rec["programs"]["decode"],
                                 "latent_attention")
    return None if got is None else 100.0 * got[0] / got[1]
