"""The least time the chip could take to read the cached latent rows
that the decode steps of the traced slice attended over — each live
(position, layer) row once a step (the program's
``serve/latent_positions_read``; ``flops.latent_attend_bytes``) over the
HBM peak — against the own device time of the ops wearing the program's
``latent_attend`` scope (scores, softmax, weighted sum over the rows)
inside the runs of the decode program (layer: kernels).  A step's few
query rows make the read bandwidth-bound, so bytes.  A lane read whole,
whatever part of it is live, reads below the live share of the lanes.
Nothing to read where the program keeps no such scope or counter."""
from harness import scoped_ops


def read(rec):
    if not hasattr(rec["flops"], "latent_attend_bytes"):
        return None
    return scoped_ops.roofline_pct(
        rec, "latent_attend", "", "serve/latent_positions_read",
        lambda n: rec["flops"].latent_attend_bytes(rec["cfg"], n))
