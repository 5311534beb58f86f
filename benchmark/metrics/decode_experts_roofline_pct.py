"""The least time the chip could take to read the experts that the
decode steps of the traced slice hit — every held expert with at least
one row, once a step and a layer, gate, up and down (the program's
``moe/experts_hit``; ``flops.experts_bytes``) over the HBM peak —
against the own device time of the ops wearing the program's
``moe_experts`` scope (routing, the sort and gather of the pairs, the
combine) and of the grouped-matmul kernel itself (``ragged-dot``) inside
the runs of the decode program (layer: kernels).  The experts' matmuls
are bound by bandwidth at a decode step's few rows, so bytes.  Nothing to
read where the program keeps no such scope or counter."""
from harness import scoped_ops


def read(rec):
    if not hasattr(rec["flops"], "experts_bytes"):
        return None
    return scoped_ops.roofline_pct(
        rec, "moe_experts", scoped_ops.GROUPED_MATMUL, "moe/experts_hit",
        lambda hit: rec["flops"].experts_bytes(rec["cfg"], hit))
