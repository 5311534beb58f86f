"""The least time the chip could take to move the recurrent state that
the decode steps of the traced slice updated — each (slot, step, linear
layer) row's float32 state read once and written once (the program's
``engine/state_rows``; ``flops.state_update_bytes``) over the HBM peak —
against the own device time of the ops wearing the program's
``state_update`` scope inside the runs of the decode program (layer:
kernels).  The update is elementwise over the state, so bytes.  Nothing
to read where the program keeps no such scope or counter."""
from harness import scoped_ops


def read(rec):
    if not hasattr(rec["flops"], "state_update_bytes"):
        return None
    return scoped_ops.roofline_pct(
        rec, "state_update", "", "engine/state_rows",
        lambda rows: rec["flops"].state_update_bytes(rec["cfg"], rows))
