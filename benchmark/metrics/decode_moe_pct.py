"""Share of the decode program's device time that the routed FFN took:
the ops wearing the program's ``moe`` scope (router, top-k, the sort and
gather of the (row, expert) pairs, the combine, the shared expert) and
the grouped-matmul kernel the experts run in, which the TPU compiler
names ``ragged-dot`` and strips of its scope; own time inside the runs
of ``jit_decode`` in the traced slice (layer: kernels).  Nothing to read
where the program declares no such scope."""
from harness import program_trace, scoped_ops


def read(rec):
    if program_trace.vocabulary() and "moe" in program_trace.vocabulary():
        scoped_ops.report(rec)      # the run's [scope] and [ops] lines
    got = scoped_ops.own_seconds(rec, rec["programs"]["decode"], "moe",
                                 scoped_ops.GROUPED_MATMUL)
    return None if got is None else 100.0 * got[0] / got[1]
