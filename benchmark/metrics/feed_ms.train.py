"""Mean duration of the program's ``runner/place`` span, the transfer of
one window's host batches, over the windows dispatched in the traced
slice (layer: runner)."""
from harness import program_trace


def read(rec):
    table = program_trace.span_report(rec)
    if table is None or "runner/place" not in table["spans"]:
        return None
    return table["spans"]["runner/place"][1]
