"""Prompt tokens admitted over the tokens the prefill dispatches
computed (``num_slots x prefill_len`` each), in the traced slice (layer:
batcher).  The dispatches are the program's own ``serve/prefill`` spans;
the prompt tokens are the benchmark's own requests."""


def read(rec):
    spans = rec["prefill_spans"]
    if not spans:
        return None
    if len(spans) != len(rec["prefills"]):
        raise RuntimeError(
            f"the program recorded {len(spans)} serve/prefill spans, the "
            f"benchmark saw {len(rec['prefills'])} prefill dispatches")
    admitted = sum(p[2] for p in rec["prefills"])
    return 100.0 * admitted / (len(spans) * rec["num_slots"]
                               * rec["prefill_len"])
