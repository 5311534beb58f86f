"""Share of the slice that the rounds flagged slow ran over the medians
they were held against: the ``decode_excess_ms`` and ``own_excess_ms`` of
the program's ``kind="slow_round"`` records since the window opened,
summed, over the slice's length (layer: batcher).  0 in a sound slice;
nothing to read where the program counts no rounds (``serve/rounds``)."""
from harness import loader


def read(rec):
    base = loader.load_module("metrics", "round_ms_p50")
    if "serve/rounds" not in (base.instruments() or {}):
        return None
    excess_ms = sum(r.get("decode_excess_ms", 0.0)
                    + r.get("own_excess_ms", 0.0)
                    for r in base.events("slow_round"))
    return 100.0 * excess_ms * 1e-3 / rec["window_s"]
