"""Host clock around ``AutoDist(...).build``: strategy search, lowering
and state placement (layer: facade / search)."""


def read(rec):
    return rec["host"].get("build_s")
