"""Operations and bytes ``gpt2-large-postln`` needs, from its shapes."""

_BYTES = {"bfloat16": 2, "float32": 4, "float16": 2}


def param_count(cfg: dict, with_positions: bool = True) -> int:
    """Every parameter once (the output projection is the embedding)."""
    H, V, L = cfg["n_embd"], cfg["vocab_size"], cfg["n_layer"]
    M = cfg["n_inner"] or 4 * H
    per_layer = (H * 3 * H + 3 * H) + (H * H + H) + (H * M + M) \
        + (M * H + H) + 4 * H
    return L * per_layer + V * H + 2 * H \
        + (cfg["n_positions"] * H if with_positions else 0)


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values of one position over all layers, as cached."""
    return 2 * cfg["n_layer"] * cfg["n_embd"] \
        * _BYTES[cfg["serving"]["dtype"]]


def decode_step_bytes(cfg: dict, live_kv_tokens: float) -> float:
    """The least one decode step must read: every weight once at the
    type the engine holds it (one position row aside), and the cached
    keys and values of the positions that are live."""
    weights = param_count(cfg, with_positions=False) \
        * _BYTES[cfg["serving"]["weights_dtype"]]
    return weights + live_kv_tokens * kv_bytes_per_token(cfg)


def forward_flops(cfg: dict, tokens: int, context: float) -> float:
    """Matmul FLOPs of a forward pass over ``tokens`` positions that
    each attend to ``context`` positions on average; the output
    projection is ``logits_flops``, for the rows that need it."""
    H, L = cfg["n_embd"], cfg["n_layer"]
    M = cfg["n_inner"] or 4 * H
    return tokens * L * (8.0 * H * H + 4.0 * H * M + 4.0 * context * H)


def logits_flops(cfg: dict, rows: int) -> float:
    return 2.0 * rows * cfg["n_embd"] * cfg["vocab_size"]
