"""Operations and bytes ``ouro-2.6b`` needs, from its shapes.

The 48 layers' weights count ``total_ut_steps`` times in a decode step's
least bytes: every loop step multiplies all of them again, and no on-chip
memory holds 4.93 GB from one loop step to the next (a v5e core has
128 MiB of VMEM), so each must come from HBM once per loop step.  The
cache holds ``total_ut_steps x num_hidden_layers`` layers: a layer's
input differs from loop step to loop step, so its keys and values do."""

_BYTES = {"bfloat16": 2, "float32": 4, "float16": 2}


def layer_param_count(cfg: dict) -> int:
    """One layer: q, k, v, o; gate, up, down; four RMSNorm scales."""
    H, M = cfg["hidden_size"], cfg["intermediate_size"]
    return 4 * H * H + 3 * H * M + 4 * H


def param_count(cfg: dict) -> int:
    """Every parameter once: the layers (ONE set, whatever the loop
    count), the embedding and the untied head, the final norm's scale
    and the exit gate (``Linear(H, 1)``)."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    return cfg["num_hidden_layers"] * layer_param_count(cfg) \
        + 2 * V * H + H + (H + 1)


def cache_layers(cfg: dict) -> int:
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values of one position over all ``cache_layers``, as
    cached."""
    return 2 * cache_layers(cfg) * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * _BYTES[cfg["serving"]["dtype"]]


def decode_step_bytes(cfg: dict, live_kv_tokens: float) -> float:
    """The least one decode step must read: the layers' weights once per
    loop step, the head (with the final norm) once, at the type the
    engine holds them, and the cached keys and values of the positions
    that are live.  The embedding is one row a slot and the exit gate is
    not run (threshold 1.0): neither counts."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    w = _BYTES[cfg["serving"]["weights_dtype"]]
    layers = cfg["num_hidden_layers"] * layer_param_count(cfg) * w
    return cfg["total_ut_steps"] * layers + (V * H + H) * w \
        + live_kv_tokens * kv_bytes_per_token(cfg)


def decode_attention_kernel_bytes(cfg: dict, blocks_attended: float,
                                  rows_written: float,
                                  block_len: int) -> float:
    """The least the dense decode-attention kernel must move over a
    stretch of decoding: ``blocks_attended`` counts, per cache layer,
    the ``block_len``-position blocks of one slot's lane that a step
    reads (the live ones, to the block the new token lands in; the
    program's ``serve/kv_blocks_attended``), each read once for K and
    once for V over all heads; ``rows_written`` counts, per cache layer,
    the (slot, step) pairs that write their new key and value row."""
    row = cfg["num_key_value_heads"] * cfg["head_dim"] \
        * _BYTES[cfg["serving"]["dtype"]]
    return 2.0 * cache_layers(cfg) * row \
        * (blocks_attended * block_len + rows_written)


def forward_flops(cfg: dict, tokens: int, context: float) -> float:
    """Matmul FLOPs of a forward pass (all loop steps) over ``tokens``
    positions that each attend to ``context`` positions on average; the
    output projection is ``logits_flops``, for the rows that need it."""
    H, M = cfg["hidden_size"], cfg["intermediate_size"]
    n_d = cfg["num_attention_heads"] * cfg["head_dim"]
    return tokens * cache_layers(cfg) * (
        8.0 * H * n_d + 6.0 * H * M + 4.0 * context * n_d)


def logits_flops(cfg: dict, rows: int) -> float:
    return 2.0 * rows * cfg["hidden_size"] * cfg["vocab_size"]
