"""Operations and bytes ``brumby-14b-base`` needs as one pipeline stage,
from its shapes.

No layer caches keys and values: ``kv_bytes_per_token`` is 0 and a decode
step's least bytes do not grow with the positions that are live.  What a
slot holds instead is a float32 state in every layer that a step reads
and writes whole: a key/value head's ``128 * 129 / 2 = 8,256`` distinct
products of its key by 128 values, and a normaliser of 8,256 beside them.
The counts here are of those PACKED rows whatever layout holds them (the
program's keeps 8,320: ``configs/brumby-14b-base.json``,
``assumed.state_layout``), so that the state step's roofline reads the
same work whatever implements it."""

_BYTES = {"bfloat16": 2, "float32": 4, "float16": 2}


def state_rows(cfg: dict) -> int:
    """The distinct products of a key of ``head_dim``: its symmetric
    square."""
    d = cfg["head_dim"]
    return d * (d + 1) // 2


def mixer_param_count(cfg: dict) -> int:
    """A power-retention mixer: q and o, k and v, the q/k norms' scales,
    the gate's projection a key/value head."""
    H, d = cfg["hidden_size"], cfg["head_dim"]
    n, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * H * n * d + 2 * H * kv * d + 2 * d + H * kv


def ffn_param_count(cfg: dict) -> int:
    """Gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_param_count(cfg: dict) -> int:
    """A mixer, an FFN and the two norms before them."""
    return mixer_param_count(cfg) + ffn_param_count(cfg) \
        + 2 * cfg["hidden_size"]


def dense_param_count(cfg: dict) -> int:
    """Every parameter a decode step reads: the layers, the head and the
    final norm (the embedding is one row a slot)."""
    H = cfg["hidden_size"]
    return cfg["num_hidden_layers"] * layer_param_count(cfg) \
        + cfg["vocab_size"] * H + H


def param_count(cfg: dict) -> int:
    """Every parameter held here once."""
    return dense_param_count(cfg) + cfg["vocab_size"] * cfg["hidden_size"]


def kv_bytes_per_token(cfg: dict) -> int:
    """No layer caches a position."""
    return 0


def state_bytes_per_layer(cfg: dict) -> int:
    """One slot's recurrent state in one layer: the packed rows by
    ``head_dim`` values a key/value head, and the normaliser's row."""
    return cfg["num_key_value_heads"] * state_rows(cfg) \
        * (cfg["head_dim"] + 1) * _BYTES[cfg["serving"]["state_dtype"]]


def state_update_bytes(cfg: dict, rows: float) -> float:
    """The least the recurrence must move: each (slot, step, layer)
    row's state and normaliser read once and written once."""
    return 2.0 * rows * state_bytes_per_layer(cfg)


def decode_step_bytes(cfg: dict, live_kv_tokens: float,
                      slots: float) -> float:
    """The least one decode step must move: the dense parameters once and
    every decoding slot's state in every layer there and back
    (``live_kv_tokens`` moves nothing: no position is cached)."""
    del live_kv_tokens
    return dense_param_count(cfg) * _BYTES[cfg["serving"]["weights_dtype"]] \
        + state_update_bytes(cfg, slots * cfg["num_hidden_layers"])


def forward_flops(cfg: dict, tokens: int, context: float = 0.0) -> float:
    """FLOPs of a forward pass over ``tokens`` positions: the projections'
    and the FFN's products, and the retention's terms in the recurrent
    form — a position writes ``phi(k) v^T`` to each key/value head's
    state (2 x rows x (head_dim + 1)) and each query head reads one
    (the same again a query head).  ``context`` moves nothing: no
    position attends to cached ones."""
    del context
    n, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    retention = 2.0 * state_rows(cfg) * (d + 1) * (kv + n)
    return tokens * cfg["num_hidden_layers"] * (
        2.0 * (mixer_param_count(cfg) + ffn_param_count(cfg)) + retention)


def logits_flops(cfg: dict, rows: int) -> float:
    return 2.0 * rows * cfg["hidden_size"] * cfg["vocab_size"]
