"""Operations and bytes ``granite-4.0-h-micro`` needs, whole on one chip,
from its shapes.

Beside the keys and values of its four attention layers a slot holds, in
each of the 36 state-space layers, a float32 state of ``heads x head size
x state size`` that a decode step reads and writes whole
(``state_update_bytes``) and ``taps - 1`` rows of the convolution's input.
The counts are of the model's own sizes whatever layout holds them (the
program's is one ``[state size, heads x head size]`` matrix a group:
``configs/granite-4.0-h-micro.json``, ``assumed.state_layout``), so that
the state step's roofline reads the same work whatever implements it."""

_BYTES = {"bfloat16": 2, "float32": 4, "float16": 2}


def _counts(cfg: dict) -> tuple:
    """``(attention layers, state-space layers)``."""
    kinds = cfg["layer_types"]
    return kinds.count("attention"), kinds.count("mamba")


def _head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _inner(cfg: dict) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def conv_channels(cfg: dict) -> int:
    """What the convolution runs over: x, B and C."""
    return _inner(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def ssd_mixer_param_count(cfg: dict) -> int:
    """A state-space mixer: the input projection [z | x | B | C | dt], the
    output projection, the convolution's taps and bias, the gated norm's
    scale, A_log, D and dt_bias a head."""
    H, heads = cfg["hidden_size"], cfg["mamba_n_heads"]
    return H * (_inner(cfg) + conv_channels(cfg) + heads) \
        + _inner(cfg) * H + (cfg["mamba_d_conv"] + 1) * conv_channels(cfg) \
        + _inner(cfg) + 3 * heads


def attention_mixer_param_count(cfg: dict) -> int:
    """Grouped-query attention without biases: q and o, k and v."""
    H, d = cfg["hidden_size"], _head_dim(cfg)
    n, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * H * n * d + 2 * H * kv * d


def ffn_param_count(cfg: dict) -> int:
    """Gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def dense_param_count(cfg: dict) -> int:
    """Every parameter a decode step reads: the mixers, an FFN and two
    norms a layer, the tied table as the head, the final norm."""
    H = cfg["hidden_size"]
    attn, ssd = _counts(cfg)
    return attn * attention_mixer_param_count(cfg) \
        + ssd * ssd_mixer_param_count(cfg) \
        + cfg["num_hidden_layers"] * (ffn_param_count(cfg) + 2 * H) \
        + cfg["vocab_size"] * H + H


def param_count(cfg: dict) -> int:
    """Every parameter held once: the tied table is the embedding too."""
    return dense_param_count(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values of one position over the attention layers."""
    return 2 * _counts(cfg)[0] * cfg["num_key_value_heads"] \
        * _head_dim(cfg) * _BYTES[cfg["serving"]["dtype"]]


def state_bytes_per_layer(cfg: dict) -> int:
    """One slot's recurrent state in one state-space layer."""
    return _inner(cfg) * cfg["mamba_d_state"] \
        * _BYTES[cfg["serving"]["state_dtype"]]


def tail_bytes_per_layer(cfg: dict) -> int:
    """One slot's convolution tail in one state-space layer."""
    return (cfg["mamba_d_conv"] - 1) * conv_channels(cfg) \
        * _BYTES[cfg["serving"]["dtype"]]


def state_update_bytes(cfg: dict, rows: float) -> float:
    """The least the recurrence must move: each (slot, step, state-space
    layer) row's state read once and written once."""
    return 2.0 * rows * state_bytes_per_layer(cfg)


def decode_attention_kernel_bytes(cfg: dict, blocks_attended: float,
                                  rows_written: float,
                                  block_len: int) -> float:
    """The least the dense decode-attention kernel must move over a
    stretch of decoding: ``blocks_attended`` counts, per cache layer, the
    ``block_len``-position blocks of one slot's lane that a step reads
    (the program's ``serve/kv_blocks_attended``), each read once for K
    and once for V over all key/value heads — the four query heads of a
    group read them together; ``rows_written`` counts, per cache layer,
    the (slot, step) pairs that write their new key and value row."""
    row = cfg["num_key_value_heads"] * _head_dim(cfg) \
        * _BYTES[cfg["serving"]["dtype"]]
    return 2.0 * _counts(cfg)[0] * row \
        * (blocks_attended * block_len + rows_written)


def decode_step_bytes(cfg: dict, live_kv_tokens: float,
                      slots: float) -> float:
    """The least one decode step must move: the parameters once, every
    decoding slot's state in every state-space layer there and back, and
    the cached keys and values of the positions that are live."""
    return dense_param_count(cfg) * _BYTES[cfg["serving"]["weights_dtype"]] \
        + state_update_bytes(cfg, slots * _counts(cfg)[1]) \
        + live_kv_tokens * kv_bytes_per_token(cfg)


def forward_flops(cfg: dict, tokens: int, context: float = 0.0) -> float:
    """FLOPs of a forward pass over ``tokens`` positions that each attend
    to ``context`` positions on average in the attention layers: the
    projections' and the FFN's products, the recurrence in its recurrent
    form (a position decays, writes and reads a state of heads x head
    size x state size: ~6 operations an element), the scores and the
    weighted sums."""
    attn, ssd = _counts(cfg)
    n_d = cfg["num_attention_heads"] * _head_dim(cfg)
    state = _inner(cfg) * cfg["mamba_d_state"]
    return tokens * (
        2.0 * attn * (attention_mixer_param_count(cfg) + 2.0 * context * n_d)
        + ssd * (2.0 * ssd_mixer_param_count(cfg) + 6.0 * state)
        + 2.0 * cfg["num_hidden_layers"] * ffn_param_count(cfg))


def logits_flops(cfg: dict, rows: int) -> float:
    return 2.0 * rows * cfg["hidden_size"] * cfg["vocab_size"]
