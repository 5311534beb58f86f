"""Operations and bytes ``qwen3-next-80b-a3b`` needs as one chip's share,
from its shapes.

A decode step's least bytes are not every parameter once: of the held
experts only those that some row hit are read (``experts_bytes``), and
beside keys and values a slot has a float32 recurrent state in every
linear layer that is read and written whole each step
(``state_update_bytes``).  ``num_experts`` counts the experts held here;
the router is ``num_experts_published`` wide."""

_BYTES = {"bfloat16": 2, "float32": 4, "float16": 2}


def _counts(cfg: dict) -> tuple:
    """``(full layers, linear layers)``."""
    full = cfg["num_hidden_layers"] // cfg["full_attention_interval"]
    return full, cfg["num_hidden_layers"] - full


def expert_param_count(cfg: dict) -> int:
    """One expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def ffn_shared_param_count(cfg: dict) -> int:
    """A layer's routed block outside its experts: the router over all
    published experts, the shared expert and its gate."""
    H = cfg["hidden_size"]
    return H * cfg["num_experts_published"] \
        + 3 * H * cfg["shared_expert_intermediate_size"] + H


def linear_mixer_param_count(cfg: dict) -> int:
    """A gated-DeltaNet mixer: qkvz, ba, the taps, A_log and dt_bias, the
    gated norm's scale, out."""
    H = cfg["hidden_size"]
    kh, vh = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    keys = kh * cfg["linear_key_head_dim"]
    inner = vh * cfg["linear_value_head_dim"]
    conv = 2 * keys + inner
    return H * (conv + inner) + H * 2 * vh \
        + cfg["linear_conv_kernel_dim"] * conv + 2 * vh \
        + cfg["linear_value_head_dim"] + inner * H


def full_mixer_param_count(cfg: dict) -> int:
    """A gated attention: q with its gate, k, v, the q/k norms, out."""
    H, d = cfg["hidden_size"], cfg["head_dim"]
    n, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return H * (2 * n * d + 2 * kv * d) + 2 * d + n * d * H


def dense_param_count(cfg: dict) -> int:
    """Every parameter a decode step reads whatever the routing: the
    mixers, the routed blocks outside their experts, two norms a layer,
    the head and the final norm (the embedding is one row a slot)."""
    H = cfg["hidden_size"]
    full, linear = _counts(cfg)
    return full * full_mixer_param_count(cfg) \
        + linear * linear_mixer_param_count(cfg) \
        + cfg["num_hidden_layers"] * (ffn_shared_param_count(cfg) + 2 * H) \
        + cfg["vocab_size"] * H + H


def param_count(cfg: dict) -> int:
    """Every parameter held here once."""
    return dense_param_count(cfg) + cfg["vocab_size"] * cfg["hidden_size"] \
        + cfg["num_hidden_layers"] * cfg["num_experts"] \
        * expert_param_count(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values of one position over the full-attention layers."""
    return 2 * _counts(cfg)[0] * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * _BYTES[cfg["serving"]["dtype"]]


def state_bytes_per_layer(cfg: dict) -> int:
    """One slot's recurrent state in one linear layer, as held."""
    return cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"] * _BYTES[cfg["serving"]["state_dtype"]]


def experts_bytes(cfg: dict, experts_hit: float) -> float:
    """The least the experts' matmuls must read: each expert hit (summed
    over steps and layers), its three matrices once."""
    return experts_hit * expert_param_count(cfg) \
        * _BYTES[cfg["serving"]["weights_dtype"]]


def state_update_bytes(cfg: dict, rows: float) -> float:
    """The least the recurrence must move: each (slot, step, linear
    layer) row's state read once and written once."""
    return 2.0 * rows * state_bytes_per_layer(cfg)


def decode_step_bytes(cfg: dict, live_kv_tokens: float, slots: float,
                      experts_hit: float) -> float:
    """The least one decode step must move: the dense parameters once,
    the experts hit (over the step's layers), every decoding slot's
    state in every linear layer there and back, and the cached keys and
    values of the positions that are live."""
    return dense_param_count(cfg) * _BYTES[cfg["serving"]["weights_dtype"]] \
        + experts_bytes(cfg, experts_hit) \
        + state_update_bytes(cfg, slots * _counts(cfg)[1]) \
        + live_kv_tokens * kv_bytes_per_token(cfg)


def forward_flops(cfg: dict, tokens: int, context: float) -> float:
    """Matmul FLOPs of a forward pass over ``tokens`` positions that each
    attend to ``context`` positions on average in the full layers: the
    dense projections, the ``num_experts_per_tok * num_experts /
    num_experts_published`` experts a token hits here on average, the
    recurrence (per position and value head ~6 dk dv), the scores."""
    full, linear = _counts(cfg)
    H = cfg["hidden_size"]
    n_d = cfg["num_attention_heads"] * cfg["head_dim"]
    here = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_experts_published"]
    state = cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"]
    per_token = 2.0 * (
        full * (full_mixer_param_count(cfg) + 2.0 * context * n_d)
        + linear * (linear_mixer_param_count(cfg) + 3.0 * state)
        + cfg["num_hidden_layers"] * (ffn_shared_param_count(cfg)
                                      + here * expert_param_count(cfg)))
    return tokens * per_token


def logits_flops(cfg: dict, rows: int) -> float:
    return 2.0 * rows * cfg["hidden_size"] * cfg["vocab_size"]
