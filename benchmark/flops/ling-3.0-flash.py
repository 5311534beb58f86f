"""Operations and bytes ``ling-3.0-flash`` needs as one chip's share, from
its shapes.

Two kinds of state beside the weights.  A slot has a float32 recurrent
state in every KDA layer that is read and written whole each step, with
the decay of each of its rows read beside it (``state_update_bytes``); a
cached position is one latent row in every MLA layer, read once a step by
all 32 heads (``latent_attend_bytes``).  Of the held experts only those
that some row hit are read (``experts_bytes``).  ``num_experts`` counts
the experts held here; the router is ``num_experts_published`` wide.  The
first ``first_k_dense_replace`` layers carry a dense FFN and no router."""

_BYTES = {"bfloat16": 2, "float32": 4, "float16": 2}


def _counts(cfg: dict) -> tuple:
    """``(latent layers, KDA layers)``: a latent layer closes every group
    of ``layer_group_size``."""
    latent = cfg["num_hidden_layers"] // cfg["layer_group_size"]
    return latent, cfg["num_hidden_layers"] - latent


def _layers(cfg: dict) -> tuple:
    """``(dense layers, routed layers)``."""
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def row_width(cfg: dict) -> int:
    """Values a cached position holds in one latent layer."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def latent_mixer_param_count(cfg: dict) -> int:
    """q, kv_a, the latent's norm, kv_b, the heads' gate, out."""
    H, n = cfg["hidden_size"], cfg["num_attention_heads"]
    rank, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    return H * n * (nope + cfg["qk_rope_head_dim"]) + H * row_width(cfg) \
        + rank + rank * n * (nope + cfg["v_head_dim"]) + H * n \
        + n * cfg["v_head_dim"] * H


def linear_mixer_param_count(cfg: dict) -> int:
    """A KDA mixer: q, k, v, the decay's and the output gate's
    projections, beta, the taps, A_log and dt_bias, the norm's scale,
    out."""
    H, n, d = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["head_dim"]
    inner = n * d
    return 5 * H * inner + H * n + cfg["short_conv_kernel_size"] * 3 \
        * inner + n + inner + d + inner * H


def expert_param_count(cfg: dict) -> int:
    """One expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def ffn_shared_param_count(cfg: dict) -> int:
    """A routed layer's block outside its experts: the router over all
    published experts with its correction, and the shared expert."""
    H = cfg["hidden_size"]
    return (H + 1) * cfg["num_experts_published"] \
        + 3 * H * cfg["num_shared_experts"] \
        * cfg["moe_shared_expert_intermediate_size"]


def dense_ffn_param_count(cfg: dict) -> int:
    """A leading layer's dense SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def dense_param_count(cfg: dict) -> int:
    """Every parameter a decode step reads whatever the routing: the
    mixers and two norms of every layer, the leading layers' FFN, the
    routed blocks outside their experts, the head and the final norm
    (the embedding is one row a slot)."""
    H = cfg["hidden_size"]
    latent, linear = _counts(cfg)
    dense, routed = _layers(cfg)
    return latent * latent_mixer_param_count(cfg) \
        + linear * linear_mixer_param_count(cfg) \
        + cfg["num_hidden_layers"] * 2 * H \
        + dense * dense_ffn_param_count(cfg) \
        + routed * ffn_shared_param_count(cfg) \
        + cfg["vocab_size"] * H + H


def param_count(cfg: dict) -> int:
    """Every parameter held here once."""
    return dense_param_count(cfg) + cfg["vocab_size"] * cfg["hidden_size"] \
        + _layers(cfg)[1] * cfg["num_experts"] * expert_param_count(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """The latent rows of one position over the latent layers."""
    return _counts(cfg)[0] * row_width(cfg) * _BYTES[cfg["serving"]["dtype"]]


def state_bytes_per_layer(cfg: dict) -> int:
    """One slot's recurrent state in one KDA layer, as held."""
    return cfg["num_attention_heads"] * cfg["head_dim"] ** 2 \
        * _BYTES[cfg["serving"]["state_dtype"]]


def gate_bytes_per_layer(cfg: dict) -> int:
    """The decay of every row of one slot's state in one KDA layer: a
    float32 a (head, key channel), read once a step."""
    return cfg["num_attention_heads"] * cfg["head_dim"] * 4


def experts_bytes(cfg: dict, experts_hit: float) -> float:
    """The least the experts' matmuls must read: each expert hit (summed
    over steps and layers), its three matrices once."""
    return experts_hit * expert_param_count(cfg) \
        * _BYTES[cfg["serving"]["weights_dtype"]]


def state_update_bytes(cfg: dict, rows: float) -> float:
    """The least the recurrence must move: each (slot, step, KDA layer)
    row's state read once and written once, and its rows' decays read."""
    return rows * (2.0 * state_bytes_per_layer(cfg)
                   + gate_bytes_per_layer(cfg))


def latent_attend_bytes(cfg: dict, positions: float) -> float:
    """The least the decode attention must read: each live (position,
    latent layer) row once for all 32 heads (``positions`` counts them
    over steps and layers: the program's
    ``serve/latent_positions_read``)."""
    return positions * row_width(cfg) * _BYTES[cfg["serving"]["dtype"]]


def decode_step_bytes(cfg: dict, live_kv_tokens: float, slots: float,
                      experts_hit: float) -> float:
    """The least one decode step must move: the dense parameters once,
    the experts hit (over the step's layers), every decoding slot's
    state in every KDA layer there and back, and the cached rows of the
    positions that are live, in every latent layer."""
    return dense_param_count(cfg) * _BYTES[cfg["serving"]["weights_dtype"]] \
        + experts_bytes(cfg, experts_hit) \
        + state_update_bytes(cfg, slots * _counts(cfg)[1]) \
        + live_kv_tokens * kv_bytes_per_token(cfg)


def forward_flops(cfg: dict, tokens: int, context: float) -> float:
    """Matmul FLOPs of a forward pass over ``tokens`` positions that each
    attend to ``context`` positions on average in the latent layers, in
    the EXPANDED form: the projections, the ``num_experts_per_tok *
    num_experts / num_experts_published`` experts a token hits here on
    average, the recurrence (per position and head ~6 dk dv), the
    scores."""
    latent, linear = _counts(cfg)
    dense, routed = _layers(cfg)
    n = cfg["num_attention_heads"]
    attend = n * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                  + cfg["v_head_dim"])
    here = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_experts_published"]
    state = n * cfg["head_dim"] ** 2
    per_token = 2.0 * (
        latent * (latent_mixer_param_count(cfg) + context * attend)
        + linear * (linear_mixer_param_count(cfg) + 3.0 * state)
        + dense * dense_ffn_param_count(cfg)
        + routed * (ffn_shared_param_count(cfg)
                    + here * expert_param_count(cfg)))
    return tokens * per_token


def logits_flops(cfg: dict, rows: int) -> float:
    return 2.0 * rows * cfg["hidden_size"] * cfg["vocab_size"]
