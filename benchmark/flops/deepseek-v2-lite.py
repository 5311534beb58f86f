"""Operations and bytes ``deepseek-v2-lite`` needs as one chip's share,
from its shapes.

A cached position is one latent row a layer (``kv_lora_rank +
qk_rope_head_dim`` values), not keys and values a head: a decode step's
least bytes read each live row once a layer (``latent_attend_bytes``).
Of the held experts only those that some row hit are read
(``experts_bytes``).  ``n_routed_experts`` counts the experts held here;
the router is ``n_routed_experts_published`` wide.  The first
``first_k_dense_replace`` layers carry a dense FFN and no router."""

_BYTES = {"bfloat16": 2, "float32": 4, "float16": 2}


def _layers(cfg: dict) -> tuple:
    """``(dense layers, routed layers)``."""
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def row_width(cfg: dict) -> int:
    """Values a cached position holds in one layer."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def attention_param_count(cfg: dict) -> int:
    """q_proj, kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj, o_proj."""
    H, n = cfg["hidden_size"], cfg["num_attention_heads"]
    rank, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    return H * n * (nope + cfg["qk_rope_head_dim"]) + H * row_width(cfg) \
        + rank + rank * n * (nope + cfg["v_head_dim"]) \
        + n * cfg["v_head_dim"] * H


def expert_param_count(cfg: dict) -> int:
    """One expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def ffn_shared_param_count(cfg: dict) -> int:
    """A routed layer's block outside its experts: the router over all
    published experts and the shared experts (one SwiGLU, no gate)."""
    H = cfg["hidden_size"]
    return H * cfg["n_routed_experts_published"] \
        + cfg["n_shared_experts"] * expert_param_count(cfg)


def dense_ffn_param_count(cfg: dict) -> int:
    """A leading layer's dense SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def routed_layer_param_count(cfg: dict) -> int:
    """A routed layer as held here: attention, the held experts, the
    router, the shared experts, two norms."""
    return attention_param_count(cfg) \
        + cfg["n_routed_experts"] * expert_param_count(cfg) \
        + ffn_shared_param_count(cfg) + 2 * cfg["hidden_size"]


def dense_param_count(cfg: dict) -> int:
    """Every parameter a decode step reads whatever the routing: the
    attention and two norms of every layer, the leading layers' FFN, the
    routed blocks outside their experts, the head and the final norm
    (the embedding is one row a slot)."""
    H = cfg["hidden_size"]
    dense, routed = _layers(cfg)
    return cfg["num_hidden_layers"] * (attention_param_count(cfg) + 2 * H) \
        + dense * dense_ffn_param_count(cfg) \
        + routed * ffn_shared_param_count(cfg) \
        + cfg["vocab_size"] * H + H


def param_count(cfg: dict) -> int:
    """Every parameter held here once."""
    return dense_param_count(cfg) + cfg["vocab_size"] * cfg["hidden_size"] \
        + _layers(cfg)[1] * cfg["n_routed_experts"] * expert_param_count(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """The latent rows of one position over every layer."""
    return cfg["num_hidden_layers"] * row_width(cfg) \
        * _BYTES[cfg["serving"]["dtype"]]


def experts_bytes(cfg: dict, experts_hit: float) -> float:
    """The least the experts' matmuls must read: each expert hit (summed
    over steps and layers), its three matrices once."""
    return experts_hit * expert_param_count(cfg) \
        * _BYTES[cfg["serving"]["weights_dtype"]]


def latent_attend_bytes(cfg: dict, positions: float) -> float:
    """The least the decode attention must read: each live (position,
    layer) row once (``positions`` counts them over steps and layers:
    the program's ``serve/latent_positions_read``)."""
    return positions * row_width(cfg) * _BYTES[cfg["serving"]["dtype"]]


def decode_step_bytes(cfg: dict, live_kv_tokens: float, slots: float,
                      experts_hit: float) -> float:
    """The least one decode step must move: the dense parameters once,
    the experts hit (over the step's layers) and the cached rows of the
    positions that are live, in every layer."""
    del slots       # no state a slot beside the rows
    return dense_param_count(cfg) * _BYTES[cfg["serving"]["weights_dtype"]] \
        + experts_bytes(cfg, experts_hit) \
        + live_kv_tokens * kv_bytes_per_token(cfg)


def forward_flops(cfg: dict, tokens: int, context: float) -> float:
    """Matmul FLOPs of a forward pass over ``tokens`` positions that each
    attend to ``context`` positions on average, in the EXPANDED form
    (scores over ``qk_nope_head_dim + qk_rope_head_dim``, the weighted
    sum over ``v_head_dim``, a head): the projections, the
    ``num_experts_per_tok * n_routed_experts / n_routed_experts_published``
    experts a token hits here on average, the scores."""
    dense, routed = _layers(cfg)
    n = cfg["num_attention_heads"]
    attend = n * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                  + cfg["v_head_dim"])
    here = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["n_routed_experts_published"]
    per_token = 2.0 * (
        cfg["num_hidden_layers"] * (attention_param_count(cfg)
                                    + context * attend)
        + dense * dense_ffn_param_count(cfg)
        + routed * (ffn_shared_param_count(cfg)
                    + here * expert_param_count(cfg)))
    return tokens * per_token


def logits_flops(cfg: dict, rows: int) -> float:
    return 2.0 * rows * cfg["hidden_size"] * cfg["vocab_size"]
