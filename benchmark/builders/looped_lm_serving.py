"""A looped decoder — RMSNorm in sandwich placement, rotary positions, a
SiLU-gated FFN, no biases, an untied head, the layers run
``total_ut_steps`` times — behind the program's serving path:
``ServingEngine`` -> ``ContinuousBatcher``, the engine's defaults for
every election.  The block is said once, as the ``BlockSpec`` the
engine's ``TransformerConfig`` carries."""
from __future__ import annotations


def transformer_config(cfg: dict):
    import jax.numpy as jnp

    from autodist_tpu.models.transformer import BlockSpec, TransformerConfig

    H, n = cfg["hidden_size"], cfg["num_attention_heads"]
    if cfg["hidden_act"] != "silu" or cfg["num_key_value_heads"] != n \
            or cfg["head_dim"] * n != H or cfg["rope_scaling"] is not None \
            or cfg["use_sliding_window"] or cfg["tie_word_embeddings"] \
            or set(cfg["layer_types"]) != {"full_attention"} \
            or len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError(
            "the looped block the program serves is full attention with "
            "as many key/value heads as query heads of hidden_size / "
            "heads, SiLU-gated, plain rotary, untied; the configuration "
            "file states something else")
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], hidden_size=H,
        num_layers=cfg["num_hidden_layers"], num_heads=n,
        mlp_dim=cfg["intermediate_size"],
        max_len=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["serving"]["dtype"]), dropout_rate=0.0,
        attention_dropout_rate=0.0,
        block=BlockSpec(
            norm="rmsnorm", norm_placement="sandwich",
            norm_eps=cfg["rms_norm_eps"], positions="rope",
            rope_theta=float(cfg["rope_theta"]), ffn="swiglu", bias=False,
            tied_head=False, loop_steps=cfg["total_ut_steps"],
            exit_threshold=float(cfg["early_exit_threshold"])))


def build_serving(cfg: dict, params: dict):
    """``(engine, batcher)`` serving ``params``."""
    from autodist_tpu import serving

    s = cfg["serving"]
    engine = serving.ServingEngine(
        transformer_config(cfg), params, num_slots=s["num_slots"],
        max_len=s["max_len"], prefill_len=s["prefill_len"],
        decode_steps=s["decode_steps"], kv_layout=s["kv_layout"],
        temperature=s["temperature"])
    return engine, serving.ContinuousBatcher(engine)
