"""A decoder that mixes layer kinds — gated-DeltaNet layers with a gated
softmax-attention layer every ``full_attention_interval``-th, grouped
key/value heads, a routed FFN with a shared expert in every layer,
zero-centred RMSNorm before each sub-block, rotary positions on part of
the head, an untied head — behind the program's serving path:
``ServingEngine`` -> ``ContinuousBatcher``, the engine's defaults for
every election.  The block is said once, as the ``BlockSpec`` the
engine's ``TransformerConfig`` carries; the chip's share of the experts
is the specification's ``experts_held`` of ``num_experts``."""
from __future__ import annotations


def transformer_config(cfg: dict):
    import jax.numpy as jnp

    from autodist_tpu.models.transformer import (BlockSpec, LinearMixerSpec,
                                                 RoutedFFNSpec,
                                                 TransformerConfig)

    every = cfg["full_attention_interval"]
    if cfg["hidden_act"] != "silu" or cfg["rope_scaling"] is not None \
            or cfg["use_sliding_window"] or cfg["tie_word_embeddings"] \
            or cfg["mlp_only_layers"] or cfg["decoder_sparse_step"] != 1 \
            or not cfg["norm_topk_prob"] \
            or cfg["num_hidden_layers"] % every \
            or cfg["serving"]["state_dtype"] != "float32":
        raise ValueError(
            "the mixed block the program serves is whole periods of "
            "gated-DeltaNet layers closed by a full-attention layer, a "
            "routed SiLU-gated FFN in every layer with renormalised "
            "top-k weights, plain rotary, untied, a float32 recurrent "
            "state; the configuration file states something else")
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        mlp_dim=cfg["moe_intermediate_size"],
        max_len=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["serving"]["dtype"]), dropout_rate=0.0,
        attention_dropout_rate=0.0,
        block=BlockSpec(
            norm="rmsnorm", norm_placement="pre", norm_zero_centred=True,
            norm_eps=cfg["rms_norm_eps"], positions="rope",
            rope_theta=float(cfg["rope_theta"]),
            rope_fraction=cfg["partial_rotary_factor"], ffn="swiglu",
            bias=False, tied_head=False,
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            qk_norm=True, attn_gate=True,
            layer_period=("linear",) * (every - 1) + ("full",),
            linear=LinearMixerSpec(
                key_heads=cfg["linear_num_key_heads"],
                value_heads=cfg["linear_num_value_heads"],
                key_dim=cfg["linear_key_head_dim"],
                value_dim=cfg["linear_value_head_dim"],
                conv_taps=cfg["linear_conv_kernel_dim"]),
            moe=RoutedFFNSpec(
                num_experts=cfg["num_experts_published"],
                top_k=cfg["num_experts_per_tok"],
                expert_width=cfg["moe_intermediate_size"],
                shared_width=cfg["shared_expert_intermediate_size"],
                experts_held=cfg["num_experts"], first_expert=0)))


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
