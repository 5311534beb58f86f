"""A decoder with latent attention — every layer caches ONE row a
position, a normed latent and a rotated positional key that all query
heads share, attends a prompt in the expanded form and a cached step in
the absorbed form — YaRN-scaled rotary on the positional slice, plain
RMSNorm before each sub-block, a leading dense SwiGLU layer and then a
routed FFN (top-k weights as the softmax left them, ungated shared
experts) in every layer, an untied head — behind the program's serving
path: ``ServingEngine`` -> ``ContinuousBatcher``, the engine's defaults
for every election.  The block is said once, as the ``BlockSpec`` the
engine's ``TransformerConfig`` carries; the chip's share of the experts
is ``n_routed_experts`` of ``n_routed_experts_published``."""
from __future__ import annotations


def transformer_config(cfg: dict):
    import jax.numpy as jnp

    from autodist_tpu.models.transformer import (BlockSpec,
                                                 LatentAttentionSpec,
                                                 RopeScaling, RoutedFFNSpec,
                                                 TransformerConfig)

    scaling = cfg["rope_scaling"]
    if cfg["hidden_act"] != "silu" or cfg["q_lora_rank"] is not None \
            or cfg["attention_bias"] or cfg["tie_word_embeddings"] \
            or cfg["scoring_func"] != "softmax" \
            or cfg["topk_method"] != "greedy" or cfg["n_group"] != 1 \
            or cfg["topk_group"] != 1 or cfg["moe_layer_freq"] != 1 \
            or cfg["routed_scaling_factor"] != 1 \
            or scaling is None or scaling["type"] != "yarn" \
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"] \
            or not 0 <= cfg["first_k_dense_replace"] \
            < cfg["num_hidden_layers"]:
        raise ValueError(
            "the latent block the program serves has an uncompressed "
            "query, as many key/value heads as query heads up-projected "
            "from the latent, YaRN-scaled rotary, no biases, an untied "
            "head, leading dense SiLU-gated layers and then a routed FFN "
            "in every layer with a softmax router and a greedy top-k over "
            "one group, its weights not scaled; the configuration file "
            "states something else")
    yarn = RopeScaling(
        factor=scaling["factor"],
        original_max_len=scaling["original_max_position_embeddings"],
        beta_fast=scaling["beta_fast"], beta_slow=scaling["beta_slow"],
        mscale=scaling["mscale"], mscale_all_dim=scaling["mscale_all_dim"])
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        mlp_dim=cfg["intermediate_size"],
        max_len=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["serving"]["dtype"]), dropout_rate=0.0,
        attention_dropout_rate=0.0,
        block=BlockSpec(
            norm="rmsnorm", norm_placement="pre",
            norm_eps=cfg["rms_norm_eps"], positions="rope",
            rope_theta=float(cfg["rope_theta"]), rope_scaling=yarn,
            ffn="swiglu", bias=False, tied_head=False,
            latent=LatentAttentionSpec(
                kv_rank=cfg["kv_lora_rank"],
                nope_dim=cfg["qk_nope_head_dim"],
                rope_dim=cfg["qk_rope_head_dim"],
                value_dim=cfg["v_head_dim"]),
            dense_layers=cfg["first_k_dense_replace"],
            moe=RoutedFFNSpec(
                num_experts=cfg["n_routed_experts_published"],
                top_k=cfg["num_experts_per_tok"],
                expert_width=cfg["moe_intermediate_size"],
                shared_width=cfg["n_shared_experts"]
                * cfg["moe_intermediate_size"],
                experts_held=cfg["n_routed_experts"], first_expert=0,
                renormalise=cfg["norm_topk_prob"], shared_gate=False)))


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
