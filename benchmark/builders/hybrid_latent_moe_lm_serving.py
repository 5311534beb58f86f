"""A decoder that mixes delta-rule layers whose decay is a vector over
the key channels (Kimi Delta Attention: q, k and v convolved each with as
many heads as the values) with a latent-attention layer closing every
``layer_group_size``-th — one cached row a position, a gate a head on its
output, interleaved rotary on the positional slice — under plain RMSNorm
before each sub-block, leading dense SwiGLU layers and then a routed FFN
whose router scores by sigmoid, chooses through a correction term and
keeps ``topk_group`` of ``n_group`` expert groups, an untied head —
behind the program's serving path: ``ServingEngine`` ->
``ContinuousBatcher``, the engine's defaults for every election.  The
block is said once, as the ``BlockSpec`` the engine's
``TransformerConfig`` carries; the chip's share of the experts is
``num_experts`` of ``num_experts_published``."""
from __future__ import annotations


def transformer_config(cfg: dict):
    import jax.numpy as jnp

    from autodist_tpu.models.transformer import (BlockSpec,
                                                 LatentAttentionSpec,
                                                 LinearMixerSpec,
                                                 RoutedFFNSpec,
                                                 TransformerConfig)

    import dataclasses

    if "gate" not in {f.name for f in dataclasses.fields(LinearMixerSpec)}:
        raise NotImplementedError(
            "the program at this commit has no delta-rule mixer whose "
            "decay is a vector over the key channels (LinearMixerSpec.gate)"
            ": it cannot run this configuration")
    every, L = cfg["layer_group_size"], cfg["num_hidden_layers"]
    dense = cfg["first_k_dense_replace"]
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    limits = cfg["expert_swiglu_limit_list"][:L] \
        + cfg["share_expert_swiglu_limit_list"][:L]
    refused = {
        "a non-zero swiglu limit in a kept layer": any(limits),
        "q_lora_rank": cfg["q_lora_rank"] is not None,
        "rope_scaling": cfg["rope_scaling"] is not None,
        "use_nGPT": cfg["use_nGPT"], "up_proj_norm": cfg["up_proj_norm"],
        "value_norm": cfg["value_norm"],
        "scale_router_input": cfg["scale_router_input"],
        "mtp_use_kda": cfg["mtp_use_kda"],
        "use_kda_lora": cfg["use_kda_lora"] or not cfg["no_kda_lora"],
        "use_bias / use_qkv_bias": cfg["use_bias"] or cfg["use_qkv_bias"],
        "tie_word_embeddings": cfg["tie_word_embeddings"],
        "hidden_act other than silu": cfg["hidden_act"] != "silu",
        "a linear mixer without linear_silu, kda_safe_gate or a norm a "
        "head (group_norm_size 1)": not cfg["linear_silu"]
        or not cfg["kda_safe_gate"] or cfg["group_norm_size"] != 1,
        "fewer key/value heads than query heads": cfg["num_key_value_heads"]
        != heads or cfg["num_kv_heads_for_linear_attn"] not in (0, heads),
        "a gate on latent attention other than head_wise":
        cfg["gated_attention_proj_granularity_type"] != "head_wise",
        "a router other than sigmoid scores chosen through a correction "
        "(noaux_tc, moe_router_enable_expert_bias)":
        cfg["score_function"] != "sigmoid" or cfg["scoring_func"]
        != "sigmoid" or cfg["topk_method"] != "noaux_tc"
        or not cfg["moe_router_enable_expert_bias"],
        "head sizes that differ from head_dim": cfg["qk_nope_head_dim"] != d
        or cfg["v_head_dim"] != d or cfg["qk_head_dim"]
        != d + cfg["qk_rope_head_dim"] or cfg["rotary_dim"]
        != cfg["qk_rope_head_dim"],
        "layers that are no whole periods, or dense layers past the first "
        "period": L % every or not 0 <= dense < every,
        "a recurrent state other than float32":
        cfg["serving"]["state_dtype"] != "float32",
    }
    if any(refused.values()):
        raise ValueError(
            "the mixed latent / delta-rule block the program serves does "
            "not implement what the configuration file states: "
            + "; ".join(k for k, v in refused.items() if v))
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=L, num_heads=heads, mlp_dim=cfg["intermediate_size"],
        max_len=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["serving"]["dtype"]), dropout_rate=0.0,
        attention_dropout_rate=0.0,
        block=BlockSpec(
            norm="rmsnorm", norm_placement="pre",
            norm_eps=cfg["rms_norm_eps"], positions="rope",
            rope_theta=float(cfg["rope_theta"]),
            rope_interleave=cfg["rope_interleave"], ffn="swiglu",
            bias=False, tied_head=False, attn_gate=True,
            layer_period=("linear",) * (every - 1) + ("latent",),
            linear=LinearMixerSpec(
                key_heads=heads, value_heads=heads, key_dim=d, value_dim=d,
                conv_taps=cfg["short_conv_kernel_size"], gate="channel",
                gate_floor=float(cfg["kda_lower_bound"])),
            latent=LatentAttentionSpec(
                kv_rank=cfg["kv_lora_rank"],
                nope_dim=cfg["qk_nope_head_dim"],
                rope_dim=cfg["qk_rope_head_dim"],
                value_dim=cfg["v_head_dim"]),
            dense_layers=dense,
            moe=RoutedFFNSpec(
                num_experts=cfg["num_experts_published"],
                top_k=cfg["num_experts_per_tok"],
                expert_width=cfg["moe_intermediate_size"],
                shared_width=cfg["num_shared_experts"]
                * cfg["moe_shared_expert_intermediate_size"],
                experts_held=cfg["num_experts"], first_expert=0,
                renormalise=cfg["norm_topk_prob"], shared_gate=False,
                scores="sigmoid", groups=cfg["n_group"],
                groups_kept=cfg["topk_group"],
                scale=float(cfg["routed_scaling_factor"]),
                correction=True)))


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
