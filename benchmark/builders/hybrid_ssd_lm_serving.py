"""A decoder that mixes Mamba-2 state-space layers (heads that share one
B and one C a group, a scalar decay a head, a biased convolution over
``[x | B | C]``, a skip, a gate before one norm a group) with grouped-query
attention that has no positions at all, every layer under RMSNorm before
each sub-block with a dense SiLU-gated FFN, four scalar multipliers
(embedding, residual, softmax scale, logits) and a tied head, behind the
program's serving path: ``ServingEngine`` -> ``ContinuousBatcher``, the
engine's defaults for every election.  The block is said once, as the
``BlockSpec`` the engine's ``TransformerConfig`` carries."""
from __future__ import annotations

_KINDS = {"mamba": "linear", "attention": "full"}


def layer_period(layer_types: list) -> tuple:
    """The shortest run of kinds that ``layer_types`` repeats."""
    kinds = [_KINDS.get(t, t) for t in layer_types]
    for n in range(1, len(kinds) + 1):
        if all(k == kinds[i % n] for i, k in enumerate(kinds)):
            return tuple(kinds[:n])
    return tuple(kinds)


def transformer_config(cfg: dict):
    import jax.numpy as jnp

    from autodist_tpu.models.transformer import (BlockSpec, LinearMixerSpec,
                                                 TransformerConfig)

    if not hasattr(LinearMixerSpec, "ssd"):
        raise NotImplementedError(
            "the program at this commit has no state-space mixer "
            "(LinearMixerSpec.ssd): it cannot run this configuration")
    from autodist_tpu.models.pipeline_lm import SSD_CHUNK

    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    types = cfg["layer_types"]
    refused = {
        "layer_types other than mamba and attention":
        bool(set(types) - set(_KINDS)),
        "layer_types of another length than num_hidden_layers":
        len(types) != cfg["num_hidden_layers"],
        "attention_bias": cfg["attention_bias"],
        "mamba_proj_bias": cfg["mamba_proj_bias"],
        "routed experts (num_local_experts, num_experts_per_tok)":
        bool(cfg["num_local_experts"] or cfg["num_experts_per_tok"]),
        "a shared_intermediate_size other than intermediate_size":
        cfg["shared_intermediate_size"] != cfg["intermediate_size"],
        "hidden_act other than silu": cfg["hidden_act"] != "silu",
        "normalization_function other than rmsnorm":
        cfg["normalization_function"] != "rmsnorm",
        "position_embedding_type other than nope":
        cfg["position_embedding_type"] != "nope",
        "rope_scaling": cfg["rope_scaling"] is not None,
        "an untied head": not cfg["tie_word_embeddings"],
        "mamba_expand x hidden_size other than mamba_n_heads x mamba_d_head":
        cfg["mamba_expand"] * cfg["hidden_size"]
        != cfg["mamba_n_heads"] * cfg["mamba_d_head"],
        f"a mamba_chunk_size other than {SSD_CHUNK}":
        cfg["mamba_chunk_size"] != SSD_CHUNK,
        "query heads that are no multiple of the key/value heads":
        heads % kv != 0,
        "a recurrent state other than float32":
        cfg["serving"]["state_dtype"] != "float32",
    }
    if any(refused.values()):
        raise ValueError(
            "the state-space block the program serves does not implement "
            "what the configuration file states: "
            + "; ".join(k for k, v in refused.items() if v))
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=heads,
        mlp_dim=cfg["intermediate_size"],
        max_len=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["serving"]["dtype"]), dropout_rate=0.0,
        attention_dropout_rate=0.0,
        block=BlockSpec(
            norm="rmsnorm", norm_placement="pre",
            norm_eps=cfg["rms_norm_eps"], positions="none", ffn="swiglu",
            bias=False, tied_head=True, kv_heads=kv,
            head_dim=cfg["hidden_size"] // heads,
            layer_period=layer_period(types),
            linear=LinearMixerSpec.ssd(
                cfg["mamba_n_heads"], cfg["mamba_d_head"],
                cfg["mamba_d_state"], groups=cfg["mamba_n_groups"],
                conv_taps=cfg["mamba_d_conv"],
                conv_bias=cfg["mamba_conv_bias"]),
            embedding_multiplier=float(cfg["embedding_multiplier"]),
            residual_multiplier=float(cfg["residual_multiplier"]),
            logits_scaling=float(cfg["logits_scaling"]),
            softmax_scale=float(cfg["attention_multiplier"])))


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
