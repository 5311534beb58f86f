"""A decoder whose every layer mixes by power retention — attention's own
grouped q, k and v (a norm a head, rotate-half rotary) feeding a gated
recurrent state of the key's symmetric square a key/value head, no keys
and values cached at all — under plain RMSNorm before each sub-block, a
SiLU-gated FFN and an untied head, behind the program's serving path:
``ServingEngine`` -> ``ContinuousBatcher``, the engine's defaults for
every election.  The block is said once, as the ``BlockSpec`` the
engine's ``TransformerConfig`` carries."""
from __future__ import annotations


def transformer_config(cfg: dict):
    import jax.numpy as jnp

    from autodist_tpu.models.transformer import (BlockSpec, LinearMixerSpec,
                                                 TransformerConfig)

    if not hasattr(LinearMixerSpec, "retention"):
        raise NotImplementedError(
            "the program at this commit has no power-retention mixer "
            "(LinearMixerSpec.retention): it cannot run this configuration")
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    refused = {
        "use_sliding_window": cfg["use_sliding_window"],
        "a sliding_window": cfg["sliding_window"] is not None,
        "rope_scaling": cfg["rope_scaling"] is not None,
        "attention_bias": cfg["attention_bias"],
        "tie_word_embeddings": cfg["tie_word_embeddings"],
        "max_window_layers other than the published depth":
        cfg["max_window_layers"] != cfg["num_hidden_layers_published"],
        "hidden_act other than silu": cfg["hidden_act"] != "silu",
        "query heads that are no multiple of the key/value heads":
        heads % kv != 0,
        "a recurrent state other than float32":
        cfg["serving"]["state_dtype"] != "float32",
    }
    if any(refused.values()):
        raise ValueError(
            "the power-retention block the program serves does not "
            "implement what the configuration file states: "
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
            norm_eps=cfg["rms_norm_eps"], positions="rope",
            rope_theta=float(cfg["rope_theta"]), ffn="swiglu", bias=False,
            tied_head=False, kv_heads=kv, head_dim=cfg["head_dim"],
            qk_norm=True, layer_period=("linear",),
            linear=LinearMixerSpec.retention(kv, cfg["head_dim"])))


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
