"""A decoder of the ``pipeline_lm`` family behind the program's serving
path: ``ServingEngine`` -> ``ContinuousBatcher``, the engine's defaults
for every election."""
from __future__ import annotations


def transformer_config(cfg: dict):
    import jax.numpy as jnp

    from autodist_tpu.models.transformer import TransformerConfig

    if cfg["activation_function"] != "gelu_new" \
            or cfg["layer_norm_epsilon"] != 1e-6 \
            or cfg["norm_placement"] != "post":
        raise ValueError("pipeline_lm runs post-LN, tanh GELU and "
                         "LayerNorm eps 1e-6 only; the configuration file "
                         "states something else")
    H = cfg["n_embd"]
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], hidden_size=H,
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        mlp_dim=cfg["n_inner"] or 4 * H, max_len=cfg["n_positions"],
        dtype=jnp.dtype(cfg["serving"]["dtype"]), dropout_rate=0.0,
        attention_dropout_rate=0.0)


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
