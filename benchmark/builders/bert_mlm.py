"""``bert-base-mlm`` through the program's main training path:
``bert.make_mlm_trainable`` -> ``AutoDist(...).build`` -> the runner.

The benchmark's own seeded weights replace the trainable's flax init
(the reference is handed the same arrays), so the init runs on the host
CPU where its op-by-op dispatch is cheapest; its seconds are in
``init_s``.
"""
from __future__ import annotations

import time


def transformer_config(cfg: dict):
    import jax.numpy as jnp

    from autodist_tpu.models.transformer import TransformerConfig

    if cfg["hidden_act"] != "gelu_tanh" or cfg["layer_norm_eps"] != 1e-6:
        raise ValueError("models/transformer.py runs tanh GELU and "
                         "LayerNorm eps 1e-6 only; the configuration "
                         "file states something else")
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        mlp_dim=cfg["intermediate_size"],
        max_len=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        dropout_rate=cfg["hidden_dropout_prob"],
        attention_dropout_rate=cfg["attention_probs_dropout_prob"],
        dtype=jnp.dtype(cfg["dtype"]))


def build_training(cfg: dict, traffic: dict, params: dict, chips: int):
    """``(runner, seconds)``: the compiled-step owner with its state on
    ``chips`` chips, started from ``params``."""
    import jax
    import jax.numpy as jnp
    import optax

    import autodist_tpu
    from autodist_tpu import AutoDist
    from autodist_tpu.models import bert
    from autodist_tpu.resource import ResourceSpec

    opt = traffic["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"optimizer {opt['name']!r}: only adamw is wired")
    optimizer = optax.adamw(
        opt["learning_rate"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"], mu_dtype=jnp.dtype(opt["mu_dtype"]))
    t0 = time.perf_counter()
    with jax.default_device(jax.devices("cpu")[0]):
        trainable = bert.make_mlm_trainable(
            transformer_config(cfg), optimizer, jax.random.PRNGKey(0),
            batch_size=2, seq_len=traffic["seq_len"],
            num_masked=traffic["num_masked"], with_input_mask=False)
    want = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)),
                        trainable.params)
    have = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), params)
    if want != have:
        raise ValueError("the reference's parameter tree is not the "
                         "program's: the layout has changed")
    trainable.params = params
    init_s = time.perf_counter() - t0
    strat = dict(traffic["strategy"])
    builder = getattr(autodist_tpu, strat.pop("builder"))(**strat)
    spec = {} if jax.device_count() == chips \
        else {"topology": {"num_devices": chips}}
    t0 = time.perf_counter()
    runner = AutoDist(ResourceSpec(spec), builder).build(trainable)
    return runner, {"init_s": init_s,
                    "build_s": time.perf_counter() - t0}


def make_batch(rng, cfg: dict, traffic: dict, rows: int) -> dict:
    """One unpadded MLM batch of ``rows`` sequences from ``rng``."""
    import numpy as np

    L, P, V = traffic["seq_len"], traffic["num_masked"], cfg["vocab_size"]
    return {
        "input_ids": rng.integers(0, V, (rows, L), dtype=np.int32),
        "segment_ids": rng.integers(0, cfg["type_vocab_size"], (rows, L),
                                    dtype=np.int32),
        "masked_positions": np.sort(
            rng.integers(0, L, (rows, P), dtype=np.int32), axis=-1),
        "masked_ids": rng.integers(0, V, (rows, P), dtype=np.int32),
        "masked_weights": np.ones((rows, P), np.float32),
    }
