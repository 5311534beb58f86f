"""BERT for masked-LM pretraining.

Counterpart of the reference's bundled BERT stack
(``examples/benchmark/utils/bert_modeling.py`` 963 LoC,
``bert_models.py`` 393 LoC, driven by ``examples/benchmark/bert.py``) —
rebuilt in flax on the shared :mod:`transformer` encoder.  Masked
positions are a *static-count* gather (TPU-friendly static shapes) as in
standard MLM pretraining batches.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from autodist_tpu.models.transformer import Encoder, TransformerConfig
from autodist_tpu.telemetry import scope


def bert_base(**kw) -> TransformerConfig:
    return TransformerConfig(vocab_size=30522, hidden_size=768, num_layers=12,
                             num_heads=12, mlp_dim=3072, max_len=512, **kw)


def bert_large(**kw) -> TransformerConfig:
    return TransformerConfig(vocab_size=30522, hidden_size=1024,
                             num_layers=24, num_heads=16, mlp_dim=4096,
                             max_len=512, **kw)


class BertModel(nn.Module):
    """Embeddings + encoder + MLM transform head.

    Returns ``(logits, bias)``: the tied decode's product over the
    flattened masked positions, ``[B*P, V]`` in the model's dtype, and
    the float32 output bias ``[V]``; :func:`mlm_loss_head` takes both."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, batch, *, deterministic: bool = True):
        cfg = self.cfg
        tokens = batch["input_ids"]          # [B, L]
        segments = batch.get("segment_ids")  # [B, L]
        mask = batch.get("input_mask")       # [B, L] 1 = real token
        masked_pos = batch["masked_positions"]  # [B, P] static P

        B, L = tokens.shape
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         name="token_embed")
        with scope("embed"):
            x = embed(tokens)
            pos = self.param("pos_embed", nn.initializers.normal(0.02),
                             (cfg.max_len, cfg.hidden_size), jnp.float32)
            x = x + pos[None, :L].astype(cfg.dtype)
            if segments is not None:
                x = x + nn.Embed(cfg.type_vocab_size, cfg.hidden_size,
                                 dtype=cfg.dtype,
                                 name="segment_embed")(segments)
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_embed")(x)
        x = nn.Dropout(cfg.dropout_rate)(x, deterministic=deterministic)

        attn_mask = None
        if mask is not None:
            attn_mask = (mask[:, None, None, :] > 0)
        x = Encoder(cfg, name="encoder")(x, attn_mask, deterministic)

        # MLM head: gather masked positions (static count), transform,
        # decode against the tied embedding table.  The B x P masked
        # positions are ONE axis from here to the loss: B*P rows pad to a
        # whole tile once, where [B, P] pads P in every row of B, and the
        # table's gradient is then one contraction over all of them.
        with scope("lm_head"):
            gathered = jnp.take_along_axis(
                x, masked_pos[..., None], axis=1)     # [B, P, H]
            gathered = gathered.reshape(-1, cfg.hidden_size)  # [B*P, H]
            h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                         name="mlm_dense")(gathered)
            h = nn.gelu(h)
            h = nn.LayerNorm(dtype=cfg.dtype, name="mlm_ln")(h)
            # Tied-embedding decode on the MXU in model dtype (the
            # [H, V] matmul is the head's FLOP bulk), stored once as it
            # comes; the loss head adds the bias in fp32 inside its reads.
            logits = embed.attend(h)                  # [B*P, V]
            bias = self.param("mlm_bias", nn.initializers.zeros,
                              (cfg.vocab_size,), jnp.float32)
        return logits, bias


@scope("lm_head")
def mlm_loss_head(logits, batch, bias):
    """Masked-LM cross entropy over the static masked positions.

    ``logits`` are ``[B*P, V]`` as :class:`BertModel` hands them (a
    ``[B, P, V]`` caller is flattened here) in the model's dtype, and
    the logits proper are ``float32(logits) + bias``.  Each reduction
    forms those inside its own read of the stored product, and the
    target is gathered from the product itself plus ``bias[label]`` (the
    same float32, bit for bit), so nothing of the logits' size is ever
    written in float32.

    ``ll = logit[target] - logsumexp(logits)`` instead of a full
    ``log_softmax``: mathematically identical, but skips materializing a
    second tensor of the logits' size."""
    labels = batch["masked_ids"].reshape(-1)        # [B*P]
    weights = batch["masked_weights"].reshape(-1)   # 0 for padding predictions
    logits = logits.reshape(-1, logits.shape[-1])
    target = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    target = target.astype(jnp.float32) + bias[labels]
    logits = logits.astype(jnp.float32) + bias
    lse = jax.scipy.special.logsumexp(logits, axis=-1)           # [B*P]
    ll = target - lse
    denom = jnp.maximum(weights.sum(), 1.0)
    loss = -(ll * weights).sum() / denom
    acc = ((logits.argmax(-1) == labels) * weights).sum() / denom
    return loss, {"mlm_accuracy": acc}


def make_mlm_trainable(cfg: TransformerConfig, optimizer, rng,
                       *, batch_size=8, seq_len=128, num_masked=20,
                       with_input_mask=True):
    """Build a Trainable for BERT MLM (init on synthetic shapes).

    ``with_input_mask=False`` drops the padding mask from the init sample
    — required for attention kernels that only support unpadded batches
    (e.g. the Pallas flash path); feed batches without ``input_mask``.
    """
    from autodist_tpu.capture import Trainable

    model = BertModel(cfg)
    sample = synthetic_mlm_batch(rng, batch_size, seq_len, num_masked,
                                 cfg.vocab_size)
    if not with_input_mask:
        sample.pop("input_mask", None)
    variables = model.init({"params": rng, "dropout": rng}, sample,
                           deterministic=True)

    def loss(params, extra, batch, step_rng):
        logits, bias = model.apply({"params": params}, batch,
                                   deterministic=False,
                                   rngs={"dropout": step_rng})
        l, metrics = mlm_loss_head(logits, batch, bias)
        return l, extra, dict(metrics, loss=l)

    return Trainable(loss, variables["params"], optimizer,
                     sparse_params=("token_embed/embedding",),
                     name="bert_mlm")


def synthetic_mlm_batch(rng, batch_size, seq_len, num_masked, vocab_size):
    """Random MLM batch with the exact structure of a real one."""
    import numpy as np
    r = np.random.RandomState(int(jax.random.randint(rng, (), 0, 2**31 - 1))
                              if hasattr(rng, "dtype") else rng)
    return {
        "input_ids": r.randint(0, vocab_size, (batch_size, seq_len)).astype(np.int32),
        "segment_ids": r.randint(0, 2, (batch_size, seq_len)).astype(np.int32),
        "input_mask": np.ones((batch_size, seq_len), np.int32),
        "masked_positions": np.sort(
            r.randint(0, seq_len, (batch_size, num_masked)), axis=-1).astype(np.int32),
        "masked_ids": r.randint(0, vocab_size, (batch_size, num_masked)).astype(np.int32),
        "masked_weights": np.ones((batch_size, num_masked), np.float32),
    }
