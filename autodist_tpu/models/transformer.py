"""Transformer encoder/decoder blocks — the shared modeling stack.

Counterpart of the reference's bundled transformer layers
(``examples/benchmark/utils/modeling/layers/`` ~1,000 LoC on
TF/Keras), rebuilt TPU-first in flax:

* bfloat16 activations by default (MXU-native), fp32 params + softmax
* ``jax.checkpoint`` (remat) per layer to trade FLOPs for HBM
* attention pluggable: local einsum attention here; Pallas flash /
  ring attention live in ``autodist_tpu.ops`` and slot in via
  ``attention_fn``
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """What one layer of the stack IS, said once: the serving engine's
    prefill, decode and chunk layers and the pipelined LM's
    ``_tp_encoder_layer`` all read it (``cfg.block``).  The default is
    the block the flax :class:`EncoderLayer` defines — LayerNorm after
    each residual add, learned positions, a two-matrix GELU MLP, biases,
    the head tied to the embedding, one pass over the layers.

    * ``norm`` ``"layernorm"`` | ``"rmsnorm"`` (scale only), at
      ``norm_eps``; ``norm_placement`` ``"post"`` (``LN(x + f(x))``) or
      ``"sandwich"`` (``x + N(f(N(x)))``: a norm before AND after each
      sub-block, four a layer).
    * ``positions`` ``"learned"`` (a table added to the embedding) or
      ``"rope"`` (rotate-half rotary at ``rope_theta`` on q and k inside
      attention; the key is rotated before it is cached).
    * ``ffn`` ``"gelu"`` (``wo(gelu(wi x))``) or ``"swiglu"``
      (``wo(silu(gate x) * (up x))``, gate and up stacked in ``wi``).
    * ``bias`` — whether the projections carry biases.
    * ``tied_head`` — logits from the embedding table, or from
      ``shared["lm_head"]``.
    * ``loop_steps`` ``T`` — the layers run ``T`` times with the same
      weights; the final norm closes every pass (it is pass ``u``'s
      output and pass ``u + 1``'s input), the head reads the last, and
      the serving cache holds ``T * num_layers`` layers, pass ``u``'s
      layer ``l`` at ``u * num_layers + l``.  ``exit_threshold`` is the
      published early-exit knob: at 1.0 every token leaves at pass
      ``T``, which is all the engine runs.
    """

    norm: str = "layernorm"
    norm_placement: str = "post"
    norm_eps: float = 1e-6
    positions: str = "learned"
    rope_theta: float = 10000.0
    ffn: str = "gelu"
    bias: bool = True
    tied_head: bool = True
    loop_steps: int = 1
    exit_threshold: float = 1.0

    def __post_init__(self):
        for name, allowed in (("norm", ("layernorm", "rmsnorm")),
                              ("norm_placement", ("post", "sandwich")),
                              ("positions", ("learned", "rope")),
                              ("ffn", ("gelu", "swiglu"))):
            if getattr(self, name) not in allowed:
                raise ValueError(f"BlockSpec.{name}={getattr(self, name)!r}"
                                 f": one of {allowed}")
        if self.loop_steps < 1:
            raise ValueError("BlockSpec.loop_steps must be >= 1")

    @property
    def is_default(self) -> bool:
        return self == BlockSpec()


@dataclasses.dataclass(unsafe_hash=True)
class TransformerConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 512
    type_vocab_size: int = 2
    dropout_rate: float = 0.1
    attention_dropout_rate: float = 0.1
    dtype: Any = jnp.bfloat16
    remat: bool = False
    attention_fn: Optional[Callable] = None  # (q, k, v, mask, dropout_rng) -> out
    # (local_len) -> position ids; None = arange.  Sequence-parallel
    # models pass parallel.sequence.global_positions so shards embed
    # their true offsets instead of restarting at 0.  max_len must cover
    # the GLOBAL sequence (shards x local_len): ids beyond it are
    # NaN-poisoned at the gather (loss turns NaN immediately) instead of
    # clamping to silently wrong embeddings; global_positions(max_len=...)
    # additionally rejects the mismatch statically at trace time.
    position_fn: Optional[Callable] = None
    causal: bool = False
    # What a layer is (norms, positions, FFN, biases, head, loops).  The
    # flax modules below are the default block only; the pipelined LM's
    # ``_tp_encoder_layer`` and the serving engine read the spec.
    block: BlockSpec = BlockSpec()

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def dot_product_attention(q, k, v, mask, *, dropout_rate=0.0,
                          dropout_rng=None, dtype=jnp.bfloat16):
    """Plain einsum attention (softmax in fp32 for stability)."""
    depth = q.shape[-1]
    scores = jnp.einsum("...qhd,...khd->...hqk", q, k) / np.sqrt(depth)
    scores = scores.astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate,
                                    probs.shape)
        probs = probs * keep / (1.0 - dropout_rate)
    return jnp.einsum("...hqk,...khd->...qhd", probs, v)


class SelfAttention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask, deterministic: bool):
        cfg = self.cfg
        B, L, _ = x.shape
        qkv = nn.DenseGeneral(
            features=(3, cfg.num_heads, cfg.head_dim), axis=-1,
            dtype=cfg.dtype, name="qkv")(x)
        q, k, v = jnp.moveaxis(qkv, -3, 0)
        dropout_rng = (None if deterministic or cfg.attention_dropout_rate == 0
                       else self.make_rng("dropout"))
        if cfg.attention_fn is not None:
            out = cfg.attention_fn(q, k, v, mask, dropout_rng)
        else:
            out = dot_product_attention(
                q, k, v, mask, dropout_rate=(0.0 if deterministic
                                             else cfg.attention_dropout_rate),
                dropout_rng=dropout_rng, dtype=cfg.dtype)
        return nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1),
                               dtype=cfg.dtype, name="out")(out)


class MlpBlock(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, deterministic: bool):
        cfg = self.cfg
        h = nn.Dense(cfg.mlp_dim, dtype=cfg.dtype, name="wi")(x)
        h = nn.gelu(h)
        h = nn.Dropout(cfg.dropout_rate)(h, deterministic=deterministic)
        return nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="wo")(h)


class EncoderLayer(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask, deterministic: bool):
        cfg = self.cfg
        a = SelfAttention(cfg, name="attention")(x, mask, deterministic)
        a = nn.Dropout(cfg.dropout_rate)(a, deterministic=deterministic)
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_attention")(x + a)
        m = MlpBlock(cfg, name="mlp")(x, deterministic)
        m = nn.Dropout(cfg.dropout_rate)(m, deterministic=deterministic)
        return nn.LayerNorm(dtype=cfg.dtype, name="ln_mlp")(x + m)


class Encoder(nn.Module):
    """Stack of encoder layers, optionally rematerialized per layer."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask, deterministic: bool):
        cfg = self.cfg
        layer_cls = EncoderLayer
        if cfg.remat:
            layer_cls = nn.remat(EncoderLayer, static_argnums=(3,))
        for i in range(cfg.num_layers):
            x = layer_cls(cfg, name=f"layer_{i}")(x, mask, deterministic)
        return x


class TransformerLM(nn.Module):
    """Decoder-only causal LM (the flagship model for benchmarking)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, *, deterministic: bool = True):
        cfg = self.cfg
        B, L = tokens.shape
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         dtype=cfg.dtype, name="token_embed")
        pos_embed = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (cfg.max_len, cfg.hidden_size), jnp.float32)
        if cfg.position_fn is not None:
            pos_ids = cfg.position_fn(L)
            pos = pos_embed[pos_ids]
            # The gather clamps out-of-range ids (repeating the last row —
            # silently wrong embeddings when max_len does not cover
            # shards x local_len); poison them to NaN so the loss goes
            # NaN on the first step instead.
            oob = (pos_ids < 0) | (pos_ids >= cfg.max_len)
            pos = jnp.where(oob[:, None], jnp.nan, pos)
        else:
            pos = pos_embed[:L]
        x = embed(tokens) + pos[None].astype(cfg.dtype)
        x = nn.Dropout(cfg.dropout_rate)(x, deterministic=deterministic)
        causal = nn.make_causal_mask(tokens, dtype=jnp.bool_)
        x = Encoder(cfg, name="encoder")(x, causal, deterministic)
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_final")(x)
        # weight-tied readout
        logits = embed.attend(x.astype(jnp.float32))
        return logits


def lm_loss_head(logits, batch):
    """Next-token cross entropy with optional per-token weights.

    ``ll = logit[target] - logsumexp``: same math as log_softmax + take,
    minus one full [B, L, V] materialization (HBM traffic)."""
    targets = batch["y"]
    weights = batch.get("w")
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    target = jnp.take_along_axis(logits, targets[..., None],
                                 axis=-1)[..., 0]
    ll = target - lse
    if weights is None:
        weights = jnp.ones_like(ll)
    loss = -(ll * weights).sum() / jnp.maximum(weights.sum(), 1.0)
    acc = ((logits.argmax(-1) == targets) * weights).sum() \
        / jnp.maximum(weights.sum(), 1.0)
    return loss, {"accuracy": acc}
