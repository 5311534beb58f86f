"""Pipelined transformer LM: the flagship model family in stage form.

Beyond reference parity (pipeline parallelism was declared future work,
``architecture.rst:49-51``): the decoder-only transformer of
``models/transformer.py`` re-declared as a
:class:`~autodist_tpu.capture.PipelineTrainable` — embedding and tied
unembedding as replicated *shared* parameters (prologue on every device,
head on the last stage), the encoder layers as the stacked stage ring —
so a real LM trains through the serializable ``Pipeline`` strategy
(GPipe or interleaved virtual stages) instead of a toy MLP.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from autodist_tpu.models.transformer import (EncoderLayer,
                                             TransformerConfig,
                                             dot_product_attention)
from autodist_tpu.telemetry import scope


def _layer_norm(x, scale, bias):
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * scale + bias


def _flax_layer_norm(x, p, dtype, eps=1e-6):
    """``nn.LayerNorm`` numerics (stats in fp32, flax's mean-of-squares
    variance) on a raw ``{"scale", "bias"}`` param dict — the tensor-
    parallel stage path can't call the flax module on sharded params."""
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = jnp.maximum(jnp.mean(xf * xf, -1, keepdims=True) - mu * mu, 0.0)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(dtype)


def _tp_encoder_layer(cfg: TransformerConfig, chunk, x, mask, model_axis,
                      comm_overlap=None, return_kv=False):
    """One encoder layer on Megatron-sharded chunk params.

    The flax :class:`EncoderLayer` math, open-coded so the two
    activation all-reduces land exactly at the row-parallel boundaries
    (attention out-projection, mlp ``wo``): qkv and ``wi`` are
    column-parallel (heads / mlp features sharded — ``chunk`` holds the
    local slice), attention runs on the local heads, and
    :func:`~autodist_tpu.parallel.tensor.row_parallel` psums the
    partial output products before the replicated bias/residual/norm.
    With ``model_axis=None`` (the sequential reference, tp=1) the same
    code runs the unsharded math with zero collectives.

    ``comm_overlap`` decomposes those collectives for latency hiding
    (reduce-scatter/all-gather pairs, or the chunked collective-matmul
    ring at the row boundaries — see
    :mod:`autodist_tpu.parallel.tensor`); same math, different
    summation order.

    ``return_kv=True`` additionally returns this layer's (local-head)
    k/v projections — the serving engine's prefill
    (:mod:`autodist_tpu.serving.engine`) fills its KV cache from the
    SAME layer definition training runs, so decode-vs-training
    numerics cannot drift through a copied implementation.
    """
    from autodist_tpu.parallel.tensor import column_parallel, row_parallel

    dtype = cfg.dtype
    att = chunk["attention"]
    x = x.astype(dtype)
    with scope("attention"):
        qkv = column_parallel(x, att["qkv"]["kernel"].astype(dtype),
                              att["qkv"]["bias"].astype(dtype),
                              model_axis=model_axis,
                              comm_overlap=comm_overlap)
        q, k, v = jnp.moveaxis(qkv, -3, 0)
        if cfg.attention_fn is not None:
            out = cfg.attention_fn(q, k, v, mask, None)
        else:
            out = dot_product_attention(q, k, v, mask, dropout_rate=0.0,
                                        dtype=dtype)
        a = row_parallel(out, att["out"]["kernel"].astype(dtype),
                         att["out"]["bias"].astype(dtype),
                         model_axis=model_axis, axes=2,
                         comm_overlap=comm_overlap)
    x = _flax_layer_norm(x + a, chunk["ln_attention"], dtype)
    with scope("mlp"):
        h = column_parallel(x, chunk["mlp"]["wi"]["kernel"].astype(dtype),
                            chunk["mlp"]["wi"]["bias"].astype(dtype),
                            model_axis=model_axis,
                            comm_overlap=comm_overlap)
        h = jax.nn.gelu(h)
        m = row_parallel(h, chunk["mlp"]["wo"]["kernel"].astype(dtype),
                         chunk["mlp"]["wo"]["bias"].astype(dtype),
                         model_axis=model_axis, comm_overlap=comm_overlap)
    y = _flax_layer_norm(x + m, chunk["ln_mlp"], dtype)
    return (y, k, v) if return_kv else y


def sequential_logits(cfg: TransformerConfig, params, tokens):
    """Full-sequence next-token logits on one device — the sequential
    reference apply for the pipelined LM's logical params tree
    (``{"stages": ..., "shared": ...}``).  The single definition the
    serving-export artifact, the decode goldens, and any full-recompute
    consumer share: embedding + positions → every encoder layer
    (:func:`_tp_encoder_layer`, ``model_axis=None``) → final norm →
    tied unembedding, returning ``[B, L, V]`` fp32 logits."""
    stages, shared = params["stages"], params["shared"]
    L = tokens.shape[1]
    x = shared["embedding"][tokens] + shared["pos_embed"][None, :L]
    mask = jnp.tril(jnp.ones((L, L), bool))[None, None]
    for i in range(cfg.num_layers):
        chunk = jax.tree.map(lambda a, _i=i: a[_i], stages)
        x = _tp_encoder_layer(cfg, chunk, x, mask, None)
    x = _layer_norm(x, shared["ln_final_scale"], shared["ln_final_bias"])
    return x @ shared["embedding"].T.astype(jnp.float32)


def make_pipeline_lm_trainable(cfg: TransformerConfig, optimizer, rng, *,
                               num_stages: int = None, **kw):
    """Stage-structured causal-LM trainable.

    ``num_stages`` defaults to ``cfg.num_layers`` (one encoder layer per
    chunk); it must equal ``pipe_devices x virtual_stages`` at lowering.
    Batches are ``{"x": [B, L] tokens, "y": [B, L] next tokens}``.
    """
    from autodist_tpu.capture import PipelineTrainable

    num_stages = num_stages or cfg.num_layers
    needs_rng = bool(cfg.dropout_rate or cfg.attention_dropout_rate)
    H = cfg.hidden_size
    layer = EncoderLayer(cfg)
    probe_x = jnp.zeros((2, min(cfg.max_len, 32), H), cfg.dtype)
    probe_mask = jnp.tril(jnp.ones((probe_x.shape[1],) * 2,
                                   bool))[None, None]

    k_layers, k_embed, k_pos = jax.random.split(
        rng if hasattr(rng, "dtype") else jax.random.PRNGKey(rng), 3)
    stacked = jax.vmap(
        lambda k: layer.init(k, probe_x, probe_mask, True)["params"]
    )(jax.random.split(k_layers, num_stages))

    shared = {
        "embedding": jax.random.normal(k_embed, (cfg.vocab_size, H),
                                       jnp.float32) * 0.02,
        "pos_embed": jax.random.normal(k_pos, (cfg.max_len, H),
                                       jnp.float32) * 0.02,
        "ln_final_scale": jnp.ones((H,), jnp.float32),
        "ln_final_bias": jnp.zeros((H,), jnp.float32),
    }

    @scope("embed")
    def prologue(shared, batch, model_axis=None, comm_overlap=None):
        """Token + position embedding.  Under ``Pipeline(vocab_parallel=
        True)`` the lowering passes ``model_axis`` and ``shared
        ["embedding"]`` is the local vocab shard: the lookup becomes the
        masked shard gather + model-axis psum of
        :func:`~autodist_tpu.parallel.tensor.vocab_parallel_embedding`
        (exactly equal to the replicated lookup — one shard contributes
        the row, the rest zeros)."""
        from autodist_tpu.parallel.tensor import vocab_parallel_embedding

        tokens = batch["x"]
        L = tokens.shape[1]
        x = vocab_parallel_embedding(
            tokens, shared["embedding"], model_axis=model_axis,
            comm_overlap=comm_overlap).astype(cfg.dtype)
        return x + shared["pos_embed"][None, :L].astype(cfg.dtype)

    def stage_fn(chunk, x, rng_c=None, rows=None, model_axis=None,
                 comm_overlap=None):
        """One encoder layer; with dropout configured, masks key on
        (chunk, global sample index) — drawn per row under vmap — so the
        pipelined schedule and the sequential reference produce
        identical masks for any microbatch count / data sharding
        (pipeline_apply's stage_rng contract).

        ``model_axis`` (set by the pipeline lowering under
        ``Pipeline(tensor_parallel>1)``): ``chunk`` holds Megatron
        shards and the layer runs the explicit-collective path of
        :func:`_tp_encoder_layer`; ``comm_overlap`` selects the
        latency-hiding decomposition of its model-axis collectives."""
        L = x.shape[1]
        mask = jnp.tril(jnp.ones((L, L), bool))[None, None]
        if model_axis is not None:
            if needs_rng:
                # Dropout masks over model-sharded intermediates have
                # per-shard shapes; no keying scheme reproduces the
                # sequential full-tensor draw, so the parity contract
                # cannot hold — reject instead of drifting silently.
                raise NotImplementedError(
                    "tensor_parallel > 1 requires dropout_rate == "
                    "attention_dropout_rate == 0 in the pipelined LM")
            return _tp_encoder_layer(cfg, chunk, x, mask, model_axis,
                                     comm_overlap)
        if not needs_rng or rng_c is None:
            return layer.apply({"params": chunk}, x, mask, True)
        keys = jax.vmap(lambda r: jax.random.fold_in(rng_c, r))(rows)

        def one_row(xr, key):
            return layer.apply({"params": chunk}, xr[None], mask, False,
                               rngs={"dropout": key})[0]

        return jax.vmap(one_row)(x, keys)

    @scope("lm_head")
    def loss_head(outputs, batch, shared, model_axis=None,
                  comm_overlap=None):
        """Tied-unembedding softmax cross-entropy.  Replicated path: the
        shared :func:`~autodist_tpu.models.losses.cross_entropy_from_logits`
        on full ``[B, L, V]`` logits.  Under ``Pipeline(vocab_parallel=
        True)`` (``model_axis`` set, ``shared["embedding"]`` the local
        vocab shard): the streaming fused epilogue — never materializes
        the full-vocab logits in forward or backward."""
        from autodist_tpu.models.losses import cross_entropy_from_logits
        from autodist_tpu.parallel.tensor import vocab_parallel_cross_entropy

        x = _layer_norm(outputs, shared["ln_final_scale"],
                        shared["ln_final_bias"])
        targets = batch["y"]
        if model_axis is None:
            logits = x @ shared["embedding"].T.astype(jnp.float32)
            nll = cross_entropy_from_logits(logits, targets)
            pred = logits.argmax(-1)
        else:
            nll, pred = vocab_parallel_cross_entropy(
                x, shared["embedding"], targets,
                vocab_size=cfg.vocab_size, model_axis=model_axis,
                comm_overlap=comm_overlap)
        loss = jnp.mean(nll)
        acc = jnp.mean(pred == targets)
        return loss, {"accuracy": acc}

    return PipelineTrainable(stage_fn, stacked, loss_head, optimizer,
                             num_stages=num_stages,
                             shared_params=shared, prologue=prologue,
                             stage_rng=needs_rng,
                             name="pipeline_lm", **kw)
