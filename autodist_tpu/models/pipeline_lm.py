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
                                             TransformerConfig, attend)
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


def _rms_norm(x, scale, dtype, eps):
    """``x / sqrt(mean(x^2) + eps) * scale``, statistics in fp32."""
    with scope("norm"):
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (y * scale).astype(dtype)


def block_norm(cfg: TransformerConfig, x, p):
    """The block's norm on a raw ``{"scale"[, "bias"]}`` param dict."""
    spec = cfg.block
    if spec.norm == "rmsnorm":
        return _rms_norm(x, p["scale"], cfg.dtype, spec.norm_eps)
    return _flax_layer_norm(x, p, cfg.dtype, spec.norm_eps)


def final_norm(cfg: TransformerConfig, shared, h):
    """The norm after the last layer, on the ``shared`` tree's
    ``ln_final_*`` leaves: fp32 out for the default block (the training
    loss head's), the block's own RMSNorm otherwise."""
    if cfg.block.norm == "rmsnorm":
        return _rms_norm(h, shared["ln_final_scale"], cfg.dtype,
                         cfg.block.norm_eps)
    return _layer_norm(h, shared["ln_final_scale"], shared["ln_final_bias"])


def rope(x, positions, theta: float):
    """Rotate-half rotary embedding of ``x`` ``[B, S, heads, d]`` at
    absolute ``positions`` (``[S]`` or ``[B, S]``), angles in fp32."""
    with scope("rope"):
        d = x.shape[-1]
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        ang = positions.astype(jnp.float32)[..., None] * inv
        ang = jnp.concatenate([ang, ang], -1)[..., None, :]  # [.., S, 1, d]
        xf = x.astype(jnp.float32)
        rot = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], -1)
        return (xf * jnp.cos(ang) + rot * jnp.sin(ang)).astype(x.dtype)


def _bias(p, dtype):
    return p["bias"].astype(dtype) if "bias" in p else None


def attention_inputs(cfg: TransformerConfig, chunk, x, positions,
                     model_axis, comm_overlap=None):
    """The layer up to its attention: ``(x, q, k, v)`` — the residual
    stream in ``cfg.dtype`` and the local heads' projections of it (of
    its norm under sandwich placement), q and k rotated where positions
    are rotary.  One definition for the full-sequence layer, the decode
    step and the chunk window, which differ only in how they attend."""
    from autodist_tpu.parallel.tensor import column_parallel

    spec, dtype = cfg.block, cfg.dtype
    att = chunk["attention"]
    x = x.astype(dtype)
    h = (block_norm(cfg, x, chunk["ln_attention_in"])
         if spec.norm_placement == "sandwich" else x)
    with scope("attention"):
        qkv = column_parallel(h, att["qkv"]["kernel"].astype(dtype),
                              _bias(att["qkv"], dtype),
                              model_axis=model_axis,
                              comm_overlap=comm_overlap)
        if qkv.ndim == 3:       # a fused [H, 3 * heads * d] matrix
            q, k, v = (t.reshape(*t.shape[:2], -1, cfg.head_dim)
                       for t in jnp.split(qkv, 3, axis=-1))
        else:
            q, k, v = jnp.moveaxis(qkv, -3, 0)
    if spec.positions == "rope":
        q = rope(q, positions, spec.rope_theta)
        k = rope(k, positions, spec.rope_theta)
    return x, q, k, v


def _residual(cfg, x, y, p):
    if cfg.block.norm_placement == "sandwich":
        return x + block_norm(cfg, y, p)
    return block_norm(cfg, x + y, p)


def attention_residual(cfg: TransformerConfig, chunk, x, out, model_axis,
                       comm_overlap=None):
    """Attention's output projection and its residual add and norm."""
    from autodist_tpu.parallel.tensor import row_parallel

    dtype = cfg.dtype
    att = chunk["attention"]
    with scope("attention"):
        a = row_parallel(out, att["out"]["kernel"].astype(dtype),
                         _bias(att["out"], dtype),
                         model_axis=model_axis, axes=2,
                         comm_overlap=comm_overlap)
    return _residual(cfg, x, a, chunk["ln_attention"])


def ffn_residual(cfg: TransformerConfig, chunk, x, model_axis,
                 comm_overlap=None):
    """The feed-forward sub-block with its residual add and norm(s)."""
    from autodist_tpu.parallel.tensor import column_parallel, row_parallel

    spec, dtype = cfg.block, cfg.dtype
    mlp = chunk["mlp"]
    h = (block_norm(cfg, x, chunk["ln_mlp_in"])
         if spec.norm_placement == "sandwich" else x)
    with scope("mlp"):
        h = column_parallel(h, mlp["wi"]["kernel"].astype(dtype),
                            _bias(mlp["wi"], dtype),
                            model_axis=model_axis,
                            comm_overlap=comm_overlap)
        if spec.ffn == "swiglu":    # wi holds gate, then up: [H, 2 * M]
            gate, up = jnp.split(h, 2, axis=-1)
            h = jax.nn.silu(gate) * up
        else:
            h = jax.nn.gelu(h)
        m = row_parallel(h, mlp["wo"]["kernel"].astype(dtype),
                         _bias(mlp["wo"], dtype),
                         model_axis=model_axis, comm_overlap=comm_overlap)
    return _residual(cfg, x, m, chunk["ln_mlp"])


def _tp_encoder_layer(cfg: TransformerConfig, chunk, x, mask, model_axis,
                      comm_overlap=None, return_kv=False, positions=None):
    """One layer of ``cfg.block`` on Megatron-sharded chunk params.

    For the default block this is the flax :class:`EncoderLayer` math,
    open-coded so the two activation all-reduces land exactly at the
    row-parallel boundaries (attention out-projection, mlp ``wo``): qkv
    and ``wi`` are column-parallel (heads / mlp features sharded —
    ``chunk`` holds the local slice), attention runs on the local heads,
    and :func:`~autodist_tpu.parallel.tensor.row_parallel` psums the
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

    ``positions`` (rotary blocks only): the rows' absolute positions,
    ``arange`` of the sequence where not given.
    """
    if positions is None and cfg.block.positions == "rope":
        positions = jnp.arange(x.shape[1])
    x, q, k, v = attention_inputs(cfg, chunk, x, positions, model_axis,
                                  comm_overlap)
    with scope("attention"):
        out = attend(cfg, q, k, v, mask)
    x = attention_residual(cfg, chunk, x, out, model_axis, comm_overlap)
    y = ffn_residual(cfg, chunk, x, model_axis, comm_overlap)
    return (y, k, v) if return_kv else y


def run_stack(cfg: TransformerConfig, shared, carry, layers):
    """``cfg.block.loop_steps`` passes of ``layers(u, carry)`` (all the
    layers once; pass ``u`` traced, or 0) over ``carry = (x, *state)``:
    the residual stream and whatever rides along (the serving engine's
    caches).  One pass returns the stream as the last layer left it (the
    head norms the rows it reads); a looped stack closes EVERY pass with
    the final norm — pass ``u``'s output and pass ``u + 1``'s input —
    under one ``fori_loop`` whose body is the layers, so depth compiles
    once."""
    if cfg.block.loop_steps == 1:
        return layers(0, carry)

    def one_pass(u, carry):
        x, *state = layers(u, carry)
        return (final_norm(cfg, shared, x), *state)

    x, *state = carry
    return jax.lax.fori_loop(0, cfg.block.loop_steps, one_pass,
                             (x.astype(cfg.dtype), *state))


def head_rows(cfg: TransformerConfig, shared, h):
    """What the output projection multiplies: the final norm of ``h``,
    unless the looped stack's last pass already applied it."""
    return h if cfg.block.loop_steps > 1 else final_norm(cfg, shared, h)


def head_table(cfg: TransformerConfig, shared):
    return shared["embedding" if cfg.block.tied_head else "lm_head"]


def sequential_logits(cfg: TransformerConfig, params, tokens):
    """Full-sequence next-token logits on one device — the sequential
    reference apply for the pipelined LM's logical params tree
    (``{"stages": ..., "shared": ...}``).  The single definition the
    serving-export artifact, the decode goldens, and any full-recompute
    consumer share: embedding (+ positions) → every encoder layer
    (:func:`_tp_encoder_layer`, ``model_axis=None``), ``loop_steps``
    times → final norm → unembedding, returning ``[B, L, V]`` fp32
    logits."""
    stages, shared = params["stages"], params["shared"]
    L = tokens.shape[1]
    x = shared["embedding"][tokens]
    if cfg.block.positions == "learned":
        x = x + shared["pos_embed"][None, :L]
    mask = jnp.tril(jnp.ones((L, L), bool))[None, None]

    def layers(u, carry):
        x, = carry
        for i in range(cfg.num_layers):
            chunk = jax.tree.map(lambda a, _i=i: a[_i], stages)
            x = _tp_encoder_layer(cfg, chunk, x, mask, None)
        return x,

    x, = run_stack(cfg, shared, (x,), layers)
    x = head_rows(cfg, shared, x)
    return x.astype(jnp.float32) \
        @ head_table(cfg, shared).T.astype(jnp.float32)


def param_shapes(cfg: TransformerConfig) -> dict:
    """The logical ``{"stages": ..., "shared": ...}`` tree that
    :func:`sequential_logits` and the serving engine consume, as shapes:
    what ``cfg.block`` adds to and takes from the default block's tree.
    Stage leaves are stacked over ``num_layers`` (ONE set, whatever
    ``loop_steps``).  A non-default block keeps its fused projections as
    matrices, ``qkv`` ``[H, 3 * heads * d]`` (q, k, v; heads; d) and a
    gated ``wi`` ``[H, 2 * M]`` (gate, up): the TPU tiles an array's two
    minor dimensions, and the default block's ``[H, 3, heads, d]`` it
    copies whole, before every decode dispatch, into a layout whose
    tiles hold ``H`` (PERF.md section 6, PR 26)."""
    spec = cfg.block
    L, H, M, V = cfg.num_layers, cfg.hidden_size, cfg.mlp_dim, \
        cfg.vocab_size
    n, d = cfg.num_heads, cfg.head_dim

    def dense(shape, bias):
        return {"kernel": (L,) + shape,
                **({"bias": (L,) + bias} if spec.bias else {})}

    def norm(lead=(L,)):
        return {"scale": lead + (H,),
                **({"bias": lead + (H,)} if spec.norm == "layernorm"
                   else {})}

    wi = 2 * M if spec.ffn == "swiglu" else M
    stages = {
        "attention": {"qkv": dense((H, 3, n, d), (3, n, d))
                      if spec.is_default else dense((H, 3 * n * d),
                                                    (3 * n * d,)),
                      "out": dense((n, d, H), (H,))},
        "ln_attention": norm(),
        "mlp": {"wi": dense((H, wi), (wi,)),
                "wo": dense((M, H), (H,))},
        "ln_mlp": norm()}
    if spec.norm_placement == "sandwich":
        stages.update(ln_attention_in=norm(), ln_mlp_in=norm())
    shared = {"embedding": (V, H), "ln_final_scale": (H,)}
    if spec.norm == "layernorm":
        shared["ln_final_bias"] = (H,)
    if spec.positions == "learned":
        shared["pos_embed"] = (cfg.max_len, H)
    if not spec.tied_head:
        shared["lm_head"] = (V, H)
    if spec.loop_steps > 1:     # the exit gate, Linear(H, 1)
        shared["exit_gate"] = {"kernel": (H,), "bias": ()}
    return {"stages": stages, "shared": shared}


def make_pipeline_lm_trainable(cfg: TransformerConfig, optimizer, rng, *,
                               num_stages: int = None, **kw):
    """Stage-structured causal-LM trainable.

    ``num_stages`` defaults to ``cfg.num_layers`` (one encoder layer per
    chunk); it must equal ``pipe_devices x virtual_stages`` at lowering.
    Batches are ``{"x": [B, L] tokens, "y": [B, L] next tokens}``.
    """
    from autodist_tpu.capture import PipelineTrainable

    if not cfg.block.is_default:
        raise ValueError(
            "make_pipeline_lm_trainable trains the default block only "
            "(its stage_fn is the flax EncoderLayer); cfg.block="
            f"{cfg.block} is served, not yet trained")
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
