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
import numpy as np

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


def _norm_scale(spec, p, leaf="scale"):
    """What the block's RMSNorm multiplies by: the ``scale`` leaf, or
    ``1 + weight`` where the norm is zero-centred."""
    if spec.norm_zero_centred:
        return 1.0 + p[leaf.replace("scale", "weight")].astype(jnp.float32)
    return p[leaf]


def block_norm(cfg: TransformerConfig, x, p):
    """The block's norm on a raw ``{"scale"[, "bias"]}`` param dict
    (``{"weight"}`` where it is zero-centred)."""
    spec = cfg.block
    if spec.norm == "rmsnorm":
        return _rms_norm(x, _norm_scale(spec, p), cfg.dtype, spec.norm_eps)
    return _flax_layer_norm(x, p, cfg.dtype, spec.norm_eps)


def final_norm(cfg: TransformerConfig, shared, h):
    """The norm after the last layer, on the ``shared`` tree's
    ``ln_final_*`` leaves: fp32 out for the default block (the training
    loss head's), the block's own RMSNorm otherwise."""
    if cfg.block.norm == "rmsnorm":
        return _rms_norm(h, _norm_scale(cfg.block, shared, "ln_final_scale"),
                         cfg.dtype, cfg.block.norm_eps)
    return _layer_norm(h, shared["ln_final_scale"], shared["ln_final_bias"])


def rope(x, positions, theta: float, fraction: float = 1.0, scaling=None,
         interleave: bool = False):
    """Rotate-half rotary embedding of ``x`` ``[B, S, heads, d]`` at
    absolute ``positions`` (``[S]`` or ``[B, S]``), angles in fp32; on
    the first ``fraction`` of the ``d`` dimensions, the rest passing
    through.  ``scaling``
    (:class:`~autodist_tpu.models.transformer.RopeScaling`): its
    frequencies in place of ``theta ** (-2i / d)``, cos and sin times
    its factor.  ``interleave``: pair ``i`` is dimensions ``(2i, 2i +
    1)`` and not ``(i, i + d / 2)``."""
    if interleave:
        with scope("rope"):
            d = x.shape[-1]
            inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d) \
                if scaling is None else jnp.asarray(scaling.inv_freq(d, theta))
            ang = (positions.astype(jnp.float32)[..., None] * inv
                   )[..., None, :]                       # [.., S, 1, d / 2]
            factor = 1.0 if scaling is None else scaling.cos_sin_scale
            cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
            xf = x.astype(jnp.float32)
            even, odd = xf[..., 0::2], xf[..., 1::2]
            return jnp.stack([even * cos - odd * sin,
                              odd * cos + even * sin], -1) \
                .reshape(x.shape).astype(x.dtype)
    if fraction != 1.0:
        r = int(x.shape[-1] * fraction)
        return jnp.concatenate(
            [rope(x[..., :r], positions, theta, scaling=scaling),
             x[..., r:]], -1)
    with scope("rope"):
        d = x.shape[-1]
        if scaling is None:
            inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        else:
            inv = jnp.asarray(scaling.inv_freq(d, theta))
        ang = positions.astype(jnp.float32)[..., None] * inv
        ang = jnp.concatenate([ang, ang], -1)[..., None, :]  # [.., S, 1, d]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        if scaling is not None and scaling.cos_sin_scale != 1.0:
            cos, sin = (t * scaling.cos_sin_scale for t in (cos, sin))
        xf = x.astype(jnp.float32)
        rot = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], -1)
        return (xf * cos + rot * sin).astype(x.dtype)


def _bias(p, dtype):
    return p["bias"].astype(dtype) if "bias" in p else None


def attention_inputs(cfg: TransformerConfig, chunk, x, positions,
                     model_axis, comm_overlap=None, mixer="attention"):
    """The layer up to its attention: ``(x, q, k, v)`` — the residual
    stream in ``cfg.dtype`` and the local heads' projections of it (of
    its norm under sandwich placement), q and k rotated where positions
    are rotary.  One definition for the full-sequence layer, the decode
    step and the chunk window, which differ only in how they attend.
    Returns ``(x, q, k, v, gate)``: ``k`` and ``v`` carry
    ``cfg.kv_heads`` heads, and ``gate`` (``None`` but in an
    ``attn_gate`` block) is :func:`attention_residual`'s.  ``mixer``
    names the chunk's sub-tree that holds the projections, and the scope
    they wear (a retention layer's: ``"linear_attention"``)."""
    from autodist_tpu.parallel.tensor import column_parallel

    spec, dtype = cfg.block, cfg.dtype
    att = chunk[mixer]
    x = x.astype(dtype)
    h = (block_norm(cfg, x, chunk["ln_attention_in"])
         if spec.norm_placement in ("sandwich", "pre") else x)
    gate = None
    with scope(mixer):
        qkv = column_parallel(h, att["qkv"]["kernel"].astype(dtype),
                              _bias(att["qkv"], dtype),
                              model_axis=model_axis,
                              comm_overlap=comm_overlap)
        if spec.attn_gate or cfg.kv_heads != cfg.num_heads:
            # [H, heads * (d [+ d gate]) + 2 * kv_heads * d]: each query
            # head's q (then its gate), then the key heads, the value heads
            n, kv, d = cfg.num_heads, cfg.kv_heads, cfg.head_dim
            q_w = n * d * (2 if spec.attn_gate else 1)
            q, k, v = jnp.split(qkv, [q_w, q_w + kv * d], axis=-1)
            q = q.reshape(*q.shape[:2], n, -1)
            if spec.attn_gate:
                q, gate = q[..., :d], q[..., d:]
            k, v = (t.reshape(*t.shape[:2], kv, d) for t in (k, v))
        elif qkv.ndim == 3:     # a fused [H, 3 * heads * d] matrix
            q, k, v = (t.reshape(*t.shape[:2], -1, cfg.head_dim)
                       for t in jnp.split(qkv, 3, axis=-1))
        else:
            q, k, v = jnp.moveaxis(qkv, -3, 0)
    if spec.qk_norm:
        q = block_norm(cfg, q, att["q_norm"])
        k = block_norm(cfg, k, att["k_norm"])
    if spec.positions == "rope":
        q = rope(q, positions, spec.rope_theta, spec.rope_fraction,
                 spec.rope_scaling)
        k = rope(k, positions, spec.rope_theta, spec.rope_fraction,
                 spec.rope_scaling)
    return x, q, k, v, gate


def _residual(cfg, x, y, chunk, after):
    """The residual add around a sub-block's output ``y`` and the norm
    the placement puts after it (``chunk[after]``; none under ``pre``);
    ``y`` times the block's ``residual_multiplier`` first, where it has
    one."""
    if cfg.block.residual_multiplier != 1.0:
        y = y * cfg.block.residual_multiplier
    if cfg.block.norm_placement == "pre":
        return x + y.astype(x.dtype)
    if cfg.block.norm_placement == "sandwich":
        return x + block_norm(cfg, y, chunk[after])
    return block_norm(cfg, x + y, chunk[after])


def attention_residual(cfg: TransformerConfig, chunk, x, out, model_axis,
                       comm_overlap=None, gate=None, mixer="attention"):
    """Attention's output projection (of ``out * sigmoid(gate)`` in a
    gated block) and its residual add and norm.  ``mixer``: as
    :func:`attention_inputs`'s."""
    from autodist_tpu.parallel.tensor import row_parallel

    dtype = cfg.dtype
    att = chunk[mixer]
    with scope(mixer):
        if gate is not None:
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(dtype)
        a = row_parallel(out, att["out"]["kernel"].astype(dtype),
                         _bias(att["out"], dtype),
                         model_axis=model_axis, axes=2,
                         comm_overlap=comm_overlap)
    return _residual(cfg, x, a, chunk, "ln_attention")


def expand_kv_heads(cfg: TransformerConfig, t):
    """``[B, S, kv_heads, d]`` keys or values with each head repeated for
    the query heads that read it (head ``i`` reads ``i // group``)."""
    group = cfg.num_heads // cfg.kv_heads
    return t if group == 1 else jnp.repeat(t, group, axis=2)


# --------------------------------------------------------------------- #
# latent attention
# --------------------------------------------------------------------- #
# A position is projected down to one row [c | k_pe]: a latent c of
# kv_rank, normed, and a rotated positional key that every head shares.
# Head h's key is [W_UK,h c | k_pe] and its value W_UV,h c, both halves
# of the one up-projection kv_b.  One set of weights, two entry points:
# a window of positions projects the rows up and attends at the heads'
# own sizes (:func:`latent_expanded`); one position against cached rows
# folds W_UK into the query and W_UV into the output, so that the rows
# are attended as they are cached (:func:`latent_absorbed`):
#     q_h . k_h(t) = (W_UK,h^T q_nope,h) . c_t + q_pe,h . k_pe,t
#     sum_t a_h(t) v_h(t) = W_UV,h (sum_t a_h(t) c_t)
def _latent_inputs(cfg: TransformerConfig, chunk, x, positions):
    """``(x, q_nope [B, S, heads, nope], q_pe [B, S, heads, rope], row
    [B, S, kv_rank + rope])``: the residual stream, the heads' queries
    (``q_pe`` rotated) and the row a position caches."""
    spec, dtype, lat = cfg.block, cfg.dtype, cfg.block.latent
    la = chunk["latent_attention"]
    x = x.astype(dtype)
    h = block_norm(cfg, x, chunk["ln_attention_in"])
    with scope("latent_attention"):
        q = (h @ la["q"]["kernel"].astype(dtype)).reshape(
            *h.shape[:2], cfg.num_heads, lat.nope_dim + lat.rope_dim)
        q_nope, q_pe = q[..., :lat.nope_dim], q[..., lat.nope_dim:]
        down = h @ la["kv_a"]["kernel"].astype(dtype)
        c = _rms_norm(down[..., :lat.kv_rank], la["kv_norm"]["scale"],
                      dtype, spec.norm_eps)
        turn = lambda t: rope(t, positions, spec.rope_theta,
                              scaling=spec.rope_scaling,
                              interleave=spec.rope_interleave)
        k_pe = turn(down[..., None, lat.kv_rank:])[:, :, 0]
        return x, q_nope, turn(q_pe), jnp.concatenate([c, k_pe], -1)


def _latent_output(cfg, chunk, x, out):
    """The heads' outputs ``[B, S, heads, value_dim]`` — in an
    ``attn_gate`` block each head's times ``sigmoid(N(x) w_head)``, the
    gate in float32 — through the output projection, and the residual."""
    la = chunk["latent_attention"]
    if cfg.block.attn_gate:
        h = block_norm(cfg, x, chunk["ln_attention_in"])
        gate = jax.nn.sigmoid(jnp.matmul(
            h.astype(jnp.float32), la["gate"]["kernel"].astype(jnp.float32),
            precision=_HI))                              # [B, S, heads]
        out = (out.astype(jnp.float32) * gate[..., None]).astype(cfg.dtype)
    y = out.reshape(*out.shape[:2], -1) @ la["out"]["kernel"].astype(
        cfg.dtype)
    return _residual(cfg, x, y, chunk, "ln_attention")


def _up_projection(cfg, chunk):
    """``kv_b`` as ``[kv_rank, heads, nope_dim + value_dim]``."""
    lat = cfg.block.latent
    return chunk["latent_attention"]["kv_b"]["kernel"].astype(
        cfg.dtype).reshape(lat.kv_rank, cfg.num_heads, -1)


def latent_expanded(cfg: TransformerConfig, chunk, x, positions, mask):
    """Latent attention over a window, with its residual: ``(x +
    attn(N(x)), row)``.  Every position's row is projected up to the
    heads' keys and values and attended at their own sizes (``nope_dim +
    rope_dim`` against ``value_dim``) under ``mask``; ``row`` ``[B, S,
    kv_rank + rope_dim]`` is what each position would cache."""
    lat, dtype = cfg.block.latent, cfg.dtype
    x, q_nope, q_pe, row = _latent_inputs(cfg, chunk, x, positions)
    with scope("latent_attention"):
        kv = jnp.einsum("bsr,rhd->bshd", row[..., :lat.kv_rank],
                        _up_projection(cfg, chunk))
        k_pe = jnp.broadcast_to(row[..., None, lat.kv_rank:], q_pe.shape)
        q = jnp.concatenate([q_nope, q_pe], -1)
        k = jnp.concatenate([kv[..., :lat.nope_dim], k_pe], -1)
        with scope("latent_attend"):
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) \
                .astype(jnp.float32) * cfg.block.latent_softmax_scale
            if mask is not None:
                scores = jnp.where(mask, scores,
                                   jnp.finfo(jnp.float32).min)
            probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs,
                             kv[..., lat.nope_dim:])
        return _latent_output(cfg, chunk, x, out), row


def latent_absorbed(cfg: TransformerConfig, chunk, x, positions, attend):
    """Latent attention of positions against cached rows, with its
    residual.  ``attend(q [B, S, heads, kv_rank + rope_dim], row [B, S,
    1, kv_rank + rope_dim]) -> (o_lat [B, S, heads, kv_rank], carry)``
    is the cache's: it writes the rows and attends ``q`` over them as ONE
    key head whose values are its first ``kv_rank`` columns, at
    ``cfg.block.latent_softmax_scale``.  Returns ``(x + attn(N(x)),
    carry)``."""
    lat = cfg.block.latent
    x, q_nope, q_pe, row = _latent_inputs(cfg, chunk, x, positions)
    w = _up_projection(cfg, chunk)
    with scope("latent_attention"):
        q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w[..., :lat.nope_dim])
        q = jnp.concatenate([q_lat, q_pe], -1)
    o_lat, carry = attend(q, row[:, :, None, :])
    with scope("latent_attention"):
        out = jnp.einsum("bshr,rhd->bshd", o_lat, w[..., lat.nope_dim:])
        return _latent_output(cfg, chunk, x, out), carry


# --------------------------------------------------------------------- #
# the linear mixer: gated DeltaNet
# --------------------------------------------------------------------- #
# Per value head a float32 state S [key_dim, value_dim]:
#     S <- exp(g_t) S;  delta = beta_t (v_t - S^T k_t);  S <- S + k_t delta^T
#     o_t = S^T q_t
# One definition, two entry points: a window of positions (prefill, the
# full-recompute apply) runs the chunked form, one position (a decode
# step) the recurrence itself.
_HI = jax.lax.Precision.HIGHEST
DELTA_CHUNK = 64       # positions a chunk of the chunked form spans


def _l2_normalise(x, eps=1e-6):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


def causal_conv(x, taps, tail, bias=None):
    """Depthwise causal convolution and SiLU of ``x`` ``[B, S, C]`` with
    ``taps`` ``[T, C]`` (the last multiplies the current position), the
    ``T - 1`` inputs before ``x`` given as ``tail`` ``[B, T - 1, C]``,
    and ``bias`` ``[C]`` added before the SiLU where the convolution has
    one.  Returns the activations and the window ``[B, T - 1 + S, C]``
    the next tail is cut from.  Sums in float32."""
    S, T = x.shape[1], taps.shape[0]
    window = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    out = sum(window[:, j:j + S].astype(jnp.float32)
              * taps[j].astype(jnp.float32) for j in range(T))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return jax.nn.silu(out).astype(x.dtype), window


def conv_tail(window, taps: int, length=None):
    """The ``taps`` inputs the next window's convolution still needs, cut
    from :func:`causal_conv`'s ``window``: its last rows, or — ``length``
    ``[B]``, the rows' real positions — those before position ``length``
    (a row's padding lies behind them)."""
    if length is None:
        return window[:, -taps:]
    return jax.vmap(lambda w, n: jax.lax.dynamic_slice_in_dim(
        w, n, taps, axis=0))(window, length)


def causal_conv_step(x, taps, tail, bias=None):
    """:func:`causal_conv` of ONE position over a flat tail: ``x`` ``[B,
    C]``, ``tail`` ``[B, (T - 1) * C]`` (the inputs before it, oldest
    first).  Returns the activations ``[B, C]`` and the next tail.  Where
    ``C`` is whole lanes every slice here is lane-aligned: nothing is
    re-laid out."""
    T, C = taps.shape[0], x.shape[-1]
    window = jnp.concatenate([tail.astype(x.dtype), x], axis=-1)
    out = sum(window[:, j * C:(j + 1) * C].astype(jnp.float32)
              * taps[j].astype(jnp.float32) for j in range(T))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return jax.nn.silu(out).astype(x.dtype), window[:, C:]


def _unit_lower_inverse(m, base: int = 8):
    """``(I + m)^-1`` for strictly lower-triangular ``m`` ``[.., n, n]``,
    ``n`` a power of two times ``base``: blocked forward substitution
    done as whole-matrix matmuls.  The ``base``-wide diagonal blocks are
    inverted all at once by the finite series ``(I - d)(I + d^2)(I +
    d^4)..`` on ``m`` masked to them (a product of block-diagonal
    matrices stays block-diagonal; the series over all ``n`` at once
    would cancel catastrophically for keys that repeat), then blocks
    twice as wide from their halves, ``[[A, 0], [B, D]]^-1 = inv - inv
    [[0, 0], [B, 0]] inv`` with ``inv`` the halves' inverses, until one
    block is left: 11 matmuls at ``n = 64`` whatever the batch."""
    n = m.shape[-1]
    eye = jnp.eye(n, dtype=m.dtype)
    mm = lambda a, b: jnp.matmul(a, b, precision=_HI)
    block = jnp.arange(n)[:, None] // base == jnp.arange(n)[None, :] // base
    d = jnp.where(block, m, 0.0)
    inv, power, span = eye - d, mm(d, d), 2
    while span < base:
        inv, power, span = mm(inv, eye + power), mm(power, power), 2 * span
    width = base
    while width < n:
        wider = jnp.arange(n)[:, None] // (2 * width) \
            == jnp.arange(n)[None, :] // (2 * width)
        below = jnp.where(wider & ~block, m, 0.0)   # each pair's B
        inv = inv - mm(mm(inv, below), inv)
        block, width = wider, 2 * width
    return inv


DELTA_SUBCHUNK = 16    # positions whose decays are multiplied out apart


def _channel_decay_products(q, k, gc, sub: int = DELTA_SUBCHUNK):
    """``(kk, qk)`` ``[.., C, C]`` of a chunk whose decay is a vector over
    the key channels: ``kk[t, j] = sum_c k_tc k_jc exp(G_tc - G_jc)`` and
    ``qk`` the same with ``q_t``, for ``j <= t`` (above the diagonal
    whatever comes out; the callers mask it).  ``q``, ``k``, ``gc`` (the
    log decays summed from the chunk's first position, falling):
    ``[.., C, dk]``.

    ``exp(-G)`` over a whole chunk overflows float32 (a channel at its
    floor of -5 a position passes e^88 in 18 positions), so no ``exp``
    here takes a positive argument: the chunk is cut into sub-chunks of
    ``sub`` positions; a block of rows ``I`` against the columns before
    it factors at ``B_I``, the sum just before ``I``'s first position —
    ``exp(G_t - B_I)`` and ``exp(B_I - G_j)`` are both at most 1 — and
    the ``[sub, sub]`` blocks on the diagonal take the difference ``G_t -
    G_j`` before the ``exp``, channel by channel."""
    C = k.shape[-2]
    mm = lambda a, b: jnp.einsum("...ik,...jk->...ij", a, b, precision=_HI)
    kk_rows, qk_rows = [], []
    for i in range(0, C, sub):
        at = slice(i, i + sub)
        g_i = gc[..., at, :]
        before = gc[..., i - 1:i, :] if i else jnp.zeros_like(gc[..., :1, :])
        # the diagonal block: differences first, j <= t kept
        diff = g_i[..., :, None, :] - g_i[..., None, :, :]
        low = jnp.tril(jnp.ones((g_i.shape[-2],) * 2, bool))[..., None]
        w = jnp.exp(jnp.where(low, diff, 0.0)) * k[..., None, at, :]
        blocks = [(w * t[..., at, None, :]).sum(-1) for t in (k, q)]
        if i:
            # the columns before the block, through B_I
            into = jnp.exp(g_i - before)
            back = k[..., :i, :] * jnp.exp(before - gc[..., :i, :])
            blocks = [jnp.concatenate([mm(t[..., at, :] * into, back), d],
                                      -1) for t, d in zip((k, q), blocks)]
        pad = [(0, 0)] * (k.ndim - 1) + [(0, C - i - g_i.shape[-2])]
        kk_i, qk_i = (jnp.pad(b, pad) for b in blocks)
        kk_rows.append(kk_i)
        qk_rows.append(qk_i)
    return jnp.concatenate(kk_rows, -2), jnp.concatenate(qk_rows, -2)


def gated_delta_chunked(q, k, v, g, beta, state, chunk: int = DELTA_CHUNK):
    """The recurrence over a window, ``chunk`` positions at a time.
    ``q``, ``k``: ``[B, T, heads, dk]`` (normalised, ``q`` scaled);
    ``v``: ``[B, T, heads, dv]``; ``g`` (log decay, <= 0): ``[B, T,
    heads]``, one a head, or ``[B, T, heads, dk]``, one a row of the
    state; ``beta``: ``[B, T, heads]``; ``state``: ``[B, heads, dk,
    dv]``; all float32.
    Returns ``(o [B, T, heads, dv], state after position T - 1)``.  A
    position with ``g == 0`` and ``beta == 0`` leaves the state bit for
    bit (a padded position; the window is padded so to whole chunks).

    Inside a chunk the ``delta`` of every position is solved for at once
    (the WY form: ``(I + tril(beta K K^T * decay, -1))^-1``), so the
    chunk costs matmuls and the state moves once a chunk.  With a decay
    a channel the chunk's ``K K^T * decay`` is no product of two
    matrices: :func:`_channel_decay_products` sums it channel by
    channel."""
    B, T, Hh, dk = q.shape
    C = chunk
    by_channel = g.ndim == q.ndim
    pad = -T % C
    if pad:
        q, k, v, g, beta = (jnp.pad(t, [(0, 0), (0, pad)]
                                    + [(0, 0)] * (t.ndim - 2))
                            for t in (q, k, v, g, beta))
    N = (T + pad) // C
    # [B, heads, N, C, ..]
    split = lambda t: jnp.moveaxis(t, 1, 2).reshape(
        B, Hh, N, C, *t.shape[3:])
    q, k, v, g, beta = map(split, (q, k, v, g, beta))
    mm = lambda eq, a, b: jnp.einsum(eq, a, b, precision=_HI)
    if by_channel:
        gc = jnp.cumsum(g, -2)                           # [B, Hh, N, C, dk]
        kk, within = _channel_decay_products(q, k, gc)
        k_beta = k * beta[..., None]
        m = jnp.where(jnp.tril(jnp.ones((C, C), bool), -1),
                      kk * beta[..., None], 0.0)
        solve = _unit_lower_inverse(m)
        u = mm("...ij,...jv->...iv", solve, v * beta[..., None])
        w = mm("...ij,...jk->...ik", solve, k_beta * jnp.exp(gc))
        within = jnp.where(jnp.tril(jnp.ones((C, C), bool)), within, 0.0)
        q_in = q * jnp.exp(gc)
        last = gc[..., -1:, :]                           # [B, Hh, N, 1, dk]
        k_out = k * jnp.exp(last - gc)
        dec = jnp.exp(last)[..., 0, :]                   # a row's decay
    else:
        gc = jnp.cumsum(g, -1)                           # [B, Hh, N, C]
        upto = jnp.tril(jnp.ones((C, C), bool))          # j <= i
        decay = jnp.where(upto, jnp.exp(jnp.where(
            upto, gc[..., :, None] - gc[..., None, :], 0.0)), 0.0)
        k_beta = k * beta[..., None]
        m = jnp.where(jnp.tril(jnp.ones((C, C), bool), -1),
                      mm("...ik,...jk->...ij", k_beta, k) * decay, 0.0)
        solve = _unit_lower_inverse(m)
        u = mm("...ij,...jv->...iv", solve, v * beta[..., None])
        w = mm("...ij,...jk->...ik", solve, k_beta * jnp.exp(gc)[..., None])
        within = mm("...ik,...jk->...ij", q, k) * decay  # j <= i kept
        q_in = q * jnp.exp(gc)[..., None]
        last = gc[..., -1:]                              # [B, Hh, N, 1]
        k_out = k * jnp.exp(last - gc)[..., None]
        dec = jnp.exp(last)
    chunks = lambda t: jnp.moveaxis(t, 2, 0)             # N first

    def one_chunk(S, c):
        u_c, w_c, within_c, q_c, k_c, dec_c = c
        v_new = u_c - mm("...ik,...kv->...iv", w_c, S)
        o = mm("...ik,...kv->...iv", q_c, S) \
            + mm("...ij,...jv->...iv", within_c, v_new)
        # dec_c: [B, heads, 1] a head, or [B, heads, dk] a row
        S = S * dec_c[..., None] + mm("...ik,...iv->...kv", k_c, v_new)
        return S, o

    state, o = jax.lax.scan(
        one_chunk, state,
        tuple(map(chunks, (u, w, within, q_in, k_out, dec))))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2).reshape(B, Hh, N * C, -1), 1, 2)
    return o[:, :T], state


def gated_delta_step(q, k, v, g, beta, state):
    """One position of the recurrence: ``q``, ``k`` ``[B, heads, dk]``,
    ``v`` ``[B, heads, dv]``, ``g`` ``[B, heads]`` (or ``[B, heads, dk]``,
    a decay a row of the state), ``beta`` ``[B, heads]``, ``state``
    ``[B, heads, dk, dv]``, float32.  Returns ``(o [B, heads, dv],
    state)``.  Elementwise, not matmuls (a float32 product on the MXU
    would round the state to bf16), and the state is read twice and
    written once, nothing of its size in between: ``S^T k`` and ``S^T q``
    come from the state as it stands in one pass, the decay applied to
    the sums (``o = (S_d + k delta^T)^T q = S_d^T q + delta (k . q)``
    with ``S_d = exp(g) S``; a decay a row goes into ``k`` and ``q``
    first: ``S_d^T k = S^T (exp(g) k)``), and the update reads it
    again."""
    with scope("state_update"):
        if g.ndim == q.ndim:
            rows = jnp.exp(g)                            # [B, heads, dk]
            s_k = (state * (rows * k)[..., None]).sum(-2)
            s_q = (state * (rows * q)[..., None]).sum(-2)
        else:
            decay = jnp.exp(g)[..., None]
            s_k = decay * (state * k[..., None]).sum(-2)
            s_q = decay * (state * q[..., None]).sum(-2)
            rows = decay
        delta = (v - s_k) * beta[..., None]
        o = s_q + delta * (k * q).sum(-1, keepdims=True)
        return o, state * rows[..., None] \
            + k[..., None] * delta[..., None, :]


def linear_attention(cfg: TransformerConfig, chunk, x, state, *,
                     valid=None, length=None, step=gated_delta_step):
    """The delta-rule mixer with its residual — gated DeltaNet, or
    (``LinearMixerSpec.gate`` ``"channel"``) Kimi Delta Attention, whose
    decay is a vector over the key channels — ``(x + mixer(N(x)),
    (tail, S))``.  ``x``: ``[B, S, H]``; ``state``: ``(tail [B, taps - 1,
    channels], S [B, value_heads, key_dim, value_dim] float32)`` before
    the window.  One position (``S == 1``, a decode step) runs the
    recurrence, a longer window the chunked form.  ``valid`` ``[B, S]``
    marks the window's real positions (a padded one moves neither the
    state nor, being later, any real position's output) and ``length``
    ``[B]`` their count: the tail handed back is the one before position
    ``length``.  ``step``: what advances ``S`` by the one position, with
    :func:`gated_delta_step`'s operands and results — a caller that
    keeps the state elsewhere (the serving engine's cache manager:
    ``serving.kv_cache.DenseLayout.advance_state``) hands its own, and
    ``S`` is then whatever that takes and returns."""
    spec, dtype, lin = cfg.block, cfg.dtype, cfg.block.linear
    la = chunk["linear_attention"]
    kh, vh, dk, dv = lin.key_heads, lin.value_heads, lin.key_dim, \
        lin.value_dim
    B, S, _ = x.shape
    x = x.astype(dtype)
    h = block_norm(cfg, x, chunk["ln_attention_in"])
    f32 = lambda t: t.astype(jnp.float32)
    tail, ssm = state
    by_channel = lin.gate == "channel"
    with scope("linear_attention"):
        if by_channel:
            # q, k, v, the decay and the output gate each on its own
            # projection; a decay a key channel, bounded at the floor
            qkv = h @ la["qkv"]["kernel"].astype(dtype)
            z = h @ la["gate"]["kernel"].astype(dtype)
            beta = jax.nn.sigmoid(jnp.matmul(
                f32(h), f32(la["beta"]["kernel"]), precision=_HI))
            f = jnp.matmul(h, la["decay"]["kernel"].astype(dtype),
                           preferred_element_type=jnp.float32)
            # flat over (head, channel) until the recurrence takes it: a
            # reshape right behind the projection has the compiler re-lay
            # the stacked kernel out, whole, before every dispatch
            g = (lin.gate_floor * jax.nn.sigmoid(
                jnp.repeat(jnp.exp(f32(la["A_log"])), dk)
                * (f + f32(la["dt_bias"])))).reshape(B, S, vh, dk)
            if valid is not None:
                beta = beta * valid[..., None]
                g = g * valid[..., None, None]
        else:
            mixed = h @ la["qkvz"]["kernel"].astype(dtype)
            qkv, z = jnp.split(mixed, [lin.conv_channels], axis=-1)
            # the write strength and the decay: float32 end to end
            ba = jnp.matmul(f32(h), f32(la["ba"]["kernel"]), precision=_HI)
            beta = jax.nn.sigmoid(ba[..., :vh])
            g = -jnp.exp(f32(la["A_log"])) * jax.nn.softplus(
                ba[..., vh:] + f32(la["dt_bias"]))
            if valid is not None:
                beta, g = beta * valid[..., None], g * valid[..., None]
        qkv, window = causal_conv(qkv, la["conv"]["kernel"], tail)
        tail = conv_tail(window, lin.conv_taps - 1, length)
        q, k, v = jnp.split(f32(qkv), [kh * dk, 2 * kh * dk], axis=-1)
        q = _l2_normalise(q.reshape(B, S, kh, dk)) * dk ** -0.5
        k = _l2_normalise(k.reshape(B, S, kh, dk))
        q, k = (jnp.repeat(t, vh // kh, axis=2) for t in (q, k))
        v = v.reshape(B, S, vh, dv)
        if S == 1:
            o, ssm = step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                          ssm)
            o = o[:, None]
        else:
            with scope("state_update"):
                o, ssm = gated_delta_chunked(q, k, v, g, beta, ssm)
        # the gated norm: per head over value_dim, a plain scale
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + spec.norm_eps) * f32(la["norm"]["scale"])
        if by_channel:      # flat, as the decay above
            o = (o.reshape(B, S, vh * dv)
                 * jax.nn.sigmoid(f32(z))).astype(dtype)
        else:
            o = (o * jax.nn.silu(f32(z.reshape(B, S, vh, dv)))).astype(dtype)
        y = o.reshape(B, S, vh * dv) @ la["out"]["kernel"].astype(dtype)
    return _residual(cfg, x, y, chunk, "ln_attention"), (tail, ssm)


# --------------------------------------------------------------------- #
# the linear mixer: power retention
# --------------------------------------------------------------------- #
# q, k and v are attention's own (grouped heads, a norm a head, rotary);
# a log gate gamma <= 0 a key/value head and position.  In the attention
# form position t weighs position s <= t by
#     a_ts = exp(G_t - G_s) (q_t . k_s)^2,  G_t = sum_{r <= t} gamma_r
# (q scaled by d^-0.5) and y_t = sum_s a_ts v_s / (sum_s a_ts + eps).
# With phi(x) the symmetric square of x, phi(q) . phi(k) = (q . k)^2, so
# a key/value head carries the same sums forward as a state S and a
# normaliser z, float32:
#     S_t = exp(gamma_t) S_{t-1} + phi(k_t) v_t^T;  z_t likewise of phi(k_t)
#     y_t = S_t^T phi(q_t) / (z_t . phi(q_t) + eps)   (decay, write, READ)
# phi is laid out by offset: phi(x)[o, i] = c_o x_i x_{(i + o) mod d} for
# o = 0 .. d / 2, c = 1 at o = 0 (the squares) and at o = d / 2 (whose
# pairs each appear twice), sqrt(2) between: (d / 2 + 1) d entries for
# d (d + 1) / 2 distinct products, and a row is one rotation of x.  S is
# [offsets, dv, d], an offset's tile with d on its last axis.
RETENTION_CHUNK = 64   # positions of a window whose keys are expanded at once
RETENTION_EPS = 1e-6   # what the normaliser's sum is kept above


def symmetric_square(x):
    """``phi(x)`` ``[.., d / 2 + 1, d]`` of ``x`` ``[.., d]`` (``d``
    even), float32: ``phi(q) . phi(k) == (q . k) ** 2`` over both axes."""
    d = x.shape[-1]
    x = x.astype(jnp.float32)
    rolled = jnp.stack([jnp.roll(x, -o, -1) for o in range(d // 2 + 1)], -2)
    c = np.full((d // 2 + 1, 1), 2.0 ** 0.5, np.float32)
    c[0] = c[d // 2] = 1.0      # the squares; the pairs that appear twice
    return x[..., None, :] * rolled * c


def retention_step(q, k, v, g, state, eps: float = RETENTION_EPS):
    """One position of the recurrence.  ``q`` ``[B, heads, d]`` (scaled);
    ``k``, ``v`` ``[B, kv, d]``; ``g`` ``[B, kv]`` (log gate, <= 0);
    ``state``: ``(S [B, kv, offsets, dv, d], z [B, offsets, kv, d])``
    float32 (``LinearMixerSpec.normaliser_shape`` says why the heads lie
    inside the offsets there).  Query head ``i`` reads key/value head ``i // (heads //
    kv)``.  Returns ``(y [B, heads, dv], (S, z))``: the state decayed,
    written to and THEN read, so a position sees itself."""
    S, z = state
    B, kv = k.shape[:2]
    f32 = lambda t: t.astype(jnp.float32)
    with scope("state_update"):
        decay = jnp.exp(f32(g))
        pk = symmetric_square(k)                         # [B, kv, O, d]
        pq = symmetric_square(q).reshape(B, kv, -1, *pk.shape[-2:])
        S = S * decay[..., None, None, None] \
            + f32(v)[:, :, None, :, None] * pk[..., None, :]
        z = z * decay[:, None, :, None] + jnp.swapaxes(pk, 1, 2)
        num = jnp.einsum("bgoai,bghoi->bgha", S, pq, precision=_HI)
        den = jnp.einsum("bogi,bghoi->bgh", z, pq, precision=_HI)
        y = num / (den[..., None] + eps)
        return y.reshape(B, -1, y.shape[-1]), (S, z)


def retention_chunked(q, k, v, g, state, chunk: int = RETENTION_CHUNK,
                      eps: float = RETENTION_EPS):
    """The recurrence over a window.  ``q`` ``[B, T, heads, d]``
    (scaled); ``k``, ``v`` ``[B, T, kv, d]``; ``g`` ``[B, T, kv]``;
    ``state`` as :func:`retention_step`'s, or ``None``: no inputs yet,
    the window starts at position 0.  Returns ``(y [B, T, heads, dv],
    state after position T - 1)``.  A position with ``g == 0`` and ``k
    == 0`` leaves the state bit for bit and weighs nothing at any later
    position (a padded position).

    The outputs are the attention form over the whole window (``[T, T]``
    scores squared, the gates' differences taken before the ``exp``) —
    ``phi(q) . phi(k) = (q . k)^2``, so no query is expanded — plus, only
    where a state came in, its read through ``phi(q)``.  The state after
    is built once, from the keys alone, ``chunk`` positions at a time
    (what bounds ``phi(k)``), on top of the decayed state that came
    in."""
    B, T, H, d = q.shape
    kv, C = k.shape[2], chunk
    heads_first = lambda t: jnp.moveaxis(                # [B, heads.., T, x]
        t.astype(jnp.float32), 1, -2)
    q = heads_first(q.reshape(B, T, kv, H // kv, d))     # [B, kv, grp, T, d]
    k, v = heads_first(k), heads_first(v)                # [B, kv, T, d]
    G = jnp.cumsum(heads_first(g[..., None])[..., 0], -1)    # [B, kv, T]
    mm = lambda eq, a, b: jnp.einsum(eq, a, b, precision=_HI)
    upto = jnp.tril(jnp.ones((T, T), bool))              # s <= t
    decay = jnp.where(upto, jnp.exp(jnp.where(
        upto, G[..., :, None] - G[..., None, :], 0.0)), 0.0)
    a = mm("bghtd,bgsd->bghts", q, k) ** 2 * decay[:, :, None]
    num, den = mm("bghts,bgsv->bghtv", a, v), a.sum(-1)
    last = G[..., -1:]                                   # [B, kv, 1]
    O = d // 2 + 1
    if state is None:       # nothing to read, and the build starts at 0
        state = (jnp.zeros((B, kv, O, v.shape[-1], d), jnp.float32),
                 jnp.zeros((B, O, kv, d), jnp.float32))
    else:
        S, z = state
        pq = symmetric_square(q)                         # [B,kv,grp,T,O,d]
        into = jnp.exp(G)[:, :, None, :]                 # [B, kv, 1, T]
        num = num + into[..., None] * mm("bghtoi,bgoai->bghta", pq, S)
        den = den + into * mm("bghtoi,bogi->bght", pq, z)
        state = (S * jnp.exp(last)[..., None, None],
                 z * jnp.exp(last)[:, None])
    y = (num / (den[..., None] + eps)).reshape(B, H, T, -1)

    # what position s leaves in the state after the window: its key's
    # square times exp(G_T - G_s); whole chunks, the tail's keys 0
    pad = -T % C
    w = jnp.pad(jnp.exp(last - G), [(0, 0), (0, 0), (0, pad)])
    k, v = (jnp.pad(t, [(0, 0), (0, 0), (0, pad), (0, 0)]) for t in (k, v))
    chunks = lambda t: jnp.moveaxis(
        t.reshape(B, kv, (T + pad) // C, C, *t.shape[3:]), 2, 0)

    def one_chunk(state, c):
        k_c, v_c, w_c = c
        pk = symmetric_square(k_c) * w_c[..., None, None]    # [B,kv,C,O,d]
        return (state[0] + mm("bgsoi,bgsa->bgoai", pk, v_c),
                state[1] + jnp.swapaxes(pk.sum(2), 1, 2)), None

    state, _ = jax.lax.scan(one_chunk, state,
                            (chunks(k), chunks(v), chunks(w)))
    return jnp.moveaxis(y, 1, 2), state


def retention_attention(cfg: TransformerConfig, chunk, x, state, positions,
                        *, valid=None, step=retention_step):
    """The power-retention mixer with its residual: ``(x + mixer(N(x)),
    (S, z))``.  ``x``: ``[B, S, H]`` at absolute ``positions``; ``state``
    before the window, as :func:`retention_chunked`'s (``None``: the
    window starts at position 0).  q, k and v are
    :func:`attention_inputs`' (grouped heads, q/k norm, rotary) from the
    layer's ``linear_attention`` sub-tree, which holds the gate's ``[H,
    kv]`` projection beside them; the log gate ``log sigmoid`` of it is
    float32 end to end.  One position on a state runs the recurrence,
    any other window the chunked form; ``valid`` and ``step`` are
    :func:`linear_attention`'s."""
    spec = cfg.block
    la = chunk["linear_attention"]
    x, q, k, v, _ = attention_inputs(cfg, chunk, x, positions, None,
                                     mixer="linear_attention")
    h = (block_norm(cfg, x, chunk["ln_attention_in"])
         if spec.norm_placement in ("sandwich", "pre") else x)
    f32 = lambda t: t.astype(jnp.float32)
    with scope("linear_attention"):
        g = jax.nn.log_sigmoid(jnp.matmul(
            f32(h), f32(la["gate"]["kernel"]), precision=_HI))
        q, k, v = f32(q) * cfg.softmax_scale, f32(k), f32(v)
        if valid is not None:       # a padded position: no decay, no write
            g = g * valid[..., None]
            k = k * valid[..., None, None]
        if x.shape[1] == 1 and state is not None:
            y, state = step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], state)
            y = y[:, None]
        else:
            with scope("state_update"):
                y, state = retention_chunked(q, k, v, g, state)
    x = attention_residual(cfg, chunk, x, y.astype(cfg.dtype), None,
                           mixer="linear_attention")
    return x, state


# --------------------------------------------------------------------- #
# the linear mixer: a Mamba-2 state-space layer (SSD)
# --------------------------------------------------------------------- #
# heads of P values; a GROUP of heads shares one B and one C of N (the
# state size); a head and position has one step Delta = softplus(dt +
# dt_bias) and one decay a = exp(Delta A), A = -exp(A_log), float32:
#     S_t[h] = a_t[h] S_{t-1}[h] + (Delta_t[h] x_t[h]) (x) B_t    [P x N]
#     y_t[h] = S_t[h] C_t + D[h] x_t[h]            (decay, write, READ)
# A group's heads are kept as ONE matrix [N, heads a group * P]: B down
# the rows, each head's P values side by side along the columns, the
# decay a row vector constant over a head's P columns — an update is
# ``S * a_row + B_col * (Delta x)_row`` and a read a sum down the rows.
SSD_CHUNK = 256        # positions a chunk of the chunked form spans


def ssd_step(x, Bm, Cm, g, dt, state):
    """One position of the recurrence.  ``x`` ``[B, heads, P]``; ``Bm``,
    ``Cm`` ``[B, groups, N]``; ``g`` (log decay ``Delta A``, <= 0) and
    ``dt`` (``Delta``) ``[B, heads]``; ``state`` ``[B, groups, N, heads a
    group * P]``; all float32.  Returns ``(y [B, heads, P], state)``
    without the skip: the state decayed, written to and THEN read.
    Elementwise, not matmuls (a float32 product on the MXU at default
    precision would round the state), and ``g == 0`` with ``dt == 0``
    leaves the state bit for bit."""
    with scope("state_update"):
        Bsz, heads, P = x.shape
        G = Bm.shape[1]
        rows = lambda t: t.reshape(Bsz, G, 1, -1)        # [B, G, 1, W]
        decay = rows(jnp.repeat(jnp.exp(g), P, axis=-1))
        dx = rows(dt[..., None] * x)
        state = state * decay + Bm[..., None] * dx
        y = (state * Cm[..., None]).sum(-2)              # [B, G, W]
        return y.reshape(Bsz, heads, P), state


def ssd_chunked(x, Bm, Cm, g, dt, state, chunk: int = SSD_CHUNK):
    """The recurrence over a window, ``chunk`` positions at a time
    (Mamba-2's SSD).  ``x`` ``[B, T, heads, P]``; ``Bm``, ``Cm`` ``[B, T,
    groups, N]``; ``g``, ``dt`` ``[B, T, heads]``; ``state`` as
    :func:`ssd_step`'s, or ``None``: no inputs yet, the window starts at
    position 0.  Returns ``(y [B, T, heads, P], state after position T -
    1)``, ``y`` without the skip.  A position with ``g == 0`` and ``dt ==
    0`` (a padded position; the window is padded so to whole chunks)
    leaves the state as it was and weighs nothing at any later position.

    Inside a chunk the attention form, ``(L * (C B^T)) (Delta x)`` with
    ``L_ts = exp(sum_{s < r <= t} g_r)`` (the sums' difference taken
    before the ``exp``, nothing above the diagonal); each chunk's closing
    state from its own positions; the carry from chunk to chunk, read by
    the next chunk's positions through their ``C``.  float32 at
    ``highest`` throughout."""
    Bsz, T, heads, P = x.shape
    G, N, C = Bm.shape[2], Bm.shape[3], min(chunk, T)
    hg = heads // G
    pad = -T % C
    if pad:
        x, Bm, Cm, g, dt = (jnp.pad(t, [(0, 0), (0, pad)]
                                    + [(0, 0)] * (t.ndim - 2))
                            for t in (x, Bm, Cm, g, dt))
    n = (T + pad) // C
    mm = lambda eq, a, b: jnp.einsum(eq, a, b, precision=_HI)
    # [B, n, C, G, hg, ..]: a chunk's positions, a group's heads
    cut = lambda t, *last: t.reshape(Bsz, n, C, G, *last)
    dx = cut(dt[..., None] * x, hg, P)
    Bm, Cm = cut(Bm, N), cut(Cm, N)
    gc = jnp.cumsum(cut(g, hg), 2)                       # [B, n, C, G, hg]
    upto = jnp.tril(jnp.ones((C, C), bool))[:, :, None, None]    # s <= t
    diff = gc[:, :, :, None] - gc[:, :, None, :]         # [B,n,t,s,G,hg]
    L = jnp.where(upto, jnp.exp(jnp.where(upto, diff, 0.0)), 0.0)
    cb = mm("bntgk,bnsgk->bntsg", Cm, Bm)
    y = mm("bntsgh,bnsghp->bntghp", cb[..., None] * L, dx)
    last = gc[:, :, -1]                                  # [B, n, G, hg]
    # what a chunk's own positions leave in its closing state
    built = mm("bnsgk,bnsghp->bngkhp", Bm,
               dx * jnp.exp(last[:, :, None] - gc)[..., None])
    into = jnp.exp(gc)                                   # a position's decay
    over = jnp.exp(last)                                 # a chunk's
    if state is None:
        state = jnp.zeros((Bsz, G, N, hg * P), jnp.float32)
    chunks = lambda t: jnp.moveaxis(t, 1, 0)             # n first

    def one_chunk(S, c):
        C_c, into_c, built_c, over_c = c
        S = S.reshape(Bsz, G, N, hg, P)
        read = mm("btgk,bgkhp->btghp", C_c, S) * into_c[..., None]
        S = S * over_c[:, :, None, :, None] + built_c
        return S.reshape(Bsz, G, N, hg * P), read

    state, read = jax.lax.scan(
        one_chunk, state, tuple(map(chunks, (Cm, into, built, over))))
    y = y + jnp.moveaxis(read, 0, 1)
    return y.reshape(Bsz, n * C, heads, P)[:, :T], state


def gated_group_norm(y, z, scale, groups: int, eps: float):
    """``y`` ``[.., width]`` gated by ``SiLU(z)`` and THEN normed: one
    RMSNorm over each of ``groups`` equal runs of the width, times
    ``scale`` ``[width]``; float32."""
    gated = (y * jax.nn.silu(z)).reshape(*y.shape[:-1], groups, -1)
    o = gated * jax.lax.rsqrt(jnp.mean(gated * gated, -1, keepdims=True)
                              + eps)
    return o.reshape(y.shape) * scale


def ssd_attention(cfg: TransformerConfig, chunk, x, state, *, valid=None,
                  length=None, step=ssd_step):
    """The state-space mixer with its residual: ``(x + m mixer(N(x)),
    (tail, S))``, ``m`` the block's ``residual_multiplier``.  ``x``:
    ``[B, S, H]``; ``state``: ``(tail [B, (taps - 1) * channels] —
    flat, oldest first: ``LinearMixerSpec.tail_shape`` —, S [B, groups,
    N, heads a group * P] float32)`` before the window — ``S`` ``None``:
    no inputs yet.  The one input projection is ``[z | xBC |
    dt]`` and the convolution (with its bias, under ``state_conv``) runs
    over ``xBC = [x | B | C]``; ``softplus``, the decay and the state are
    float32; the skip ``D x`` joins the read-out; the output is gated by
    ``SiLU(z)`` and THEN normed, one RMSNorm a group.  One position on a
    state runs the recurrence, any other window the chunked form;
    ``valid``, ``length`` and ``step`` are :func:`linear_attention`'s."""
    spec, dtype, lin = cfg.block, cfg.dtype, cfg.block.linear
    la = chunk["linear_attention"]
    G, heads, N, P = lin.key_heads, lin.value_heads, lin.key_dim, \
        lin.value_dim
    inner = heads * P
    B, S, _ = x.shape
    x = x.astype(dtype)
    h = block_norm(cfg, x, chunk["ln_attention_in"])
    f32 = lambda t: t.astype(jnp.float32)
    tail, ssm = state
    with scope("linear_attention"):
        proj = jnp.matmul(h, la["in_proj"]["kernel"].astype(dtype),
                          preferred_element_type=jnp.float32)
        z, xbc, dt = jnp.split(proj, [inner, inner + lin.conv_channels],
                               axis=-1)
        # the step and the log decay: float32 end to end
        dt = jax.nn.softplus(dt + f32(la["dt_bias"]))    # [B, S, heads]
        g = -jnp.exp(f32(la["A_log"])) * dt
        if valid is not None:       # a padded position: no decay, no write
            dt, g = dt * valid[..., None], g * valid[..., None]
        with scope("state_conv"):
            conv = la["conv"]
            if S == 1:      # a decode step: the flat tail as it is held
                xbc, tail = causal_conv_step(
                    xbc[:, 0].astype(dtype), conv["kernel"], tail,
                    conv.get("bias"))
                xbc = xbc[:, None]
            else:
                taps = lin.conv_taps - 1
                xbc, window = causal_conv(
                    xbc.astype(dtype), conv["kernel"],
                    tail.reshape(B, taps, -1), conv.get("bias"))
                tail = conv_tail(window, taps, length).reshape(B, -1)
        xs, Bm, Cm = jnp.split(f32(xbc), [inner, inner + G * N], axis=-1)
        xs = xs.reshape(B, S, heads, P)
        Bm, Cm = Bm.reshape(B, S, G, N), Cm.reshape(B, S, G, N)
        if S == 1 and ssm is not None:
            y, ssm = step(xs[:, 0], Bm[:, 0], Cm[:, 0], g[:, 0], dt[:, 0],
                          ssm)
            y = y[:, None]
        else:
            with scope("state_update"):
                y, ssm = ssd_chunked(xs, Bm, Cm, g, dt, ssm)
        y = y + f32(la["D"])[:, None] * xs
        o = gated_group_norm(y.reshape(B, S, inner), z,
                             f32(la["norm"]["scale"]), G, spec.norm_eps)
        y = o.astype(dtype) @ la["out"]["kernel"].astype(dtype)
    return _residual(cfg, x, y, chunk, "ln_attention"), (tail, ssm)


def mix_linear(cfg: TransformerConfig, chunk, x, state, positions, *,
               valid=None, length=None, step=None):
    """A ``"linear"`` layer's mixer with its residual, whichever
    recurrence ``cfg.block.linear.rule`` names: ``(x + mixer(N(x)),
    state)``.  ``state`` is the tuple of arrays the rule keeps (the delta
    rule's and the state-space layer's ``(tail, S)``, retention's ``(S,
    z)``), or ``None``: no inputs yet, the window starts at position 0 —
    which retention's chunked form takes as it is, the state-space
    layer's as a blank tail and no matrix, and the delta rule as
    :func:`blank_linear_state`'s zeros; ``positions`` reach the rule
    whose q and k are rotated."""
    kw = {} if step is None else {"step": step}
    rule = cfg.block.linear.rule
    if rule == "retention":
        return retention_attention(cfg, chunk, x, state, positions,
                                   valid=valid, **kw)
    if rule == "ssd":
        if state is None:
            state = (blank_linear_state(cfg, x.shape[0])[0], None)
        return ssd_attention(cfg, chunk, x, state, valid=valid,
                             length=length, **kw)
    if state is None:
        state = blank_linear_state(cfg, x.shape[0])
    return linear_attention(cfg, chunk, x, state, valid=valid,
                            length=length, **kw)


def blank_linear_state(cfg: TransformerConfig, batch: int):
    """The state before position 0: no inputs, ``S = 0`` (and, of
    retention, ``z = 0``)."""
    lin = cfg.block.linear
    ssm = jnp.zeros((batch, *lin.state_shape), jnp.float32)
    if lin.has_normaliser:
        return (ssm, jnp.zeros((batch, *lin.normaliser_shape), jnp.float32))
    return (jnp.zeros((batch, *lin.tail_shape), cfg.dtype), ssm)


def _swiglu(h, wi, wo):
    gate, up = jnp.split(h @ wi, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ wo


def _router_rule(spec, moe_params) -> dict:
    """What a router other than the plain softmax top-k adds to
    :func:`autodist_tpu.parallel.moe.route_top_k`'s arguments (nothing
    for that one: its call stays as it was)."""
    if spec.scores == "softmax" and spec.groups == 1 and spec.scale == 1.0 \
            and not spec.correction:
        return {}
    return dict(scores=spec.scores, groups=spec.groups,
                groups_kept=spec.groups_kept, scale=spec.scale,
                correction=moe_params["router"]["correction"]
                if spec.correction else None)


def routed_ffn(cfg: TransformerConfig, moe_params, h, valid=None,
               kernel=None):
    """The routed block on normed rows ``h`` ``[B, S, H]``: ``(y, stats)``
    — the held experts' part of the routed sum
    (:func:`autodist_tpu.parallel.moe.routed_experts`, which ``kernel``,
    the kernel slot's word on ``grouped_matmul``, is for) plus the shared
    expert (behind its sigmoid gate, where the block has one), which
    every device computes."""
    from autodist_tpu.parallel.moe import routed_experts

    spec, dtype = cfg.block.moe, cfg.dtype
    rows = h.reshape(-1, h.shape[-1])
    with scope("moe"):
        with scope("moe_experts"):
            y, stats = routed_experts(
                rows, moe_params["router"]["kernel"],
                moe_params["experts"]["wi"], moe_params["experts"]["wo"],
                top_k=spec.top_k, first_expert=spec.first_expert,
                valid=None if valid is None else valid.reshape(-1),
                renormalise=spec.renormalise, kernel=kernel,
                **_router_rule(spec, moe_params))
        if spec.shared_width:
            sh = moe_params["shared"]
            gate = None
            if spec.shared_gate:
                gate = jax.nn.sigmoid(jnp.matmul(
                    rows.astype(jnp.float32),
                    moe_params["shared_gate"]["kernel"].astype(jnp.float32),
                    precision=_HI))[:, None]
            shared = _swiglu(
                rows, sh["wi"]["kernel"].astype(dtype),
                sh["wo"]["kernel"].astype(dtype)).astype(jnp.float32)
            y = y + (shared if gate is None else gate * shared)
    return y.reshape(h.shape).astype(dtype), stats


def ffn_residual(cfg: TransformerConfig, chunk, x, model_axis,
                 comm_overlap=None, valid=None, tally=None, kernel=None):
    """The feed-forward sub-block with its residual add and norm(s).  A
    routed layer (its chunk holds ``moe``: every layer of a routed
    stack but its leading dense ones) goes to :func:`routed_ffn`:
    ``valid`` marks the rows that are some request's (the others choose
    no expert), ``tally``, a list, is handed the layer's ``[rows_held,
    experts_hit]``, and ``kernel`` is :func:`routed_ffn`'s."""
    from autodist_tpu.parallel.tensor import column_parallel, row_parallel

    spec, dtype = cfg.block, cfg.dtype
    h = (block_norm(cfg, x, chunk["ln_mlp_in"])
         if spec.norm_placement in ("sandwich", "pre") else x)
    if "moe" in chunk:
        m, stats = routed_ffn(cfg, chunk["moe"], h, valid, kernel)
        if tally is not None:
            tally.append(stats)
        return _residual(cfg, x, m, chunk, "ln_mlp")
    mlp = chunk["mlp"]
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
    return _residual(cfg, x, m, chunk, "ln_mlp")


def _tp_encoder_layer(cfg: TransformerConfig, chunk, x, mask, model_axis,
                      comm_overlap=None, return_kv=False, positions=None,
                      valid=None):
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

    A ``"linear"`` layer of a mixed stack (its chunk holds
    ``linear_attention``) runs the mixer from no state: the whole
    sequence is the window.  ``valid`` is :func:`ffn_residual`'s.

    A latent-attention layer (its chunk holds ``latent_attention``)
    attends in the expanded form; ``return_kv`` hands back the rows it
    would cache as one key head, ``[B, S, 1, kv_rank + rope_dim]``, and
    no values.
    """
    if "latent_attention" in chunk:
        if positions is None:
            positions = jnp.arange(x.shape[1])
        x, row = latent_expanded(cfg, chunk, x, positions, mask)
        y = ffn_residual(cfg, chunk, x, model_axis, comm_overlap,
                         valid=valid)
        return (y, row[:, :, None, :], None) if return_kv else y
    if "linear_attention" in chunk:
        if positions is None:
            positions = jnp.arange(x.shape[1])
        x, _ = mix_linear(cfg, chunk, x, None, positions)
        return ffn_residual(cfg, chunk, x, model_axis, comm_overlap)
    if positions is None and cfg.block.positions == "rope":
        positions = jnp.arange(x.shape[1])
    x, q, k, v, gate = attention_inputs(cfg, chunk, x, positions,
                                        model_axis, comm_overlap)
    with scope("attention"):
        out = attend(cfg, q, expand_kv_heads(cfg, k),
                     expand_kv_heads(cfg, v), mask)
    x = attention_residual(cfg, chunk, x, out, model_axis, comm_overlap,
                           gate)
    y = ffn_residual(cfg, chunk, x, model_axis, comm_overlap, valid=valid)
    return (y, k, v) if return_kv else y


MIXERS = {"full": "attention", "linear": "linear_attention",
          "latent": "latent_attention"}
_FFNS = ("mlp", "moe")


def layer_key(l: int) -> str:
    """Where layer ``l``'s own arrays sit in a sub-tree that is kept a
    layer apart and not stacked (a routed FFN's experts)."""
    return f"layer_{l:02d}"


def _layer_of(tree, l: int, nth: int):
    """Layer ``l``'s part of ``tree``, the ``nth`` of its stacked
    leaves."""
    if not isinstance(tree, dict):
        return tree[nth]
    if layer_key(l) in tree:
        return tree[layer_key(l)]
    return {name: _layer_of(sub, l, nth) for name, sub in tree.items()}


def layer_chunk(cfg: TransformerConfig, stages, l: int):
    """Layer ``l``'s parameters out of ``stages``.  A leaf is stacked
    over the layers that have it: all of them, but for a mixed stack's
    mixers (``attention`` over the full layers, ``linear_attention``
    over the linear ones, ``latent_attention`` over the latent ones, each
    in stack order) and a routed stack's
    feed-forward kinds (``mlp`` over its leading dense layers, ``moe``
    over the routed ones: the chunk holds the one its layer runs).  A
    routed FFN's experts are arrays of their own a layer
    (:func:`layer_key`): the grouped matmul takes the array whole, and a
    layer's slice of a stack would reach it as a copy of all its
    experts, every step."""
    spec = cfg.block
    if not (spec.layer_period or spec.moe):
        return jax.tree.map(lambda p: p[l], stages)
    kinds = spec.layer_kinds(cfg.num_layers)
    mixers = tuple(MIXERS.values())
    routed = spec.moe is not None and l >= spec.dense_layers
    # the layer's place among the leaves of a sub-tree: the FFN it runs
    # stacks over the layers of its kind, the FFN it does not run is left
    # out, everything else stacks over all layers
    place = {_FFNS[routed]: l - spec.dense_layers if routed else l,
             _FFNS[not routed]: None}
    chunk = {name: _layer_of(tree, l, place.get(name, l))
             for name, tree in stages.items()
             if name not in mixers and place.get(name, l) is not None}
    mixer = MIXERS[kinds[l]]
    nth = kinds[:l].count(kinds[l])
    chunk[mixer] = jax.tree.map(lambda p: p[nth], stages[mixer])
    return chunk


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
    unless the looped stack's last pass already applied it — divided by
    the block's ``logits_scaling`` where it has one (in float32; the
    logits are the rows' products, so they come out divided)."""
    rows = h if cfg.block.loop_steps > 1 else final_norm(cfg, shared, h)
    if cfg.block.logits_scaling != 1.0:
        rows = (rows.astype(jnp.float32) / cfg.block.logits_scaling) \
            .astype(rows.dtype)
    return rows


def embedding_rows(cfg: TransformerConfig, rows):
    """The token embedding's ``rows`` as the stream takes them: times the
    block's ``embedding_multiplier`` where it has one."""
    if cfg.block.embedding_multiplier != 1.0:
        rows = rows * cfg.block.embedding_multiplier
    return rows


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
    x = embedding_rows(cfg, shared["embedding"][tokens])
    if cfg.block.positions == "learned":
        x = x + shared["pos_embed"][None, :L]
    mask = jnp.tril(jnp.ones((L, L), bool))[None, None]

    def layers(u, carry):
        x, = carry
        for i in range(cfg.num_layers):
            x = _tp_encoder_layer(cfg, layer_chunk(cfg, stages, i), x,
                                  mask, None)
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
    tiles hold ``H`` (PERF.md section 6, PR 26).

    A mixed stack (``layer_period``) stacks each mixer's leaves over the
    layers of its kind (:func:`layer_chunk`).  A grouped or gated
    attention's ``qkv`` is ``[H, heads * (d [+ d gate]) + 2 * kv_heads *
    d]``; the linear mixer holds ``qkvz`` ``[H, q | k | v | z]``, ``ba``
    ``[H, 2 * value_heads]``, the convolution's taps, ``A_log`` and
    ``dt_bias`` a value head, the gated norm's scale and ``out``; a
    routed FFN holds the router over ALL experts, the held experts' ``wi``
    ``[held, H, 2 * M]`` (gate, up) and ``wo`` as arrays of their own a
    layer (:func:`layer_chunk` says why), and the shared expert with its
    gate (where it has one); its ``dense_layers`` leading layers hold
    ``mlp`` instead, and the routed leaves stack over the rest.  Latent
    attention holds ``q`` ``[H, heads * (nope + rope)]``, the
    down-projection ``kv_a`` ``[H, kv_rank + rope]``, the latent's norm,
    the up-projection ``kv_b`` ``[kv_rank, heads * (nope + value)]``
    (each head's key part, then its value part) and ``out``, and in an
    ``attn_gate`` block the heads' ``gate`` ``[H, heads]``; in a mixed
    stack they stack over the latent layers.  A linear mixer whose gate
    is a channel's holds ``qkv`` ``[H, q | k | v]``, ``decay`` ``[H,
    value_heads * key_dim]``, ``gate`` ``[H, value_heads * value_dim]``
    and ``beta`` ``[H, value_heads]`` in place of ``qkvz`` and ``ba``,
    and ``dt_bias`` a channel.  A power-retention mixer holds attention's
    own leaves under ``linear_attention`` — ``qkv``, ``out`` and, in a
    ``qk_norm`` block, ``q_norm`` and ``k_norm``, stacked over its layers
    — and the gate's ``gate`` ``[H, kv_heads]`` beside them.  A
    state-space (ssd) mixer holds ``in_proj`` ``[H, z | x | B | C | dt]``,
    the convolution's taps (and ``bias``), ``A_log``, ``D`` and
    ``dt_bias`` a head, the gated norm's scale over the inner width and
    ``out``.  A router
    with a correction holds it beside its kernel, ``correction``
    ``[num_experts]``.  A zero-centred norm's leaf is ``weight``."""
    spec = cfg.block
    L, H, M, V = cfg.num_layers, cfg.hidden_size, cfg.mlp_dim, \
        cfg.vocab_size
    n, d, kv = cfg.num_heads, cfg.head_dim, cfg.kv_heads
    kinds = spec.layer_kinds(L)
    Lf = kinds.count("full")
    scale = "weight" if spec.norm_zero_centred else "scale"

    def dense(shape, bias, lead=L):
        return {"kernel": (lead,) + shape,
                **({"bias": (lead,) + bias} if spec.bias else {})}

    def norm(lead=(L,), width=H):
        return {scale: lead + (width,),
                **({"bias": lead + (width,)} if spec.norm == "layernorm"
                   else {})}

    wi = 2 * M if spec.ffn == "swiglu" else M
    retention = spec.linear is not None and spec.linear.rule == "retention"
    # the layers whose mixer projects attention's q, k and v
    La = kinds.count("linear") if retention else Lf
    if spec.is_default:
        qkv = dense((H, 3, n, d), (3, n, d))
    elif spec.attn_gate or kv != n:
        qkv = dense((H, n * d * (2 if spec.attn_gate else 1) + 2 * kv * d),
                    (), La)
    else:
        qkv = dense((H, 3 * n * d), (3 * n * d,), La)
    # a routed stack's leading layers alone carry the dense FFN
    Ld = spec.dense_layers if spec.moe is not None else L
    attention = {"qkv": qkv, "out": dense((n, d, H), (H,), La)}
    if spec.qk_norm:
        attention.update(q_norm=norm((La,), d), k_norm=norm((La,), d))
    stages = {
        "attention": attention,
        "mlp": {"wi": dense((H, wi), (wi,), Ld),
                "wo": dense((M, H), (H,), Ld)}}
    if not Lf:
        del stages["attention"]
    if spec.latent is not None:
        lat, Lt = spec.latent, kinds.count("latent")
        stages["latent_attention"] = {
            "q": dense((H, n * (lat.nope_dim + lat.rope_dim)), (), Lt),
            "kv_a": dense((H, lat.row), (), Lt),
            "kv_norm": {"scale": (Lt, lat.kv_rank)},
            "kv_b": dense((lat.kv_rank,
                           n * (lat.nope_dim + lat.value_dim)), (), Lt),
            "out": dense((n * lat.value_dim, H), (), Lt)}
        if spec.attn_gate:
            stages["latent_attention"]["gate"] = dense((H, n), (), Lt)
    if retention:
        # attention's own projections and norms feed the recurrence; the
        # gate's projection a key/value head rides with them
        stages["linear_attention"] = dict(attention,
                                          gate=dense((H, kv), (), La))
    elif spec.linear is not None and spec.linear.rule == "ssd":
        lin, Ll = spec.linear, kinds.count("linear")
        inner = lin.value_heads * lin.value_dim
        stages["linear_attention"] = {
            "in_proj": dense((H, inner + lin.conv_channels
                              + lin.value_heads), (), Ll),
            "conv": {"kernel": (Ll, lin.conv_taps, lin.conv_channels),
                     **({"bias": (Ll, lin.conv_channels)}
                        if lin.conv_bias else {})},
            "A_log": (Ll, lin.value_heads),
            "D": (Ll, lin.value_heads),
            "dt_bias": (Ll, lin.value_heads),
            "norm": {"scale": (Ll, inner)},
            "out": dense((inner, H), (), Ll)}
    elif spec.linear is not None:
        lin, Ll = spec.linear, kinds.count("linear")
        inner = lin.value_heads * lin.value_dim
        stages["linear_attention"] = {
            "qkvz": dense((H, lin.conv_channels + inner), (), Ll),
            "ba": dense((H, 2 * lin.value_heads), (), Ll),
            "conv": {"kernel": (Ll, lin.conv_taps, lin.conv_channels)},
            "A_log": (Ll, lin.value_heads),
            "dt_bias": (Ll, lin.value_heads),
            "norm": {"scale": (Ll, lin.value_dim)},
            "out": dense((inner, H), (), Ll)}
        if lin.gate == "channel":
            mixer = stages["linear_attention"]
            del mixer["qkvz"], mixer["ba"]
            mixer.update(
                qkv=dense((H, lin.conv_channels), (), Ll),
                decay=dense((H, lin.value_heads * lin.key_dim), (), Ll),
                gate=dense((H, inner), (), Ll),
                beta=dense((H, lin.value_heads), (), Ll),
                dt_bias=(Ll, lin.value_heads * lin.key_dim))
    if spec.moe is not None:
        moe, Ms, Lr = spec.moe, spec.moe.shared_width, L - Ld
        if not Ld:
            del stages["mlp"]
        stages["moe"] = {
            "router": {"kernel": (Lr, H, moe.num_experts),
                       **({"correction": (Lr, moe.num_experts)}
                          if moe.correction else {})},
            "experts": {layer_key(l): {
                "wi": (moe.experts_held, H, 2 * moe.expert_width),
                "wo": (moe.experts_held, moe.expert_width, H)}
                for l in range(Ld, L)}}
        if Ms:
            stages["moe"]["shared"] = {"wi": {"kernel": (Lr, H, 2 * Ms)},
                                       "wo": {"kernel": (Lr, Ms, H)}}
            if moe.shared_gate:
                stages["moe"]["shared_gate"] = {"kernel": (Lr, H)}
    if spec.norm_placement != "pre":
        stages.update(ln_attention=norm(), ln_mlp=norm())
    if spec.norm_placement in ("sandwich", "pre"):
        stages.update(ln_attention_in=norm(), ln_mlp_in=norm())
    shared = {"embedding": (V, H), "ln_final_" + scale: (H,)}
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
