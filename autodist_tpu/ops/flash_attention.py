"""Flash attention as a Pallas TPU kernel.

The hot op of the transformer stack (SURVEY.md §7 step 8 "compressor/
custom kernels"; the reference had no fused attention — its bundled BERT
benchmark ran plain einsum attention, ``examples/benchmark/utils/
bert_modeling.py``).  This is the TPU-idiomatic replacement: blockwise
online-softmax attention that never materializes the [L, L] score matrix
in HBM — scores live in VMEM one (block_q, block_k) tile at a time, so
memory is O(L·D) instead of O(L²) and the MXU sees back-to-back matmuls.

Layout contract matches ``models/transformer.py``: q/k/v are
``[batch, length, heads, head_dim]``; softmax in fp32 regardless of input
dtype.  Forward and backward are both Pallas kernels: the backward is
the standard blockwise recompute from the saved logsumexp, as dq and
dk/dv kernels (``_bwd_dq_kernel`` / ``_bwd_dkv_kernel`` below) wired
through a custom VJP.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from autodist_tpu.kernel.pallas import default_interpret

NEG_INF = float(np.finfo(np.float32).min)

# The kernels stage K and V (and, in the dK/dV kernel, Q, g, lse and
# delta) as whole-sequence VMEM blocks, so the sequence length is bounded
# by the 16 MiB scoped-VMEM default.  Measured on a v5e (jax 0.9.0,
# libtpu 0.0.34, head_dim 64): forward and backward compile at 16384 rows
# of bf16; at 32768 the backward is refused (48 MB scoped allocation).
# A row costs in proportion to its bytes, so the bound is on rows x
# itemsize x 128-lane groups of head_dim.
MAX_SEQ_BYTES = 16384 * 2

# The block sizes a caller gets who names none: chosen, with the training
# election's bounds (``FUSED_HEAD_DIMS``, ``MIN_FUSED_LEN``,
# ``MAX_FUSED_LEN`` below), from ``tools/flash_crossover.py``'s readings
# on the chip.
DEFAULT_BLOCK = 128


def _resolve_blocks(block_q: Optional[int],
                    block_k: Optional[int]) -> tuple[int, int]:
    return (DEFAULT_BLOCK if block_q is None else block_q,
            DEFAULT_BLOCK if block_k is None else block_k)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float,
                causal: bool, block_k: int, seq_len: int, valid_len: int):
    """One (batch·head, q-block) program: online softmax over k blocks.

    ``seq_len`` is the (possibly padded) physical length; ``valid_len``
    the logical one — padded key columns are masked with the same finite
    ``NEG_INF`` the causal mask uses, so fully-masked rows stay NaN-free.
    """
    block_q = q_ref.shape[1]
    head_dim = q_ref.shape[2]
    iq = pl.program_id(1)
    # Matmul inputs stay in their native dtype (bf16 runs the MXU at
    # full rate; an fp32 upcast would halve it) with fp32 accumulation
    # via preferred_element_type; softmax statistics are fp32 throughout.
    q = q_ref[0]                              # [BQ, D]

    num_kb = pl.cdiv(seq_len, block_k)
    if causal:
        # Blocks strictly above the diagonal contribute nothing.
        num_kb = jnp.minimum(num_kb, pl.cdiv((iq + 1) * block_q, block_k))

    def body(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [BQ, BK] fp32
        cols = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            rows = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(rows >= cols, s, NEG_INF)
        if valid_len < seq_len:
            s = jnp.where(cols < valid_len, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, head_dim), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_kb, body, (m0, l0, acc0))

    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)  # [BQ, 1]


def _aligned_block(seq_len: int, block: int) -> int:
    """Clamp a requested block size to the sequence and round down to the
    TPU sublane tile (8); sequences shorter than a tile use one padded
    8-row block."""
    return max(8, (min(block, seq_len) // 8) * 8)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
               valid_len):
    """q/k/v: [BH, L_pad, D] (pre-padded so both blocks divide L_pad) →
    (out [BH, L_pad, D], lse [BH, L_pad, 1])."""
    bh, seq_len, head_dim = q.shape
    assert seq_len % block_q == 0 and seq_len % block_k == 0
    grid = (bh, seq_len // block_q)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_k=block_k,
        seq_len=seq_len, valid_len=valid_len)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim), lambda bh_, iq: (bh_, iq, 0)),
            pl.BlockSpec((1, seq_len, head_dim), lambda bh_, iq: (bh_, 0, 0)),
            pl.BlockSpec((1, seq_len, head_dim), lambda bh_, iq: (bh_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, head_dim), lambda bh_, iq: (bh_, iq, 0)),
            # lse kept 3D [BH, L, 1]: TPU block shapes must tile the last
            # two dims (divisible by 8/128 or full-size); a trailing
            # singleton satisfies that where a 2D (1, block_q) cannot.
            pl.BlockSpec((1, block_q, 1), lambda bh_, iq: (bh_, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_len, head_dim), q.dtype),
            jax.ShapeDtypeStruct((bh, seq_len, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def _flash_fwd_2d(q, k, v, scale, causal, block_q, block_k, interpret,
                  valid_len):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                          interpret, valid_len)
    return out, lse[..., 0]


def _bwd_dq_kernel(q_ref, k_ref, v_ref, lse_ref, delta_ref, g_ref, dq_ref,
                   *, scale: float, causal: bool, block_k: int,
                   seq_len: int, valid_len: int):
    """One (batch·head, q-block) program: dq via recompute over k blocks."""
    block_q = q_ref.shape[1]
    iq = pl.program_id(1)
    # Native-dtype matmul inputs (bf16 at full MXU rate), fp32
    # accumulation + fp32 softmax math — same policy as the forward.
    q = q_ref[0]                            # [BQ, D]
    g = g_ref[0]                            # [BQ, D]
    lse = lse_ref[0]                        # [BQ, 1]
    delta = delta_ref[0]                    # [BQ, 1]

    num_kb = pl.cdiv(seq_len, block_k)
    if causal:
        num_kb = jnp.minimum(num_kb, pl.cdiv((iq + 1) * block_q, block_k))

    def body(kb, dq):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        cols = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            rows = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(rows >= cols, s, NEG_INF)
        if valid_len < seq_len:
            s = jnp.where(cols < valid_len, s, NEG_INF)
        p = jnp.exp(s - lse)                                   # [BQ, BK]
        dp = jax.lax.dot_general(g, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq0 = jnp.zeros((block_q, q_ref.shape[2]), jnp.float32)
    dq_ref[0] = jax.lax.fori_loop(0, num_kb, body, dq0)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, lse_ref, delta_ref, g_ref,
                    dk_ref, dv_ref, *, scale: float, causal: bool,
                    block_q: int, seq_len: int, valid_len: int):
    """One (batch·head, k-block) program: dk/dv via recompute over q
    blocks.  Padded q rows contribute nothing (their g and delta are
    zero); padded k columns are masked like the forward."""
    block_k = k_ref.shape[1]
    head_dim = k_ref.shape[2]
    ik = pl.program_id(1)
    # Native-dtype matmul inputs, fp32 accumulation (see _fwd_kernel).
    k_blk = k_ref[0]                        # [BK, D]
    v_blk = v_ref[0]                        # [BK, D]

    num_qb = pl.cdiv(seq_len, block_q)
    qb0 = (ik * block_k) // block_q if causal else 0

    def body(qb, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qb * block_q, block_q), :]
        g = g_ref[0, pl.ds(qb * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(qb * block_q, block_q), :]     # [BQ, 1]
        delta = delta_ref[0, pl.ds(qb * block_q, block_q), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        cols = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            rows = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(rows >= cols, s, NEG_INF)
        if valid_len < seq_len:
            s = jnp.where(cols < valid_len, s, NEG_INF)
        p = jnp.exp(s - lse)                                   # [BQ, BK]
        dv = dv + jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [BK, D]
        dp = jax.lax.dot_general(g, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk = dk + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    zeros = jnp.zeros((block_k, head_dim), jnp.float32)
    dk, dv = jax.lax.fori_loop(qb0, num_qb, body, (zeros, zeros))
    dk_ref[0] = dk
    dv_ref[0] = dv


def _flash_bwd(q, k, v, out, lse, g, scale, causal, block_q, block_k,
               interpret, valid_len, g_lse=None):
    """Flash backward as two Pallas kernels (dq over q blocks; dk/dv over
    k blocks), recomputing probabilities from the saved logsumexp.

    All inputs [BH, L_pad, D] (lse [BH, L_pad]); returns (dq, dk, dv) in
    fp32.  The recompute re-applies the valid-length mask: padded k rows
    are zeros, which would otherwise contribute p = exp(-lse) ≠ 0.

    ``g_lse`` [BH, L_pad] is the cotangent of the logsumexp output when
    the caller consumes it (ring-merge).  d(lse)/d(s) is exactly the
    softmax ``p``, so it folds into the existing kernels as
    ``ds = p·(dp − (delta − g_lse))·scale`` — an adjustment of delta,
    not a new kernel.  (lse does not depend on v, and dv = pᵀg is
    correctly unaffected.)
    """
    bh, seq_len, head_dim = q.shape
    gf = g.astype(jnp.float32)
    delta = jnp.sum(gf * out.astype(jnp.float32), axis=-1,
                    keepdims=True)                          # [BH, L, 1]
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)[..., None]
    lse3 = lse[..., None]                                   # [BH, L, 1]

    full = lambda bh_, i: (bh_, 0, 0)
    qblk = lambda bh_, i: (bh_, i, 0)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, seq_len=seq_len,
                          valid_len=valid_len),
        grid=(bh, seq_len // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim), qblk),      # q
            pl.BlockSpec((1, seq_len, head_dim), full),      # k
            pl.BlockSpec((1, seq_len, head_dim), full),      # v
            pl.BlockSpec((1, block_q, 1), qblk),             # lse
            pl.BlockSpec((1, block_q, 1), qblk),             # delta
            pl.BlockSpec((1, block_q, head_dim), qblk),      # g
        ],
        out_specs=pl.BlockSpec((1, block_q, head_dim), qblk),
        out_shape=jax.ShapeDtypeStruct((bh, seq_len, head_dim),
                                       jnp.float32),
        interpret=interpret,
    )(q, k, v, lse3, delta, g)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, seq_len=seq_len,
                          valid_len=valid_len),
        grid=(bh, seq_len // block_k),
        in_specs=[
            pl.BlockSpec((1, seq_len, head_dim), full),      # q
            pl.BlockSpec((1, block_k, head_dim), qblk),      # k
            pl.BlockSpec((1, block_k, head_dim), qblk),      # v
            pl.BlockSpec((1, seq_len, 1), full),             # lse
            pl.BlockSpec((1, seq_len, 1), full),             # delta
            pl.BlockSpec((1, seq_len, head_dim), full),      # g
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, head_dim), qblk),
            pl.BlockSpec((1, block_k, head_dim), qblk),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_len, head_dim), jnp.float32),
            jax.ShapeDtypeStruct((bh, seq_len, head_dim), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, lse3, delta, g)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_bhld(q, k, v, scale, causal, block_q, block_k, interpret,
                valid_len):
    out, _ = _flash_fwd_2d(q, k, v, scale, causal, block_q, block_k,
                           interpret, valid_len)
    return out


def _flash_bhld_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                    valid_len):
    out, lse = _flash_fwd_2d(q, k, v, scale, causal, block_q, block_k,
                             interpret, valid_len)
    return out, (q, k, v, out, lse)


def _flash_bhld_bwd(scale, causal, block_q, block_k, interpret, valid_len,
                    res, g):
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, g, scale, causal, block_q,
                            block_k, interpret, valid_len)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_bhld.defvjp(_flash_bhld_fwd, _flash_bhld_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_bhld_lse(q, k, v, scale, causal, block_q, block_k, interpret,
                    valid_len):
    """Like :func:`_flash_bhld` but also returns the logsumexp — the
    chunk primitive for ring flash attention, whose merge consumes (and
    therefore differentiates through) lse."""
    return _flash_fwd_2d(q, k, v, scale, causal, block_q, block_k,
                         interpret, valid_len)


def _flash_bhld_lse_fwd(q, k, v, scale, causal, block_q, block_k,
                        interpret, valid_len):
    out, lse = _flash_fwd_2d(q, k, v, scale, causal, block_q, block_k,
                             interpret, valid_len)
    return (out, lse), (q, k, v, out, lse)


def _flash_bhld_lse_bwd(scale, causal, block_q, block_k, interpret,
                        valid_len, res, cotangents):
    q, k, v, out, lse = res
    g, g_lse = cotangents
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, g, scale, causal, block_q,
                            block_k, interpret, valid_len, g_lse=g_lse)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_bhld_lse.defvjp(_flash_bhld_lse_fwd, _flash_bhld_lse_bwd)


# --------------------------------------------------------------------------- #
# One-pass kernels: a head's whole [L, L] score tile lives in VMEM.
#
# Where the sequence is short enough for that (a float32 [512, 512] tile
# is 1 MB), the online softmax over key blocks and the backward's split
# into a dq kernel and a dk/dv kernel, each recomputing the scores, buy
# nothing: one forward kernel makes the scores, the softmax and the
# value product in one pass (2 products, 1 exponential a head), and one
# backward kernel makes dq, dk and dv from one recomputation (5
# products, 1 exponential).
#
# Operands stay as the model holds them, [B, L, heads * head_dim]: a
# grid step reads lane tiles of whole heads (two heads of 64 to a
# 128-lane tile), so no head is moved into the batch and nothing is
# transposed or copied in front of the call.  A head narrower than the
# tile is picked out by zeroing the other heads' lanes of ONE operand of
# each product; the MXU contracts over (or fills) 128 lanes either way,
# so the zeros cost it nothing.
# --------------------------------------------------------------------------- #

# The longest sequence the one-pass kernels take: some five float32
# [L, L] tiles live at once in the backward kernel (20 MB at 1024).
MAX_ONE_PASS_LEN = 1024
# Mosaic's scoped-VMEM default is 16 MiB; a whole-row step of the
# backward kernel holds eight double-buffered [L, heads * head_dim]
# blocks beside the tiles.  A v5e core has 128 MiB.
_ONE_PASS_VMEM_BYTES = 96 * 2 ** 20

# Rows x lane tiles a grid step takes by default (six tiles of 512).
_ONE_PASS_STEP_ROWS = 6 * 512

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _lane_tile(heads: int, head_dim: int) -> int:
    """Lanes of one tile: whole heads, a multiple of the 128-lane vreg
    where the row divides into such tiles, else the whole row."""
    tile = head_dim * 128 // math.gcd(head_dim, 128)
    return tile if (heads * head_dim) % tile == 0 else heads * head_dim


def one_pass_fits(seq_len: int) -> bool:
    """Whether the one-pass kernels take this length."""
    return seq_len % 8 == 0 and seq_len <= MAX_ONE_PASS_LEN


def _own_lanes(tile: int, head_dim: int, i: int):
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    return (lane >= i * head_dim) & (lane < (i + 1) * head_dim)


def _one_pass_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                         scale: float, head_dim: int, tile: int):
    """One (batch row, group of lane tiles) program.  ``lse_ref`` is the
    row's whole ``[L, heads]`` block, revisited by every group: a head's
    column is set by a lane select, whichever group it is in."""
    group = pl.program_id(1)
    tiles = q_ref.shape[2] // tile
    per_tile = tile // head_dim
    head_lane = jax.lax.broadcasted_iota(
        jnp.int32, (1, lse_ref.shape[2]), 1)

    @pl.when(group == 0)
    def _():
        lse_ref[0] = jnp.zeros(lse_ref.shape[1:], lse_ref.dtype)

    lse = lse_ref[0]
    for t in range(tiles):
        lanes = slice(t * tile, (t + 1) * tile)
        q, k, v = q_ref[0, :, lanes], k_ref[0, :, lanes], v_ref[0, :, lanes]
        acc = jnp.zeros(q.shape, jnp.float32)
        for i in range(per_tile):
            qi, vi = q, v
            if per_tile > 1:
                own = _own_lanes(tile, head_dim, i)
                qi = jnp.where(own, q, jnp.zeros_like(q))
                vi = jnp.where(own, v, jnp.zeros_like(v))
            s = jax.lax.dot_general(
                qi, k, _NT, preferred_element_type=jnp.float32) * scale
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            acc += jnp.dot(p.astype(v.dtype), vi,
                           preferred_element_type=jnp.float32) / l
            head = (group * tiles + t) * per_tile + i
            lse = jnp.where(head_lane == head, m + jnp.log(l), lse)
        o_ref[0, :, lanes] = acc.astype(o_ref.dtype)
    lse_ref[0] = lse


def _one_pass_bwd_kernel(q_ref, k_ref, v_ref, lse_ref, g_ref,
                         dq_ref, dk_ref, dv_ref, *, scale: float,
                         head_dim: int, tile: int, group=None):
    """dq, dk and dv of one (batch row, group of lane tiles) from one
    recomputation of the probabilities (saved logsumexp).

    The softmax's backward is taken as ``dot_product_attention``'s is:
    ``ds = p * (dp - sum_k(p * dp))`` with the recomputed float32 ``p``,
    so that a row of ``ds`` sums to zero whatever the forward pass
    rounded.  The usual shortcut, ``sum_k(p * dp) = sum_d(out * g)``
    from the saved output, holds only as far as ``out`` was not rounded:
    the difference lands on every key alike, which is the (otherwise
    exactly zero) gradient of the key bias, and Adam scales that to a
    full-size update (PERF.md section 6, PR 31: the cell's
    ``delta_norm_gap`` read 0.15-0.17 on ``qkv/bias`` with the
    shortcut)."""
    if group is None:
        group = pl.program_id(1)
    tiles = q_ref.shape[2] // tile
    per_tile = tile // head_dim
    head_lane = jax.lax.broadcasted_iota(
        jnp.int32, (1, lse_ref.shape[2]), 1)
    lse_all = lse_ref[0]                                      # [L, heads]
    for t in range(tiles):
        lanes = slice(t * tile, (t + 1) * tile)
        q, k, v = q_ref[0, :, lanes], k_ref[0, :, lanes], v_ref[0, :, lanes]
        g = g_ref[0, :, lanes]
        dq = jnp.zeros(q.shape, jnp.float32)
        dk = jnp.zeros(q.shape, jnp.float32)
        dv = jnp.zeros(q.shape, jnp.float32)
        for i in range(per_tile):
            qi, ki, gi = q, k, g
            if per_tile > 1:
                own = _own_lanes(tile, head_dim, i)
                qi = jnp.where(own, q, jnp.zeros_like(q))
                ki = jnp.where(own, k, jnp.zeros_like(k))
                gi = jnp.where(own, g, jnp.zeros_like(g))
            head = (group * tiles + t) * per_tile + i
            lse = jnp.sum(jnp.where(head_lane == head, lse_all, 0.0),
                          axis=-1, keepdims=True)              # [L, 1]
            s = jax.lax.dot_general(
                qi, k, _NT, preferred_element_type=jnp.float32) * scale
            p = jnp.exp(s - lse)
            dp = jax.lax.dot_general(
                gi, v, _NT, preferred_element_type=jnp.float32)
            pdp = p * dp
            ds = ((pdp - p * jnp.sum(pdp, axis=-1, keepdims=True))
                  * scale).astype(q.dtype)
            dv += jax.lax.dot_general(
                p.astype(g.dtype), gi, _TN,
                preferred_element_type=jnp.float32)
            dk += jax.lax.dot_general(
                ds, qi, _TN, preferred_element_type=jnp.float32)
            dq += jnp.dot(ds, ki, preferred_element_type=jnp.float32)
        dq_ref[0, :, lanes] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, lanes] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, lanes] = dv.astype(dv_ref.dtype)


def _one_pass_specs(b, l, hd, heads, tiles_per_step):
    """(grid, lane tile, ``block(section)`` of an operand that is
    section ``section`` of its array's last dimension, block of lse)."""
    tile = _lane_tile(heads, hd // heads)
    tiles = hd // tile
    if tiles_per_step is None or tiles % tiles_per_step:
        # the whole row up to 512 (2.84 ms a layer in the cell's shape
        # against 2.94 at one tile a step); at 1024 a whole row of six
        # tiles read 5.09 ms against 4.76 at two or three, and took 28 s
        # to compile (my chip run, PR 31)
        tiles_per_step = max(t for t in range(1, tiles + 1)
                             if tiles % t == 0
                             and (t == 1 or t * l <= _ONE_PASS_STEP_ROWS))
    groups = tiles // tiles_per_step

    def block(section=0):
        return pl.BlockSpec(
            (1, l, tile * tiles_per_step),
            lambda b_, g_: (b_, 0, section * groups + g_))

    return ((b, groups), tile, block,
            pl.BlockSpec((1, l, heads), lambda b_, g_: (b_, 0, 0)))


def _one_pass_params(interpret, grid_axes):
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",) + ("arbitrary",) * (grid_axes - 1),
        vmem_limit_bytes=_ONE_PASS_VMEM_BYTES)}


# jitted, so that a model's layers share one trace and one lowering of
# each kernel (12 traces of the whole-row kernels were 3.5 s of the
# training cell's set-up: my chip run, PR 31)
_ONE_PASS_STATIC = ("sections", "heads", "scale", "tiles_per_step",
                    "interpret")


@functools.partial(jax.jit, static_argnames=_ONE_PASS_STATIC)
def _one_pass_fwd(q, k, v, sections, heads, scale, tiles_per_step,
                  interpret):
    """q/k/v: sections ``sections`` of ``[B, L, n * heads * head_dim]``
    arrays (three arrays of one section each, or one array given three
    times) -> (out ``[B, L, heads * head_dim]``, lse ``[B, L, heads]``)."""
    b, l, hd = q.shape[0], q.shape[1], q.shape[2] // (max(sections) + 1)
    grid, tile, block, lse_block = _one_pass_specs(b, l, hd, heads,
                                                   tiles_per_step)
    return pl.pallas_call(
        functools.partial(_one_pass_fwd_kernel, scale=scale,
                          head_dim=hd // heads, tile=tile),
        grid=grid, in_specs=[block(s) for s in sections],
        out_specs=[block(), lse_block],
        out_shape=[jax.ShapeDtypeStruct((b, l, hd), q.dtype),
                   jax.ShapeDtypeStruct((b, l, heads), jnp.float32)],
        interpret=interpret, **_one_pass_params(interpret, 2),
    )(q, k, v)


def _one_pass_bwd_packed_kernel(q_ref, k_ref, v_ref, lse_ref, g_ref,
                                dqkv_ref, dk_ref, dv_ref, **kw):
    """The backward kernel writing dq, dk and dv as the three sections
    of ONE array, so that the projection's gradient needs no
    concatenate: a third grid axis writes a section a step; its first
    step computes all three, dk and dv wait in VMEM scratch."""
    group, section = pl.program_id(1), pl.program_id(2)

    @pl.when(section == 0)
    def _():
        _one_pass_bwd_kernel(q_ref, k_ref, v_ref, lse_ref, g_ref,
                             dqkv_ref, dk_ref, dv_ref, group=group, **kw)

    @pl.when(section == 1)
    def _():
        dqkv_ref[...] = dk_ref[...]

    @pl.when(section == 2)
    def _():
        dqkv_ref[...] = dv_ref[...]


@functools.partial(jax.jit, static_argnames=_ONE_PASS_STATIC)
def _one_pass_bwd(q, k, v, sections, lse, g, heads, scale,
                  tiles_per_step, interpret):
    """(dq, dk, dv) for three arrays; for one packed array, its
    gradient."""
    b, l, hd = g.shape
    grid, tile, block, lse_block = _one_pass_specs(b, l, hd, heads,
                                                   tiles_per_step)
    kw = dict(scale=scale, head_dim=hd // heads, tile=tile)
    in_specs = [*(block(s) for s in sections), lse_block, block()]
    if sections == (0, 0, 0):
        return pl.pallas_call(
            functools.partial(_one_pass_bwd_kernel, **kw),
            grid=grid, in_specs=in_specs, out_specs=[block()] * 3,
            out_shape=[jax.ShapeDtypeStruct(g.shape, q.dtype)] * 3,
            interpret=interpret, **_one_pass_params(interpret, 2),
        )(q, k, v, lse, g)
    from jax.experimental.pallas import tpu as pltpu

    def with_section(spec):     # the same block at every section step
        return pl.BlockSpec(spec.block_shape,
                            lambda b_, g_, s_: spec.index_map(b_, g_))

    groups, width = grid[1], block().block_shape[2]
    return pl.pallas_call(
        functools.partial(_one_pass_bwd_packed_kernel, **kw),
        grid=(*grid, 3), in_specs=[with_section(s) for s in in_specs],
        out_specs=pl.BlockSpec(
            (1, l, width), lambda b_, g_, s_: (b_, 0, s_ * groups + g_)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((1, l, width), q.dtype)] * 2,
        interpret=interpret, **_one_pass_params(interpret, 3),
    )(q, k, v, lse, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_one_pass(q, k, v, heads, scale, tiles_per_step, interpret):
    return _one_pass_fwd(q, k, v, (0, 0, 0), heads, scale, tiles_per_step,
                         interpret)[0]


def _flash_one_pass_fwd(q, k, v, heads, scale, tiles_per_step, interpret):
    out, lse = _one_pass_fwd(q, k, v, (0, 0, 0), heads, scale,
                             tiles_per_step, interpret)
    return out, (q, k, v, lse)


def _flash_one_pass_bwd(heads, scale, tiles_per_step, interpret, res, g):
    q, k, v, lse = res
    return tuple(_one_pass_bwd(q, k, v, (0, 0, 0), lse, g, heads, scale,
                               tiles_per_step, interpret))


_flash_one_pass.defvjp(_flash_one_pass_fwd, _flash_one_pass_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _flash_one_pass_packed(qkv, heads, scale, tiles_per_step, interpret):
    return _one_pass_fwd(qkv, qkv, qkv, (0, 1, 2), heads, scale,
                         tiles_per_step, interpret)[0]


def _flash_one_pass_packed_fwd(qkv, heads, scale, tiles_per_step,
                               interpret):
    out, lse = _one_pass_fwd(qkv, qkv, qkv, (0, 1, 2), heads, scale,
                             tiles_per_step, interpret)
    return out, (qkv, lse)


def _flash_one_pass_packed_bwd(heads, scale, tiles_per_step, interpret,
                               res, g):
    qkv, lse = res
    return (_one_pass_bwd(qkv, qkv, qkv, (0, 1, 2), lse, g, heads, scale,
                          tiles_per_step, interpret),)


_flash_one_pass_packed.defvjp(_flash_one_pass_packed_fwd,
                              _flash_one_pass_packed_bwd)


def _one_pass_args(length, head_dim, scale, interpret):
    if not one_pass_fits(length):
        raise ValueError(
            f"the one-pass kernels take lengths that are a multiple of 8 "
            f"up to {MAX_ONE_PASS_LEN}; got {length}")
    return (float(1.0 / math.sqrt(head_dim) if scale is None else scale),
            bool(default_interpret() if interpret is None else interpret))


def flash_attention_one_pass(q, k, v, *, scale: Optional[float] = None,
                             tiles_per_step: Optional[int] = None,
                             interpret: Optional[bool] = None):
    """Unmasked attention over ``[batch, length, heads, head_dim]`` by
    the one-pass kernels (``length`` a multiple of 8, at most
    :data:`MAX_ONE_PASS_LEN`).  ``tiles_per_step``: lane tiles a grid
    step takes (``None``: the whole row)."""
    b, l, h, d = q.shape
    scale, interpret = _one_pass_args(l, d, scale, interpret)
    out = _flash_one_pass(*(x.reshape(b, l, h * d) for x in (q, k, v)),
                          h, scale, tiles_per_step, interpret)
    return out.reshape(b, l, h, d)


def flash_attention_packed(qkv, heads: int, *,
                           scale: Optional[float] = None,
                           tiles_per_step: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """The same attention on the projection as a matmul leaves it:
    ``qkv`` ``[batch, length, 3 * heads * head_dim]`` (q, then k, then
    v; heads; head_dim) -> ``[batch, length, heads * head_dim]``.  The
    kernels read the three sections in place, so no ``[batch, length,
    heads, head_dim]`` array exists on either side of the call: the chip
    keeps one with ``length`` minor-most where ``head_dim`` is under
    128, and a whole-tensor copy then stands in front of every operand
    (PERF.md section 6, PR 25 and PR 31)."""
    b, l, width = qkv.shape
    scale, interpret = _one_pass_args(l, width // (3 * heads), scale,
                                      interpret)
    return _flash_one_pass_packed(qkv, heads, scale, tiles_per_step,
                                  interpret)


def _layout_bhld(q, k, v, scale, block_q, block_k, interpret):
    """Shared wrapper plumbing: pick blocks (8-aligned), zero-pad the
    sequence to a common block multiple (masked inside the kernel), and
    fold heads into batch — so any length lowers on TPU without
    materializing [L, L] scores.  Returns the kernel inputs plus the
    facts needed to undo the layout."""
    if interpret is None:
        interpret = default_interpret()
    b, l, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    bq = _aligned_block(l, block_q)
    bk = _aligned_block(l, block_k)
    lcm = bq * bk // math.gcd(bq, bk)
    l_pad = ((l + lcm - 1) // lcm) * lcm
    row_bytes = jnp.dtype(q.dtype).itemsize * -(-d // 128)
    if l_pad * row_bytes > MAX_SEQ_BYTES:
        raise ValueError(
            f"flash attention holds whole-sequence K/V blocks in VMEM: "
            f"seq_len {l} ({jnp.dtype(q.dtype).name}, head_dim {d}) is "
            f"past the longest that compiles, "
            f"{MAX_SEQ_BYTES // row_bytes}; shard the sequence "
            "(parallel/ring_attention.py) or use the einsum attention")

    def to_bhld(x):
        x = jnp.moveaxis(x, 2, 1).reshape(b * h, x.shape[1], d)
        if l_pad != l:
            x = jnp.pad(x, ((0, 0), (0, l_pad - l), (0, 0)))
        return x

    args = (to_bhld(q), to_bhld(k), to_bhld(v), float(scale))
    return args, (bq, bk, bool(interpret)), (b, l, h, d)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Fused attention over ``[batch, length, heads, head_dim]`` inputs.

    Unmasked, with no block sizes given and a length the one-pass
    kernels take (:func:`flash_attention_one_pass`), those run;
    otherwise the blockwise kernels.
    ``block_q``/``block_k`` default to :data:`DEFAULT_BLOCK`.
    ``interpret=None`` follows :func:`~autodist_tpu.kernel.pallas
    .default_interpret` (the Pallas interpreter off-TPU).  Sequences
    past :data:`MAX_SEQ_BYTES` raise ``ValueError``.
    """
    if (not causal and block_q is None and block_k is None
            and one_pass_fits(q.shape[1])):
        return flash_attention_one_pass(q, k, v, scale=scale,
                                        interpret=interpret)
    block_q, block_k = _resolve_blocks(block_q, block_k)
    (qb, kb, vb, s), (bq, bk, interp), (b, l, h, d) = _layout_bhld(
        q, k, v, scale, block_q, block_k, interpret)
    out = _flash_bhld(qb, kb, vb, s, bool(causal), bq, bk, interp, int(l))
    out = out[:, :l]
    return jnp.moveaxis(out.reshape(b, h, l, d), 1, 2)


def flash_attention_with_lse(q, k, v, *, causal: bool = False,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: Optional[bool] = None):
    """Fused attention returning ``(out, lse)`` over ``[batch, length,
    heads, head_dim]`` inputs; ``lse`` is ``[batch, length, heads]``.

    The chunk primitive for ring flash attention
    (``parallel/ring_attention.py``): per-kv-chunk results merge exactly
    via ``lse_m = logaddexp(lse_a, lse_b); out_m = out_a·e^{lse_a−lse_m}
    + out_b·e^{lse_b−lse_m}`` — and the merge's lse cotangent is handled
    by the kernel's VJP.
    """
    block_q, block_k = _resolve_blocks(block_q, block_k)
    (qb, kb, vb, s), (bq, bk, interp), (b, l, h, d) = _layout_bhld(
        q, k, v, scale, block_q, block_k, interpret)
    out, lse = _flash_bhld_lse(qb, kb, vb, s, bool(causal), bq, bk,
                               interp, int(l))
    out, lse = out[:, :l], lse[:, :l]
    out = jnp.moveaxis(out.reshape(b, h, l, d), 1, 2)
    lse = jnp.moveaxis(lse.reshape(b, h, l), 1, 2)       # [B, L, H]
    return out, lse


# --------------------------------------------------------------------------- #
# The election (``models.transformer.attend``): training attention takes
# the kernels where, and only where, the call can observe that they fit.
# --------------------------------------------------------------------------- #
# Head widths and lengths (multiples of 128) inside which the one-pass
# kernels were measured to beat ``dot_product_attention``, forward and
# backward, on one v5e (``tools/flash_crossover.py --cell`` at 32768
# tokens a call, bf16; PERF.md section 6, PR 31): heads of 64 at 128 /
# 256 / 512 / 1024, composed 4.79 / 7.23 / 11.96 / 20.48 ms a layer
# against 3.90 / 4.12 / 4.93 / 6.89 on ``[B, L, heads, head_dim]``
# views and 1.79 / 2.01 / 2.84 / 4.76 on the packed projection; heads
# of 128 at 256 / 512 / 1024, 7.22 / 10.15 / 15.99 against 6.41 / 6.28 /
# 7.54 and 2.53 / 2.40 / 3.67.  Nothing outside was measured.
FUSED_HEAD_DIMS = (64, 128)
MIN_FUSED_LEN = 128
MAX_FUSED_LEN = 1024


def _backend_is_tpu() -> bool:
    """The platform a program traced now is compiled for."""
    device = jax.config.jax_default_device    # None, a Device or a name
    return (getattr(device, "platform", device)
            or jax.default_backend()) == "tpu"


def _per_device_operands() -> bool:
    """Whether what is traced now sees whole per-device arrays: inside a
    ``shard_map`` over every axis of its mesh, or in a process with one
    device.  Under ``jit`` alone on several devices the operands may be
    GSPMD-sharded, and XLA cannot partition a bare ``pallas_call``."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty:
        return mesh.are_all_axes_manual
    return jax.device_count() == 1


def fused_attention_fits(q, k, v) -> bool:
    """Whether unmasked, dropout-free attention over these ``[batch,
    length, heads, head_dim]`` operands takes the fused kernels.  The
    kernel slot's word (``parallel.tensor.kernel_scope``) overrides what
    was measured — ``False`` forbids them, ``True`` takes them on any
    backend, width and length — never what they cannot do: operands of
    one type, bf16 or float32, whole on the device."""
    from autodist_tpu.parallel.tensor import kernel_word

    word = kernel_word("flash_attention")
    if word is False or not (
            q.shape == k.shape == v.shape and q.ndim == 4
            and q.dtype == k.dtype == v.dtype
            and q.dtype in (jnp.bfloat16, jnp.float32)
            and _per_device_operands()):
        return False
    if word:
        return True
    length, head_dim = q.shape[1], q.shape[3]
    return (_backend_is_tpu() and head_dim in FUSED_HEAD_DIMS
            and MIN_FUSED_LEN <= length <= MAX_FUSED_LEN
            and length % 128 == 0)


def make_attention_fn(causal: bool, *, block_q: Optional[int] = None,
                      block_k: Optional[int] = None):
    """Adapter for ``TransformerConfig.attention_fn``: ``(q, k, v, mask,
    dropout_rng) -> out``.  Block sizes default to :data:`DEFAULT_BLOCK`.

    The flash kernel supports exactly two masking structures: none, and
    the static causal triangle.  With ``causal=True`` the mask the model
    passes is taken to *be* the causal mask (set the config's ``causal``
    flag to match); with ``causal=False`` any non-None mask (i.e. a
    padding mask, as in the BERT stack) is rejected rather than silently
    ignored.  Attention dropout is likewise rejected — use the default
    einsum attention for those cases.
    """

    def attention_fn(q, k, v, mask, dropout_rng):
        if dropout_rng is not None:
            raise ValueError(
                "flash attention does not support attention dropout; set "
                "attention_dropout_rate=0 or use the default attention")
        if mask is not None and not causal:
            raise ValueError(
                "flash attention supports only causal or no masking; got a "
                "mask with causal=False (padding masks need the default "
                "attention)")
        return flash_attention(q, k, v, causal=causal,
                               block_q=block_q, block_k=block_k)

    # Recognition tag: the serving engine accepts exactly this family
    # of attention_fns (numerics-equivalent to the trained einsum path,
    # decode served by the flash-decode cache kernel).
    attention_fn._adt_flash = True
    return attention_fn


def is_flash_attention_fn(fn) -> bool:
    """True when ``fn`` is this module's flash attention (the
    :func:`make_attention_fn` adapter or the kernel itself) — the
    family ``ServingEngine`` accepts as ``cfg.attention_fn``.  Only
    the tagged adapter and the kernel qualify: other helpers from this
    module (``make_attention_fn`` itself uncalled,
    ``flash_attention_with_lse``'s two-output form) must still get the
    engine's coded rejection rather than a trace-time shape error."""
    return bool(getattr(fn, "_adt_flash", False)) \
        or fn is flash_attention
