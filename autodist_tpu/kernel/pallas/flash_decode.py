"""Flash-decode attention: one query per slot, block-streamed KV cache.

The decode analog of ``ops/flash_attention.py``: a single-token step's
attention over a layer's cache slice (``serving/kv_cache.py
cached_attention``) computes a ``[B, heads, 1, T]`` score row, a full-T
softmax, and a second full-T contraction — three HBM-shaped passes over
the cache per layer per token.  This kernel streams the cache in
``block_k``-sized tiles with the online-softmax recurrence (running
max / sum / accumulator in VMEM), so the cache is read once and the
scores never exist outside a ``[1, block_k]`` tile.

Masking matches ``cached_attention`` exactly: key positions ``<=
lengths[slot]`` are visible (the just-written token attends to itself
and everything before it), everything past a slot's occupancy —
including the zero tail and any previous occupant's stale rows — is
unreachable.  Slot lengths shorter than one block and cache lengths
that don't divide ``block_k`` are handled by the same mask (the wrapper
zero-pads T up to a block multiple; padded positions sit above every
legal length).

Softmax statistics in fp32 regardless of cache dtype, the trained
model's scaling — the greedy-parity goldens pin token-for-token
agreement with the full-recompute ``sequential_logits`` reference.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.kernel.pallas import default_interpret, kernel_marker

NEG_INF = float(np.finfo(np.float32).min)

# Default cache-tile length.  Small caches stream in one tile; the
# tuning table measured by ``tools/flash_crossover.py --decode`` can
# override per call.
DEFAULT_BLOCK_K = 128


def online_softmax_step(first_pos, j, q_ref, k_ref, v_ref, o_ref, m_ref,
                        s_ref, acc_ref, *, block_len: int, scale: float,
                        out_dtype):
    """One (slot, head, kv-block) grid step of cached attention for a
    window of ``C`` query rows — shared by the dense decode, the paged
    decode (``C == 1``) and the paged prefill kernel (``C`` = the
    chunk); they differ only in how the BlockSpec index maps pick block
    ``j``'s ``[block_len, d]`` tile.

    Window row ``r`` sits at absolute position ``first_pos + r`` and
    sees keys at positions ``<= first_pos + r`` (a decode step's one
    query is the token just written at ``first_pos == length``).
    Everything later — the zero tail, a previous occupant's stale rows,
    an unassigned table entry's aliased block — is hidden by that mask.
    Position 0 is visible to every row, so the running max is finite
    from block 0 on and fully-masked later blocks contribute
    ``exp(NEG_INF - finite) == 0``.

    The kv-block walk lives in the GRID's innermost dimension, so
    Pallas's own pipeline double-buffers the per-block DMA and the VMEM
    working set is one block per operand — independent of the cache
    length or pool size.  The online-softmax carry (running max / sum /
    accumulator, one row per query) persists across the ``j`` steps in
    VMEM scratch: initialized at ``j == 0``, emitted at the last
    block."""
    rows, d = q_ref.shape[-2:]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].reshape(rows, d).astype(jnp.float32)
    kblk = k_ref[...].reshape(block_len, d).astype(jnp.float32)
    scores = jax.lax.dot_general(
        q, kblk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # [C, bl]
    idx = j * block_len + jax.lax.broadcasted_iota(
        jnp.int32, (rows, block_len), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, block_len), 0)
    scores = jnp.where(idx <= first_pos + row, scores, NEG_INF)
    m, s, acc = m_ref[...], s_ref[...], acc_ref[...]
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)                             # [C, 1]
    p = jnp.exp(scores - m_new)                            # [C, bl]
    vblk = v_ref[...].reshape(block_len, d).astype(jnp.float32)
    m_ref[...] = m_new
    s_ref[...] = s * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc * alpha + jax.lax.dot_general(
        p, vblk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # [C, d]

    @pl.when(j == pl.num_programs(2) - 1)
    def _emit():
        # Position 0 is visible to every row, so s > 0 rowwise.
        o_ref[...] = (acc_ref[...] / s_ref[...]) \
            .reshape(o_ref.shape).astype(out_dtype)


def carry_scratch(rows: int, d: int):
    return [pltpu.VMEM((rows, 1), jnp.float32),   # running max per row
            pltpu.VMEM((rows, 1), jnp.float32),   # running sum per row
            pltpu.VMEM((rows, d), jnp.float32)]   # accumulator per row


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, s_ref,
                   acc_ref, **kw):
    """Dense cache: block ``j`` is rows ``[j*bk, (j+1)*bk)`` of the
    slot's lane.  ``len_ref``: scalar-prefetched ``[B]`` int32."""
    online_softmax_step(len_ref[pl.program_id(0)], pl.program_id(2),
                        q_ref, k_ref, v_ref, o_ref, m_ref, s_ref,
                        acc_ref, **kw)


def flash_decode_attention(q, k_layer, v_layer, lengths, *,
                           dtype=jnp.float32,
                           block_k: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """Drop-in fused replacement for :func:`autodist_tpu.serving.
    kv_cache.cached_attention`.

    ``q``: ``[B, 1, heads, head_dim]`` (the step's query);
    ``k_layer``/``v_layer``: ``[B, heads, T, head_dim]`` (one layer's
    cache slice in its native layout); ``lengths``: ``[B]`` int32.
    Returns ``[B, 1, heads, head_dim]`` in ``dtype``.

    ``interpret=None`` follows :func:`default_interpret`; ``block_k``
    defaults to :data:`DEFAULT_BLOCK_K` capped at the cache length.  A
    cache length that ``block_k`` does not divide is zero-padded per
    call (a copy of the layer's cache) — size ``max_len`` to a block
    multiple where that matters.
    """
    B, _, H, d = q.shape
    T = k_layer.shape[2]
    interp = default_interpret() if interpret is None else bool(interpret)
    bk = min(int(block_k or DEFAULT_BLOCK_K), T)
    pad = (-T) % bk
    if pad:
        # Padded positions sit at idx >= T > any legal length, so the
        # in-kernel mask never reads them as real keys.
        cfg = [(0, 0), (0, 0), (0, pad), (0, 0)]
        k_layer = jnp.pad(k_layer, cfg)
        v_layer = jnp.pad(v_layer, cfg)
    scale = 1.0 / float(np.sqrt(d))

    q2 = jnp.swapaxes(q, 1, 2)                 # [B, H, 1, d]
    kern = functools.partial(_decode_kernel, block_len=bk, scale=scale,
                             out_dtype=dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                 # lengths (SMEM)
        grid=(B, H, (T + pad) // bk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), lambda b, h, j, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, j, lens: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, j, lens: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d),
                               lambda b, h, j, lens: (b, h, 0, 0)),
        scratch_shapes=carry_scratch(1, d),
    )
    with jax.named_scope(kernel_marker("flash_decode")):
        out = pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, 1, d), dtype),
            interpret=interp,
        )(lengths.astype(jnp.int32), q2, k_layer, v_layer)
    return jnp.swapaxes(out, 1, 2)             # [B, 1, H, d]


# --------------------------------------------------------------------------- #
# Paged variant: the block loop IS the page loop
# --------------------------------------------------------------------------- #
def _paged_decode_kernel(len_ref, tab_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, s_ref, acc_ref, **kw):
    """Paged cache: block ``j`` is pool block ``tab[b, j]`` — the k/v
    BlockSpecs' index maps read the scalar-prefetched block table, so
    the kernel body never sees the table (``tab_ref`` is unused here)."""
    del tab_ref
    online_softmax_step(len_ref[pl.program_id(0)], pl.program_id(2),
                        q_ref, k_ref, v_ref, o_ref, m_ref, s_ref,
                        acc_ref, **kw)


def flash_decode_attention_paged(q, k_pool, v_pool, lengths, block_table,
                                 *, block_len: int, dtype=jnp.float32,
                                 interpret: Optional[bool] = None):
    """Drop-in fused replacement for :func:`autodist_tpu.serving.
    kv_cache.paged_cached_attention` — the paged-cache flash decode.

    ``q``: ``[B, 1, heads, head_dim]``; ``k_pool``/``v_pool``: one
    layer's ``[num_blocks, heads, block_len, head_dim]`` pool slice;
    ``lengths``: ``[B]`` int32; ``block_table``: ``[B, max_blocks]``
    int32.  Returns ``[B, 1, heads, head_dim]`` in ``dtype``.

    Unlike the composed path there is NO gather/materialization of a
    contiguous ``[B, heads, max_blocks·block_len, head_dim]`` lane, and
    the pool itself never stages into VMEM whole: the block table rides
    ``PrefetchScalarGridSpec`` so each (slot, head, logical-block) grid
    step's BlockSpec index map routes ONE ``[block_len, d]`` pool block
    into VMEM (double-buffered by the Pallas pipeline — the per-block
    DMA the paged layout promises), the scores never exist outside a
    ``[1, block_len]`` tile, and the VMEM working set is independent of
    ``num_blocks``.
    """
    B, _, H, d = q.shape
    mb = block_table.shape[1]
    interp = default_interpret() if interpret is None else bool(interpret)
    scale = 1.0 / float(np.sqrt(d))

    q2 = jnp.swapaxes(q, 1, 2)                 # [B, H, 1, d]
    tab = block_table.astype(jnp.int32)

    kern = functools.partial(_paged_decode_kernel, block_len=block_len,
                             scale=scale, out_dtype=dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # lengths, tab (SMEM)
        grid=(B, H, mb),
        in_specs=[
            pl.BlockSpec((1, 1, 1, d),
                         lambda b, h, j, lens, t: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_len, d),
                         lambda b, h, j, lens, t: (t[b, j], h, 0, 0)),
            pl.BlockSpec((1, 1, block_len, d),
                         lambda b, h, j, lens, t: (t[b, j], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d),
                               lambda b, h, j, lens, t: (b, h, 0, 0)),
        scratch_shapes=carry_scratch(1, d),
    )
    with jax.named_scope(kernel_marker("flash_decode")):
        out = pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, 1, d), dtype),
            interpret=interp,
        )(lengths.astype(jnp.int32), tab, q2, k_pool, v_pool)
    return jnp.swapaxes(out, 1, 2)             # [B, 1, H, d]
