"""Paged flash prefill: a prompt chunk's causal attention over the
block table — the kernel-tier item's prefill half.

Chunked prefill (``serving/engine.py``) writes a prompt ``C`` tokens at
a time through the block table and needs every chunk row to attend over
ALL cache so far: earlier chunks, prefix-cache hit blocks, and the
chunk's own rows (written first — the decode step's write-then-attend
ordering).  The composed fallback
(``serving/kv_cache.paged_chunk_attention``) gathers the slot's blocks
into a contiguous ``[B, heads, max_blocks·block_len, head_dim]`` lane
and materializes a ``[B, heads, C, T]`` score tensor — three HBM-shaped
passes over the cache per layer per chunk.  This kernel walks the pool
block-by-block exactly like the paged flash decode: the grid's
innermost dimension is the logical block index, the scalar-prefetched
block table routes one ``[block_len, d]`` pool tile into VMEM per step,
and the online-softmax carry — now ``[C, 1]`` running max/sum and a
``[C, d]`` accumulator, one row per chunk query — persists across the
block walk in VMEM scratch.

Masking is the causal chunk rule: chunk row ``r`` of slot ``b`` sits at
absolute position ``starts[b] + r`` and sees key positions
``<= starts[b] + r``.  Position 0 is visible to every row, so the
running max is finite from block 0 on and fully-masked later blocks
contribute ``exp(NEG_INF - finite) == 0`` — the same guarantee the
decode kernel leans on.  Per-slot ``starts`` (not one scalar) let the
speculative verify pass reuse the kernel, where each slot's window
begins at its own length.

Interpreter mode off-TPU (``default_interpret``); the parity golden
pins this kernel against the composed gather path token-for-token.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.kernel.pallas import default_interpret, kernel_marker
from autodist_tpu.kernel.pallas.flash_decode import (carry_scratch,
                                                     online_softmax_step,
                                                     softmax_scale)


def _paged_prefill_kernel(start_ref, tab_ref, q_ref, k_ref, v_ref,
                          o_ref, m_ref, s_ref, acc_ref, **kw):
    """One (slot, head, logical-block) program: ``C`` chunk queries
    against one pool block — the decode kernels' grid step
    (:func:`~autodist_tpu.kernel.pallas.flash_decode
    .online_softmax_step`) with a ``C``-row window starting at the
    slot's ``starts`` entry.  The block table is read by the k/v index
    maps, not here."""
    del tab_ref
    online_softmax_step(start_ref[pl.program_id(0)], pl.program_id(2),
                        q_ref, k_ref, v_ref, o_ref, m_ref, s_ref, acc_ref,
                        **kw)


def flash_prefill_attention_paged(q, k_pool, v_pool, starts, block_table,
                                  *, block_len: int, dtype=jnp.float32,
                                  interpret: Optional[bool] = None,
                                  scale: Optional[float] = None):
    """Drop-in fused replacement for :func:`autodist_tpu.serving.
    kv_cache.paged_chunk_attention` — the paged-cache flash prefill.

    ``q``: ``[B, C, heads, head_dim]`` (one chunk's queries);
    ``k_pool``/``v_pool``: one layer's ``[num_blocks, heads, block_len,
    head_dim]`` pool slice; ``starts``: ``[B]`` int32 absolute position
    of each slot's chunk row 0; ``block_table``: ``[B, max_blocks]``
    int32.  Returns ``[B, C, heads, head_dim]`` in ``dtype``.

    No gather, no ``[B, heads, C, T]`` score tensor: the VMEM working
    set is one ``[block_len, d]`` tile per operand plus the ``[C, d]``
    carry, independent of pool size.
    """
    B, C, H, d = q.shape
    mb = block_table.shape[1]
    interp = default_interpret() if interpret is None else bool(interpret)
    scale = softmax_scale(d, scale)

    q2 = jnp.swapaxes(q, 1, 2)                 # [B, H, C, d]
    tab = block_table.astype(jnp.int32)

    kern = functools.partial(_paged_prefill_kernel, block_len=block_len,
                             scale=scale, out_dtype=dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # starts, tab (SMEM)
        grid=(B, H, mb),
        in_specs=[
            pl.BlockSpec((1, 1, C, d),
                         lambda b, h, j, st, t: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_len, d),
                         lambda b, h, j, st, t: (t[b, j], h, 0, 0)),
            pl.BlockSpec((1, 1, block_len, d),
                         lambda b, h, j, st, t: (t[b, j], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, C, d),
                               lambda b, h, j, st, t: (b, h, 0, 0)),
        scratch_shapes=carry_scratch(C, d),
    )
    with jax.named_scope(kernel_marker("flash_prefill")):
        out = pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, C, d), dtype),
            interpret=interp,
        )(starts.astype(jnp.int32), tab, q2, k_pool, v_pool)
    return jnp.swapaxes(out, 1, 2)             # [B, C, H, d]
