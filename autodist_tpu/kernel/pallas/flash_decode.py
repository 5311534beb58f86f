"""Flash-decode attention: one query per slot, block-streamed KV cache.

The decode analog of ``ops/flash_attention.py``: a single-token step's
attention over a layer's cache slice (``serving/kv_cache.py
cached_attention``) computes a ``[B, heads, 1, T]`` score row over all
``T`` positions of every slot, a full-T softmax, and a second full-T
contraction.  The kernels here stream the cache in blocks with the
online-softmax recurrence (running max / sum / accumulator in VMEM), so
the cache is read once and the scores never exist outside one block.

The **dense** kernel (:func:`flash_decode_attention_dense`) is what
``ServingEngine`` runs by default on a TPU.  It takes the whole
``[L, B, H, T, d]`` cache and a layer index (no slice, so no copy),
reads only each slot's live blocks (a block above ``lengths[slot]`` is
neither fetched nor computed), handles all the heads of a slot that
fit VMEM in one grid step, and multiplies the cache's own bf16 tiles.
Fewer key/value heads than query heads: the group of query heads that
reads a key/value head rides in the row dimension of the products (as
in ``cached_attention``), so a block is fetched once for all of them.
It reads the cache as the TPU lays it out, so the kernel's view of the
array is the array (:func:`fused_decode_block`): the lanes transposed,
``[.., d, T]`` tiles, for heads narrower than the chip's 128 lanes, and
row-major ``[.., T, d]`` tiles for heads of 128 and wider.  The
**latent** kernel (:func:`flash_decode_attention_latent`) is its sibling
for a cache of latent-attention rows, ``[L, B, 1, T, row]``: one array
that is every query head's keys and, a row's leading columns, their
values, so a slot's live blocks are read once for both products.  The
**paged** kernel walks a slot's block table one ``[block_len, d]`` pool
block per grid step (:func:`online_softmax_step`, shared with the paged
prefill kernel).

Masking matches ``cached_attention`` exactly: key positions ``<=
lengths[slot]`` are visible (the just-written token attends to itself
and everything before it), everything past a slot's occupancy —
including the zero tail and any previous occupant's stale rows — is
unreachable.

Softmax statistics in fp32 regardless of cache dtype, the trained
model's scaling — the greedy-parity goldens pin token-for-token
agreement with the full-recompute ``sequential_logits`` reference.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.kernel.pallas import default_interpret, kernel_marker

NEG_INF = float(np.finfo(np.float32).min)

# The dense kernel's cache-block length, the shortest lane that
# ``ServingEngine``'s default election gives it (from there on it won at
# every fill), and its VMEM sizing: read on a v5e with
# ``tools/flash_crossover.py --decode`` at 8 slots x 20 heads x 64, bf16
# (PERF.md section 6, PR 25).
DEFAULT_BLOCK_K = 128
MIN_FUSED_DECODE_LEN = 256
KV_BLOCK_BYTES = 2 << 20       # one K (or V) block, lane-padded
VMEM_LIMIT_BYTES = 32 << 20    # K and V blocks double-buffered + carry
# Heads of 256 and wider walk blocks of 256: one block of each array is
# on its way at a time, ~0.3 us before its bytes flow, and the few
# key/value heads such a model has make a block of 128 too small to hide
# it.  Read on a v5e with ``chip_smoke.py:mixed_block_phase`` at 32 slots
# x 2 key/value heads x 256 under 8 query heads each, bf16: 107 / 89 / 87
# us a layer call at blocks of 128 / 256 / 512 with the lanes a fifth
# full, 400 / 281 / 245 us full (PERF.md section 6, PR 44).
WIDE_HEAD_DIM = 256
WIDE_BLOCK_K = 256
# The latent kernel's cache-block length and how many blocks it keeps in
# VMEM (all but one of them on their way while one is computed): read on
# a v5e with ``tools/flash_crossover.py --decode --latent`` at 64 slots x
# 3,072 x 576, bf16 (PERF.md section 6, PR 36).
LATENT_BLOCK_K = 256
LATENT_BUFFERS = 4


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


# --------------------------------------------------------------------------- #
# Dense cache: the whole ``[L, B, H, T, d]`` array, live blocks only
# --------------------------------------------------------------------------- #
def decode_block_len(max_len: int, block_k: Optional[int] = None):
    """The block length the dense kernel walks a ``max_len`` lane in,
    or ``None`` when no block does without a padded copy of the lane:
    ``block_k`` (default :data:`DEFAULT_BLOCK_K`) where it divides
    ``max_len``, the whole lane where the lane is shorter than one
    block."""
    bk = int(block_k or DEFAULT_BLOCK_K)
    if max_len <= bk:
        return int(max_len)
    return bk if max_len % bk == 0 else None


def fused_decode_block(max_len: int, head_dim: int):
    """The block length with which the dense kernel reads a
    ``[L, B, H, max_len, head_dim]`` cache in place, or ``None`` where
    it cannot: the lane has to divide into blocks, and the kernel's view
    has to be the array as the TPU keeps it.  Heads under the chip's 128
    lanes it keeps with the positions minor-most when ``max_len`` is a
    multiple of 128, and the kernel reads ``[d, block]`` tiles; heads of
    a multiple of 128 it keeps row-major, and the kernel reads
    ``[block, d]`` tiles (:func:`rows_layout`).  Any other shape would
    be a copy of all of it.  The block is :data:`DEFAULT_BLOCK_K`, or
    for heads of :data:`WIDE_HEAD_DIM` and wider :data:`WIDE_BLOCK_K`
    where that divides the lane."""
    bk = None
    if head_dim >= WIDE_HEAD_DIM:
        bk = decode_block_len(max_len, WIDE_BLOCK_K)
    bk = bk or decode_block_len(max_len)
    if bk is None:
        return None
    if rows_layout(head_dim):
        return bk if head_dim % 128 == 0 else None
    return bk if max_len % 128 == 0 else None


def rows_layout(head_dim: int) -> bool:
    """Whether the dense kernel walks ``[block, d]`` tiles of the cache
    as stored (heads of 128 and wider) or ``[d, block]`` tiles of its
    transposed view (narrower heads)."""
    return head_dim >= 128


def dense_decode_elected(word, max_len: int, head_dim: int,
                         backend: Optional[str] = None):
    """The engine's election for a dense cache of keys and values: the
    block the kernel reads it with, the step's rows written on the way,
    or ``None`` for ``write_token`` and ``cached_attention``.  ``word``
    is the kernel slot's on ``flash_decode``: ``False`` forbids the
    kernel; ``None`` leaves it to what can be observed — a TPU under the
    programs, a cache the kernel reads in place
    (:func:`fused_decode_block`: grouped query heads ride in its rows), a
    lane of at least :data:`MIN_FUSED_DECODE_LEN` by the chip's own
    readings; ``True`` takes it wherever it can run (the interpreter off
    the TPU), with any block that divides the lane where the kernel's
    view of the cache is a copy of it — what forcing the latent kernel
    means too (:func:`latent_decode_elected`)."""
    if word is False:
        return None
    block = fused_decode_block(max_len, head_dim)
    if word:
        return block or decode_block_len(max_len) \
            or math.gcd(max_len, DEFAULT_BLOCK_K)
    if block and max_len >= MIN_FUSED_DECODE_LEN \
            and (backend or jax.default_backend()) == "tpu":
        return block
    return None


def softmax_scale(d: int, scale=None) -> float:
    """What a kernel multiplies its float32 scores by: ``scale`` (the
    block's ``softmax_scale``) where given, else ``d ** -0.5``."""
    return 1.0 / float(np.sqrt(d)) if scale is None else float(scale)


def _heads_per_step(heads: int, block_len: int, d: int, itemsize: int):
    """Most heads of a slot (a divisor of ``heads``) whose K block fits
    :data:`KV_BLOCK_BYTES` of VMEM (the minor dimension pads to the 128
    lanes there)."""
    per_head = d * max(block_len, 128) * itemsize
    cap = max(1, KV_BLOCK_BYTES // per_head)
    return max(h for h in range(1, heads + 1)
               if heads % h == 0 and h <= cap)


def _dense_decode_kernel(len_ref, wpos_ref, layer_ref, q_ref, *refs,
                         block_len: int, num_blocks: int, scale: float,
                         write: bool, rows: bool):
    """One (slot, block of key/value heads) grid step: walk the slot's
    live blocks of the lane with the kernel's own double-buffered DMA —
    once for the ``G`` query heads that read each key/value head, the
    rows of ``q_ref`` (``[hb, G, d]``; one a head without grouping) — of the
    TRANSPOSED lane, ``[hb, d, bk]`` tiles with the positions on the
    lanes (the layout a TPU keeps a cache of narrow heads in), or with
    ``rows`` of the lane as stored, ``[hb, bk, d]`` tiles (heads of 128
    and wider).  Block ``j`` holds positions
    ``[j*bk, (j+1)*bk)``; the last live block is ``lengths[slot] // bk``
    (position ``lengths`` is this step's token), so the trip count is
    the slot's own and a dead block costs nothing.  The block after the
    last is the next grid step's first: it is on its way while this
    step's last is computed, and ``par_ref`` tells the next step which
    buffer it went to.

    With ``write`` the step's new key and value rows are put into the
    block that holds position ``wpos[slot]`` while it is in VMEM, before
    the products, and the positions around it (128 columns; with
    ``rows`` one sublane tile of rows) go back to the cache (the aliased
    outputs): the cache write of the step, without a pass of its own.
    ``wpos < 0`` writes nothing.

    Products take the cache's own tiles (bf16 stays bf16) with float32
    results; the running max, sum and accumulator are float32."""
    if write:
        (new_ref, kt_hbm, vt_hbm, o_ref, kt_out, vt_out,
         kbuf, vbuf, sem, wbuf, wsem, par_ref, m_ref, s_ref, acc_ref) = refs
    else:
        (kt_hbm, vt_hbm, o_ref,
         kbuf, vbuf, sem, par_ref, m_ref, s_ref, acc_ref) = refs
    bk = block_len
    hb, d = kbuf.shape[1], kbuf.shape[3 if rows else 2]
    b, h, nh = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
    g = b * nh + h
    layer = layer_ref[0]
    length = len_ref[b]
    n = jnp.minimum(length // bk, num_blocks - 1) + 1      # live blocks

    def span(start, size):
        """``size`` positions from ``start``: the last two indices of a
        cache or a buffer, after its heads."""
        pos = pl.ds(start, size)
        return (pos, slice(None)) if rows else (slice(None), pos)

    def fetch(slot, group, j, buf):
        at = (layer, slot, pl.ds(group * hb, hb),
              *span(pl.multiple_of(j * bk, bk), bk))
        return (pltpu.make_async_copy(kt_hbm.at[at], kbuf.at[buf],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(vt_hbm.at[at], vbuf.at[buf],
                                      sem.at[1, buf]))

    @pl.when(g == 0)
    def _prime():
        par_ref[0] = 0
        if write:
            par_ref[1] = 0              # no write-back in flight
        for dma in fetch(0, 0, 0, 0):
            dma.start()

    par = par_ref[0]
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    s_ref[...] = jnp.zeros_like(s_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[...]                                         # [hb, G, d]
    if write:
        # the new key and value rows [hb, 2, d]; as columns, [hb, d, 2],
        # for the transposed tiles
        new = new_ref[...].astype(kbuf.dtype)
        if not rows:
            new = jnp.swapaxes(new, 1, 2)
        wpos = wpos_ref[b]
        # positions written back as one
        w = wbuf.shape[2 if rows else 3]

        def put_back(at):
            return (pltpu.make_async_copy(wbuf.at[0], kt_out.at[at],
                                          wsem.at[0]),
                    pltpu.make_async_copy(wbuf.at[1], vt_out.at[at],
                                          wsem.at[1]))

        def settle():                   # the write-back in flight, if any
            @pl.when(par_ref[1] == 1)
            def _():
                for dma in put_back((layer, b, pl.ds(h * hb, hb),
                                     *span(0, w))):
                    dma.wait()
                par_ref[1] = 0

    def block(j, carry):
        cur = (par + j) % 2

        @pl.when(j + 1 < n)
        def _next_block():
            for dma in fetch(b, h, j + 1, 1 - cur):
                dma.start()

        @pl.when((j + 1 == n) & (g + 1 < pl.num_programs(0) * nh))
        def _next_step():
            for dma in fetch((g + 1) // nh, (g + 1) % nh, 0, 1 - cur):
                dma.start()

        for dma in fetch(b, h, j, cur):
            dma.wait()

        if write:
            here = (wpos >= 0) & (wpos // bk == j)

            def insert(off, sl):
                """Put the new rows at ``wpos`` into the ``w`` positions
                from ``off`` of this block (``sl``: their index in a
                buffer), and send those back to the cache."""
                settle()
                pos = jax.lax.broadcasted_iota(
                    jnp.int32, (1, w, 1) if rows else (1, 1, w),
                    1 if rows else 2)
                hit = pos == wpos - j * bk - off
                for i, buf in enumerate((kbuf, vbuf)):
                    row = new[:, i:i + 1, :] if rows else new[:, :, i:i + 1]
                    tile = jnp.where(hit, row, buf[sl])
                    buf[sl] = tile
                    wbuf[i] = tile
                for dma in put_back((
                        layer, b, pl.ds(h * hb, hb),
                        *span(pl.multiple_of(j * bk + off, w), w))):
                    dma.start()
                par_ref[1] = 1

            if rows:
                # a sublane offset may be dynamic: one insertion
                @pl.when(here)
                def _insert():
                    off = pl.multiple_of((wpos - j * bk) // w * w, w)
                    insert(off, (cur, slice(None), *span(off, w)))
            else:
                # a lane offset may not: one branch per 128 columns
                for c in range(bk // w):
                    @pl.when(here & ((wpos - j * bk) // w == c))
                    def _insert(c=c):
                        insert(c * w, (cur, slice(None), *span(c * w, w)))

        k, v = kbuf[cur], vbuf[cur]         # [hb, d, bk]; rows: [hb, bk, d]
        scores = jax.lax.dot_general(
            q.astype(k.dtype), k,
            (((2,), (2 if rows else 1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale    # [hb, G, bk]
        idx = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bk), 2)
        scores = jnp.where(idx <= length, scores, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)                         # [hb, G, 1]
        p = jnp.exp(scores - m_new)                        # [hb, G, bk]
        m_ref[...] = m_new
        s_ref[...] = s_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                   keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v,
            (((2,), (1 if rows else 2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # [hb, G, d]

        return carry

    jax.lax.fori_loop(0, n, block, 0)
    par_ref[0] = (par + n) % 2
    if write:
        @pl.when(g + 1 == pl.num_programs(0) * nh)
        def _last():
            settle()
    # Position 0 is visible to every slot, so s > 0.
    o_ref[...] = (acc_ref[...] / s_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_len", "heads_per_step", "dtype", "interpret", "rows", "scale"))
def flash_decode_layer(lengths, wpos, layer, q2, new_kv, kt_cache, vt_cache,
                       *, block_len: int, heads_per_step: int, dtype,
                       interpret: bool, rows: bool = False, scale=None):
    """The one inner function every layer's call goes through: ``layer``
    is an operand, so a decode body of any depth lowers this kernel
    once.  ``q2``: ``[B, H, G, d]``, the ``G`` query heads of each of the
    ``H`` key/value heads; ``new_kv``: ``[B, H, 2, d]`` (the step's key
    and value rows, one a key/value head) or ``None``; the caches whole,
    as ``[L, B, H, d, T]`` (with ``rows`` as ``[L, B, H, T, d]``).
    Returns the attention output ``[B, H, G, d]``, and the two caches
    after it when ``new_kv`` was written.  ``scale``:
    :func:`softmax_scale`'s."""
    if rows:
        _, B, H, T, d = kt_cache.shape
    else:
        _, B, H, d, T = kt_cache.shape
    bk, hb, G = block_len, heads_per_step, q2.shape[2]
    write = new_kv is not None

    def row_map(b, h, *_):
        return b, h, 0, 0

    row = pl.BlockSpec((None, hb, G, d), row_map)
    pair = pl.BlockSpec((None, hb, 2, d), row_map)     # new key, value
    # what goes back to the cache around the new position: 128 columns
    # of the transposed lane; of the lane as stored, one sublane tile of
    # rows (8 of 32 bits, 16 of 16, 32 of 8)
    w = min(bk, 32 // kt_cache.dtype.itemsize if rows else 128)
    tile = (lambda n: (hb, n, d)) if rows else (lambda n: (hb, d, n))
    whole = pl.BlockSpec(memory_space=pl.ANY)
    out = jax.ShapeDtypeStruct((B, H, G, d), dtype)
    cache = jax.ShapeDtypeStruct(kt_cache.shape, kt_cache.dtype)
    buf = pltpu.VMEM((2, *tile(bk)), kt_cache.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # lengths, wpos, layer (SMEM)
        grid=(B, H // hb),
        in_specs=[row] + [pair] * write + [whole, whole],
        out_specs=[row] + [whole, whole] * write,
        scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2))]
        + [pltpu.VMEM((2, *tile(w)), kt_cache.dtype),      # write-back
           pltpu.SemaphoreType.DMA((2,))] * write
        + [pltpu.SMEM((2,), jnp.int32),     # parity, write-back pending
           pltpu.VMEM((hb, G, 1), jnp.float32),            # max
           pltpu.VMEM((hb, G, 1), jnp.float32),            # sum
           pltpu.VMEM((hb, G, d), jnp.float32)],           # acc
    )
    kern = functools.partial(
        _dense_decode_kernel, block_len=bk, num_blocks=T // bk,
        scale=softmax_scale(d, scale), write=write, rows=rows)
    with jax.named_scope(kernel_marker("flash_decode")):
        res = pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=[out] + [cache, cache] * write,
            # operands: 3 scalars, q, (the new rows), the two caches
            input_output_aliases={5: 1, 6: 2} if write else {},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(lengths, wpos, layer, q2, *([new_kv] * write), kt_cache, vt_cache)
    return tuple(res) if write else res[0]


def flash_decode_attention_dense(q, k_cache, v_cache, layer, lengths, *,
                                 new_kv=None, active=None,
                                 dtype=jnp.float32,
                                 block_k: Optional[int] = None,
                                 heads_per_step: Optional[int] = None,
                                 interpret: Optional[bool] = None,
                                 scale: Optional[float] = None):
    """Fused :func:`autodist_tpu.serving.kv_cache.cached_attention` over
    layer ``layer`` of the whole dense cache, reading only each slot's
    live blocks — and, given ``new_kv``, the step's
    :func:`~autodist_tpu.serving.kv_cache.write_token` in the same pass.

    ``q``: ``[B, 1, heads, head_dim]``; ``k_cache``/``v_cache``:
    ``[L, B, kv_heads, T, head_dim]`` (the cache arrays themselves: no
    slice is taken, the kernel picks the layer), ``kv_heads`` a divisor
    of ``heads`` — query head ``h`` reads key/value head ``h // (heads
    // kv_heads)``, as in ``cached_attention``; ``layer``: int or int32
    scalar; ``lengths``: ``[B]`` int32.  Returns
    ``[B, 1, heads, head_dim]`` in ``dtype``.

    ``new_kv=(k, v)``, each ``[B, 1, kv_heads, head_dim]``: slot ``i``'s
    rows are written at position ``lengths[i]`` before the slot attends,
    and ``(out, k_cache, v_cache)`` comes back, the caches updated in
    place under ``jit`` with donation.  ``active`` (``[B]`` bool, with or
    without ``new_kv``): a slot that is not active writes nothing and
    reads one block; its output means nothing.

    For heads under 128 the kernel reads the lanes transposed,
    ``[.., head_dim, T]``.  That is how a TPU lays out an array whose
    minor dimension is under 128 and whose next is a multiple of 128, so
    there the transposition is a relabelling; heads of 128 and wider it
    reads as stored (:func:`rows_layout`).  On any other shape its view
    is a copy of what it is given (:func:`fused_decode_block` says
    which).  ``T`` must divide into
    blocks (:func:`decode_block_len`); ``heads_per_step`` defaults to as
    many key/value heads of a slot as fit :data:`KV_BLOCK_BYTES`;
    ``scale`` is :func:`softmax_scale`'s.
    """
    _, _, H, T, d = k_cache.shape
    B, _, heads, _ = q.shape
    if heads % H:
        raise ValueError(f"heads={heads} must be a multiple of the cache's "
                         f"key/value heads={H}")
    bk = decode_block_len(T, block_k)
    if bk is None:
        raise ValueError(
            f"a cache lane of {T} positions does not divide into blocks "
            f"of {int(block_k or DEFAULT_BLOCK_K)}: give a block_k that "
            "divides it (dense_decode_elected does)")
    hb = int(heads_per_step or _heads_per_step(
        H, bk, d, jnp.dtype(k_cache.dtype).itemsize))
    if H % hb:
        raise ValueError(f"heads_per_step={hb} must divide the cache's "
                         f"heads={H}")
    interp = default_interpret() if interpret is None else bool(interpret)
    lengths = lengths.astype(jnp.int32)
    live = lengths if active is None else jnp.where(active, lengths, 0)
    wpos = jnp.full_like(lengths, -1) if new_kv is None else (
        lengths if active is None else jnp.where(active, lengths, -1))
    if new_kv is not None:
        new_kv = jnp.concatenate(new_kv, axis=1).swapaxes(1, 2) \
            .astype(k_cache.dtype)                 # [B, H, 2, d]
    rows = rows_layout(d)
    view = (lambda c: c) if rows else (lambda c: jnp.swapaxes(c, 3, 4))
    res = flash_decode_layer(
        live, wpos, jnp.asarray(layer, jnp.int32).reshape(1),
        q.reshape(B, H, heads // H, d), new_kv, view(k_cache),
        view(v_cache),
        block_len=bk, heads_per_step=hb, dtype=jnp.dtype(dtype),
        interpret=interp, rows=rows,
        scale=None if scale is None else float(scale))
    if new_kv is None:
        return res.reshape(q.shape)                # [B, 1, heads, d]
    out, kt, vt = res
    return out.reshape(q.shape), view(kt), view(vt)


# --------------------------------------------------------------------------- #
# Latent rows: one ``[L, B, 1, T, row]`` array, keys and values at once
# --------------------------------------------------------------------------- #
def latent_decode_block(max_len: int, row: int, kv_rank: int, dtype):
    """The block length with which the latent kernel reads a
    ``[L, B, 1, max_len, row]`` cache of ``dtype`` in place, or ``None``
    where it cannot.  Its view of the cache, ``[.., row, max_len]``, is
    the array only where the chip keeps the positions minor-most: a row
    that is no multiple of its 128 lanes under a lane that is.  The rows
    then lie on the sublanes, so ``row`` and ``kv_rank`` (where the
    values' slice of a tile ends) have to be whole sublane tiles of
    ``dtype`` (8 of 32 bits, 16 of 16).  The block is the longest
    multiple of 128 up to :data:`LATENT_BLOCK_K` that divides
    ``max_len``."""
    sublanes = 32 // jnp.dtype(dtype).itemsize
    if max_len % 128 or row % 128 == 0 or row % sublanes \
            or kv_rank % sublanes or not 0 < kv_rank <= row:
        return None
    return max(bk for bk in range(128, min(LATENT_BLOCK_K, max_len) + 1, 128)
               if max_len % bk == 0)


def latent_decode_elected(word, max_len: int, row: int, kv_rank: int,
                          dtype, backend: Optional[str] = None):
    """The engine's election for a cache of latent rows: the block the
    kernel reads it with, or ``None`` for ``write_token`` and
    ``cached_attention``.  ``word`` is the kernel slot's on
    ``flash_decode``: ``False`` forbids the kernel; ``None`` leaves it to
    what can be observed — a TPU under the programs, a cache the kernel
    reads in place (:func:`latent_decode_block`), a lane of at least
    :data:`MIN_FUSED_DECODE_LEN`; ``True`` takes it wherever it can run
    (the interpreter off the TPU), with any block that divides the lane
    where the kernel's view of the cache is a copy of it."""
    if word is False:
        return None
    block = latent_decode_block(max_len, row, kv_rank, dtype)
    if word:
        return block or math.gcd(max_len, LATENT_BLOCK_K)
    if block and max_len >= MIN_FUSED_DECODE_LEN \
            and (backend or jax.default_backend()) == "tpu":
        return block
    return None


def _latent_decode_kernel(len_ref, wpos_ref, layer_ref, q_ref, *refs,
                          block_len: int, num_blocks: int, kv_rank: int,
                          scale: float, write: bool):
    """One slot a grid step: walk the slot's live blocks of its lane of
    latent rows, ONE ``[row, bk]`` tile of the transposed lane a block —
    a tile is the keys of every query head and, its first ``kv_rank``
    sublanes, their values.  Block ``j`` holds positions ``[j*bk,
    (j+1)*bk)``; the last live block is ``lengths[slot] // bk`` (position
    ``lengths`` is this step's token), so the trip count is the slot's
    own and a dead block costs nothing.  The kernel's own DMAs run
    ahead of the products by all the buffers but one, over the walk of
    every slot's live blocks in turn (``cur_ref`` holds where it
    stands), so a slot's first blocks are on their way while the slot
    before it is computed.

    With ``write`` the step's row is put into the block that holds
    position ``wpos[slot]`` while it is in VMEM, before the products,
    and the 128 columns around it go back to the cache (the aliased
    output), as :func:`_dense_decode_kernel` does with one array more:
    the cache write of the step, without a pass of its own.  ``wpos <
    0`` writes nothing.

    Products take the cache's own tiles with float32 results; the
    running max, sum and accumulator are float32 and ride the loop."""
    if write:
        (new_ref, rows_hbm, o_ref, rows_out,
         buf, sem, wbuf, wsem, cur_ref) = refs
    else:
        rows_hbm, o_ref, buf, sem, cur_ref = refs
    bk, depth = block_len, buf.shape[0]
    heads = q_ref.shape[0]
    b, slots = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]
    length = len_ref[b]

    def live(slot):                     # its live blocks
        return jnp.minimum(len_ref[slot] // bk, num_blocks - 1) + 1

    def fetch(slot, j, at):
        return pltpu.make_async_copy(
            rows_hbm.at[layer, slot, 0, :,
                        pl.ds(pl.multiple_of(j * bk, bk), bk)],
            buf.at[at], sem.at[at])

    # cur_ref: blocks computed, blocks fetched, the slot and the block to
    # fetch next, whether a write-back is in flight
    def fetch_next():
        @pl.when(cur_ref[2] < slots)
        def _():
            slot, j = cur_ref[2], cur_ref[3]
            fetch(slot, j, cur_ref[1] % depth).start()
            cur_ref[1] = cur_ref[1] + 1
            last = j + 1 == live(slot)
            cur_ref[2] = jnp.where(last, slot + 1, slot)
            cur_ref[3] = jnp.where(last, 0, j + 1)

    @pl.when(b == 0)
    def _prime():
        for i in range(5):
            cur_ref[i] = 0
        for _ in range(depth - 1):
            fetch_next()

    q = q_ref[...].astype(buf.dtype)                       # [heads, row]
    if write:
        wpos = wpos_ref[b]
        w = wbuf.shape[1]               # positions written back as one
        # this slot's row as a column: its lane of the rows' transpose
        lane = jax.lax.broadcasted_iota(jnp.int32, new_ref.shape, 1)
        new = jnp.sum(
            jnp.where(lane == b, new_ref[...].astype(jnp.float32), 0.0),
            axis=1, keepdims=True).astype(buf.dtype)       # [row, 1]

        def put_back(start):
            return pltpu.make_async_copy(
                wbuf, rows_out.at[layer, b, 0, :, pl.ds(start, w)],
                wsem.at[0])

        def settle():                   # the write-back in flight, if any
            @pl.when(cur_ref[4] == 1)
            def _():
                put_back(0).wait()
                cur_ref[4] = 0

    def block(j, carry):
        m, s, acc = carry
        at = cur_ref[0] % depth
        fetch_next()        # into the buffer of the block computed last
        fetch(b, j, at).wait()
        cur_ref[0] = cur_ref[0] + 1

        if write:
            # a lane offset may not be dynamic: one branch per 128 columns
            for c in range(bk // w):
                @pl.when((wpos >= 0) & (wpos // w == j * (bk // w) + c))
                def _insert(c=c):
                    settle()
                    cols = (at, slice(None), pl.ds(c * w, w))
                    pos = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
                    tile = jnp.where(pos == wpos - j * bk - c * w, new,
                                     buf[cols])
                    buf[cols] = tile
                    wbuf[...] = tile
                    put_back(pl.multiple_of(j * bk + c * w, w)).start()
                    cur_ref[4] = 1

        rows = buf[at]                                     # [row, bk]
        scores = jax.lax.dot_general(
            q, rows, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [heads, bk]
        idx = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        scores = jnp.where(idx <= length, scores, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)                         # [heads, 1]
        p = jnp.exp(scores - m_new)                        # [heads, bk]
        s = s * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:kv_rank],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [heads, kv_rank]
        return m_new, s, acc

    _, s, acc = jax.lax.fori_loop(0, live(b), block, (
        jnp.full((heads, 1), NEG_INF, jnp.float32),
        jnp.zeros((heads, 1), jnp.float32),
        jnp.zeros((heads, kv_rank), jnp.float32)))
    if write:
        @pl.when(b + 1 == slots)
        def _last():
            settle()
    # Position 0 is visible to every slot, so s > 0.
    o_ref[...] = (acc / s).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_len", "buffers", "kv_rank", "scale", "dtype", "interpret"))
def flash_decode_latent_layer(lengths, wpos, layer, q2, new_t, rows_t, *,
                              block_len: int, buffers: int, kv_rank: int,
                              scale: float, dtype, interpret: bool):
    """The one inner function every layer's call goes through, as
    :func:`flash_decode_layer` is the dense kernel's.  ``q2``:
    ``[B, heads, row]``; ``new_t``: the step's rows transposed,
    ``[row, B]``, or ``None``; ``rows_t``: the cache whole, as
    ``[L, B, 1, row, T]``.  Returns the weighted sums
    ``[B, heads, kv_rank]``, and the cache after them when ``new_t`` was
    written."""
    _, B, _, row, T = rows_t.shape
    heads = q2.shape[1]
    bk = block_len
    write = new_t is not None
    w = min(bk, 128)        # the columns that go back around the new one
    whole = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # lengths, wpos, layer (SMEM)
        grid=(B,),
        in_specs=[pl.BlockSpec((None, heads, row), lambda b, *_: (b, 0, 0))]
        # every slot's new row, fetched once: the index never changes
        + [pl.BlockSpec((row, B), lambda b, *_: (0, 0))] * write + [whole],
        out_specs=[pl.BlockSpec((None, heads, kv_rank),
                                lambda b, *_: (b, 0, 0))] + [whole] * write,
        scratch_shapes=[pltpu.VMEM((buffers, row, bk), rows_t.dtype),
                        pltpu.SemaphoreType.DMA((buffers,))]
        + [pltpu.VMEM((row, w), rows_t.dtype),             # write-back
           pltpu.SemaphoreType.DMA((1,))] * write
        + [pltpu.SMEM((5,), jnp.int32)],    # where the walk stands
    )
    kern = functools.partial(
        _latent_decode_kernel, block_len=bk, num_blocks=T // bk,
        kv_rank=kv_rank, scale=scale, write=write)
    with jax.named_scope(kernel_marker("flash_decode")):
        res = pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((B, heads, kv_rank), dtype)]
            + [jax.ShapeDtypeStruct(rows_t.shape, rows_t.dtype)] * write,
            # operands: 3 scalars, q, (the new rows), the cache
            input_output_aliases={5: 1} if write else {},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(lengths, wpos, layer, q2, *([new_t] * write), rows_t)
    return tuple(res) if write else res[0]


def flash_decode_attention_latent(q, rows_cache, layer, lengths, *,
                                  kv_rank: int, scale: float, new_row=None,
                                  active=None, dtype=jnp.float32,
                                  block_k: Optional[int] = None,
                                  interpret: Optional[bool] = None):
    """Fused :func:`autodist_tpu.serving.kv_cache.cached_attention` of a
    decode step's absorbed queries over layer ``layer`` of a cache of
    latent-attention rows (``serving/kv_cache.py LatentLayout``), reading
    each slot's live blocks once for scores and weighted sum alike —
    and, given ``new_row``, the step's
    :func:`~autodist_tpu.serving.kv_cache.write_token` in the same pass.

    ``q``: ``[B, 1, heads, row]``; ``rows_cache``: ``[L, B, 1, T, row]``
    (the cache array itself, every query head's one key head; a row's
    first ``kv_rank`` columns are its values); ``layer``: int or int32
    scalar; ``lengths``: ``[B]`` int32; ``scale``: what the float32
    scores are multiplied by.  Returns ``[B, 1, heads, kv_rank]`` in
    ``dtype``.

    ``new_row`` ``[B, 1, 1, row]``: slot ``i``'s row is written at
    position ``lengths[i]`` before the slot attends, and ``(out,
    rows_cache)`` comes back, the cache updated in place under ``jit``
    with donation.  ``active`` (``[B]`` bool): a slot that is not active
    writes nothing and reads one block; its output means nothing.

    The kernel reads the lanes transposed, ``[.., row, T]``:
    :func:`latent_decode_block` says where that is how the chip keeps
    them and gives the default ``block_k``, which must divide ``T``."""
    _, B, _, T, row = rows_cache.shape
    bk = int(block_k or latent_decode_block(T, row, kv_rank,
                                            rows_cache.dtype) or 0)
    if not bk or T % bk:
        raise ValueError(
            f"a cache of latent rows [.., {T}, {row}] of "
            f"{jnp.dtype(rows_cache.dtype).name} does not divide into "
            f"blocks of {bk or LATENT_BLOCK_K} positions that the kernel "
            "reads in place (latent_decode_block)")
    interp = default_interpret() if interpret is None else bool(interpret)
    lengths = lengths.astype(jnp.int32)
    live = lengths if active is None else jnp.where(active, lengths, 0)
    wpos = jnp.full_like(lengths, -1) if new_row is None else (
        lengths if active is None else jnp.where(active, lengths, -1))
    if new_row is not None:
        new_row = new_row.reshape(B, row).T.astype(rows_cache.dtype)
    res = flash_decode_latent_layer(
        live, wpos, jnp.asarray(layer, jnp.int32).reshape(1), q[:, 0],
        new_row, jnp.swapaxes(rows_cache, 3, 4), block_len=bk,
        buffers=LATENT_BUFFERS, kv_rank=int(kv_rank), scale=float(scale),
        dtype=jnp.dtype(dtype), interpret=interp)
    if new_row is None:
        return res[:, None]                        # [B, 1, heads, kv_rank]
    out, rows_t = res
    return out[:, None], jnp.swapaxes(rows_t, 3, 4)


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
                                 interpret: Optional[bool] = None,
                                 scale: Optional[float] = None):
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
    scale = softmax_scale(d, scale)

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
