"""TP-sharded KV cache for batched decode: dense and block-paged.

**Dense** (the original layout): one pair of arrays holds every layer's
keys and values, laid out

    ``[layer, batch_slot, heads/tp, max_len, head_dim]``

so the whole cache shards over the ``model`` mesh axis with a single
``P(None, None, 'model', None, None)`` spec — the same head split the
Megatron column-parallel qkv projection produces, so a decode step's
freshly projected k/v shards land in their cache slots with zero
resharding (the GSPMD property: one sharding-annotated layout serves
both the training program's attention and the decode program's cache,
arxiv 2105.04663).

**Paged** (the vLLM PagedAttention design adapted to this layout,
PAPERS.md: block tables + non-contiguous KV): the per-slot ``max_len``
lane is replaced by a pool of fixed-size blocks

    ``[layer, num_blocks, heads/tp, block_len, head_dim]``

with the SAME model-axis sharding spec (axis 2 is still the head
split), a per-slot **block table** ``[num_slots, max_blocks]`` mapping
logical block ``j`` of a slot's sequence to a pool block, and a
host-side free-list :class:`BlockAllocator`.  A logical position ``p``
of slot ``s`` lives at pool coordinates
``(block_table[s, p // block_len], p % block_len)``.  Short requests
stop squatting on ``max_len`` bytes they never touch: the batcher
admits against *free blocks*, not slots, so equal pool bytes carry
strictly more concurrent short requests than dense reservation.

Writes are in-place ``lax.dynamic_update_slice`` updates in both
layouts (the paged write's start index merely routes through the
table); under ``jax.jit`` with the cache donated, XLA aliases the
update into the live buffer — ``tools/hlo_probe.py --probe decode``
and the ADT111/ADT115 program-lint rules assert the compiled step
carries the dynamic-update-slices, no per-step full-cache copy, and
(paged) no dense ``[slots, max_len]``-shaped cache buffer at all.
Slots are recycled by the batcher: a newly admitted request's prefill
overwrites positions ``[0, prompt_len)`` and decode overwrites forward
from there, and reads are always masked to ``pos < length``, so stale
tail entries from the previous occupant — or, paged, from a freed
block's previous owner — are never observable.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from autodist_tpu import const
from autodist_tpu.telemetry import gauge, scope


def cache_spec() -> P:
    """Partition spec of either cache array: heads over the model axis."""
    return P(None, None, const.MODEL_AXIS, None, None)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """The decode-time state: cache arrays + per-slot occupancy.

    ``k``/``v``: ``[L, B, heads_local, T, head_dim]`` (``heads_local =
    num_heads/tp`` inside ``shard_map``; the full head count on the host
    view).  ``lengths``: ``[B]`` int32 — tokens currently materialized
    per slot (the next write position).  Registered as a pytree so the
    whole cache rides jit/scan carries and donation in one piece.
    """

    k: Any
    v: Any
    lengths: Any
    # what a linear mixer's layers keep for a slot (RecurrentState);
    # None where every layer caches keys and values
    state: Any = None


def init_cache(num_layers: int, num_slots: int, num_heads: int,
               head_dim: int, max_len: int, dtype=jnp.float32) -> KVCache:
    """All-zero cache with every slot empty.  ``num_layers`` counts the
    layers that cache keys and values, ``num_heads`` their key/value
    heads."""
    shape = (num_layers, num_slots, num_heads, max_len, head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   lengths=jnp.zeros((num_slots,), jnp.int32))


# --------------------------------------------------------------------------- #
# State that is not per token: a linear mixer's
# --------------------------------------------------------------------------- #
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RecurrentState:
    """What the linear layers hold for each slot, whatever its length —
    what the mixer's rule keeps, as its spec says
    (:class:`~autodist_tpu.models.transformer.LinearMixerSpec`):
    ``ssm`` ``[linear_layer, slot, *state_shape]`` float32, the
    recurrence's matrix (the delta rule's ``[value_heads, key_dim,
    value_dim]``, power retention's ``[kv_heads, offsets, value_dim,
    key_dim]``, a state-space layer's ``[groups, state size, heads a
    group * head size]``); ``conv`` ``[linear_layer, slot, *tail_shape]``
    — the inputs a causal convolution still needs, ``[taps - 1,
    channels]`` or (a state-space layer's) flat — where the rule has
    one, else ``None``; ``norm`` ``[linear_layer, slot,
    *normaliser_shape]`` float32 where a normaliser rides beside the
    matrix, else ``None``.  A slot's rows are overwritten whole by the
    prefill that admits it (:func:`write_state`) and carried by decode;
    between an eviction and the next admission they may hold anything
    and nothing reads them."""

    conv: Any
    ssm: Any
    norm: Any = None

    def arrays(self) -> tuple:
        """The arrays held, in the order the programs take them and
        :meth:`of` puts them back: ``(conv, ssm)`` or ``(ssm, norm)``."""
        return tuple(a for a in (self.conv, self.ssm, self.norm)
                     if a is not None)

    @classmethod
    def of(cls, mixer, arrays) -> "RecurrentState":
        """:meth:`arrays` back into a state of ``mixer``'s rule."""
        if mixer.has_normaliser:
            ssm, norm = arrays
            return cls(conv=None, ssm=ssm, norm=norm)
        return cls(*arrays)


def init_state(linear_layers: int, num_slots: int, mixer,
               dtype) -> RecurrentState:
    """All-zero state for ``mixer``
    (:class:`~autodist_tpu.models.transformer.LinearMixerSpec`)."""
    lead = (linear_layers, num_slots)
    return RecurrentState(
        conv=jnp.zeros(lead + mixer.tail_shape, dtype)
        if mixer.has_conv else None,
        ssm=jnp.zeros(lead + mixer.state_shape, jnp.float32),
        norm=jnp.zeros(lead + mixer.normaliser_shape, jnp.float32)
        if mixer.has_normaliser else None)


def bytes_held(dims, dtype, recurrent=None, arrays: int = 2) -> dict:
    """What a request costs the manager: ``kv_bytes_per_token`` over the
    caching layers of ``dims`` (0 where the stack has none) and
    ``state_bytes_per_slot`` over the linear ones (``recurrent``:
    ``(linear layers, LinearMixerSpec)``).  ``arrays``: what a position
    holds a head — keys and values, or (:class:`LatentLayout`) the one
    row."""
    layers, _, heads, head_dim, _ = dims
    item = jnp.dtype(dtype).itemsize
    state = 0
    if recurrent is not None:
        n, mixer = recurrent
        conv = (mixer.conv_taps - 1) * mixer.conv_channels * item \
            if mixer.has_conv else 0
        state = n * (conv + mixer.state_floats * 4)
    return {"kv_bytes_per_token": arrays * layers * heads * head_dim * item,
            "state_bytes_per_slot": state}


def read_state(arrays, layer: int, slot=None):
    """The state's ``arrays`` (:meth:`RecurrentState.arrays`) of linear
    layer ``layer``: every slot's, or the one row of ``slot`` (a traced
    scalar) as a batch of one."""
    if slot is None:
        return tuple(a[layer] for a in arrays)
    return tuple(lax.dynamic_slice_in_dim(a[layer], slot, 1, axis=0)
                 for a in arrays)


def write_state(arrays, layer: int, new, slot=None):
    """Linear layer ``layer``'s state replaced in place by ``new`` (an
    array for each of ``arrays``): every slot's (a decode step), or
    ``slot``'s alone
    by the prefill that admits it, as :func:`write_prompt` writes its
    lane — no other slot's row is read or written.  The write is the
    last of the recurrence's passes over the state (the new state is
    computed into it), so it wears the caller's scope
    (``linear_attention`` / ``state_update``), not ``kv_write``."""
    out = []
    for arr, rows in zip(arrays, new):
        start = (layer, 0 if slot is None else slot) + (0,) * (arr.ndim - 2)
        out.append(lax.dynamic_update_slice(
            arr, rows[None].astype(arr.dtype), start))
    return tuple(out)


@scope("kv_write")
def write_token(cache_arr, layer: int, kv, positions):
    """Write one decode step's projections into ``cache_arr`` in place.

    ``kv``: ``[B, 1, heads, head_dim]`` (the qkv projection's layout for
    a single-token step); ``positions``: ``[B]`` int32 — slot ``i``'s
    row lands at ``[layer, i, :, positions[i], :]``.  Per-slot scalar
    positions keep the update a true ``dynamic_update_slice`` (the
    in-place form XLA aliases) instead of a scatter; the slot loop is
    unrolled — ``B`` is the static slot count, small by construction.
    """
    B = kv.shape[0]
    for slot in range(B):
        upd = kv[slot, 0][None, None, :, None, :].astype(cache_arr.dtype)
        cache_arr = lax.dynamic_update_slice(
            cache_arr, upd, (layer, slot, 0, positions[slot], 0))
    return cache_arr


@scope("kv_write")
def write_prompt(cache_arr, layer: int, kv, slot):
    """Write one admitted prompt's projections into its slot's lane.

    ``kv``: ``[1, S, heads, head_dim]`` (the one-row prefill's
    projections); ``slot``: a traced int32 scalar — the rows land at
    ``[layer, slot, :, 0:S, :]`` through ONE ``dynamic_update_slice``,
    the in-place form XLA aliases.  The row is admitted by construction
    (the engine dispatches the program once per admitted slot), so there
    is no read-modify-write and every other slot's lane is not touched.
    """
    new = jnp.transpose(kv, (0, 2, 1, 3))[None].astype(cache_arr.dtype)
    return lax.dynamic_update_slice(cache_arr, new,      # [1,1,heads,S,dh]
                                    (layer, slot, 0, 0, 0))


def keep_row_major(cache_arr):
    """Pin ``cache_arr`` to the layout it is declared in, ``head_dim``
    minor-most.  A cache of heads of 128 and wider arrives that way and
    the decode kernel reads it so; left to itself the TPU compiler
    gives the prefill program's carried cache the layout of the prompt's
    keys (positions minor-most, from the score product) and copies the
    whole cache in and out of it (PERF.md section 6, PR 26)."""
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(
        cache_arr, Layout(major_to_minor=tuple(range(cache_arr.ndim))))


def cached_attention(q, k_layer, v_layer, lengths, *, dtype=jnp.float32,
                     scale=None):
    """One decode step's attention over a layer's cache slice.

    ``q``: ``[B, 1, heads, head_dim]`` (the step's query — the token
    just written at position ``lengths``); ``k_layer``/``v_layer``:
    ``[B, heads, T, head_dim]``.  Key positions ``> lengths`` are masked
    (the just-written token attends to itself and everything before it),
    so stale or zero entries past a slot's occupancy are unreachable.
    Softmax in fp32 with the trained model's scaling — matching
    :func:`~autodist_tpu.models.transformer.dot_product_attention`
    numerics so incremental decode agrees with full-sequence recompute.
    Scores live at ``[B, heads, 1, T]`` — never the ``[T, T]`` square
    the prefill's causal pass needs (the HLO decode probe asserts no
    such buffer exists).  Fewer key/value heads than query heads: the
    group of query heads that reads a key/value head rides in the row
    dimension, ``[B, kv_heads, group, T]``, and the lane is read once.
    ``scale``: what the scores are multiplied by in float32, where it is
    not ``head_dim ** -0.5``.
    """
    depth = q.shape[-1]
    B, _, heads, _ = q.shape
    kv_heads = k_layer.shape[1]
    if kv_heads != heads:
        q2 = q.reshape(B, kv_heads, heads // kv_heads, depth)
    else:
        q2 = jnp.transpose(q, (0, 2, 1, 3))          # [B, heads, 1, dh]
    # dot_general contracting head_dim directly against the cache's
    # native [.., T, head_dim] layout — an einsum spelling makes XLA
    # transpose (= copy) the whole cache lane every step.
    scores = lax.dot_general(
        q2, k_layer.astype(q.dtype),
        (((3,), (3,)), ((0, 1), (0, 1))))
    if scale is None:
        scores = (scores / np.sqrt(depth)).astype(jnp.float32)
    else:
        scores = scores.astype(jnp.float32) * scale  # [B, heads, 1, T]
    T = k_layer.shape[2]
    ok = jnp.arange(T)[None, None, None, :] <= \
        lengths[:, None, None, None]
    scores = jnp.where(ok, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    out = lax.dot_general(
        probs, v_layer.astype(dtype),
        (((3,), (2,)), ((0, 1), (0, 1))))            # [B, heads, 1, dh]
    if kv_heads != heads:
        return out.reshape(B, 1, heads, depth)
    return jnp.transpose(out, (0, 2, 1, 3))          # [B, 1, heads, dh]


# --------------------------------------------------------------------------- #
# Block-paged cache
# --------------------------------------------------------------------------- #
class PoolExhaustedError(RuntimeError):
    """The block pool cannot satisfy an allocation: the request must
    wait in the admission queue (or be shed) instead of silently
    corrupting another slot's blocks.  Coded, like the batcher's
    :class:`~autodist_tpu.serving.batcher.OverloadedError`."""

    code = "serve/kv_pool_exhausted"


class BlockAllocator:
    """Host-side refcounted free-list over the pool's ``num_blocks`` ids.

    Pure accounting — no device traffic.  Allocation pops from one flat
    free list, so there is no fragmentation by construction: any
    ``n <= free_blocks`` allocation succeeds, and
    ``free_blocks + used_blocks == num_blocks`` is an invariant the unit
    tests pin (``used_blocks`` counts *physical* blocks with refcount
    >= 1, not table references).  Prefix caching shares a physical block
    between slots by bumping its refcount (:meth:`share`); :meth:`free`
    decrements, and a block returns to the free list only when the last
    reference drops — so ``free + used == total`` survives sharing with
    no special cases.  Double-frees and foreign ids are rejected loudly
    (a bookkeeping bug must not silently double-map a block to two
    slots).

    Every allocate/share/free transition is appended to :attr:`events`
    — the block event trace the ADT116/ADT117 shared-block rules replay
    (``lint_block_trace``).  The engine appends ``write``/``cow``
    events through :meth:`note` for the writes it dispatches, so the
    trace carries enough to prove no shared block is ever written
    through a table entry without a copy first."""

    #: bounded so a long-lived serving process cannot grow the trace
    #: without bound; the lints run over fresh, short traces.
    TRACE_LIMIT = 1 << 18

    def __init__(self, num_blocks: int):
        if num_blocks < 1:
            raise ValueError("num_blocks must be >= 1")
        self.num_blocks = int(num_blocks)
        # LIFO free list: deterministic reuse order (a freed block is
        # handed to the next admission — the recycling edge the paged
        # parity goldens pin).
        self._free = list(range(self.num_blocks - 1, -1, -1))
        self._rc: dict = {}
        self.events = collections.deque(maxlen=self.TRACE_LIMIT)

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return len(self._rc)

    def refcount(self, block: int) -> int:
        return self._rc.get(block, 0)

    def note(self, *event) -> None:
        """Append an engine-observed event (``write``/``cow``) to the
        trace.  The allocator records its own alloc/share/free."""
        self.events.append(tuple(event))

    def alloc(self, n: int) -> list:
        if n < 0:
            raise ValueError("alloc count must be >= 0")
        if n > len(self._free):
            raise PoolExhaustedError(
                f"[{PoolExhaustedError.code}] {n} block(s) requested, "
                f"{len(self._free)} free of {self.num_blocks} — the "
                "admission predicate must gate on free blocks")
        blocks = [self._free.pop() for _ in range(n)]
        for b in blocks:
            self._rc[b] = 1
            self.events.append(("alloc", b))
        return blocks

    def share(self, block: int) -> int:
        """Take one more reference on an allocated block (prefix hit)."""
        if block not in self._rc:
            raise ValueError(
                f"block {block} is not allocated — cannot share a free "
                "block")
        self._rc[block] += 1
        self.events.append(("share", block))
        return block

    def free(self, blocks) -> list:
        """Drop one reference per listed block.  Returns the blocks
        whose LAST reference dropped (now back on the free list) so the
        caller can retire any prefix-index entries keyed on them."""
        released = []
        for b in blocks:
            if self.free_one(b):
                released.append(b)
        return released

    def free_one(self, block: int) -> bool:
        """Drop one reference; True iff the block was fully released."""
        if block not in self._rc:
            raise ValueError(
                f"block {block} is not allocated (double-free or "
                "foreign id)")
        self.events.append(("free", block))
        self._rc[block] -= 1
        if self._rc[block] == 0:
            del self._rc[block]
            self._free.append(block)
            return True
        return False


def blocks_for(tokens: int, block_len: int) -> int:
    """Pool blocks covering ``tokens`` logical positions."""
    return -(-max(int(tokens), 0) // int(block_len))


def prefix_block_keys(prompt, block_len: int):
    """Content keys for a prompt's blocks, chained so a key commits to
    the WHOLE prefix through its block (two prompts agreeing on block
    ``j``'s key agree on every token before it — the property that
    makes a single dict lookup sufficient for prefix matching).

    Returns ``(full_keys, partial_key)``: one key per *full* prompt
    block, plus a key for the trailing partial block (``None`` when the
    prompt length is a block multiple).  The partial key commits to the
    exact tail run — a prompt extending past another's partial tail
    does NOT match it (the shared block would be missing the extra
    tokens' projections)."""
    toks = np.asarray(prompt, dtype=np.int64)
    bl = int(block_len)
    n_full = len(toks) // bl
    full_keys, h = [], hashlib.sha1(b"adt-prefix")
    for j in range(n_full):
        h.update(toks[j * bl:(j + 1) * bl].tobytes())
        full_keys.append(("full", h.hexdigest()))
    partial_key = None
    tail = toks[n_full * bl:]
    if len(tail):
        h.update(tail.tobytes())
        partial_key = ("partial", len(tail), h.hexdigest())
    return full_keys, partial_key


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVCache:
    """The paged decode state: block pools + table + occupancy.

    ``k``/``v``: ``[L, num_blocks, heads_local, block_len, head_dim]``
    pools.  ``lengths``: ``[num_slots]`` int32.  ``block_table``:
    ``[num_slots, max_blocks]`` int32 — logical block ``j`` of slot
    ``s`` lives in pool block ``block_table[s, j]`` (unassigned entries
    hold 0; reads past a slot's occupancy are masked, so the value is
    never observable).  Registered as a pytree so the whole cache rides
    jit/scan carries and donation in one piece."""

    k: Any
    v: Any
    lengths: Any
    block_table: Any


def init_paged_cache(num_layers: int, num_slots: int, num_heads: int,
                     head_dim: int, max_len: int, *, block_len: int,
                     num_blocks: int, dtype=jnp.float32) -> PagedKVCache:
    """All-zero block pool with every slot empty and no block mapped."""
    if block_len < 1:
        raise ValueError("block_len must be >= 1")
    max_blocks = blocks_for(max_len, block_len)
    if num_blocks < max_blocks:
        raise ValueError(
            f"num_blocks={num_blocks} cannot hold even one full-length "
            f"request ({max_blocks} blocks of {block_len} for "
            f"max_len={max_len})")
    shape = (num_layers, num_blocks, num_heads, block_len, head_dim)
    return PagedKVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        lengths=jnp.zeros((num_slots,), jnp.int32),
        block_table=jnp.zeros((num_slots, max_blocks), jnp.int32))


@scope("kv_write")
def paged_write_token(cache_arr, layer: int, kv, positions, block_table,
                      block_len: int, write_mask=None):
    """The paged :func:`write_token`: slot ``i``'s row lands in pool
    block ``block_table[i, positions[i] // block_len]`` at in-block
    offset ``positions[i] % block_len`` — still one true
    ``dynamic_update_slice`` per slot (the block id merely becomes part
    of the dynamic start index), so XLA aliases the write exactly like
    the dense path.

    ``write_mask`` (``[B]`` bool): slots where it is False keep the
    target row bit-for-bit (read-modify-write).  The dense path can
    afford garbage writes for inactive slots — each slot owns its whole
    lane — but a paged slot holding NO reservation has a zeroed table
    row pointing at pool block 0, which may be another slot's live
    block, so inactive writes must be suppressed, not just masked at
    read time.  A logical block index past the table's extent clamps
    (jnp gather semantics) to the row's last entry, which the allocator
    tail-fills with the slot's own last block — so a final window's
    over-decode dirties the slot's own tail block only, the paged
    analog of the dense path's clamped last-lane writes."""
    B = kv.shape[0]
    for slot in range(B):
        pos = positions[slot]
        blk = block_table[slot, pos // block_len]
        upd = kv[slot, 0][None, None, :, None, :].astype(cache_arr.dtype)
        start = (layer, blk, 0, pos % block_len, 0)
        if write_mask is not None:
            cur = lax.dynamic_slice(cache_arr, start, upd.shape)
            upd = jnp.where(write_mask[slot], upd, cur)
        cache_arr = lax.dynamic_update_slice(cache_arr, upd, start)
    return cache_arr


@scope("kv_write")
def paged_write_prompt(cache_arr, layer: int, kv, table_row,
                       block_len: int, p_len, write_from=None):
    """The paged :func:`write_prompt`: one admitted prompt's rows land
    block by block through its slot's table row.  ``kv``: ``[1, S,
    heads, head_dim]``; ``table_row``: ``[1, max_blocks]``; ``p_len``
    (and ``write_from``): ``[1]``.  Unlike the dense path — which writes
    the whole zero-padded prompt bucket into the slot's private lane — a
    logical block holding NO real prompt row (``j·block_len >= p_len``)
    is left untouched: a short request reserves only its own blocks, so
    its table row past the reservation points at block 0 (possibly
    another slot's), and the padding garbage must never land there.  The
    final *partial* prompt block (``lo < p_len < hi``) is the slot's own
    reserved block and is overwritten WHOLE — its tail takes the prompt
    bucket's zero-padding projections, unreachable behind the length
    mask (the block-granular write never splits below a block, so only
    the all-or-nothing ``lo < p_len`` predicate decides).

    ``write_from``: logical blocks ``j < write_from`` are skipped — they
    are prefix-cache hits whose physical blocks already hold the
    identical projections (possibly shared with another slot, where an
    unsuppressed write would be a write through a shared table entry —
    ADT116)."""
    S = kv.shape[1]
    rows = jnp.transpose(kv[0], (1, 0, 2))           # [heads, S, dh]
    for j in range(blocks_for(S, block_len)):
        lo = j * block_len
        hi = min(lo + block_len, S)
        new = rows[:, lo:hi][None, None].astype(cache_arr.dtype)
        start = (layer, table_row[0, j], 0, 0, 0)
        take = lo < p_len[0]
        if write_from is not None:
            take = take & (j >= write_from[0])
        cur = lax.dynamic_slice(cache_arr, start, new.shape)
        cache_arr = lax.dynamic_update_slice(
            cache_arr, jnp.where(take, new, cur), start)
    return cache_arr


@scope("kv_write")
def paged_write_chunk(cache_arr, layer: int, kv, admit, block_table,
                      block_len: int, chunk_start, p_lens,
                      write_from=None):
    """The chunked :func:`paged_write_prompt`: one prompt *chunk*'s
    projections land block by block through the table at logical blocks
    ``chunk_start // block_len + j``.  ``kv``: ``[B, C, heads, dh]``
    with ``C % block_len == 0`` (the engine validates the chunk knob),
    so every chunk covers whole logical blocks and the write stays
    block-granular; ``chunk_start`` is a traced scalar — ONE compiled
    program serves every chunk of every prompt length.  The same
    ``lo < p_lens`` / ``write_from`` predicates as the single-shot
    writer decide per block; a chunk wholly past a slot's prompt writes
    nothing for it."""
    B, C = kv.shape[0], kv.shape[1]
    n_blocks = C // block_len
    base = chunk_start // block_len
    for slot in range(B):
        rows = jnp.transpose(kv[slot], (1, 0, 2))    # [heads, C, dh]
        for j in range(n_blocks):
            lo = j * block_len
            new = rows[:, lo:lo + block_len][None, None] \
                .astype(cache_arr.dtype)
            blk = block_table[slot, base + j]
            cur = lax.dynamic_slice(cache_arr, (layer, blk, 0, 0, 0),
                                    new.shape)
            take = admit[slot] & (chunk_start + lo < p_lens[slot])
            if write_from is not None:
                take = take & (base + j >= write_from[slot])
            sel = jnp.where(take, new, cur)
            cache_arr = lax.dynamic_update_slice(
                cache_arr, sel, (layer, blk, 0, 0, 0))
    return cache_arr


def chunk_attention(q, k_layer, v_layer, starts, *, dtype=jnp.float32,
                    scale=None):
    """A token window's causal attention over contiguous cache lanes:
    window row ``r`` of slot ``i`` is the query at absolute position
    ``starts[i] + r`` and attends to every cached key at positions
    ``<= starts[i] + r`` — earlier chunks AND this window's own rows,
    which the caller writes into the cache FIRST (write-then-attend,
    exactly the decode step's ordering).  ``q``: ``[B, C, heads,
    head_dim]``; ``k_layer``/``v_layer``: ``[B, heads, T, head_dim]``.
    Serves the chunked-prefill composed path (via
    :func:`paged_chunk_attention`) and the dense speculative verify
    pass, where every slot's window begins at its own length.
    ``scale``: as :func:`cached_attention`'s."""
    depth = q.shape[-1]
    C = q.shape[1]
    q2 = jnp.transpose(q, (0, 2, 1, 3))              # [B, H, C, dh]
    scores = lax.dot_general(
        q2, k_layer.astype(q.dtype),
        (((3,), (3,)), ((0, 1), (0, 1))))
    if scale is None:
        scores = (scores / np.sqrt(depth)).astype(jnp.float32)
    else:
        scores = scores.astype(jnp.float32) * scale  # [B, H, C, T]
    T = k_layer.shape[2]
    ok = jnp.arange(T)[None, None, None, :] <= \
        (starts[:, None] + jnp.arange(C)[None, :])[:, None, :, None]
    scores = jnp.where(ok, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    out = lax.dot_general(
        probs, v_layer.astype(dtype),
        (((3,), (2,)), ((0, 1), (0, 1))))            # [B, H, C, dh]
    return jnp.transpose(out, (0, 2, 1, 3))          # [B, C, H, dh]


def paged_chunk_attention(q, k_pool, v_pool, starts, block_table, *,
                          block_len: int, dtype=jnp.float32, scale=None):
    """The paged :func:`chunk_attention`: gather the slot's blocks into
    contiguous lanes, then the same masked math (``T`` becomes the
    padded ``max_blocks * block_len`` extent).  The composed gather
    fallback the paged flash-prefill kernel replaces, and its
    interpreter-mode golden."""
    del block_len  # implied by the pool's block extent
    k_layer = gather_blocks(k_pool, block_table)     # [B, H, T, dh]
    v_layer = gather_blocks(v_pool, block_table)
    return chunk_attention(q, k_layer, v_layer, starts, dtype=dtype,
                           scale=scale)


def copy_pool_block(k_pool, v_pool, src, dst):
    """Copy one physical block's K/V rows across every layer — the
    copy-on-write device op: the writer redirects its table entry to
    ``dst`` and writes there, while the other holders keep reading the
    untouched ``src``.  ``src``/``dst`` are traced scalars so one
    compiled copy serves every CoW; a dynamic slice along the block
    axis only, so the model-axis head sharding passes through."""
    kb = lax.dynamic_slice_in_dim(k_pool, src, 1, axis=1)
    vb = lax.dynamic_slice_in_dim(v_pool, src, 1, axis=1)
    k_pool = lax.dynamic_update_slice_in_dim(k_pool, kb, dst, axis=1)
    v_pool = lax.dynamic_update_slice_in_dim(v_pool, vb, dst, axis=1)
    return k_pool, v_pool


def gather_blocks(pool, block_table):
    """Assemble per-slot contiguous K/V lanes from the pool.

    ``pool``: one layer's ``[num_blocks, heads, block_len, head_dim]``
    slice; ``block_table``: ``[B, max_blocks]`` int32.  Returns
    ``[B, heads, max_blocks * block_len, head_dim]`` — the block-table
    *gather* (the structural evidence the ADT115 paged program rule
    keys on).  Positions past a slot's occupancy come from unassigned
    table entries (block 0) and are masked by every reader."""
    B, mb = block_table.shape
    nb, H, bl, dh = pool.shape
    g = jnp.take(pool, block_table, axis=0)      # [B, mb, H, bl, dh]
    g = jnp.moveaxis(g, 2, 1)                    # [B, H, mb, bl, dh]
    return g.reshape(B, H, mb * bl, dh)


def paged_cached_attention(q, k_pool, v_pool, lengths, block_table, *,
                           block_len: int, dtype=jnp.float32, scale=None):
    """One decode step's attention over a layer's *paged* cache slice:
    gather the slot's blocks into a contiguous lane, then run the exact
    :func:`cached_attention` masked math (T becomes the padded
    ``max_blocks * block_len`` extent; the same ``<= length`` mask
    hides the padded tail and any stale block content).  The composed
    fallback the paged flash-decode kernel replaces."""
    del block_len  # implied by the pool's block extent
    k_layer = gather_blocks(k_pool, block_table)
    v_layer = gather_blocks(v_pool, block_table)
    return cached_attention(q, k_layer, v_layer, lengths, dtype=dtype,
                            scale=scale)


# --------------------------------------------------------------------------- #
# The seam: how the engine meets a layout
# --------------------------------------------------------------------------- #
# :func:`layout_for` picks one of the three classes below once, from the
# block and ``kv_layout``, and elects the decode-attention kernel: the
# engine's programs call the traced methods where a layer writes or reads
# the cache, its host API delegates the accounting (a method that can
# change the table takes the live cache and hands it back).  A layout
# says what it ``serves`` of :data:`FEATURES`, and why not the rest
# (``refusal``): the engine's options and the disaggregated handoff ask
# it.  A new format is one more class, in this module alone.

# What rides the block table, by the knob that asks for it: what a
# refusal calls it, and what it does with the table.
FEATURES = {
    "prefill_chunk": ("chunked prefill",
                      "writes prompt chunks through the block table"),
    "speculative": ("speculative verify",
                    "attends a window through the block table's readers"),
    "prefix_caching": ("prefix caching", "shares physical pool blocks"),
    "handoff": ("the disaggregated handoff",
                "copies a request's blocks of keys and values"),
}
# what a linear mixer's rule is called where a refusal names it
_RULES = {"delta": "delta-rule", "retention": "power-retention",
          "ssd": "state-space (ssd)"}


def _both(write, kc, vc, layer, k, v, *a, **kw):
    """``write`` the keys into ``kc`` and the values into ``vc``."""
    return write(kc, layer, k, *a, **kw), write(vc, layer, v, *a, **kw)


class DenseLayout:
    """Per-slot ``max_len`` lanes.  A slot owns its lane, so there is no
    pool to account for: the host methods are constants that touch no
    device, and admission gates on slots alone.  ``fused_block``: the
    block with which the fused decode kernel reads the cache in place
    and writes the step's rows itself — :func:`layout_for`'s election
    (``flash_decode.dense_decode_elected``); without one,
    :func:`write_token` then :func:`cached_attention`.

    ``dims``: ``(caching layers, slots, key/value heads, head_dim,
    max_len)``.  ``recurrent``: ``(linear layers, LinearMixerSpec)`` of
    a stack whose other layers keep a :class:`RecurrentState` for the
    slot — the second kind of state this manager holds, met through
    :func:`read_state` / :func:`write_state` and, a decode step's
    recurrence, :meth:`advance_state` (the delta rule) or
    :meth:`advance_retention` (power retention).  A stack of linear
    layers alone has no caching layer: the key/value arrays are empty,
    a slot's memory does not depend on its length, ``max_len`` bounds
    positions only and no decode-attention kernel is elected."""

    arrays = 2          # what a position holds a head: a key and a value
    # why the block's cache is lanes whatever ``kv_layout`` was asked,
    # each ``(what it is, what stands in the way)``: :func:`layout_for`
    # says
    unpaged = ()

    @property
    def serves(self) -> frozenset:
        """Lanes of keys and values a head serve the verify window, and
        its rollback behind the lengths; lanes that could not be paged
        (:attr:`unpaged`) serve nothing of :data:`FEATURES`."""
        return frozenset() if self.unpaged else frozenset({"speculative"})

    def __init__(self, dims, kernel, *, fused_block=None, recurrent=None,
                 scale=None):
        from autodist_tpu.kernel.pallas.flash_decode import rows_layout

        self.dims, self.scale = dims, scale
        self.cache_layers, num_slots, _, head_dim, self.max_len = dims
        self.kernel = kernel        # the engine's elections, by name
        self.fused_block = fused_block
        self.recurrent = recurrent
        # heads of 128 and wider: the cache stays as the decode kernel
        # reads it (narrower heads the chip keeps positions minor-most)
        self._row_major = rows_layout(head_dim)
        # no table: one unused column, the programs' table operand
        self.table = np.zeros((num_slots, 1), np.int32)

    def init_cache(self, dims, dtype) -> KVCache:
        return self._with_state(init_cache(*dims, dtype=dtype), dtype)

    def _with_state(self, cache: KVCache, dtype) -> KVCache:
        """``cache`` with the linear layers' blank state beside it, where
        the stack has such layers."""
        if self.recurrent is not None:
            cache.state = init_state(self.recurrent[0],
                                     cache.lengths.shape[0],
                                     self.recurrent[1], dtype)
        return cache

    def gauges(self, dtype) -> dict:
        """What the layout holds, by the gauge that says it: the bytes a
        token takes, and of a recurrent state the bytes a slot and all
        slots take and the rows a head holds as they are laid out."""
        held = bytes_held(self.dims, dtype, self.recurrent, self.arrays)
        out = {"engine/kv_bytes_per_token": held["kv_bytes_per_token"]}
        if self.recurrent is not None:
            out["engine/state_bytes_per_slot"] = held["state_bytes_per_slot"]
            out["kv/state_bytes"] = \
                held["state_bytes_per_slot"] * self.dims[1]
            out["kv/state_rows"] = self.recurrent[1].state_rows
        return out

    def refusal(self, feature: str, subject: str) -> str:
        """Why ``feature`` (of :data:`FEATURES`; any other name is what
        the refusal calls it) is not served, for the ``ValueError`` of
        whoever was asked for it (``subject``: the knob, or the
        handoff's pool)."""
        what, uses = FEATURES.get(feature, (feature, None))
        if not self.unpaged:
            return (f"{subject}: {what} {uses} — it requires "
                    "kv_layout='paged'")
        kinds, why = zip(*self.unpaged)
        return (f"{subject}: {what} {' and '.join(kinds)} is not served "
                f"— {'; '.join(why)}")

    # ---- traced ------------------------------------------------------ #
    def write_prompt(self, kc, vc, layer, k, v, slot, table_row, p_len,
                     write_from):
        kc, vc = _both(write_prompt, kc, vc, layer, k, v, slot)
        if self._row_major:
            kc, vc = keep_row_major(kc), keep_row_major(vc)
        return kc, vc

    def write_token(self, kc, vc, layer, k, v, positions, table, active):
        return _both(write_token, kc, vc, layer, k, v, positions)

    def decode_attend(self, q, k, v, kc, vc, layer, lengths, table, active,
                      *, dtype):
        """``(out, kc, vc)``: the step's rows written at ``lengths`` and
        ``q`` attended over ``layer`` — at once in the fused kernel,
        which writes the rows itself, as it reads.  ``q`` holds the query
        heads, ``k`` and ``v`` one row a key/value head: either path
        puts the group that reads a key/value head in its rows."""
        fused = self.fused_block
        if not fused:
            kc, vc = self.write_token(kc, vc, layer, k, v, lengths, table,
                                      active)
        with scope("attention"):
            if fused:
                # The caches themselves, the layer an operand; a slot
                # that is not decoding writes nothing and reads one block.
                from autodist_tpu.kernel.pallas.flash_decode import \
                    flash_decode_attention_dense
                out, kc, vc = flash_decode_attention_dense(
                    q, kc, vc, layer, lengths, new_kv=(k, v),
                    active=active, dtype=dtype, block_k=fused,
                    scale=self.scale)
            else:
                out = cached_attention(q, kc[layer], vc[layer], lengths,
                                       dtype=dtype, scale=self.scale)
        return out, kc, vc

    def attend_window(self, q, kc, vc, layer, starts, table, *, dtype):
        with scope("attention"):
            return chunk_attention(q, kc[layer], vc[layer], starts,
                                   dtype=dtype, scale=self.scale)

    def state_kernel(self, ssm, group: int = 1, rule=None) -> bool:
        """Whether a decode step advances ``ssm`` (the stacked recurrent
        matrices, or their shape and type) in the fused kernel of the
        mixer's rule (``rule``: the seam's own where a seam asks, else
        ``self.recurrent``'s spec's; the delta rule's for a layout built
        without one): the election, from what can be observed where it is
        called
        (:func:`~autodist_tpu.kernel.pallas.delta_step
        .delta_step_elected`, :func:`~autodist_tpu.kernel.pallas.ssd_step
        .ssd_step_elected`, or :func:`~autodist_tpu.kernel.pallas
        .retention_step.retention_step_elected` with the ``group`` of
        query heads that read a state; the kernel slot's ``delta_step``
        / ``ssd_step`` / ``retention_step`` forces or forbids)."""
        rule = rule or (self.recurrent[1].rule if self.recurrent
                        else "delta")
        if rule == "retention":
            from autodist_tpu.kernel.pallas.retention_step import \
                retention_step_elected
            return retention_step_elected(
                self.kernel.get("retention_step"), ssm.shape, ssm.dtype,
                group)
        if rule == "ssd":
            from autodist_tpu.kernel.pallas.ssd_step import ssd_step_elected
            return ssd_step_elected(self.kernel.get("ssd_step"), ssm.shape,
                                    ssm.dtype)
        from autodist_tpu.kernel.pallas.delta_step import delta_step_elected

        return delta_step_elected(self.kernel.get("delta_step"), ssm.shape,
                                  ssm.dtype)

    def advance_state(self, q, k, v, g, beta, ssm, layer):
        """``(o, ssm)``: every slot's recurrent matrix of linear layer
        ``layer`` advanced by one position
        (:func:`~autodist_tpu.models.pipeline_lm.gated_delta_step`'s
        operands — ``g`` a head's decay or a key channel's —, ``ssm`` the
        stacked array) and read out — in the fused
        kernel, which reads and writes each tile of the array once, in
        place, or the composed step on the layer's slice and its
        :func:`write_state`."""
        if self.state_kernel(ssm, rule="delta"):
            from autodist_tpu.kernel.pallas.delta_step import \
                gated_delta_step_fused
            with scope("state_update"):
                return gated_delta_step_fused(q, k, v, g, beta, ssm, layer)
        from autodist_tpu.models.pipeline_lm import gated_delta_step

        o, new = gated_delta_step(q, k, v, g, beta, ssm[layer])
        with scope("state_update"):
            return o, write_state((ssm,), layer, (new,))[0]

    def advance_retention(self, q, k, v, g, state, layer):
        """``(y, (ssm, norm))``: every slot's state and normaliser of
        linear layer ``layer`` advanced by one position of power
        retention (:func:`~autodist_tpu.models.pipeline_lm
        .retention_step`'s operands, ``state`` the stacked arrays) and
        read by every query head — in the fused kernel, which takes the
        arrays whole and moves each tile once, in place, or the composed
        step on the layer's slices and their :func:`write_state`."""
        from autodist_tpu.models.pipeline_lm import (RETENTION_EPS,
                                                     retention_step)

        if self.state_kernel(state[0], q.shape[1] // k.shape[1],
                             "retention"):
            from autodist_tpu.kernel.pallas.retention_step import \
                retention_step_fused
            with scope("state_update"):
                return retention_step_fused(q, k, v, g, state, layer,
                                            eps=RETENTION_EPS)
        y, new = retention_step(q, k, v, g, read_state(state, layer))
        with scope("state_update"):
            return y, write_state(state, layer, new)

    def advance_ssd(self, x, Bm, Cm, g, dt, ssm, layer):
        """``(y, ssm)``: every slot's matrices of linear layer ``layer``
        advanced by one position of a state-space layer
        (:func:`~autodist_tpu.models.pipeline_lm.ssd_step`'s operands,
        ``ssm`` the stacked array) and read through ``Cm`` — in the fused
        kernel, which reads and writes each matrix once, in place
        (``kernel/ssd_step_calls`` counts the programs that call it), or
        the composed step on the layer's slice and its
        :func:`write_state`."""
        if self.state_kernel(ssm, rule="ssd"):
            from autodist_tpu import telemetry
            from autodist_tpu.kernel.pallas.ssd_step import ssd_step_fused
            if isinstance(ssm, jax.core.Tracer):    # programs, not calls
                telemetry.counter("kernel/ssd_step_calls").inc()
            with scope("state_update"):
                return ssd_step_fused(x, Bm, Cm, g, dt, ssm, layer)
        from autodist_tpu.models.pipeline_lm import ssd_step

        y, new = ssd_step(x, Bm, Cm, g, dt, ssm[layer])
        with scope("state_update"):
            return y, write_state((ssm,), layer, (new,))[0]

    # ---- host -------------------------------------------------------- #
    def table_arg(self, cache):
        return jnp.asarray(self.table)

    @property
    def decode_block_len(self) -> Optional[int]:
        """Positions of a lane the decode attention reads as one; ``None``
        where no layer caches a lane."""
        return (self.fused_block or self.max_len) if self.cache_layers \
            else None

    def blocks_needed(self, prompt_len, max_new_tokens, prompt=None) -> int:
        return 0

    def accounting(self) -> tuple:
        return (0, 0, 0)

    def reserve(self, cache, slot, prompt_len, max_new_tokens, prompt=None):
        return cache, 0

    def release(self, cache, slot):
        return cache

    def protect(self, cache, active, n):
        return cache


class LatentLayout(DenseLayout):
    """Per-slot ``max_len`` lanes of latent-attention rows: a cached
    position of a layer is ONE row ``[c | k_pe]`` that every query head
    reads, not keys and values a head.  The cache's ``k`` holds the rows
    as one key head, ``[layer, slot, 1, max_len, row]``; its values ARE
    the first ``kv_rank`` columns of those keys, so ``v`` is the same
    lanes at width 0 and rides the programs untouched.  The traced
    methods take the row where the other layouts take keys (``k``
    ``[B, S, 1, row]``) and nothing for ``v``; the host constants are a
    dense lane's.

    ``dims``: ``(layers, slots, 1, row, max_len)``; ``kv_rank``: how many
    of a row's leading columns are its values; ``scale``: what the scores
    are multiplied by (``BlockSpec.latent_softmax_scale``);
    ``fused_block``: the block with which the latent decode kernel reads
    the cache in place, a slot's live blocks once for scores and
    weighted sum alike, and writes the step's row itself — elected as a
    dense lane's (``flash_decode.latent_decode_elected``).
    ``recurrent``: as a dense lane's — the
    stack's other layers are linear ones, and the manager holds their
    :class:`RecurrentState` beside the latent layers' rows."""

    arrays = 1          # the one row
    serves = frozenset()    # no reader takes a window of rows

    def __init__(self, dims, kernel, *, kv_rank: int, scale: float,
                 fused_block=None, recurrent=None):
        super().__init__(dims, kernel, fused_block=fused_block,
                         recurrent=recurrent)
        self.kv_rank, self.scale = kv_rank, scale

    def gauges(self, dtype) -> dict:
        """A dense lane's, and: the positions of a lane of rows; the
        rows' decode attention, 1 the latent kernel over the live
        blocks, 0 the composed products over whole lanes; beside a
        recurrent state, the two kinds of state in the one manager — how
        many layers of each, and the bytes the rows take over all
        slots."""
        out = super().gauges(dtype)
        layers, slots, _, _, max_len = self.dims
        out["engine/latent_lane_rows"] = max_len
        out["kernel/latent_decode_elected"] = int(bool(self.fused_block))
        if self.recurrent is not None:
            out["kv/latent_layers"] = layers
            out["kv/linear_layers"] = self.recurrent[0]
            out["kv/row_bytes"] = \
                out["engine/kv_bytes_per_token"] * max_len * slots
        return out

    def init_cache(self, dims, dtype) -> KVCache:
        layers, slots, heads, row, max_len = dims
        lanes = (layers, slots, heads, max_len)
        return self._with_state(
            KVCache(k=jnp.zeros(lanes + (row,), dtype),
                    v=jnp.zeros(lanes + (0,), dtype),
                    lengths=jnp.zeros((slots,), jnp.int32)), dtype)

    # ---- traced ------------------------------------------------------ #
    def write_prompt(self, kc, vc, layer, k, v, slot, table_row, p_len,
                     write_from):
        return write_prompt(kc, layer, k, slot), vc

    def write_token(self, kc, vc, layer, k, v, positions, table, active):
        return write_token(kc, layer, k, positions), vc

    def decode_attend(self, q, k, v, kc, vc, layer, lengths, table, active,
                      *, dtype):
        """``(o_lat, kc, vc)``: the step's rows written at ``lengths``,
        then ``q`` ``[B, 1, heads, row]`` — the absorbed queries —
        attended over ``layer``'s rows as one key head, every query head
        in the row dimension of the products over the one lane; ``o_lat``
        ``[B, 1, heads, kv_rank]`` is the weighted sum of the rows'
        latents.  At once in the fused kernel, which takes the cache
        itself and the layer as an operand, writes the row as it reads
        and sums the rows' first ``kv_rank`` columns alone."""
        if self.fused_block:
            from autodist_tpu.kernel.pallas.flash_decode import \
                flash_decode_attention_latent
            with scope("latent_attention"), scope("latent_attend"):
                out, kc = flash_decode_attention_latent(
                    q, kc, layer, lengths, kv_rank=self.kv_rank,
                    scale=self.scale, new_row=k, active=active,
                    dtype=dtype, block_k=self.fused_block)
            return out, kc, vc
        kc, vc = self.write_token(kc, vc, layer, k, v, lengths, table,
                                  active)
        with scope("latent_attention"), scope("latent_attend"):
            rows = kc[layer]
            out = cached_attention(q, rows, rows, lengths, dtype=dtype,
                                   scale=self.scale)
        return out[..., :self.kv_rank], kc, vc

    def attend_window(self, q, kc, vc, layer, starts, table, *, dtype):
        raise NotImplementedError(
            "a window of positions against cached latent rows (chunked "
            "prefill, the speculative verify pass) is not served")


class PagedLayout:
    """The block pool, the per-slot table and who holds which block:
    the free-list allocator, the prefix index, the copy-on-write
    reserve.  The numpy ``table`` is the single source its device mirror
    ``cache.block_table`` reflects; a method that changes it hands back
    a cache whose mirror is current, so the cache pytree IS the complete
    decode state (serialized or inspected between dispatches — elastic
    checkpointing, debug dumps — it never shows a stale mapping)."""

    decode_block_len = None     # nothing reads a paged lane as one
    arrays = 2
    recurrent = None
    serves = frozenset(FEATURES)
    gauges = DenseLayout.gauges     # of dims, arrays and no recurrent state

    def __init__(self, dims, kernel, *, block_len: int, num_blocks: int,
                 prefix_caching: bool = False, scale=None):
        self.dims, self.scale = dims, scale
        _, self.num_slots, _, _, self.max_len = dims
        self.kernel = kernel        # the engine's elections, by name
        self.block_len = block_len
        self.prefix_caching = prefix_caching
        # Host-side block accounting: the free-list allocator and the
        # numpy mirror of the device block table (refreshed into the
        # compiled programs as a replicated input).
        self.allocator = BlockAllocator(num_blocks)
        self.table = np.zeros(
            (self.num_slots, blocks_for(self.max_len, block_len)), np.int32)
        self._slot_blocks: list = [[] for _ in range(self.num_slots)]
        # Prefix-cache state: block-content keys -> ready physical
        # block (registered only AFTER the owning prefill dispatch
        # wrote it — a same-batch sibling must never share an
        # unwritten block), the reverse map for retirement at
        # refcount 0, per-slot novel-write floor, registrations pending
        # the prefill, and the CoW reserve pool: one pre-allocated
        # replacement block per extra reference on a shared
        # *partial-tail* block, so a copy-on-write can never hit an
        # exhausted pool mid-stream.
        self._prefix_index: dict = {}
        self._block_keys: dict = {}
        self._pending_register: dict = {}
        self._cow_reserve: dict = {}
        self.write_from = np.zeros((self.num_slots,), np.int32)
        self._copy_block = jax.jit(copy_pool_block, donate_argnums=(0, 1))
        self._emit_gauges()

    def init_cache(self, dims, dtype) -> PagedKVCache:
        return init_paged_cache(*dims, block_len=self.block_len, dtype=dtype,
                                num_blocks=self.allocator.num_blocks)

    # ---- traced ------------------------------------------------------ #
    def write_prompt(self, kc, vc, layer, k, v, slot, table_row, p_len,
                     write_from):
        return _both(paged_write_prompt, kc, vc, layer, k, v, table_row,
                     self.block_len, p_len, write_from=write_from)

    def write_token(self, kc, vc, layer, k, v, positions, table, active):
        return _both(paged_write_token, kc, vc, layer, k, v, positions,
                     table, self.block_len, write_mask=active)

    def write_chunk(self, kc, vc, layer, k, v, admit, table, chunk_start,
                    p_lens, write_from):
        return _both(paged_write_chunk, kc, vc, layer, k, v, admit, table,
                     self.block_len, chunk_start, p_lens, write_from)

    def decode_attend(self, q, k, v, kc, vc, layer, lengths, table, active,
                      *, dtype):
        """``(out, kc, vc)``, as :meth:`DenseLayout.decode_attend`."""
        kc, vc = self.write_token(kc, vc, layer, k, v, lengths, table,
                                  active)
        if self.kernel.get("flash_decode"):
            from autodist_tpu.kernel.pallas.flash_decode import \
                flash_decode_attention_paged as attend
        else:
            attend = paged_cached_attention
        with scope("attention"):
            return attend(q, kc[layer], vc[layer], lengths, table,
                          block_len=self.block_len, dtype=dtype,
                          scale=self.scale), kc, vc

    def attend_window(self, q, kc, vc, layer, starts, table, *, dtype):
        if self.kernel.get("flash_prefill"):
            from autodist_tpu.kernel.pallas.flash_prefill import \
                flash_prefill_attention_paged as attend
        else:
            attend = paged_chunk_attention
        with scope("attention"):
            return attend(q, kc[layer], vc[layer], starts, table,
                          block_len=self.block_len, dtype=dtype,
                          scale=self.scale)

    # ---- host: the batcher's admission predicate ---------------------- #
    def table_arg(self, cache):
        return cache.block_table

    def accounting(self) -> tuple:
        return (self.allocator.free_blocks, self.allocator.used_blocks,
                self.allocator.num_blocks)

    def slot_blocks(self, slot: int) -> list:
        """``slot``'s pool blocks in logical order; none, and it is free."""
        return list(self._slot_blocks[slot])

    def _prefix_lookup(self, prompt, prompt_len):
        """Walk the prefix index for ``prompt``'s leading blocks.
        Returns ``(hits, novel, partial_hit)``: ``hits`` — physical
        blocks already holding the shared prefix (a contiguous leading
        run; the chained keys make the first miss terminal), ``novel``
        — ``{logical_index: key}`` for the blocks THIS request must
        compute (registered only after its prefill lands, so a same-
        batch sharer can never read an unwritten block), and
        ``partial_hit`` — the shared partial-tail physical block, or
        ``None``.  A partial hit is the one shared block decode will
        write into, so admission pre-funds its copy-on-write."""
        if not self.prefix_caching or prompt is None:
            return [], {}, None
        toks = np.asarray(prompt).reshape(-1)[:int(prompt_len)]
        full_keys, partial_key = prefix_block_keys(toks, self.block_len)
        keys = full_keys + ([partial_key] if partial_key is not None else [])
        hits, novel = [], {}
        for j, key in enumerate(keys):
            # the first miss is terminal: novel is non-empty from there
            phys = None if novel else self._prefix_index.get(key)
            if phys is None:
                novel[j] = key
            else:
                hits.append(phys)
        shared_tail = partial_key is not None and not novel
        return hits, novel, hits[-1] if shared_tail else None

    def blocks_needed(self, prompt_len, max_new_tokens, prompt=None) -> int:
        span = min(int(prompt_len) + int(max_new_tokens), self.max_len)
        n = blocks_for(span, self.block_len)
        hits, _, partial_hit = self._prefix_lookup(prompt, prompt_len)
        return n - len(hits) + (1 if partial_hit is not None else 0)

    def reserve(self, cache, slot, prompt_len, max_new_tokens, prompt=None):
        """``ServingEngine.reserve_slot``: ``(cache, prefix-hit blocks)``."""
        if self._slot_blocks[slot]:
            raise ValueError(f"slot {slot} already holds blocks "
                             f"{self._slot_blocks[slot]}")
        span = min(int(prompt_len) + int(max_new_tokens), self.max_len)
        n = blocks_for(span, self.block_len)
        hits, novel, partial_hit = self._prefix_lookup(prompt, prompt_len)
        n_hit = len(hits)
        need = n - n_hit + (1 if partial_hit is not None else 0)
        new_blocks = self.allocator.alloc(need)
        if partial_hit is not None:
            # The shared partial-tail block WILL be written (the first
            # generated token lands inside it): park one replacement
            # block per extra reference so the copy-on-write in
            # protect never has to allocate mid-stream.
            self._cow_reserve.setdefault(partial_hit, []).append(
                new_blocks.pop())
        for b in hits:
            self.allocator.share(b)
        blocks = hits + new_blocks
        self._slot_blocks[slot] = blocks
        self.write_from[slot] = n_hit
        if novel:
            self._pending_register[slot] = novel
        # Tail-fill the row with the slot's LAST block: an over-decode
        # position past the reservation (a final fused window's
        # overshoot, or the clamped >= max_len write) then routes into
        # the slot's own tail block — never block 0, which may be
        # another slot's live block.
        self.table[slot, :] = blocks[-1]
        self.table[slot, :n] = blocks
        return self._sync(cache), n_hit

    def _trim_reserves(self, block: int) -> None:
        """Keep ``_cow_reserve[block]`` at one replacement per EXTRA
        reference (``max(rc - 1, 0)``) — a sharer releasing, or a
        copy-on-write consuming a reference, returns the now-surplus
        reserve to the pool."""
        pool = self._cow_reserve.get(block)
        if pool is None:
            return
        want = max(self.allocator.refcount(block) - 1, 0)
        while len(pool) > want:
            self.allocator.free_one(pool.pop())
        if not pool:
            del self._cow_reserve[block]

    def release(self, cache, slot):
        """Drop one reference per block of ``slot``; fully-released
        blocks retire their prefix-index registration, and shared
        survivors shed any now-surplus copy-on-write reserves.  The pool
        rows keep their stale content — unreachable behind the next
        owner's length mask."""
        if not self._slot_blocks[slot]:
            return cache
        for b in self._slot_blocks[slot]:
            if self.allocator.free_one(b):
                key = self._block_keys.pop(b, None)
                if key is not None and self._prefix_index.get(key) == b:
                    del self._prefix_index[key]
            self._trim_reserves(b)
        self._slot_blocks[slot] = []
        self.table[slot, :] = 0
        self._pending_register.pop(slot, None)
        self.write_from[slot] = 0
        return self._sync(cache)

    def _emit_gauges(self):
        gauge("serve/kv_blocks_free").set(self.allocator.free_blocks)
        gauge("serve/kv_blocks_used").set(self.allocator.used_blocks)

    def _sync(self, cache) -> PagedKVCache:
        """``cache`` with the host table mirrored onto its
        ``block_table``, and the gauges brought up to date."""
        self._emit_gauges()
        return dataclasses.replace(cache,
                                   block_table=jnp.asarray(self.table))

    # ---- host: copy-on-write + prefix registration -------------------- #
    def protect(self, cache, active, n: int):
        """The copy-on-write gate: before a dispatch writes positions
        ``[L, L + n)`` of each active slot, any table entry in that
        span whose physical block is shared (refcount > 1) is copied
        into the slot's pre-funded reserve and the writer's row
        redirected — the sharer keeps the pristine block, and the ADT
        rule that no write goes through a shared table entry holds by
        construction.  Every span block (post-redirect) is noted as a
        ``write`` trace event so ``lint_block_trace`` can replay the
        protocol."""
        lengths = np.asarray(jax.device_get(cache.lengths))
        bl = self.block_len
        max_blocks = self.table.shape[1]
        changed = False
        for slot in range(self.num_slots):
            if not active[slot]:
                continue
            L = int(lengths[slot])
            lo = L // bl
            hi = min((L + n - 1) // bl, max_blocks - 1)
            for j in range(lo, hi + 1):
                b = int(self.table[slot, j])
                if self.allocator.refcount(b) > 1:
                    pool = self._cow_reserve.get(b)
                    if not pool:
                        raise RuntimeError(
                            f"shared block {b} in slot {slot}'s write "
                            "span has no copy-on-write reserve — "
                            "admission must pre-fund every extra "
                            "reference on a writable block")
                    r = pool.pop()
                    if not pool:
                        del self._cow_reserve[b]
                    # the data move: block b into r across every
                    # layer's k/v pools
                    k, v = self._copy_block(
                        cache.k, cache.v, jnp.int32(b), jnp.int32(r))
                    cache = dataclasses.replace(cache, k=k, v=v)
                    # Redirect EVERY row entry holding b (tail-fill
                    # duplicates included) — the slot must never write
                    # through the shared id again.
                    row = self.table[slot]
                    row[row == b] = r
                    self._slot_blocks[slot] = [
                        r if x == b else x
                        for x in self._slot_blocks[slot]]
                    self.allocator.note("cow", b, r)
                    self.allocator.free_one(b)
                    self._trim_reserves(b)
                    changed = True
                self.allocator.note("write", int(self.table[slot, j]))
        return self._sync(cache) if changed else cache

    def register(self, admit) -> None:
        """Publish the prefix keys of blocks the just-landed prefill
        actually wrote.  Registration waits until AFTER the dispatch so
        a same-batch request can never hit a block whose content is
        still pending; two same-batch requests with equal prefixes each
        keep private blocks and the first to flush wins the index."""
        for slot in range(self.num_slots):
            pend = self._pending_register.get(slot)
            if not pend or not admit[slot]:
                continue
            blocks = self._slot_blocks[slot]
            for j, key in pend.items():
                if j >= len(blocks) or key in self._prefix_index:
                    continue
                self._prefix_index[key] = blocks[j]
                self._block_keys[blocks[j]] = key
            self._pending_register.pop(slot, None)


# --------------------------------------------------------------------------- #
# The one decision: which layout a block's cache takes, and its kernel
# --------------------------------------------------------------------------- #
def layout_for(cfg, kernel, *, num_slots: int, max_len: int,
               kv_layout: str = "dense",
               kv_block_len: Optional[int] = None,
               kv_num_blocks: Optional[int] = None,
               prefix_caching: bool = False, prefill_chunk=None,
               speculative=None):
    """The cache layout of an engine of ``num_slots`` slots of
    ``max_len`` positions over ``cfg``'s stack — the class, its ``dims``,
    the recurrent state beside it, the decode-attention kernel's block —
    from what can be observed where it is built: the block (which layers
    cache keys and values a head, which a latent row, which keep a
    recurrent state), the head sizes and type, and ``kv_layout`` with
    (paged) its pool of ``kv_num_blocks`` blocks of ``kv_block_len``.

    ``kernel``: the normalised kernel words, but with ``flash_decode``
    ``True`` (forces the decode-attention kernel), ``False`` (forbids it)
    or absent — left to the one election a kernel, beside the kernel
    (``flash_decode.dense_decode_elected`` / ``latent_decode_elected``;
    zero caching layers elect nothing, and a paged pool's kernel runs on
    ``True`` alone).  The layout's ``kernel`` is those words, canonical
    again: ``flash_decode`` ``True`` where forced or elected.

    ``prefix_caching``, ``prefill_chunk``, ``speculative``: the engine's
    knobs of :data:`FEATURES`, as it was asked for them.  What the layout
    does not serve of them is refused here, by name (``ValueError``), in
    the order of :data:`FEATURES`, and after them a ``kv_layout='paged'``
    that the block's cache cannot be."""
    from autodist_tpu.kernel.pallas import flash_decode

    spec = cfg.block
    linear = spec.layer_kinds(cfg.num_layers).count("linear")
    # the cache holds every pass's keys and values: a layer's input
    # differs from pass to pass, so its projections do too (no layer at
    # all where every one is a linear one)
    layers = (cfg.num_layers - linear) * spec.loop_steps
    dims = (layers, num_slots, cfg.kv_heads, cfg.head_dim, max_len)
    recurrent = (linear, spec.linear) if linear else None
    unpaged = []
    if linear:
        unpaged.append((
            "over recurrent state",
            f"the block's {linear} linear ({_RULES[spec.linear.rule]}) "
            "layers keep a state a slot that cannot be rolled back, shared "
            "by blocks or cut at a chunk's edge"))
    if spec.latent is not None:
        unpaged.append((
            "with a latent KV row",
            f"a cached position is one row of {spec.latent.row} values for "
            f"all {cfg.num_heads} query heads, and the block table's "
            "readers and the window attention take keys and values a head"))
    if cfg.kv_heads != cfg.num_heads:
        unpaged.append((
            f"with grouped-query attention ({cfg.num_heads} query heads on "
            f"{cfg.kv_heads} key/value heads)",
            "the block table's readers take a key/value head a query head"))
    word = kernel.get("flash_decode")
    kernel = {k: v for k, v in kernel.items() if k != "flash_decode" or v}
    if kv_layout == "paged" and not unpaged:
        if kv_num_blocks < blocks_for(max_len, kv_block_len):
            raise ValueError(
                f"kv_num_blocks={kv_num_blocks} cannot hold even one "
                f"full-length request ({blocks_for(max_len, kv_block_len)} "
                f"blocks of {kv_block_len})")
        return PagedLayout(dims, kernel, block_len=kv_block_len,
                           num_blocks=kv_num_blocks,
                           prefix_caching=prefix_caching,
                           scale=spec.softmax_scale)
    if spec.latent is not None:
        # one row a position, one key head: the values are its first
        # kv_rank columns
        row, rank = spec.latent.row, spec.latent.kv_rank
        layout = LatentLayout(
            (layers, num_slots, 1, row, max_len), kernel, kv_rank=rank,
            scale=spec.latent_softmax_scale, recurrent=recurrent,
            fused_block=flash_decode.latent_decode_elected(
                word, max_len, row, rank, cfg.dtype))
    else:
        layout = DenseLayout(
            dims, kernel, recurrent=recurrent, scale=spec.softmax_scale,
            fused_block=flash_decode.dense_decode_elected(
                word, max_len, cfg.head_dim) if layers else None)
    if layout.fused_block:
        kernel["flash_decode"] = True
    layout.unpaged = tuple(unpaged)
    asked = dict(prefill_chunk=prefill_chunk, speculative=speculative,
                 prefix_caching=prefix_caching)
    for feature in FEATURES:
        if asked.get(feature) and feature not in layout.serves:
            raise ValueError(layout.refusal(feature, feature))
    if kv_layout == "paged":        # which this block's cache cannot be
        raise ValueError(layout.refusal("paged KV", "kv_layout='paged'"))
    return layout
