"""Paged-KV serving goldens: block-granular allocation, block-aware
admission, the paged flash-decode kernel, and the sampling rung.

The acceptance bar (ISSUE 14): greedy decode under ``kv_layout="paged"``
matches the dense engine token-for-token across tp∈{1,2} ×
vocab-parallel — including the eviction/re-admission edge where a freed
block is reused by a new request mid-stream — the paged flash kernel
matches the composed gather+attention golden across block-boundary edge
lengths, a short-request mix admits strictly MORE concurrent requests
under paged than dense at equal pool bytes, and the ``decode_cost``
capacity objective elects paged exactly when length variance makes
dense reservation wasteful (both directions).  Plus the allocator's
coded-exhaustion/accounting contract and the sampling rung's
interleave-parity extension.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models.pipeline_lm import make_pipeline_lm_trainable
from autodist_tpu.models.transformer import TransformerConfig
from autodist_tpu.serving import (BlockAllocator, ContinuousBatcher,
                                  PoolExhaustedError, ServingEngine)
from autodist_tpu.serving import kv_cache
from autodist_tpu.serving.engine import seed_engine_kwargs
from tests.unit.test_serving import (ADMIT_SUBSETS, admit_id,
                                     check_prefill_admits, resident_engine)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

V = 33          # odd: V % 2 != 0 exercises the vocab zero-pad path
MAX_LEN = 24
PROMPT = [3, 1, 4, 1, 5]


def make_cfg(vocab=V, max_len=MAX_LEN):
    return TransformerConfig(
        vocab_size=vocab, hidden_size=16, num_layers=2, num_heads=2,
        mlp_dim=32, max_len=max_len, dtype=jnp.float32,
        dropout_rate=0.0, attention_dropout_rate=0.0)


@pytest.fixture(scope="module")
def cfg():
    return make_cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return make_pipeline_lm_trainable(
        cfg, optax.sgd(0.1), jax.random.PRNGKey(0)).params


def make_engine(cfg, params, *, kv_layout="dense", tp=1,
                vocab_parallel=False, slots=2, decode_steps=3,
                prefill_len=8, **kw):
    return ServingEngine(cfg, params, tensor_parallel=tp,
                         vocab_parallel=vocab_parallel, num_slots=slots,
                         max_len=cfg.max_len, prefill_len=prefill_len,
                         decode_steps=decode_steps, kv_layout=kv_layout,
                         **kw)


# --------------------------------------------------------------------- #
# the block allocator (pure host accounting)
# --------------------------------------------------------------------- #
def test_allocator_exhaustion_is_coded():
    a = BlockAllocator(4)
    got = a.alloc(3)
    assert len(got) == 3 and a.free_blocks == 1
    with pytest.raises(PoolExhaustedError, match="kv_pool_exhausted"):
        a.alloc(2)
    # the failed alloc must not leak blocks
    assert a.free_blocks == 1 and a.used_blocks == 3


def test_allocator_fragmentation_free_accounting():
    """One flat free list: any n <= free allocation succeeds whatever
    the alloc/free interleaving, and free + used == total always."""
    a = BlockAllocator(8)
    r = np.random.RandomState(0)
    held = []
    for _ in range(200):
        assert a.free_blocks + a.used_blocks == 8
        if held and r.rand() < 0.5:
            blocks = held.pop(r.randint(len(held)))
            a.free(blocks)
        else:
            n = int(r.randint(0, a.free_blocks + 1))
            held.append(a.alloc(n))
    # by construction no allocation of n <= free can ever fail
    a.free([b for blocks in held for b in blocks])
    assert a.free_blocks == 8 and a.used_blocks == 0
    assert sorted(a.alloc(8)) == list(range(8))


def test_allocator_rejects_double_free_and_foreign_ids():
    a = BlockAllocator(3)
    blocks = a.alloc(2)
    a.free(blocks)
    with pytest.raises(ValueError, match="double-free or"):
        a.free(blocks)
    b = BlockAllocator(3)
    b.alloc(1)
    with pytest.raises(ValueError, match="not allocated"):
        b.free([99])


def test_blocks_for_math():
    assert kv_cache.blocks_for(0, 16) == 0
    assert kv_cache.blocks_for(1, 16) == 1
    assert kv_cache.blocks_for(16, 16) == 1
    assert kv_cache.blocks_for(17, 16) == 2
    assert kv_cache.blocks_for(-3, 16) == 0


def test_init_paged_cache_validates_pool():
    with pytest.raises(ValueError, match="cannot hold even one"):
        kv_cache.init_paged_cache(1, 2, 2, 4, max_len=64, block_len=16,
                                  num_blocks=3)
    c = kv_cache.init_paged_cache(2, 3, 2, 4, max_len=32, block_len=8,
                                  num_blocks=10)
    assert c.k.shape == (2, 10, 2, 8, 4)
    assert c.block_table.shape == (3, 4)
    # pytree: the whole cache rides jit carries in one piece
    leaves = jax.tree_util.tree_leaves(c)
    assert len(leaves) == 4


# --------------------------------------------------------------------- #
# paged attention vs the dense math, and the paged flash kernel
# --------------------------------------------------------------------- #
def test_paged_cached_attention_matches_dense_with_identity_table():
    """With the table laying blocks out contiguously, the gathered lane
    IS the dense lane — paged attention must equal dense attention
    bit-for-bit."""
    rng = np.random.RandomState(0)
    B, H, d, bl, mb = 2, 2, 8, 8, 3
    T = mb * bl
    k_lane = jnp.asarray(rng.randn(B, H, T, d), jnp.float32)
    v_lane = jnp.asarray(rng.randn(B, H, T, d), jnp.float32)
    q = jnp.asarray(rng.randn(B, 1, H, d), jnp.float32)
    lengths = jnp.asarray([5, 17], jnp.int32)
    # pool block  s*mb + j  holds slot s's logical block j
    k_pool = k_lane.reshape(B, H, mb, bl, d).transpose(0, 2, 1, 3, 4) \
        .reshape(B * mb, H, bl, d)
    v_pool = v_lane.reshape(B, H, mb, bl, d).transpose(0, 2, 1, 3, 4) \
        .reshape(B * mb, H, bl, d)
    table = jnp.asarray(
        [[s * mb + j for j in range(mb)] for s in range(B)], jnp.int32)
    dense = kv_cache.cached_attention(q, k_lane, v_lane, lengths)
    paged = kv_cache.paged_cached_attention(q, k_pool, v_pool, lengths,
                                            table, block_len=bl)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))


@pytest.mark.parametrize("lengths", [[0, 1, 5], [15, 16, 17],
                                     [32, 47, 63]])
def test_paged_flash_decode_matches_composed_golden(lengths):
    """The paged flash kernel (CPU ``interpret=True``) equals the
    composed gather+masked-attention fallback across block-boundary
    edge lengths: shorter than one block, exactly on a boundary, one
    past it, and the full padded extent."""
    from autodist_tpu.kernel.pallas.flash_decode import \
        flash_decode_attention_paged

    rng = np.random.RandomState(1)
    B, H, d, bl, nb, mb = 3, 2, 8, 16, 13, 4
    k_pool = jnp.asarray(rng.randn(nb, H, bl, d), jnp.float32)
    v_pool = jnp.asarray(rng.randn(nb, H, bl, d), jnp.float32)
    q = jnp.asarray(rng.randn(B, 1, H, d), jnp.float32)
    table = jnp.asarray(rng.randint(0, nb, (B, mb)), jnp.int32)
    L = jnp.asarray(lengths, jnp.int32)
    ref = kv_cache.paged_cached_attention(q, k_pool, v_pool, L, table,
                                          block_len=bl)
    got = flash_decode_attention_paged(q, k_pool, v_pool, L, table,
                                       block_len=bl, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)


def test_paged_write_respects_write_mask():
    """An inactive slot's table row points at block 0 — possibly
    another slot's live block — so suppressed writes must keep the
    target row bit-for-bit."""
    c = kv_cache.init_paged_cache(1, 2, 2, 3, max_len=8, block_len=4,
                                  num_blocks=4)
    resident = c.k + 7.0
    kv = jnp.ones((2, 1, 2, 3), jnp.float32)
    table = jnp.asarray([[1, 2], [0, 0]], jnp.int32)   # slot1 unmapped
    positions = jnp.asarray([0, 0], jnp.int32)
    mask = jnp.asarray([True, False])
    k = kv_cache.paged_write_token(resident, 0, kv, positions, table, 4,
                                   write_mask=mask)
    # active slot 0's row landed in its block 1
    np.testing.assert_array_equal(np.asarray(k[0, 1, :, 0, :]),
                                  np.ones((2, 3)))
    # inactive slot 1's write into block 0 was suppressed entirely
    np.testing.assert_array_equal(np.asarray(k[0, 0]),
                                  np.asarray(resident[0, 0]))


def test_paged_prompt_write_lands_in_the_rows_own_blocks_only():
    """One admitted row through its table row: blocks holding a real
    prompt row are overwritten whole, a logical block past the prompt
    (its entry may name another slot's block) and one under
    ``write_from`` (a prefix hit, maybe shared) keep every bit."""
    c = kv_cache.init_paged_cache(1, 2, 2, 3, max_len=12, block_len=4,
                                  num_blocks=5)
    resident = c.k + 7.0
    kv = jnp.ones((1, 12, 2, 3), jnp.float32)          # [1, S, heads, dh]
    row = jnp.asarray([[3, 1, 0]], jnp.int32)          # block 0: not ours
    write = jax.jit(kv_cache.paged_write_prompt, static_argnums=(1, 4))
    k = write(resident, 0, kv, row, 4, jnp.asarray([6], jnp.int32))
    for block, new in enumerate([False, True, False, True, False]):
        np.testing.assert_array_equal(
            np.asarray(k[0, block]),
            np.ones((2, 4, 3)) if new else np.asarray(resident[0, block]))
    k = write(resident, 0, kv, row, 4, jnp.asarray([6], jnp.int32),
              jnp.asarray([1], jnp.int32))             # block 3 is a hit
    np.testing.assert_array_equal(np.asarray(k[0, 3]),
                                  np.asarray(resident[0, 3]))
    np.testing.assert_array_equal(np.asarray(k[0, 1]), np.ones((2, 4, 3)))


# --------------------------------------------------------------------- #
# the one-row prefill through the block table (the dense twin and the
# helpers: test_serving.py)
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module", params=[False, True],
                ids=["paged", "paged-prefix-caching"])
def resident_paged(request, cfg, params):
    # block_len 5 against a bucket of 8: every prompt ends in a partial
    # block, and a short one leaves its second table entry unreserved
    return resident_engine(cfg, params, kv_layout="paged", kv_block_len=5,
                           prefix_caching=request.param)


@pytest.mark.parametrize("admit", ADMIT_SUBSETS, ids=admit_id)
def test_paged_prefill_computes_and_writes_only_admitted_slots(
        resident_paged, cfg, params, admit):
    check_prefill_admits(resident_paged, cfg, params, admit)


# --------------------------------------------------------------------- #
# greedy parity goldens: paged == dense token-for-token
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("tp,vocab_parallel", [(1, False), (2, False),
                                               (2, True)])
def test_paged_greedy_matches_dense(cfg, params, tp, vocab_parallel):
    """Paged decode (non-divisible block_len 5 against max_len 24, so
    every request crosses a partial tail block) equals the dense engine
    token-for-token across tp∈{1,2} × vocab-parallel, V=33 odd."""
    reqs = [(PROMPT, 9), ([2, 7, 1], 6)]

    def run(kv_layout, **kw):
        b = ContinuousBatcher(make_engine(
            cfg, params, tp=tp, vocab_parallel=vocab_parallel,
            kv_layout=kv_layout, **kw))
        rids = [b.submit(p, max_new_tokens=m) for p, m in reqs]
        done = b.run()
        return [done[r].tokens for r in rids]

    dense = run("dense")
    paged = run("paged", kv_block_len=5)
    assert paged == dense
    assert all(0 <= t < cfg.vocab_size for toks in paged for t in toks)


def test_paged_block_recycling_mid_stream(cfg, params):
    """The eviction/re-admission edge: a pool too small for all
    requests at once forces later requests to wait for freed blocks and
    decode into them MID-STREAM of the survivors — every request still
    matches its run-alone tokens."""
    # 6-block pool of block_len 8; each request spans 2 blocks
    # (prompt 5 + budget 8 = 13) -> at most 3 in flight, requests 4-5
    # admit only into recycled blocks while earlier slots keep decoding.
    reqs = [(PROMPT, 8), ([2, 7, 1], 10), ([5, 5, 5, 5, 9], 7),
            ([1, 2, 3], 9), ([8, 6, 7], 11)]
    eng = make_engine(cfg, params, kv_layout="paged", slots=5,
                      kv_block_len=8, kv_num_blocks=6)
    b = ContinuousBatcher(eng)
    rids = [b.submit(p, max_new_tokens=m) for p, m in reqs]
    inter = b.run()
    assert eng.free_blocks == 6           # all blocks returned
    for (p, m), rid in zip(reqs, rids):
        solo = ContinuousBatcher(make_engine(
            cfg, params, kv_layout="paged", slots=5, kv_block_len=8,
            kv_num_blocks=6))
        srid = solo.submit(p, max_new_tokens=m)
        assert inter[rid].tokens == solo.run()[srid].tokens, rid


def test_paged_max_len_eviction(cfg, params):
    """The over-budget truncation edge rides the paged layout too: the
    clamped tail write lands in the slot's own tail block (never block
    0), so a concurrent short request's tokens are unperturbed."""
    b = ContinuousBatcher(make_engine(cfg, params, kv_layout="paged",
                                      kv_block_len=5))
    rid = b.submit(PROMPT, max_new_tokens=200)
    short = b.submit([2, 7], max_new_tokens=3)
    done = b.run()
    assert done[rid].finish_reason == "max_len"
    assert len(done[rid].tokens) == cfg.max_len - len(PROMPT)
    solo = ContinuousBatcher(make_engine(cfg, params))
    srid = solo.submit([2, 7], max_new_tokens=3)
    assert done[short].tokens == solo.run()[srid].tokens


# --------------------------------------------------------------------- #
# block-aware admission: free blocks, not slots
# --------------------------------------------------------------------- #
def test_short_mix_capacity_paged_beats_dense(cfg, params):
    """At EQUAL pool bytes (2 full max_len lanes == 6 blocks of 8), a
    short-request mix reaches strictly higher peak concurrency under
    paged admission than the dense slot ceiling — the ISSUE 14
    acceptance capacity claim."""
    reqs = [([2, 3], 4)] * 6                       # span 6 -> 1 block

    def peak(engine):
        b = ContinuousBatcher(engine)
        for p, m in reqs:
            b.submit(p, max_new_tokens=m)
        peak = 0
        while b._queue or b.active_slots:
            b.step()
            peak = max(peak, b.active_slots)
        return peak

    dense_peak = peak(make_engine(cfg, params, slots=2))
    paged_peak = peak(make_engine(cfg, params, kv_layout="paged",
                                  slots=6, kv_block_len=8,
                                  kv_num_blocks=6))
    assert dense_peak == 2                          # slot-bound
    assert paged_peak > dense_peak                  # block-bound: 6


def test_admission_gates_on_free_blocks_head_of_line(cfg, params):
    """A head request too big for the current free pool WAITS (no
    queue-jumping — admission order stays deterministic) and the
    engine's reserve path is never driven into PoolExhaustedError."""
    eng = make_engine(cfg, params, kv_layout="paged", slots=4,
                      kv_block_len=8, kv_num_blocks=3)
    b = ContinuousBatcher(eng)
    big = b.submit(PROMPT, max_new_tokens=18)      # 23 -> 3 blocks
    small = b.submit([2, 7], max_new_tokens=4)     # 6 -> 1 block
    b.step()                                       # one admission round
    # the whole pool went to the head request; the small one queued
    # even though 3 slots are free
    assert b.active_slots == 1 and len(b._queue) == 1
    assert eng.free_blocks == 0
    done = b.run()
    assert set(done) == {big, small}
    assert eng.free_blocks == 3


def test_cache_block_table_mirrors_live_reservations(cfg, params):
    """The device-side ``engine.cache.block_table`` is the complete
    decode state, not a stale zeros placeholder: it reflects every
    reserve/release the moment it happens (a consumer serializing the
    cache pytree between dispatches — elastic checkpointing, debug
    dumps — must see the real mapping), and it is the SAME array the
    compiled programs consume."""
    eng = make_engine(cfg, params, kv_layout="paged", slots=3,
                      kv_block_len=8, kv_num_blocks=6)
    assert np.all(np.asarray(eng.cache.block_table) == 0)
    eng.reserve_slot(1, 5, 8)                  # 13 -> 2 blocks
    np.testing.assert_array_equal(np.asarray(eng.cache.block_table),
                                  eng.kv.table)
    assert np.any(np.asarray(eng.cache.block_table)[1] != 0)
    assert eng.kv.table_arg(eng.cache) is eng.cache.block_table
    eng.release_slot(1)
    np.testing.assert_array_equal(np.asarray(eng.cache.block_table),
                                  np.zeros_like(eng.kv.table))


def test_engine_reserve_release_accounting(cfg, params):
    eng = make_engine(cfg, params, kv_layout="paged", slots=3,
                      kv_block_len=8, kv_num_blocks=6)
    assert eng.blocks_needed(5, 8) == 2            # 13 -> 2 blocks
    assert eng.blocks_needed(5, 200) == 3          # clamped at max_len
    eng.reserve_slot(0, 5, 8)
    assert eng.free_blocks == 4
    with pytest.raises(ValueError, match="already holds"):
        eng.reserve_slot(0, 2, 2)
    eng.release_slot(0)
    assert eng.free_blocks == 6
    eng.release_slot(0)                            # idempotent
    assert eng.free_blocks == 6
    # dense: the predicate is vacuous
    dense = make_engine(cfg, params)
    assert dense.blocks_needed(5, 8) == 0 and dense.free_blocks == 0


# --------------------------------------------------------------------- #
# the layouts' host protocol, without an engine: no model, no program
# --------------------------------------------------------------------- #
LAYOUT_DIMS = (2, 3, 2, 8, 16)     # layers, slots, heads, head_dim, max_len
SHARED = list(range(40, 50))       # 10 tokens: two blocks of 4 and a tail


def _paged_layout(num_blocks=12):
    layout = kv_cache.PagedLayout(LAYOUT_DIMS, {}, block_len=4,
                                  num_blocks=num_blocks,
                                  prefix_caching=True)
    return layout, kv_cache.PagedKVCache(None, None, None, None)


def _admit_shared(layout, cache, slot):
    """Reserve ``slot`` for ``SHARED`` + 4 tokens and publish what its
    prefill would have written."""
    cache, hits = layout.reserve(cache, slot, len(SHARED), 4, SHARED)
    layout.register(np.arange(LAYOUT_DIMS[1]) == slot)
    return cache, hits


def _reserve_release_restores_the_pool():
    layout, cache = _paged_layout()
    assert layout.accounting() == (12, 0, 12)
    assert layout.blocks_needed(10, 4) == 4
    cache, hits = layout.reserve(cache, 1, 10, 4)
    assert hits == 0 and layout.accounting() == (8, 4, 12)
    blocks = layout.slot_blocks(1)
    assert len(blocks) == 4 and layout.slot_blocks(0) == []
    # the row: its blocks, then tail-filled with the last one
    assert list(layout.table[1]) == blocks
    np.testing.assert_array_equal(np.asarray(cache.block_table),
                                  layout.table)
    with pytest.raises(ValueError, match="already holds"):
        layout.reserve(cache, 1, 4, 4)
    cache = layout.release(cache, 1)
    assert layout.accounting() == (12, 0, 12)
    assert not layout.table.any() and layout.slot_blocks(1) == []
    assert layout.release(cache, 1) is cache           # idempotent


def _prefix_hit_charges_the_novel_suffix_and_the_cow_reserve():
    layout, cache = _paged_layout()
    cache, hits = _admit_shared(layout, cache, 0)
    assert hits == 0 and layout.accounting() == (8, 4, 12)
    # the same prompt again: both full blocks and the tail are shared;
    # the pool supplies the one block past the prompt and the
    # replacement the shared tail's first write will be copied into
    assert layout.blocks_needed(10, 4, SHARED) == 2
    # a prompt that parts ways inside the second block shares one
    assert layout.blocks_needed(10, 4, SHARED[:5] + [0] * 5) == 3
    cache, hits = layout.reserve(cache, 1, 10, 4, SHARED)
    assert hits == 3 and layout.accounting() == (6, 6, 12)
    assert layout.slot_blocks(1)[:3] == layout.slot_blocks(0)[:3]
    assert layout.write_from[1] == 3 and layout.write_from[0] == 0
    tail = layout.slot_blocks(0)[2]
    assert layout.allocator.refcount(tail) == 2
    # the sharer leaves: its reference and the parked replacement go back
    cache = layout.release(cache, 1)
    assert layout.accounting() == (8, 4, 12)
    assert layout.allocator.refcount(tail) == 1
    layout.release(cache, 0)
    assert layout.accounting() == (12, 0, 12)
    assert layout.blocks_needed(10, 4, SHARED) == 4    # index retired


def _failed_reservation_leaves_every_refcount():
    layout, cache = _paged_layout(num_blocks=6)
    cache, _ = _admit_shared(layout, cache, 0)
    cache, hits = layout.reserve(cache, 1, 10, 4, SHARED)
    assert hits == 3 and layout.accounting() == (0, 6, 6)
    before = ([layout.allocator.refcount(b) for b in range(6)],
              layout.table.copy(), layout.write_from.copy())
    assert layout.blocks_needed(10, 4, SHARED) == 2
    with pytest.raises(PoolExhaustedError):
        layout.reserve(cache, 2, 10, 4, SHARED)
    assert [layout.allocator.refcount(b) for b in range(6)] == before[0]
    np.testing.assert_array_equal(layout.table, before[1])
    np.testing.assert_array_equal(layout.write_from, before[2])
    assert layout.slot_blocks(2) == []
    assert layout.accounting() == (0, 6, 6)


def _dense_layout_accounts_for_nothing():
    layout = kv_cache.DenseLayout(LAYOUT_DIMS, {})
    cache = object()        # anything: the hooks must not look inside

    def no_device(*a, **k):
        raise AssertionError("a dense host hook touched the device")

    with pytest.MonkeyPatch.context() as patch:
        for name in ("device_put", "device_get", "jit"):
            patch.setattr(jax, name, no_device)
        patch.setattr(jnp, "asarray", no_device)
        assert layout.blocks_needed(10, 4, SHARED) == 0
        assert layout.accounting() == (0, 0, 0)
        assert layout.reserve(cache, 0, 10, 4, SHARED) == (cache, 0)
        assert layout.release(cache, 0) is cache
        assert layout.protect(cache, np.ones(3, bool), 4) is cache
    assert layout.decode_block_len == 16
    assert layout.table.shape == (3, 1) and not layout.table.any()


@pytest.mark.parametrize("case", [
    _reserve_release_restores_the_pool,
    _prefix_hit_charges_the_novel_suffix_and_the_cow_reserve,
    _failed_reservation_leaves_every_refcount,
    _dense_layout_accounts_for_nothing,
], ids=lambda f: f.__name__.strip("_"))
def test_layout_host_protocol(case):
    case()


# --------------------------------------------------------------------- #
# engine config validation + Strategy-IR seeding
# --------------------------------------------------------------------- #
def test_engine_validates_kv_layout(cfg, params):
    from autodist_tpu.strategy.ir import UnknownKVLayoutError

    with pytest.raises(UnknownKVLayoutError, match="blocked"):
        make_engine(cfg, params, kv_layout="blocked")
    with pytest.raises(ValueError, match="cannot hold even one"):
        make_engine(cfg, params, kv_layout="paged", kv_block_len=8,
                    kv_num_blocks=2)               # max_len 24 -> 3
    with pytest.raises(ValueError, match="temperature"):
        make_engine(cfg, params, temperature=-0.1)
    with pytest.raises(ValueError, match="top_k"):
        make_engine(cfg, params, top_k=-1)


def test_seed_engine_kwargs_threads_kv_layout():
    from autodist_tpu.strategy.ir import GraphConfig, Strategy

    strategy = Strategy(node_configs=[], graph_config=GraphConfig(
        replicas=1, lowering="pipeline",
        parallel={"tensor_parallel": 1, "kv_layout": "paged"}))
    kwargs = seed_engine_kwargs({}, strategy)
    assert kwargs["kv_layout"] == "paged"
    # pre-PR-14 strategies (no knob) seed dense
    old = Strategy(node_configs=[], graph_config=GraphConfig(
        replicas=1, lowering="pipeline", parallel={}))
    assert seed_engine_kwargs({}, old)["kv_layout"] == "dense"


def test_normalize_kv_layout_contract():
    from autodist_tpu.strategy.ir import (UnknownKVLayoutError,
                                          normalize_kv_layout)

    assert normalize_kv_layout(None) == "dense"
    assert normalize_kv_layout("") == "dense"
    assert normalize_kv_layout("paged") == "paged"
    with pytest.raises(UnknownKVLayoutError):
        normalize_kv_layout("vllm")


# --------------------------------------------------------------------- #
# the sampling rung: temperature/top_k with interleave parity
# --------------------------------------------------------------------- #
def _sample_stream(cfg, params, *, interleaved, tp=1,
                   vocab_parallel=False, kv_layout="dense",
                   temperature=0.8, top_k=5, seed=11):
    b = ContinuousBatcher(make_engine(
        cfg, params, tp=tp, vocab_parallel=vocab_parallel,
        kv_layout=kv_layout, temperature=temperature, top_k=top_k))
    rid = b.submit(PROMPT, max_new_tokens=7, seed=seed)
    if interleaved:
        b.submit([2, 7], max_new_tokens=5, seed=99)
    return b.run()[rid].tokens


def test_sampled_interleave_parity(cfg, params):
    """A sampled stream keyed per (request seed, context length) is
    identical run-alone, interleaved, under tp=2 × vocab-parallel, and
    under the paged layout — the interleave-parity contract extended to
    sampling."""
    alone = _sample_stream(cfg, params, interleaved=False)
    assert alone == _sample_stream(cfg, params, interleaved=True)
    assert alone == _sample_stream(cfg, params, interleaved=True, tp=2,
                                   vocab_parallel=True)
    assert alone == _sample_stream(cfg, params, interleaved=True,
                                   kv_layout="paged")
    assert all(0 <= t < cfg.vocab_size for t in alone)


def test_sampled_streams_vary_by_seed_and_temperature(cfg, params):
    base = _sample_stream(cfg, params, interleaved=False, seed=11)
    other = _sample_stream(cfg, params, interleaved=False, seed=12)
    hot = _sample_stream(cfg, params, interleaved=False, seed=11,
                         temperature=5.0, top_k=0)
    assert base != other or base != hot   # sampling actually samples


def test_temperature_zero_is_bit_identical_to_greedy(cfg, params):
    """temperature == 0 compiles the exact pre-sampling program (the
    sampler is never traced), so the tokens ARE the greedy goldens —
    whatever seed the request carries."""
    greedy = ContinuousBatcher(make_engine(cfg, params))
    g = greedy.submit(PROMPT, max_new_tokens=9)
    want = greedy.run()[g].tokens
    t0 = ContinuousBatcher(make_engine(cfg, params, temperature=0.0))
    rid = t0.submit(PROMPT, max_new_tokens=9, seed=123)
    assert t0.run()[rid].tokens == want


def test_top_k_one_recovers_greedy_at_any_temperature(cfg, params):
    """top_k=1 restricts sampling to the argmax row, so even at a high
    temperature the stream equals the greedy tokens — the sampler's
    distributional clamp, pinned across tp and the paged layout."""
    greedy = ContinuousBatcher(make_engine(cfg, params))
    g = greedy.submit(PROMPT, max_new_tokens=7)
    want = greedy.run()[g].tokens
    for kw in ({}, {"tp": 2, "vocab_parallel": True},
               {"kv_layout": "paged"}):
        got = _sample_stream(cfg, params, interleaved=False,
                             temperature=5.0, top_k=1, **kw)
        assert got == want, kw


def test_sampler_rejects_temperature_zero():
    from autodist_tpu.parallel.tensor import vocab_parallel_sample_token

    with pytest.raises(ValueError, match="greedy"):
        vocab_parallel_sample_token(
            jnp.zeros((1, 4)), jnp.zeros((8, 4)), vocab_size=8,
            seeds=jnp.zeros((1,), jnp.int32),
            positions=jnp.zeros((1,), jnp.int32), temperature=0.0)


# --------------------------------------------------------------------- #
# the cost model's capacity objective (election pinned both ways)
# --------------------------------------------------------------------- #
def test_decode_cost_elects_paged_exactly_when_variance_pays():
    from autodist_tpu.resource import ResourceSpec
    from autodist_tpu.simulator import CostModel

    trainable = make_pipeline_lm_trainable(
        make_cfg(vocab=512, max_len=64), optax.sgd(0.1),
        jax.random.PRNGKey(0))
    rs = ResourceSpec({"topology": {"platform": "cpu",
                                    "num_devices": 2}})
    cm = CostModel(rs)
    # short-request mix: paged's per-request residency is ~1 block
    # instead of the max_len lane -> capacity multiplies
    dense = cm.decode_cost(trainable, {"tensor_parallel": 1},
                           max_len=2048, mean_request_len=64.0)
    paged = cm.decode_cost(trainable, {"tensor_parallel": 1,
                                       "kv_layout": "paged"},
                           max_len=2048, mean_request_len=64.0)
    assert paged.request_capacity > dense.request_capacity
    assert paged.serve_score < dense.serve_score       # paged elected
    # latency side still pays the table indirection
    assert paged.token_time_s > dense.token_time_s
    # no-variance mix: capacities tie (block-rounded), the indirection
    # overhead decides -> dense elected
    d2 = cm.decode_cost(trainable, {"tensor_parallel": 1},
                        max_len=2048, mean_request_len=2048.0)
    p2 = cm.decode_cost(trainable, {"tensor_parallel": 1,
                                    "kv_layout": "paged"},
                        max_len=2048, mean_request_len=2048.0)
    assert p2.serve_score > d2.serve_score             # dense elected


def test_rank_serving_capacity_objective_both_ways():
    from autodist_tpu.resource import ResourceSpec
    from autodist_tpu.simulator import rank_serving

    trainable = make_pipeline_lm_trainable(
        make_cfg(vocab=512, max_len=64), optax.sgd(0.1),
        jax.random.PRNGKey(0))
    rs = ResourceSpec({"topology": {"platform": "cpu",
                                    "num_devices": 2}})
    short = rank_serving(trainable, rs, objective="capacity",
                         mean_request_len=64.0, max_len=2048)
    assert short[0][0].get("kv_layout") == "paged"
    uniform = rank_serving(trainable, rs, objective="capacity",
                           mean_request_len=2048.0, max_len=2048)
    assert uniform[0][0].get("kv_layout", "dense") == "dense"
    # the latency objective ignores capacity and keeps dense first
    # (paged only pays the indirection there)
    latency = rank_serving(trainable, rs, max_len=2048)
    assert latency[0][0].get("kv_layout", "dense") == "dense"
    with pytest.raises(ValueError, match="objective"):
        rank_serving(trainable, rs, objective="throughput")


def test_default_serving_candidates_carry_layouts():
    from autodist_tpu.simulator.auto_strategy import \
        default_serving_candidates

    cands = default_serving_candidates(2)
    layouts = {(c.get("tensor_parallel"), c.get("kv_layout", "dense"))
               for c in cands}
    assert (1, "dense") in layouts and (1, "paged") in layouts
    assert (2, "paged") in layouts
    # a dense candidate carries NO kv_layout key: its JSON round-trips
    # byte-identically to a pre-PR-14 config
    assert all("kv_layout" not in c or c["kv_layout"] != "dense"
               for c in cands)


# --------------------------------------------------------------------- #
# program lint: the ADT115 paged-cache rule (mutations ride the
# test_analysis matrix; here the honest programs + derivation)
# --------------------------------------------------------------------- #
def test_rules_for_decode_derive_paged_contract():
    from autodist_tpu.analysis import rules_for_decode

    paged = rules_for_decode(1, False, vocab_size=93, max_len=57,
                             num_layers=2, num_slots=3, heads_local=2,
                             head_dim=8, kv_layout="paged",
                             pool_blocks=13)
    codes = {r.code for r in paged}
    assert "ADT115" in codes
    dense = rules_for_decode(1, False, vocab_size=93, max_len=57,
                             num_layers=2, num_slots=3, heads_local=2,
                             head_dim=8)
    assert "ADT115" not in {r.code for r in dense}
    # flash-elected paged: the rule stays but its gather half is off
    # (the page walk lives inside the Pallas kernel)
    flash = rules_for_decode(1, False, vocab_size=93, max_len=57,
                             num_layers=2, num_slots=3, heads_local=2,
                             head_dim=8, kv_layout="paged",
                             pool_blocks=13, kernel=("flash_decode",))
    fr = [r for r in flash if r.code == "ADT115"]
    assert len(fr) == 1


def test_paged_decode_program_is_lint_clean():
    """The compiled paged decode program carries ZERO dense
    [slots x max_len] cache buffers and >= 1 block-table gather — the
    ISSUE 14 acceptance structure, on the real program."""
    from autodist_tpu.analysis import lint_program, rules_for_decode
    from autodist_tpu.analysis import programs

    text = programs.decode_step_text(1, False, kv_layout="paged")
    rules = rules_for_decode(
        1, False, vocab_size=programs.DEC_V, max_len=programs.DEC_T,
        num_layers=programs.DEC_LAYERS, num_slots=programs.DEC_SLOTS,
        heads_local=2, head_dim=programs.DEC_HEAD_DIM,
        kv_layout="paged", pool_blocks=programs.DEC_POOL_BLOCKS)
    report = lint_program(text, rules, where="decode/paged")
    assert not report.errors, [d.to_dict() for d in report.errors]
    # and the dense sibling DOES carry the lane the rule forbids
    from autodist_tpu.analysis.facts import ProgramFacts
    dense_facts = ProgramFacts.from_hlo(
        programs.decode_step_text(1, False))
    assert dense_facts.buffers_with_dims(
        (programs.DEC_SLOTS, programs.DEC_T)) > 0


# --------------------------------------------------------------------- #
# telemetry: pool gauges + kv_layout record field, schema-gated
# --------------------------------------------------------------------- #
def test_paged_telemetry_gauges_and_schema_gate(cfg, params, tmp_path):
    telemetry.reset()
    telemetry.configure(out_dir=str(tmp_path), enabled=True)
    try:
        b = ContinuousBatcher(make_engine(cfg, params,
                                          kv_layout="paged",
                                          kv_block_len=8))
        rid = b.submit(PROMPT, max_new_tokens=4)
        b.run()
        paths = telemetry.flush()
    finally:
        telemetry.reset()
    with open(paths["metrics"]) as f:
        recs = [json.loads(line) for line in f]
    serve = next(r for r in recs if r.get("kind") == "serve")
    assert serve["request"] == rid
    assert serve["kv_layout"] == "paged"
    gauges = {r["name"]: r["value"] for r in recs
              if r.get("kind") == "gauge"}
    assert "serve/kv_blocks_free" in gauges
    assert "serve/kv_blocks_used" in gauges
    assert gauges["serve/kv_blocks_used"] == 0     # all released

    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    assert telemetry_report.check_schema(str(tmp_path)) == []
    md = telemetry_report.render(str(tmp_path))
    assert "paged" in md and "kv block pool" in md

    # a paged run stripped of its pool gauges fails the CI gate
    metrics = os.path.join(tmp_path, "metrics.jsonl")
    with open(metrics) as f:
        kept = [line for line in f
                if "serve/kv_blocks" not in line]
    with open(metrics, "w") as f:
        f.writelines(kept)
    problems = telemetry_report.check_schema(str(tmp_path))
    assert any("kv_blocks" in p for p in problems)


def test_dense_run_passes_schema_without_pool_gauges(cfg, params,
                                                     tmp_path):
    """Dense runs carry kv_layout="dense" and owe no pool gauges."""
    telemetry.reset()
    telemetry.configure(out_dir=str(tmp_path), enabled=True)
    try:
        b = ContinuousBatcher(make_engine(cfg, params))
        b.submit(PROMPT, max_new_tokens=3)
        b.run()
        telemetry.flush()
    finally:
        telemetry.reset()
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    assert telemetry_report.check_schema(str(tmp_path)) == []
