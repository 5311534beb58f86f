"""The latent decode kernel on the whole cache of rows, and its election.

Interpreter-mode goldens of ``flash_decode_attention_latent`` against
``write_token`` then ``cached_attention`` on the same rows (the kernel
takes the ``[L, B, 1, T, row]`` cache itself and a layer index, reads a
slot's live blocks once for scores and weighted sum alike, sums a row's
first ``kv_rank`` columns and puts the step's row in as it reads): the
output and the cache after the step; the election's table, alone and
through the engine on a backend reported as a TPU; the engine's greedy
tokens with the kernel forced; the counters that say what share of the
lanes a window read.

Kernel modules are imported inside the tests (conftest guard); shapes
stay small so the interpreter runs in seconds.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry

HEADS, ROW, RANK, SCALE = 4, 24, 16, 0.3
F32, BF16 = jnp.float32, jnp.bfloat16


def _lengths(bk, T):
    """An empty lane, one short of a block's edge, the edge, one past
    it, and the lane's last position (a full lane after the step)."""
    return [min(n, T - 1) for n in (0, 1, bk - 1, bk, bk + 1, T - 1)]


def _stale(r, shape, live, layer, dtype):
    """``(clean, stale)``: rows of ``layer`` with zeros, and the whole
    cache with 1e4, above each slot's length and in every other layer."""
    clean = r.randn(*shape[1:]).astype(np.float32) \
        * live[:, None, :, None]
    stale = np.full(shape, 1e4, np.float32)
    stale[layer] = np.where(live[:, None, :, None], clean, 1e4)
    return jnp.asarray(clean, dtype), jnp.asarray(stale, dtype)


def _close(got, ref, dtype, keep=slice(None)):
    tol = 1e-5 if dtype == F32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32)[keep],
                               np.asarray(ref, np.float32)[keep],
                               atol=tol, rtol=tol)


# --------------------------------------------------------------------------- #
# the kernel against write_token and cached_attention
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("bk,blocks", [(8, 1), (8, 2), (8, 8), (16, 3)])
def test_latent_kernel_reads_the_live_rows_alone(dtype, bk, blocks):
    """Lengths on every side of a block's edge, mixed over the slots;
    the rows above each slot's length and every other layer hold 1e4,
    and the answer is the one a cache of zeros there gives: the first
    ``RANK`` columns of ``cached_attention``'s weighted sum."""
    from autodist_tpu.kernel.pallas.flash_decode import \
        flash_decode_attention_latent
    from autodist_tpu.serving.kv_cache import cached_attention

    T = bk * blocks
    lengths = _lengths(bk, T)
    L, B, layer = 3, len(lengths), 1
    r = np.random.RandomState(blocks)
    q = jnp.asarray(r.randn(B, 1, HEADS, ROW), dtype)
    live = np.arange(T)[None, :] <= np.asarray(lengths)[:, None]   # [B, T]
    clean, stale = _stale(r, (L, B, 1, T, ROW), live, layer, dtype)
    lens = jnp.asarray(lengths, jnp.int32)
    ref = cached_attention(q, clean, clean, lens, dtype=dtype,
                           scale=SCALE)[..., :RANK]
    got = flash_decode_attention_latent(
        q, stale, layer, lens, kv_rank=RANK, scale=SCALE, dtype=dtype,
        block_k=bk)
    assert got.dtype == dtype and got.shape == (B, 1, HEADS, RANK)
    _close(got, ref, dtype)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("bk,blocks", [(8, 1), (8, 2), (8, 8), (16, 3)])
def test_latent_kernel_writes_the_row_as_write_token_does(dtype, bk, blocks):
    """``new_row``: the cache comes back as ``write_token`` leaves it,
    bit for bit — stale rows, other layers and a slot that is not active
    untouched — and the output is ``cached_attention``'s over the written
    rows."""
    from autodist_tpu.kernel.pallas.flash_decode import \
        flash_decode_attention_latent
    from autodist_tpu.serving.kv_cache import cached_attention, write_token

    T = bk * blocks
    lengths = _lengths(bk, T)
    L, B, layer, idle = 2, len(lengths), 1, 2
    r = np.random.RandomState(blocks)
    q = jnp.asarray(r.randn(B, 1, HEADS, ROW), dtype)
    new = jnp.asarray(r.randn(B, 1, 1, ROW), dtype)
    rows = jnp.asarray(r.randn(L, B, 1, T, ROW), dtype)
    lens = jnp.asarray(lengths, jnp.int32)
    active = jnp.arange(B) != idle
    want = write_token(rows, layer, new, lens) \
        .at[layer, idle].set(rows[layer, idle])
    ref = cached_attention(q, want[layer], want[layer], lens, dtype=dtype,
                           scale=SCALE)[..., :RANK]
    got, after = flash_decode_attention_latent(
        q, rows, layer, lens, kv_rank=RANK, scale=SCALE, new_row=new,
        active=active, dtype=dtype, block_k=bk)
    np.testing.assert_array_equal(np.asarray(after, np.float32),
                                  np.asarray(want, np.float32))
    _close(got, ref, dtype, np.asarray(active))


@pytest.mark.parametrize("max_len,block", [
    (128, 128), (256, 256), (384, 128), (512, 256), (640, 128)])
def test_every_block_the_election_may_pick(max_len, block):
    """The blocks ``latent_decode_block`` gives lanes of whole 128s, each
    with the step's row landing in every 128 columns of a block (what
    goes back to the cache around it): output and cache."""
    from autodist_tpu.kernel.pallas import flash_decode as fd
    from autodist_tpu.serving.kv_cache import cached_attention, write_token

    assert fd.latent_decode_block(max_len, ROW, RANK, F32) == block
    lengths = sorted({0, max_len - 1, *range(100, max_len, 128)})[:8]
    L, B, layer = 2, len(lengths), 0
    r = np.random.RandomState(max_len)
    q = jnp.asarray(r.randn(B, 1, HEADS, ROW), F32)
    new = jnp.asarray(r.randn(B, 1, 1, ROW), F32)
    rows = jnp.asarray(r.randn(L, B, 1, max_len, ROW), F32)
    lens = jnp.asarray(lengths, jnp.int32)
    want = write_token(rows, layer, new, lens)
    ref = cached_attention(q, want[layer], want[layer], lens, dtype=F32,
                           scale=SCALE)[..., :RANK]
    got, after = fd.flash_decode_attention_latent(
        q, rows, layer, lens, kv_rank=RANK, scale=SCALE, new_row=new,
        dtype=F32)
    np.testing.assert_array_equal(np.asarray(after), np.asarray(want))
    _close(got, ref, F32)


def test_an_idle_slot_reads_one_block_and_writes_nothing():
    """A slot that is not active: its lane comes back untouched whatever
    ``lengths`` says of it, and its first block alone is read — a NaN
    anywhere above it reaches no slot's output."""
    from autodist_tpu.kernel.pallas.flash_decode import \
        flash_decode_attention_latent

    bk, T, B = 8, 32, 3
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(B, 1, HEADS, ROW), F32)
    new = jnp.asarray(r.randn(B, 1, 1, ROW), F32)
    rows = jnp.asarray(r.randn(1, B, 1, T, ROW), F32) \
        .at[0, 1, 0, bk:].set(jnp.nan)
    got, after = flash_decode_attention_latent(
        q, rows, 0, jnp.asarray([20, 30, 5], jnp.int32), kv_rank=RANK,
        scale=SCALE, new_row=new, active=jnp.asarray([True, False, True]),
        block_k=bk)
    np.testing.assert_array_equal(np.asarray(after[0, 1]),
                                  np.asarray(rows[0, 1]))
    assert np.isfinite(np.asarray(got)).all()


def test_one_lowering_serves_every_layer():
    """The layer is an operand: a second layer's call traces nothing new,
    and only that layer's rows change."""
    from autodist_tpu.kernel.pallas import flash_decode as fd

    r = np.random.RandomState(1)
    q = jnp.asarray(r.randn(2, 1, HEADS, ROW), F32)
    new = jnp.asarray(r.randn(2, 1, 1, ROW), F32)
    rows = jnp.asarray(r.randn(3, 2, 1, 16, ROW), F32)
    lens = jnp.asarray([3, 9], jnp.int32)
    call = lambda layer: fd.flash_decode_attention_latent(
        q, rows, layer, lens, kv_rank=RANK, scale=SCALE, new_row=new,
        block_k=8)[1]
    call(0)
    traced = fd.flash_decode_latent_layer._cache_size()
    after = call(jnp.int32(2))
    assert fd.flash_decode_latent_layer._cache_size() == traced
    changed = np.asarray((after != rows).any(axis=(1, 2, 3, 4)))
    assert changed.tolist() == [False, False, True]


def test_latent_kernel_refuses_a_lane_no_block_divides():
    from autodist_tpu.kernel.pallas.flash_decode import \
        flash_decode_attention_latent

    rows = jnp.zeros((1, 1, 1, 200, ROW))
    q, lens = jnp.zeros((1, 1, HEADS, ROW)), jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="does not divide into blocks"):
        flash_decode_attention_latent(q, rows, 0, lens, kv_rank=RANK,
                                      scale=SCALE)
    with pytest.raises(ValueError, match="does not divide into blocks"):
        flash_decode_attention_latent(q, rows, 0, lens, kv_rank=RANK,
                                      scale=SCALE, block_k=128)


# --------------------------------------------------------------------------- #
# the election
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("word,backend,max_len,row,rank,dtype,block", [
    (None, "tpu", 3072, 576, 512, BF16, 256),    # the benchmark's cell
    (None, "tpu", 1920, 576, 512, BF16, 128),    # the longest that divides
    (None, "tpu", 256, 576, 512, BF16, 256),     # the measured threshold
    (None, "tpu", 128, 576, 512, BF16, None),    # a lane below it
    (None, "tpu", 3000, 576, 512, BF16, None),   # no block divides it
    (None, "tpu", 3072, 640, 512, BF16, None),   # whole lanes: kept row-major
    (None, "tpu", 3072, 568, 512, BF16, None),   # no whole bf16 sublane tile
    (None, "tpu", 3072, 568, 512, F32, 256),     # ... whole float32 ones
    (None, "tpu", 3072, 576, 520, BF16, None),   # the values end inside one
    (None, "cpu", 3072, 576, 512, BF16, None),   # the composed path
    (True, "cpu", 3072, 576, 512, BF16, 256),    # forced: the interpreter
    (True, "cpu", 48, 40, 32, F32, 16),          # ... on a copy, any divisor
    (False, "tpu", 3072, 576, 512, BF16, None),  # forbidden
])
def test_latent_decode_election(word, backend, max_len, row, rank, dtype,
                                block):
    from autodist_tpu.kernel.pallas.flash_decode import latent_decode_elected

    assert latent_decode_elected(word, max_len, row, rank, dtype,
                                 backend) == block


def _latent_lm(max_len=256, dtype=F32):
    """Two latent-attention layers, 4 heads on a row of 16 + 8."""
    from autodist_tpu.models import pipeline_lm as lm
    from autodist_tpu.models.transformer import (BlockSpec,
                                                 LatentAttentionSpec,
                                                 TransformerConfig)

    cfg = TransformerConfig(
        vocab_size=61, hidden_size=32, num_layers=2, num_heads=HEADS,
        mlp_dim=48, max_len=max_len, dtype=dtype, dropout_rate=0.0,
        attention_dropout_rate=0.0,
        block=BlockSpec(norm="rmsnorm", norm_placement="pre",
                        positions="rope", ffn="swiglu", bias=False,
                        tied_head=False,
                        latent=LatentAttentionSpec(RANK, 8, ROW - RANK, 8)))
    leaves, tree = jax.tree.flatten(
        lm.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.PRNGKey(0), len(leaves))
    params = tree.unflatten(
        [0.2 * jax.random.normal(k, s, dtype) for k, s in zip(keys, leaves)])
    return cfg, params


def _gauges():
    return {m["name"]: m["value"]
            for m in telemetry.get().registry.snapshot()
            if m["kind"] == "gauge"}


@pytest.mark.parametrize("backend,kernel,max_len,block", [
    ("tpu", None, 256, 256),
    ("tpu", ("quant_ring",), 256, 256),        # no word on flash_decode
    ("tpu", None, 128, None),                  # a lane below the threshold
    ("tpu", None, 200, None),                  # no block divides it
    ("tpu", {"flash_decode": False}, 256, None),
    ("cpu", None, 256, None),
    ("cpu", {"flash_decode": True}, 256, 256),
    ("cpu", {"flash_decode": True}, 48, 16),   # forced where it copies
])
def test_engine_elects_the_latent_kernel(monkeypatch, backend, kernel,
                                         max_len, block):
    """The engine's branch: the block reaches the layout and
    ``decode_block_len``, the kernel slot and both gauges say what was
    elected — ``kernel/latent_decode_elected`` 1 or 0 whenever the cache
    holds latent rows."""
    from autodist_tpu.serving import ServingEngine

    cfg, params = _latent_lm()
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    telemetry.reset()
    try:
        eng = ServingEngine(cfg, params, num_slots=2, max_len=max_len,
                            prefill_len=8, kernel=kernel)
        gauges = _gauges()
    finally:
        telemetry.reset()
    assert eng.kv.fused_block == block
    assert eng.decode_block_len == (block or max_len)
    assert bool(eng.kernel.get("flash_decode")) == bool(block)
    assert gauges["kernel/latent_decode_elected"] == int(bool(block))
    assert gauges.get("kernel/flash_decode_elected") == \
        (1 if block else None)


def test_a_dense_engine_says_nothing_of_the_latent_kernel():
    import optax

    from autodist_tpu.models.pipeline_lm import make_pipeline_lm_trainable
    from autodist_tpu.models.transformer import TransformerConfig
    from autodist_tpu.serving import ServingEngine

    cfg = TransformerConfig(
        vocab_size=32, hidden_size=16, num_layers=1, num_heads=2,
        mlp_dim=32, max_len=64, dtype=F32, dropout_rate=0.0,
        attention_dropout_rate=0.0)
    params = make_pipeline_lm_trainable(cfg, optax.sgd(0.05),
                                        jax.random.PRNGKey(0)).params
    telemetry.reset()
    try:
        ServingEngine(cfg, params, num_slots=2, max_len=64, prefill_len=8)
        assert "kernel/latent_decode_elected" not in _gauges()
    finally:
        telemetry.reset()


# --------------------------------------------------------------------------- #
# through the engine
# --------------------------------------------------------------------------- #
def test_forced_decode_program_hands_the_kernel_the_cache_itself():
    """Every layer of the decode body calls ONE inner function with the
    layer as an operand; its pallas_call takes the 5-D cache transposed
    and hands it back, and no ``write_token`` is left in the body."""
    from autodist_tpu.serving import ServingEngine

    from tests.unit.test_flash_decode_dense import _walk

    cfg, params = _latent_lm()
    eng = ServingEngine(cfg, params, num_slots=2, max_len=256,
                        prefill_len=8, decode_steps=2,
                        kernel={"flash_decode": True})
    c = eng.cache
    jaxpr = jax.make_jaxpr(eng._decode_jit.fn)(
        eng.params, c.k, c.v, c.lengths, eng._tok, eng.kv.table_arg(c),
        jnp.asarray(eng._sample_seeds), jnp.ones((2,), bool)).jaxpr
    calls = [e for e in _walk(jaxpr) if e.primitive.name == "jit"
             and e.params["name"] == "flash_decode_latent_layer"]
    assert len(calls) == cfg.num_layers
    assert len({id(e.params["jaxpr"].jaxpr) for e in calls}) == 1
    L, B, T = 2, 2, 256
    for e in calls:
        shapes = [v.aval.shape for v in e.invars]
        assert shapes.count((L, B, 1, ROW, T)) == 1, shapes
        assert shapes[2] == (1,), "the layer is an operand"
    pallas = [e for e in _walk(calls[0].params["jaxpr"].jaxpr)
              if e.primitive.name == "pallas_call"]
    assert len(pallas) == 1
    assert [v.aval.shape for v in pallas[0].outvars] \
        == [(B, HEADS, RANK), (L, B, 1, ROW, T)]
    assert not [e for e in _walk(jaxpr)
                if e.primitive.name == "dynamic_update_slice"
                and e.invars[0].aval.shape == (L, B, 1, T, ROW)]


def test_kv_block_counters_say_what_share_of_the_lanes_was_read():
    """One request of 250 prompt tokens beside an empty slot, K = 4, two
    windows, blocks of 128 (four a lane of 512): the first window's
    steps land at 250..253 (two blocks each), the second's at 254..257
    (two, two, three, three); the empty slot reads one block a step."""
    from autodist_tpu.serving import ContinuousBatcher, ServingEngine

    cfg, params = _latent_lm(max_len=640)
    eng = ServingEngine(cfg, params, num_slots=2, max_len=640,
                        prefill_len=256, decode_steps=4,
                        kernel={"flash_decode": True})
    assert eng.decode_block_len == 128
    telemetry.reset()
    try:
        b = ContinuousBatcher(eng)
        b.submit(list(range(1, 251)), max_new_tokens=9)
        b.run()
        counters = {m["name"]: m["value"]
                    for m in telemetry.get().registry.snapshot()
                    if m["kind"] == "counter"}
    finally:
        telemetry.reset()
    assert counters["serve/kv_blocks_attended"] == (8 + 4) + (10 + 4)
    assert counters["serve/kv_blocks_resident"] == 2 * 4 * 2 * 5
    assert counters["serve/latent_positions_read"] \
        == cfg.num_layers * sum(range(251, 259))


def test_the_scopes_the_roofline_reads():
    """The kernel's call wears ``latent_attention/latent_attend`` — the
    benchmark's ``decode_latent_attend_roofline_pct`` finds its device
    time by that component — and inside it the kernel's marker; the
    decode program holds no ``kv_write``."""
    import re

    from autodist_tpu.serving import ServingEngine

    cfg, params = _latent_lm()
    eng = ServingEngine(cfg, params, num_slots=2, max_len=256,
                        prefill_len=8, decode_steps=2,
                        kernel={"flash_decode": True})
    c = eng.cache
    names = set(re.findall(r'loc\("([^"]*)"', eng._decode_jit.lower(
        eng.params, c.k, c.v, c.lengths, eng._tok, eng.kv.table_arg(c),
        jnp.asarray(eng._sample_seeds), jnp.ones((2,), bool))
        .as_text(debug_info=True)))
    assert "latent_attention/latent_attend/jit(flash_decode_latent_layer)" \
        in names
    assert any(n.startswith("adtk_flash_decode/") for n in names)
    assert not any("kv_write" in n for n in names)


@pytest.mark.parametrize("records,says", [
    ([("engine/latent_lane_rows", 3072),
      ("kernel/latent_decode_elected", 1),
      ("kernel/flash_decode_elected", 1)], None),
    ([("engine/latent_lane_rows", 3072),
      ("kernel/latent_decode_elected", 0)], None),
    ([("engine/latent_lane_rows", 3072),
      ("kernel/latent_decode_elected", 2)], "1 (the fused kernel) or 0"),
    ([("kernel/latent_decode_elected", 1)], "caches latent rows"),
], ids=["elected", "composed", "neither", "no-latent-engine"])
def test_report_check_knows_the_gauge(tmp_path, records, says):
    import importlib
    import json
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "tools"))
    try:
        report = importlib.import_module("telemetry_report")
    finally:
        sys.path.pop(0)
    with open(tmp_path / "metrics.jsonl", "w") as f:
        f.writelines(json.dumps({"kind": "gauge", "name": n, "value": v})
                     + "\n" for n, v in records)
    problems = report.check_schema(str(tmp_path))
    assert (any(says in p for p in problems) if says else not problems), \
        problems
