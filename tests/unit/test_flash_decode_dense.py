"""The dense flash-decode kernel on the whole cache, and its election.

Interpreter-mode goldens of ``flash_decode_attention_dense`` against
``cached_attention`` (the kernel takes the ``[L, B, H, T, d]`` cache
itself and a layer index; dead blocks and stale rows are unreachable;
a group of query heads on each key/value head rides in its rows),
the engine's greedy parity with the kernel forced where it reads the
cache in place, the shape of the forced decode program (one inner
function for every layer, the layer an operand, no slice of the
cache), the default election on a backend reported as a TPU, and the
``serve/kv_blocks_*`` counter pair.

Kernel modules are imported inside the tests (conftest guard); shapes
stay tiny so the interpreter runs in seconds.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import telemetry

BLOCK = 8


def _lm_cfg(**kw):
    from autodist_tpu.models.transformer import TransformerConfig

    base = dict(vocab_size=32, hidden_size=16, num_layers=2,
                num_heads=2, mlp_dim=32, max_len=512, dtype=jnp.float32,
                dropout_rate=0.0, attention_dropout_rate=0.0)
    base.update(kw)
    return TransformerConfig(**base)


def _lm_params(cfg):
    from autodist_tpu.models.pipeline_lm import make_pipeline_lm_trainable

    return make_pipeline_lm_trainable(cfg, optax.sgd(0.05),
                                      jax.random.PRNGKey(0)).params


def _grouped(kv_heads, **kw):
    """A plain decoder whose ``num_heads`` query heads read ``kv_heads``
    key/value heads, with seeded weights (such a block is served, not
    trained)."""
    from autodist_tpu.models import pipeline_lm as lm
    from autodist_tpu.models.transformer import BlockSpec

    cfg = _lm_cfg(block=BlockSpec(kv_heads=kv_heads), **kw)
    leaves, tree = jax.tree.flatten(
        lm.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.PRNGKey(0), len(leaves))
    return cfg, tree.unflatten([0.2 * jax.random.normal(k, s, cfg.dtype)
                                for k, s in zip(keys, leaves)])


# --------------------------------------------------------------------------- #
# the kernel against cached_attention
# --------------------------------------------------------------------------- #
def _block(head_dim):
    """Heads of 128 are read as stored, ``[block, d]`` tiles whose rows
    go back to the cache a sublane tile (16 of bf16) at a time."""
    return BLOCK if head_dim < 128 else 16


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("blocks", [1, 2, 8])
@pytest.mark.parametrize("heads_per_step,d,group", [
    (1, 8, 1), (4, 8, 1), (2, 128, 1),
    (2, 64, 2), (1, 256, 8),    # query heads a key/value head
])
def test_dense_kernel_golden_vs_cached_attention(dtype, blocks,
                                                 heads_per_step, d, group):
    """Lengths on every side of a block's edge, mixed over the slots;
    the rows above each slot's length and every other layer hold 1e4,
    and the answer is the one a cache of zeros there gives."""
    from autodist_tpu.kernel.pallas.flash_decode import \
        flash_decode_attention_dense
    from autodist_tpu.serving.kv_cache import cached_attention

    BLOCK = _block(d)
    T = BLOCK * blocks
    lengths = [min(n, T - 1) for n in
               (0, 1, BLOCK - 1, BLOCK, BLOCK + 1, T - 1)]
    L, B, H, layer = 3, len(lengths), 4, 1
    r = np.random.RandomState(blocks)
    q = jnp.asarray(r.randn(B, 1, H * group, d), dtype)
    clean = r.randn(2, B, H, T, d).astype(np.float32)
    live = np.arange(T)[None, :] <= np.asarray(lengths)[:, None]  # [B, T]
    clean *= live[None, :, None, :, None]
    stale = np.full((2, L, B, H, T, d), 1e4, np.float32)
    stale[:, layer] = np.where(live[None, :, None, :, None], clean, 1e4)
    k, v = (jnp.asarray(a, dtype) for a in stale)
    lens = jnp.asarray(lengths, jnp.int32)
    ref = cached_attention(q, jnp.asarray(clean[0], dtype),
                           jnp.asarray(clean[1], dtype), lens, dtype=dtype)
    got = flash_decode_attention_dense(
        q, k, v, layer, lens, dtype=dtype, block_k=BLOCK,
        heads_per_step=heads_per_step)
    assert got.dtype == dtype and got.shape == (B, 1, H * group, d)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("blocks", [1, 2, 8])
@pytest.mark.parametrize("d,group", [
    (8, 1), (128, 1),
    (64, 2), (64, 8), (128, 2), (128, 8), (256, 2), (256, 8),
])
def test_dense_kernel_writes_the_token_as_write_token_does(dtype, blocks, d,
                                                           group):
    """``new_kv``: the caches come back as ``write_token`` leaves them,
    bit for bit (a slot that is not active untouched: it writes nothing
    and reads one block), and the output is ``cached_attention``'s over
    the written cache — with a ``group`` of query heads on each
    key/value head too: one row a key/value head goes in, not one a
    query head."""
    from autodist_tpu.kernel.pallas.flash_decode import \
        flash_decode_attention_dense
    from autodist_tpu.serving.kv_cache import cached_attention, write_token

    BLOCK = _block(d)
    T = BLOCK * blocks
    lengths = [min(n, T - 1) for n in
               (0, 1, BLOCK - 1, BLOCK, BLOCK + 1, T - 1)]
    L, B, H, layer = 2, len(lengths), 4, 1
    r = np.random.RandomState(blocks)
    q = jnp.asarray(r.randn(B, 1, H * group, d), dtype)
    k_new, v_new = (jnp.asarray(r.randn(B, 1, H, d), dtype)
                    for _ in range(2))
    k, v = (jnp.asarray(r.randn(L, B, H, T, d), dtype) for _ in range(2))
    lens = jnp.asarray(lengths, jnp.int32)
    active = jnp.asarray([True, True, False, True, True, True])
    k_ref, v_ref = (write_token(c, layer, n, jnp.where(active, lens, 0))
                    .at[layer, 2].set(c[layer, 2])
                    for c, n in ((k, k_new), (v, v_new)))
    ref = cached_attention(q, k_ref[layer], v_ref[layer], lens, dtype=dtype)
    got, k_got, v_got = flash_decode_attention_dense(
        q, k, v, layer, lens, new_kv=(k_new, v_new), active=active,
        dtype=dtype, block_k=BLOCK, heads_per_step=2)
    np.testing.assert_array_equal(np.asarray(k_got, np.float32),
                                  np.asarray(k_ref, np.float32))
    np.testing.assert_array_equal(np.asarray(v_got, np.float32),
                                  np.asarray(v_ref, np.float32))
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    keep = np.asarray(active)
    np.testing.assert_allclose(np.asarray(got, np.float32)[keep],
                               np.asarray(ref, np.float32)[keep],
                               atol=tol, rtol=tol)


def test_dense_kernel_refuses_a_lane_no_block_divides():
    from autodist_tpu.kernel.pallas.flash_decode import \
        flash_decode_attention_dense

    z = jnp.zeros((1, 1, 1, 20, 8))
    with pytest.raises(ValueError, match="does not divide into blocks"):
        flash_decode_attention_dense(jnp.zeros((1, 1, 1, 8)), z, z, 0,
                                     jnp.zeros((1,), jnp.int32),
                                     block_k=8)


def test_dense_kernel_refuses_query_heads_no_group_divides():
    from autodist_tpu.kernel.pallas.flash_decode import \
        flash_decode_attention_dense

    z = jnp.zeros((1, 1, 2, 16, 8))
    with pytest.raises(ValueError, match="multiple of the cache's"):
        flash_decode_attention_dense(jnp.zeros((1, 1, 3, 8)), z, z, 0,
                                     jnp.zeros((1,), jnp.int32),
                                     block_k=8)


@pytest.mark.parametrize("max_len,head_dim,block", [
    (1024, 64, 128), (512, 64, 128), (256, 64, 128), (128, 32, 128),
    (1000, 64, None),     # no block divides the lane
    (1024, 128, 128),     # the chip keeps wide heads row-major: read so
    (512, 128, 128), (64, 128, 64),
    (512, 256, 256), (2560, 256, 256),    # wide heads: blocks of 256
    (640, 256, 128), (128, 256, 128),     # ... where they divide the lane
    (1000, 128, None),    # no block divides that lane either
    (512, 192, None),     # not whole 128-lane tiles: its view would copy
    (24, 8, None),        # nor does it transpose a short lane
])
def test_fused_decode_block_rule(max_len, head_dim, block):
    from autodist_tpu.kernel.pallas.flash_decode import fused_decode_block

    assert fused_decode_block(max_len, head_dim) == block


# --------------------------------------------------------------------------- #
# through the engine
# --------------------------------------------------------------------------- #
def _serve(engine, p_lens, windows):
    r = np.random.RandomState(1)
    S = engine.prefill_len
    prompts = np.zeros((len(p_lens), S), np.int32)
    for i, n in enumerate(p_lens):
        prompts[i, :n] = r.randint(1, engine.cfg.vocab_size, n)
    active = np.ones((len(p_lens),), bool)
    toks = [engine.prefill(prompts, np.asarray(p_lens, np.int32), active)]
    for _ in range(windows):
        toks.extend(list(engine.decode(active)))
    return np.stack(toks)


@pytest.mark.parametrize("max_len,tp", [(128, 1), (256, 1), (512, 1),
                                        (256, 2)])
def test_engine_forced_kernel_on_the_whole_cache_greedy_parity(max_len, tp):
    """Forced where the kernel reads the cache in place (one block of
    128, two and four; on half the heads under ``shard_map``), token for
    token against the composed engine, across a block's edge."""
    from autodist_tpu.serving import ServingEngine

    cfg = _lm_cfg()
    params = _lm_params(cfg)
    kw = dict(num_slots=2, max_len=max_len, prefill_len=126,
              decode_steps=4, tensor_parallel=tp)
    fused = ServingEngine(cfg, params, kernel={"flash_decode": True}, **kw)
    plain = ServingEngine(cfg, params, kernel={"flash_decode": False},
                          **kw)
    assert fused.kv.fused_block == 128 and fused.decode_block_len == 128
    assert plain.kv.fused_block is None and plain.decode_block_len == max_len
    windows = 0 if max_len == 128 else 2
    np.testing.assert_array_equal(_serve(fused, [125, 3], windows),
                                  _serve(plain, [125, 3], windows))


@pytest.mark.parametrize("kv_heads,max_len", [(2, 256), (1, 512),
                                              (2, 48)])
def test_engine_grouped_heads_through_the_kernel_greedy_parity(kv_heads,
                                                               max_len):
    """A plain decoder of four query heads on two key/value heads and on
    one, forced through the kernel on the whole cache (a lane no block
    reads in place as one block: on a TPU, a copy): token for token the
    same engine on ``cached_attention``, across a block's edge."""
    from autodist_tpu.serving import ServingEngine

    cfg, params = _grouped(kv_heads, num_heads=4)
    p_lens = [125, 3] if max_len > 128 else [20, 3]
    kw = dict(num_slots=2, max_len=max_len, prefill_len=max(p_lens) + 1,
              decode_steps=4)
    fused = ServingEngine(cfg, params, kernel={"flash_decode": True}, **kw)
    plain = ServingEngine(cfg, params, kernel={"flash_decode": False},
                          **kw)
    assert fused.cache.k.shape[2] == kv_heads
    assert fused.kv.fused_block == min(128, max_len)
    assert plain.kv.fused_block is None
    np.testing.assert_array_equal(_serve(fused, p_lens, 2),
                                  _serve(plain, p_lens, 2))


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def test_forced_decode_program_hands_the_kernel_the_cache_itself():
    """Every layer of the decode body calls ONE inner function with the
    layer as an operand, and its pallas_call takes the 5-D cache (as the
    chip holds it, positions minor-most), never a layer's slice."""
    from autodist_tpu.serving import ServingEngine

    cfg = _lm_cfg(num_layers=3)
    eng = ServingEngine(cfg, _lm_params(cfg), num_slots=2, max_len=512,
                        prefill_len=8, decode_steps=2,
                        kernel={"flash_decode": True})
    c = eng.cache
    jaxpr = jax.make_jaxpr(eng._decode_jit.fn)(
        eng.params, c.k, c.v, c.lengths, eng._tok, eng.kv.table_arg(c),
        jnp.asarray(eng._sample_seeds), jnp.ones((2,), bool)).jaxpr
    calls = [e for e in _walk(jaxpr) if e.primitive.name == "jit"
             and e.params["name"] == "flash_decode_layer"]
    assert len(calls) == cfg.num_layers
    bodies = {id(e.params["jaxpr"].jaxpr) for e in calls}
    assert len(bodies) == 1, "one kernel body for every layer"
    L, B, H, d, T = 3, 2, cfg.num_heads, cfg.head_dim, 512
    for e in calls:
        shapes = [v.aval.shape for v in e.invars]
        assert shapes.count((L, B, H, d, T)) == 2, shapes
        assert shapes[2] == (1,), "the layer is an operand"
    pallas = [e for e in _walk(calls[0].params["jaxpr"].jaxpr)
              if e.primitive.name == "pallas_call"]
    assert len(pallas) == 1
    shapes = [v.aval.shape for v in pallas[0].invars]
    assert shapes.count((L, B, H, d, T)) == 2, shapes
    assert not any(len(s) == 4 and T in s for s in shapes), shapes
    # ... and writes the step's rows itself: no write_token in the body
    assert [v.aval.shape for v in pallas[0].outvars].count(
        (L, B, H, d, T)) == 2
    assert not [e for e in _walk(jaxpr)
                if e.primitive.name == "dynamic_update_slice"
                and e.invars[0].aval.shape == (L, B, H, T, d)]


# --------------------------------------------------------------------------- #
# the election
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("backend,kernel,max_len,heads,kv_heads,elected", [
    ("tpu", None, 1024, 2, None, True),
    ("tpu", ("quant_ring",), 1024, 2, None, True),   # no word on the kernel
    ("tpu", None, 256, 2, None, True),         # the measured threshold
    ("tpu", None, 128, 2, None, False),        # a lane below it
    ("tpu", None, 1000, 2, None, False),       # no block divides it
    ("tpu", None, 1024, 1, None, True),        # head_dim 128: row-major
    ("tpu", None, 128, 1, None, False),        # ... under the threshold
    ("tpu", {"flash_decode": False}, 1024, 2, None, False),
    ("cpu", None, 1024, 2, None, False),
    ("cpu", {"flash_decode": True}, 1024, 2, None, True),
    # four query heads on two key/value heads, and on one: as above
    ("tpu", None, 1024, 4, 2, True),
    ("tpu", None, 1024, 4, 1, True),
    ("tpu", None, 128, 4, 2, False),
    ("tpu", {"flash_decode": False}, 1024, 4, 2, False),
    ("cpu", None, 1024, 4, 2, False),
    ("cpu", {"flash_decode": True}, 1024, 4, 2, True),
])
def test_dense_decode_election(monkeypatch, backend, kernel, max_len,
                               heads, kv_heads, elected):
    from autodist_tpu.serving import ServingEngine

    size = dict(hidden_size=128, num_heads=heads, max_len=1024)
    if kv_heads:
        cfg, params = _grouped(kv_heads, **size)
    else:
        cfg = _lm_cfg(**size)
        params = _lm_params(cfg)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    telemetry.reset()
    try:
        eng = ServingEngine(cfg, params, num_slots=2,
                            max_len=max_len, prefill_len=8, kernel=kernel)
        gauges = {m["name"]: m["value"]
                  for m in telemetry.get().registry.snapshot()
                  if m["kind"] == "gauge"}
    finally:
        telemetry.reset()
    assert bool(eng.kernel.get("flash_decode")) == elected
    assert eng.kv.fused_block == (128 if elected else None)
    assert gauges.get("kernel/flash_decode_elected") == \
        (1 if elected else None)


def test_paged_engine_is_left_out_of_the_election(monkeypatch):
    from autodist_tpu.serving import ServingEngine

    cfg = _lm_cfg(hidden_size=128, max_len=1024)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = ServingEngine(cfg, _lm_params(cfg), num_slots=2, max_len=1024,
                        prefill_len=8, kv_layout="paged", kv_block_len=16)
    assert not eng.kernel and eng.decode_block_len is None


@pytest.mark.parametrize("kv_heads", [None, 1], ids=["a-head", "grouped"])
@pytest.mark.parametrize("kernel,attended,resident", [
    # one request of 250 prompt tokens beside an empty slot, K = 4, two
    # windows.  Blocks of 128 (four a lane): the first window's steps
    # land at 250..253 (two blocks each), the second's at 254..257 (two,
    # two, three, three); the empty slot reads one block a step.
    ({"flash_decode": True}, (8 + 4) + (10 + 4), 2 * 4 * 2 * 4),
    # cached_attention reads every lane whole: one "block" each.
    (None, 2 * 4 * 2, 2 * 4 * 2),
])
def test_kv_block_counters_read_what_the_lengths_imply(kernel, attended,
                                                       resident, kv_heads):
    """Per layer and key/value head, so a group of query heads on one
    changes neither count."""
    from autodist_tpu.serving import ContinuousBatcher, ServingEngine

    if kv_heads:
        cfg, params = _grouped(kv_heads)
    else:
        cfg = _lm_cfg()
        params = _lm_params(cfg)
    eng = ServingEngine(cfg, params, num_slots=2, max_len=512,
                        prefill_len=256, decode_steps=4, kernel=kernel)
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
    assert counters["serve/kv_blocks_attended"] == attended
    assert counters["serve/kv_blocks_resident"] == resident
