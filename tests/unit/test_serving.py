"""Serving-path goldens: the batched-inference engine on the Strategy IR.

The decode correctness bar (ISSUE 7 acceptance): greedy decode of the
tp∈{1,2} × vocab-parallel pipelined LM matches the single-device
full-recompute reference token-for-token — including the ``V % tp != 0``
padding edge, where padded vocab rows must never be sampled — and
continuous-batching interleaving (requests joining/leaving mid-flight)
yields exactly the tokens each request gets when run alone.  Plus the
per-token telemetry contract (``kind="serve"`` records through the PR 4
sink, schema-gated by ``tools/telemetry_report.py --check``) and the
cost model's decode-latency objective.
"""
import contextlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models.pipeline_lm import (make_pipeline_lm_trainable,
                                             sequential_logits)
from autodist_tpu.models.transformer import TransformerConfig
from autodist_tpu.serving import (ContinuousBatcher, ServingEngine,
                                  init_cache, serve)
from autodist_tpu.serving import kv_cache

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

V = 33          # odd: V % 2 != 0 exercises the vocab zero-pad path
MAX_LEN = 24


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


def reference_greedy(cfg, params, prompt, n):
    """Single-device reference: full-sequence recompute per emitted
    token — no KV cache, no masking tricks, the training stack's own
    layer/loss-head math (:func:`sequential_logits`)."""
    toks = list(prompt)
    for _ in range(n):
        logits = sequential_logits(cfg, params,
                                   jnp.asarray(toks)[None])
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def make_engine(cfg, params, tp=1, vocab_parallel=False, slots=2,
                decode_steps=3, prefill_len=8):
    return ServingEngine(cfg, params, tensor_parallel=tp,
                         vocab_parallel=vocab_parallel, num_slots=slots,
                         max_len=cfg.max_len, prefill_len=prefill_len,
                         decode_steps=decode_steps)


# --------------------------------------------------------------------- #
# KV cache
# --------------------------------------------------------------------- #
def test_kv_cache_layout_and_token_writes():
    c = init_cache(num_layers=2, num_slots=3, num_heads=4, head_dim=5,
                   max_len=7)
    assert c.k.shape == (2, 3, 4, 7, 5)       # [L, B, heads, T, dh]
    kv = jnp.arange(3 * 1 * 4 * 5, dtype=jnp.float32).reshape(3, 1, 4, 5)
    positions = jnp.array([0, 2, 6], jnp.int32)
    k = kv_cache.write_token(c.k, 1, kv, positions)
    for slot, pos in enumerate([0, 2, 6]):
        np.testing.assert_array_equal(np.asarray(k[1, slot, :, pos, :]),
                                      np.asarray(kv[slot, 0]))
    assert float(jnp.abs(k[0]).sum()) == 0.0   # other layer untouched
    # every non-written position stays zero
    mask = np.ones((3, 4, 7, 5), bool)
    for slot, pos in enumerate([0, 2, 6]):
        mask[slot, :, pos, :] = False
    assert float(jnp.abs(jnp.asarray(np.asarray(k[1])[mask])).sum()) == 0.0


def test_kv_cache_prompt_write_touches_one_slots_lane():
    c = init_cache(num_layers=2, num_slots=3, num_heads=2, head_dim=3,
                   max_len=6)
    resident = c.k + 7.0        # every other lane must survive, bit for bit
    kv = jnp.ones((1, 4, 2, 3), jnp.float32)       # [1, S, heads, dh]
    k = jax.jit(kv_cache.write_prompt, static_argnums=1)(
        resident, 1, kv, jnp.int32(2))             # the slot is traced
    assert float(k[1, 2, :, :4, :].min()) == float(k[1, 2, :, :4].max()) \
        == 1.0
    keep = np.ones(k.shape, bool)
    keep[1, 2, :, :4] = False
    np.testing.assert_array_equal(np.asarray(k)[keep],
                                  np.asarray(resident)[keep])


def test_cached_attention_masks_beyond_length():
    """Entries past a slot's occupancy are unreachable: garbage written
    there must not change the attention output."""
    B, H, T, D = 2, 2, 6, 4
    q = jnp.asarray(np.random.RandomState(0).randn(B, 1, H, D), jnp.float32)
    k = jnp.asarray(np.random.RandomState(1).randn(B, H, T, D), jnp.float32)
    v = jnp.asarray(np.random.RandomState(2).randn(B, H, T, D), jnp.float32)
    lengths = jnp.array([2, 4], jnp.int32)
    out = kv_cache.cached_attention(q, k, v, lengths)
    poison = jnp.where(
        (jnp.arange(T) > lengths[:, None])[:, None, :, None], 1e9, 0.0)
    out2 = kv_cache.cached_attention(q, k + poison, v + poison, lengths)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


# --------------------------------------------------------------------- #
# the one-row prefill: it computes and writes the admitted slots only
# (shared with test_paged_kv.py and test_looped_block.py)
# --------------------------------------------------------------------- #
PREFILL_SLOTS = 3
ADMIT_SUBSETS = [tuple(bool(m >> s & 1) for s in range(PREFILL_SLOTS))
                 for m in range(1 << PREFILL_SLOTS)]
admit_id = lambda admit: "admit-" + "".join("01"[a] for a in admit)


def sequential_prefill(cfg, params, prompt):
    """The sequential reference of one prompt's prefill: the training
    stack's own layer function over the unpadded prompt, pass by pass
    and layer by layer.  ``(first greedy token, k rows, v rows)``, the
    rows ``[cache_layers, heads, len(prompt), head_dim]``."""
    from autodist_tpu.models import pipeline_lm as lm

    stages, shared = params["stages"], params["shared"]
    toks = jnp.asarray(prompt)[None]
    n = toks.shape[1]
    x = shared["embedding"][toks]
    if cfg.block.positions == "learned":
        x = x + shared["pos_embed"][None, :n]
    x = x.astype(cfg.dtype)
    mask = jnp.tril(jnp.ones((n, n), bool))[None, None]
    ks, vs = [], []
    for _ in range(cfg.block.loop_steps):
        for i in range(cfg.num_layers):
            chunk = jax.tree.map(lambda a, _i=i: a[_i], stages)
            x, k, v = lm._tp_encoder_layer(cfg, chunk, x, mask, None,
                                           return_kv=True)
            ks.append(k[0])
            vs.append(v[0])                            # [n, heads, dh]
        if cfg.block.loop_steps > 1:
            x = lm.final_norm(cfg, shared, x)
    logits = lm.head_rows(cfg, shared, x[0, -1]).astype(jnp.float32) \
        @ lm.head_table(cfg, shared).T.astype(jnp.float32)
    rows = lambda xs: np.stack([np.transpose(np.asarray(a), (1, 0, 2))
                                for a in xs])
    return int(jnp.argmax(logits)), rows(ks), rows(vs)


def slot_lane(engine, arr, slot):
    """``slot``'s logical lane of a cache array, ``[cache_layers, heads,
    positions, head_dim]`` (numpy) — through its block-table row under
    the paged layout."""
    arr = np.asarray(arr)
    if engine.kv_layout != "paged":
        return arr[:, slot]
    got = arr[:, engine.kv.table[slot]]              # [L, mb, H, bl, dh]
    L, mb, H, bl, dh = got.shape
    return np.transpose(got, (0, 2, 1, 3, 4)).reshape(L, H, mb * bl, dh)


def resident_engine(cfg, params, prefill_len=8, **kw):
    """An engine whose every slot holds a request with a decode window
    behind it: the state a prefill must leave alone."""
    eng = ServingEngine(cfg, params, num_slots=PREFILL_SLOTS, max_len=24,
                        prefill_len=prefill_len, decode_steps=2, **kw)
    rng = np.random.default_rng(7)
    p_lens = np.array([4, prefill_len, 2])
    for slot, n in enumerate(p_lens):           # a no-op for a dense cache
        eng.reserve_slot(slot, int(n), 6)
    eng.prefill(rng.integers(0, cfg.vocab_size, (PREFILL_SLOTS, prefill_len)),
                p_lens, np.ones(PREFILL_SLOTS, bool))
    eng.decode(np.ones(PREFILL_SLOTS, bool))
    return eng


def check_prefill_admits(engine, cfg, params, admit):
    """One ``prefill`` call admitting the slots of ``admit`` into a full
    engine: the admitted slots hold token for token and cache row for
    cache row what the sequential reference gives, and every other
    slot's cache rows, length and token are bit-identical to before."""
    admit = np.asarray(admit, bool)
    rng = np.random.default_rng(int(np.packbits(admit)[0]) + 11)
    S = engine.prefill_len
    prompts = rng.integers(0, cfg.vocab_size, (PREFILL_SLOTS, S))
    p_lens = rng.integers(1, S + 1, PREFILL_SLOTS)
    for slot in np.flatnonzero(admit):          # no-ops for a dense cache
        engine.release_slot(slot)
        engine.reserve_slot(slot, int(p_lens[slot]), 6)
    c = engine.cache
    before = {"k": np.asarray(c.k), "v": np.asarray(c.v),
              "lengths": engine.lengths.copy(),
              "tok": np.asarray(engine._tok)}
    telemetry.reset()
    toks = engine.prefill(prompts, p_lens, admit)
    counters = {m["name"]: m["value"]
                for m in telemetry.get().registry.snapshot()
                if m["kind"] == "counter"}
    dispatch = [e["args"] for e in
                telemetry.get().chrome_trace()["traceEvents"]
                if e["name"] == "engine/prefill/dispatch"]
    telemetry.reset()
    # one call admitting n rows: n rows and n x prefill_len positions
    assert counters.get("engine/prefill_rows", 0) == admit.sum()
    assert counters.get("engine/prefill_positions", 0) == admit.sum() * S
    assert [d["rows"] for d in dispatch] == [admit.sum()]
    c = engine.cache
    np.testing.assert_array_equal(toks, np.asarray(engine._tok))
    for slot in range(PREFILL_SLOTS):
        if not admit[slot]:
            continue
        n = int(p_lens[slot])
        tok, k, v = sequential_prefill(cfg, params, prompts[slot, :n])
        assert toks[slot] == tok and engine.lengths[slot] == n
        for got, want in ((c.k, k), (c.v, v)):
            np.testing.assert_allclose(
                slot_lane(engine, got, slot)[:, :, :n], want, atol=2e-5,
                rtol=0)
    # everything the admitted slots do not own is as it was: the other
    # slots' lanes (dense), every block of the pool but theirs (paged)
    keep = np.ones(before["k"].shape, bool)
    for slot in np.flatnonzero(admit):
        if engine.kv_layout == "paged":
            keep[:, engine.kv.slot_blocks(slot)] = False
        else:
            keep[:, slot] = False
    for name, got in (("k", c.k), ("v", c.v)):
        np.testing.assert_array_equal(np.asarray(got)[keep],
                                      before[name][keep])
    np.testing.assert_array_equal(engine.lengths[~admit],
                                  before["lengths"][~admit])
    np.testing.assert_array_equal(toks[~admit], before["tok"][~admit])


@pytest.fixture(scope="module", params=[1, 2], ids=["tp1", "tp2"])
def resident_dense(request, cfg, params):
    return resident_engine(cfg, params, tensor_parallel=request.param)


@pytest.mark.parametrize("admit", ADMIT_SUBSETS, ids=admit_id)
def test_prefill_computes_and_writes_only_admitted_slots(
        resident_dense, cfg, params, admit):
    check_prefill_admits(resident_dense, cfg, params, admit)


# --------------------------------------------------------------------- #
# the warm-up: the prefill program compiled with no request admitted
# (shared with test_fleet.py)
# --------------------------------------------------------------------- #
class CompileEvents:
    """jax's lowerings and backend compilations while ``counting``: the
    benchmark's ``CompileCounter`` idiom, less the trace events (under
    tp > 1 a program's second call looks its trace up again, in
    microseconds, and reports that as one)."""

    def __init__(self):
        import jax.monitoring

        self.active = False
        self.events: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if self.active and "/jax/core/compile/" in name \
                and "jaxpr_trace" not in name:
            self.events.append(name)

    @contextlib.contextmanager
    def counting(self):
        self.events.clear()
        self.active = True
        try:
            yield self.events
        finally:
            self.active = False


@pytest.fixture(scope="module")
def compile_events():
    return CompileEvents()


@pytest.mark.parametrize("kw", [
    {}, {"tensor_parallel": 2},
    {"kv_layout": "paged", "kv_block_len": 4},
    {"kv_layout": "paged", "kv_block_len": 4, "prefix_caching": True},
    {"kv_layout": "paged", "kv_block_len": 4, "prefill_chunk": 4},
], ids=["dense", "dense-tp2", "paged", "paged-prefix", "paged-chunked"])
def test_warm_prefill_compiles_and_admits_nothing(cfg, params,
                                                  compile_events, kw):
    """``warm_prefill`` on an engine with two requests resident and one
    slot free: every length, the resident slots' tokens and cache rows
    are bit-identical after it; no admission after the first warm-up
    compiles anything, and the one into the slot it ran at gives the
    sequential reference's token and rows."""
    eng = ServingEngine(cfg, params, num_slots=PREFILL_SLOTS, max_len=24,
                        prefill_len=8, decode_steps=2, **kw)
    S = eng.max_prompt_tokens if eng.prefill_chunk else eng.prefill_len
    rng = np.random.default_rng(5)
    prompts = rng.integers(0, cfg.vocab_size, (PREFILL_SLOTS, S))
    p_lens = np.array([4, 6, 3])
    live = np.array([True, False, True])
    telemetry.reset()
    with compile_events.counting() as compiled:
        eng.warm_prefill()
    assert compiled                      # a fresh engine: this compiled
    for slot in np.flatnonzero(live):           # a no-op for a dense cache
        eng.reserve_slot(slot, int(p_lens[slot]), 6)
    with compile_events.counting() as compiled:
        eng.prefill(prompts, p_lens, live)
    assert compiled == []                # the first admissions: nothing
    eng.decode(live)
    c = eng.cache
    before = (np.asarray(c.k), np.asarray(c.v), eng.lengths.copy(),
              np.asarray(eng._tok))
    eng.warm_prefill()
    c = eng.cache
    after = (np.asarray(c.k), np.asarray(c.v), eng.lengths,
             np.asarray(eng._tok))
    np.testing.assert_array_equal(after[2], before[2])
    np.testing.assert_array_equal(after[3][live], before[3][live])
    for got, was in zip(after[:2], before[:2]):
        if eng.kv_layout == "paged":            # no block takes a write
            np.testing.assert_array_equal(got, was)
        else:                                   # the free slot's lane may
            np.testing.assert_array_equal(got[:, live], was[:, live])
    eng.reserve_slot(1, int(p_lens[1]), 6)
    with compile_events.counting() as compiled:
        toks = eng.prefill(prompts, p_lens, ~live)
    telemetry.reset()
    assert compiled == []
    tok, k, v = sequential_prefill(cfg, params, prompts[1, :p_lens[1]])
    assert toks[1] == tok and eng.lengths[1] == p_lens[1]
    np.testing.assert_allclose(
        slot_lane(eng, eng.cache.k, 1)[:, :, :p_lens[1]], k, atol=2e-5,
        rtol=0)
    np.testing.assert_array_equal(toks[live], before[3][live])


def test_warm_prefill_refuses_an_engine_with_no_free_slot(resident_dense):
    with pytest.raises(RuntimeError, match="free slot"):
        resident_dense.warm_prefill()


# --------------------------------------------------------------------- #
# greedy decode goldens (the acceptance bar)
# --------------------------------------------------------------------- #
PROMPT = [3, 1, 4, 1, 5]


@pytest.mark.parametrize("tp,vocab_parallel", [(1, False), (2, False),
                                               (2, True)])
def test_greedy_decode_matches_sequential_reference(cfg, params, tp,
                                                    vocab_parallel):
    """Token-for-token parity of the KV-cache incremental decode vs the
    full-recompute reference, across tp∈{1,2} × vocab-parallel — with
    V=33 odd, so the vocab-parallel case runs the zero-pad edge and a
    sampled padded row (id >= 33) would break equality immediately."""
    want = reference_greedy(cfg, params, PROMPT, 9)
    engine = make_engine(cfg, params, tp=tp, vocab_parallel=vocab_parallel)
    b = ContinuousBatcher(engine)
    rid = b.submit(PROMPT, max_new_tokens=9)
    got = b.run()[rid].tokens
    assert got == want
    assert all(0 <= t < cfg.vocab_size for t in got)


def test_padded_vocab_rows_never_win_greedy():
    """Adversarial pad-row check: hidden states crafted so every REAL
    vocab row scores negative while the zero-padded row would score 0
    (the max) if it weren't masked."""
    from jax.sharding import Mesh, PartitionSpec as P

    from autodist_tpu.parallel.tensor import vocab_parallel_greedy_token

    vocab, H, tp = 5, 8, 2                     # pads to 6 rows, 3/shard
    rng = np.random.RandomState(0)
    # all-positive rows + all-negative hidden state: every real row's
    # logit is strictly negative, while the padded all-zero row would
    # score exactly 0 (the max) if it weren't masked
    emb = jnp.asarray(np.abs(rng.randn(vocab, H)) + 0.1, jnp.float32)
    x = -jnp.ones((1, H), jnp.float32)
    logits = np.asarray(x @ emb.T)[0]
    assert (logits < 0).all(), "construction failed to go negative"
    emb_pad = jnp.pad(emb, ((0, 1), (0, 0)))   # padded row -> logit 0
    mesh = Mesh(np.array(jax.devices()[:tp]), ("model",))

    def run(xx, ee):
        tok, _ = vocab_parallel_greedy_token(xx, ee, vocab_size=vocab,
                                             model_axis="model")
        return tok

    tok = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(P(), P("model", None)),
        out_specs=P(), check_vma=False))(x, emb_pad)
    assert int(tok[0]) == int(np.argmax(logits))
    assert int(tok[0]) < vocab


def test_continuous_batching_interleave_parity(cfg, params):
    """Requests joining and leaving mid-flight (3 requests, 2 slots:
    the third admits only when a slot frees) decode exactly the tokens
    each gets when run alone."""
    reqs = [([3, 1, 4], 10), ([2, 7], 4), ([5, 5, 5, 5, 9], 7)]
    eng = make_engine(cfg, params)
    b = ContinuousBatcher(eng)
    rids = [b.submit(p, max_new_tokens=m) for p, m in reqs]
    inter = b.run()
    assert set(inter) == set(rids)
    for (p, m), rid in zip(reqs, rids):
        solo = ContinuousBatcher(make_engine(cfg, params))
        srid = solo.submit(p, max_new_tokens=m)
        assert inter[rid].tokens == solo.run()[srid].tokens
        # ... and both match the sequential reference
        assert inter[rid].tokens == reference_greedy(cfg, params, p, m)


def test_batcher_queue_eviction_and_eos(cfg, params):
    eng = make_engine(cfg, params, slots=1)
    b = ContinuousBatcher(eng)
    # discover this prompt's greedy stream, then stop at its 3rd token
    probe = ContinuousBatcher(make_engine(cfg, params, slots=1))
    probe_rid = probe.submit(PROMPT, max_new_tokens=8)
    stream = probe.run()[probe_rid].tokens
    eos = stream[2]
    first_eos = stream.index(eos)
    r1 = b.submit(PROMPT, max_new_tokens=8, eos_id=eos)
    r2 = b.submit([2, 7, 1], max_new_tokens=5)    # queued behind r1
    assert b.active_slots == 0 and len(b._queue) == 2
    done = b.run()
    assert done[r1].finish_reason == "eos"
    assert done[r1].tokens == stream[:first_eos + 1]
    assert done[r2].finish_reason == "max_tokens"
    assert len(done[r2].tokens) == 5
    assert done[r2].queue_wait_s >= 0.0
    assert done[r1].ttft_s > 0 and done[r1].tokens_per_sec > 0


def test_eos_beyond_budget_does_not_stretch_request(cfg, params):
    """An EOS landing past max_new_tokens inside the same fused window
    must not stretch the request: the budget caps first."""
    # Under jax 0.9.0 these weights decode PROMPT to one repeated token,
    # which has no late-only token to plant; this prompt's greedy
    # stream opens [32, 10, 21, 21, ...].
    prompt = [9, 9, 9]
    probe = ContinuousBatcher(make_engine(cfg, params, slots=1))
    probe_rid = probe.submit(prompt, max_new_tokens=8)
    stream = probe.run()[probe_rid].tokens
    late = next((t for t in stream[2:] if t not in stream[:2]), None)
    assert late is not None, f"degenerate stream {stream}"
    b = ContinuousBatcher(make_engine(cfg, params, slots=1))
    rid = b.submit(prompt, max_new_tokens=2, eos_id=late)
    out = b.run()[rid]
    assert out.finish_reason == "max_tokens"
    assert out.tokens == stream[:2]
    assert len(out.inter_token_ms) <= 2   # discarded tokens not timed


def test_run_returns_only_new_completions(cfg, params):
    """A long-lived loop calling run() per admission round must not
    re-receive old completions (the full history stays on
    .completions)."""
    b = ContinuousBatcher(make_engine(cfg, params))
    r1 = b.submit(PROMPT, max_new_tokens=3)
    first = b.run()
    assert set(first) == {r1}
    r2 = b.submit([2, 7], max_new_tokens=3)
    second = b.run()
    assert set(second) == {r2}
    assert set(b.completions) == {r1, r2}


def test_batcher_max_len_eviction(cfg, params):
    """A request whose budget exceeds the cache capacity evicts at
    max_len with the over-capacity tail truncated deterministically."""
    eng = make_engine(cfg, params, slots=2)
    b = ContinuousBatcher(eng)
    rid = b.submit(PROMPT, max_new_tokens=200)
    out = b.run()[rid]
    assert out.finish_reason == "max_len"
    assert len(out.tokens) == cfg.max_len - len(PROMPT)


def test_batcher_validates_requests(cfg, params):
    b = ContinuousBatcher(make_engine(cfg, params))
    with pytest.raises(ValueError, match="empty prompt"):
        b.submit([])
    with pytest.raises(ValueError, match="prefill_len"):
        b.submit(list(range(20)))
    with pytest.raises(ValueError, match="max_new_tokens"):
        b.submit([1], max_new_tokens=0)


# --------------------------------------------------------------------- #
# serve() entry + engine config validation
# --------------------------------------------------------------------- #
def test_serve_entry_point_reads_strategy_ir(cfg, params):
    from autodist_tpu.strategy.ir import GraphConfig, Strategy

    strategy = Strategy(node_configs=[], graph_config=GraphConfig(
        replicas=1, lowering="pipeline",
        parallel={"tensor_parallel": 2, "vocab_parallel": True}))
    engine = serve(cfg, params=params, strategy=strategy, num_slots=2,
                   prefill_len=8, decode_steps=2)
    assert engine.tensor_parallel == 2 and engine.vocab_parallel
    with pytest.raises(ValueError, match="exactly one"):
        serve(cfg, params=params, artifact="/tmp/nope")
    with pytest.raises(ValueError, match="exactly one"):
        serve(cfg)


def test_engine_validates_shapes(cfg, params):
    with pytest.raises(ValueError, match="num_heads"):
        ServingEngine(cfg, params, tensor_parallel=4)   # 2 heads % 4
    with pytest.raises(ValueError, match="position table"):
        ServingEngine(cfg, params, max_len=10 * cfg.max_len)
    with pytest.raises(ValueError, match="prefill_len"):
        ServingEngine(cfg, params, prefill_len=cfg.max_len + 1)


# --------------------------------------------------------------------- #
# per-token telemetry through the PR 4 sink
# --------------------------------------------------------------------- #
def test_serving_telemetry_records_and_report(cfg, params, tmp_path):
    tel = telemetry.reset()
    telemetry.configure(out_dir=str(tmp_path), enabled=True)
    try:
        b = ContinuousBatcher(make_engine(cfg, params))
        rids = [b.submit([3, 1, 4], max_new_tokens=4),
                b.submit([2, 7], max_new_tokens=3)]
        b.run()
        paths = telemetry.flush()
    finally:
        telemetry.reset()
    with open(paths["metrics"]) as f:
        recs = [json.loads(line) for line in f]
    serves = {r["request"]: r for r in recs if r.get("kind") == "serve"}
    assert set(serves) == set(rids)
    for rid in rids:
        rec = serves[rid]
        assert rec["ttft_ms"] > 0 and rec["tokens"] >= 1
        assert rec["tokens_per_sec"] > 0
        assert rec["inter_token_p50_ms"] > 0
    counters = {r["name"]: r["value"] for r in recs
                if r.get("kind") == "counter"}
    assert counters["serve/requests"] == 2
    assert counters["serve/tokens"] >= 7
    # both requests were admitted by one-row prefill dispatches
    assert counters["engine/prefill_rows"] == 2
    assert counters["engine/prefill_positions"] == 2 * 8
    hists = {r["name"] for r in recs if r.get("kind") == "histogram"}
    assert {"serve/ttft_ms", "serve/inter_token_ms"} <= hists

    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    assert telemetry_report.check_schema(str(tmp_path)) == []
    md = telemetry_report.render(str(tmp_path))
    assert "## serving" in md and "ttft" in md

    # the schema gate rejects a serve record missing its latency facts
    with open(os.path.join(tmp_path, "metrics.jsonl"), "a") as f:
        f.write(json.dumps({"kind": "serve", "request": "x"}) + "\n")
    problems = telemetry_report.check_schema(str(tmp_path))
    assert any("serve record missing" in p for p in problems)


@pytest.mark.parametrize("doctor,says", [
    (lambda recs, trace: recs.pop(), "advanced together"),
    (lambda recs, trace: recs[1].update(value=1), "at least one position"),
    (lambda recs, trace: trace[0]["args"].pop("rows"), "without their `rows`"),
    (lambda recs, trace: recs.append(
        {"kind": "counter", "name": "engine/prefill_rung_rows/16",
         "value": 2}), "a row runs at one rung"),
    (lambda recs, trace: recs.append(
        {"kind": "counter", "name": "engine/prefill_rung_rows/8",
         "value": 4}), "a row runs at one rung"),
    (lambda recs, trace: recs.append(
        {"kind": "counter", "name": "engine/prefill_rung_rows/8",
         "value": 3}), None),
    (lambda recs, trace: None, None),
], ids=["one-counter", "positions-under-rows", "span-without-rows",
        "rung-positions-over", "rung-rows-over", "rungs-sound", "sound"])
def test_schema_gate_holds_the_prefill_work_counters(tmp_path, doctor, says):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    recs = [{"kind": "counter", "name": "engine/prefill_rows", "value": 3},
            {"kind": "counter", "name": "engine/prefill_positions",
             "value": 24}]
    trace = [{"name": "engine/prefill/dispatch", "ph": "X", "ts": 0,
              "dur": 5, "args": {"loop_steps": 1, "rows": 3}}]
    doctor(recs, trace)
    with open(tmp_path / "metrics.jsonl", "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in recs)
    with open(tmp_path / "trace.json", "w") as f:
        json.dump({"traceEvents": trace}, f)
    problems = telemetry_report.check_schema(str(tmp_path))
    assert (any(says in p for p in problems) if says else not problems), \
        problems


def test_record_event_contract():
    tel = telemetry.reset()
    tel.enabled = True
    assert tel.record_event("serve", request="r", tokens=3)
    assert tel.step_records()[-1]["kind"] == "serve"
    with pytest.raises(ValueError, match="record_step"):
        tel.record_event("step", step=1)
    tel.enabled = False
    assert not tel.record_event("serve", request="r2")
    telemetry.reset()


# --------------------------------------------------------------------- #
# the cost model's decode-latency objective
# --------------------------------------------------------------------- #
def test_decode_cost_ranks_tp_by_comm_vs_compute_win(cfg):
    """tp=2 ranks above tp=1 exactly when the per-token comm cost is
    under the compute win — both directions, by link profile."""
    from autodist_tpu.resource import ResourceSpec
    from autodist_tpu.simulator import CostModel

    trainable = make_pipeline_lm_trainable(
        make_cfg(vocab=512, max_len=64), optax.sgd(0.1),
        jax.random.PRNGKey(0))
    rs = ResourceSpec({"topology": {"platform": "cpu", "num_devices": 8}})
    fast = CostModel(rs, link_profile={"ici_gbps": 1e4,
                                       "hop_alpha_s": 1e-9})
    c1 = fast.decode_cost(trainable, {"tensor_parallel": 1})
    c2 = fast.decode_cost(trainable, {"tensor_parallel": 2})
    assert c1.comm_time_s == 0.0
    assert c2.comm_time_s < c1.compute_time_s - c2.compute_time_s
    assert c2.token_time_s < c1.token_time_s          # tp=2 elected
    slow = CostModel(rs, link_profile={"ici_gbps": 1e-4,
                                       "hop_alpha_s": 1e-2})
    d1 = slow.decode_cost(trainable, {"tensor_parallel": 1})
    d2 = slow.decode_cost(trainable, {"tensor_parallel": 2})
    assert d2.comm_time_s > d1.compute_time_s - d2.compute_time_s
    assert d1.token_time_s < d2.token_time_s          # tp=1 elected
    # the KV cache and params shard with tp
    assert c2.kv_bytes_per_device == pytest.approx(
        c1.kv_bytes_per_device / 2)
    assert c2.mem_bytes_per_device < c1.mem_bytes_per_device


def test_decode_cost_layer_fallback_ignores_embedding_tables():
    """A trainable without num_stages must not mistake a [V, H]
    embedding's vocab dim for a layer count (it would inflate every
    decode term by orders of magnitude)."""
    from autodist_tpu import Trainable
    from autodist_tpu.resource import ResourceSpec
    from autodist_tpu.simulator import CostModel

    params = {
        "embedding": jnp.zeros((5000, 8), jnp.float32),
        "blocks": {"qkv": jnp.zeros((4, 8, 24), jnp.float32),
                   "wo": jnp.zeros((4, 16, 8), jnp.float32)},
    }
    t = Trainable.from_loss_fn(
        lambda p, b: jnp.sum(p["embedding"]) * 0.0, params,
        optax.sgd(0.1))
    rs = ResourceSpec({"topology": {"platform": "cpu", "num_devices": 2}})
    cost = CostModel(rs).decode_cost(t, {"tensor_parallel": 1},
                                     max_len=64)
    # kv term built from layers=4 (the stacked blocks), not 5000
    assert cost.kv_bytes_per_device < 5000 * 8 * 64
    hidden = CostModel._hidden_dim(t)
    assert cost.kv_bytes_per_device == pytest.approx(
        2.0 * 4 * hidden * 64 * 2.0)


def test_rank_serving_orders_and_reads_strategy(cfg):
    from autodist_tpu.resource import ResourceSpec
    from autodist_tpu.simulator import rank_serving

    trainable = make_pipeline_lm_trainable(
        make_cfg(vocab=512, max_len=64), optax.sgd(0.1),
        jax.random.PRNGKey(0))
    rs = ResourceSpec({"topology": {"platform": "cpu", "num_devices": 4}})
    ranked = rank_serving(trainable, rs,
                          link_profile={"ici_gbps": 1e4,
                                        "hop_alpha_s": 1e-9})
    assert len(ranked) >= 4          # tp1 + tp{2,4} x vocab{off,on}
    scores = [cost.score for _, cost in ranked]
    assert scores == sorted(scores)
    assert ranked[0][1].tensor_parallel > 1       # fast link: tp wins


# --------------------------------------------------------------------- #
# acceptance: examples/serve.py --smoke + telemetry --check (CI smoke)
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def serve_smoke_run(tmp_path_factory):
    out = tmp_path_factory.mktemp("serve_tel")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": REPO,
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples/serve.py"),
         "--smoke", "--telemetry-dir", str(out)],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    return out, proc.stdout


def test_serve_smoke_subprocess(serve_smoke_run):
    out, stdout = serve_smoke_run
    assert "serve smoke ok" in stdout
    assert "tokens/s aggregate" in stdout
    assert "serving configs by predicted token latency" in stdout
    with open(out / "metrics.jsonl") as f:
        recs = [json.loads(line) for line in f]
    serves = [r for r in recs if r.get("kind") == "serve"]
    assert len(serves) == 4
    assert all(r["ttft_ms"] > 0 and r["tokens"] >= 1 for r in serves)


def test_serve_smoke_report_check(serve_smoke_run):
    out, _ = serve_smoke_run
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    assert telemetry_report.main([str(out), "--check"]) == 0
    md = telemetry_report.render(str(out))
    assert "## serving" in md


# --------------------------------------------------------------------- #
# Graceful degradation: deadlines, bounded-queue shedding, drain
# (both-ways: no deadline pressure => completions byte-identical).
# --------------------------------------------------------------------- #
def test_no_deadline_completions_byte_identical(cfg, params):
    """Both-ways golden: a huge deadline and a bounded-but-unfull queue
    decode EXACTLY the tokens the plain batcher decodes."""
    reqs = [([3, 1, 4], 6), ([2, 7], 4)]
    plain = ContinuousBatcher(make_engine(cfg, params))
    plain_rids = [plain.submit(p, max_new_tokens=m) for p, m in reqs]
    plain_out = plain.run()
    guarded = ContinuousBatcher(make_engine(cfg, params), max_queue=16)
    g_rids = [guarded.submit(p, max_new_tokens=m, deadline_s=3600.0)
              for p, m in reqs]
    g_out = guarded.run()
    for pr, gr in zip(plain_rids, g_rids):
        assert g_out[gr].tokens == plain_out[pr].tokens
        assert g_out[gr].finish_reason == plain_out[pr].finish_reason


def test_queued_request_past_deadline_expires_unstarted(cfg, params):
    telemetry.reset()
    b = ContinuousBatcher(make_engine(cfg, params, slots=1))
    live = b.submit([3, 1, 4], max_new_tokens=3)
    doomed = b.submit([2, 7], max_new_tokens=3, deadline_s=1e-4)
    import time as _t

    _t.sleep(0.01)   # the queued deadline passes before any admission
    out = b.run()
    assert out[live].finish_reason == "max_tokens"
    assert out[doomed].finish_reason == "deadline_exceeded"
    assert out[doomed].tokens == []
    assert telemetry.get().registry.counter(
        "serve/deadline_exceeded").value == 1


def test_in_flight_deadline_keeps_partial_tokens(cfg, params):
    """A request whose deadline lapses mid-decode completes with the
    tokens it already has — partial beats nothing at the deadline."""
    b = ContinuousBatcher(make_engine(cfg, params, slots=1,
                                      decode_steps=1))
    rid = b.submit([3, 1, 4], max_new_tokens=64, deadline_s=0.05)
    out = b.run()[rid]
    assert out.finish_reason == "deadline_exceeded"
    assert 0 < len(out.tokens) < 64
    # the partial prefix matches the unconstrained stream
    free = ContinuousBatcher(make_engine(cfg, params, slots=1,
                                         decode_steps=1))
    frid = free.submit([3, 1, 4], max_new_tokens=64)
    assert out.tokens == free.run()[frid].tokens[:len(out.tokens)]


def test_bounded_queue_sheds_with_coded_error(cfg, params):
    from autodist_tpu.serving import OverloadedError

    telemetry.reset()
    b = ContinuousBatcher(make_engine(cfg, params, slots=1), max_queue=1)
    b.submit([3, 1], max_new_tokens=2)
    with pytest.raises(OverloadedError, match="serve/overloaded"):
        b.submit([2, 7], max_new_tokens=2)
    assert telemetry.get().registry.counter("serve/shed").value == 1
    # the shed request never entered: the queued one still completes
    assert len(b.run()) == 1


def test_drain_never_strands_in_flight_slots(cfg, params):
    from autodist_tpu.serving import OverloadedError

    telemetry.reset()
    eng = make_engine(cfg, params, slots=1, decode_steps=1)
    b = ContinuousBatcher(eng)
    flying = b.submit([3, 1, 4], max_new_tokens=6)
    queued = b.submit([2, 7], max_new_tokens=4)     # no free slot
    b.step()                                        # admits `flying` only
    assert b.active_slots == 1
    done = b.drain(finish_in_flight=True)
    # every submitted request ended in exactly one completion
    assert set(done) == {flying, queued}
    assert done[flying].finish_reason == "max_tokens"
    assert len(done[flying].tokens) == 6            # decoded to terminal
    assert done[queued].finish_reason == "shed"     # resubmittable
    assert done[queued].tokens == []
    assert b.active_slots == 0
    with pytest.raises(OverloadedError):            # drained = no admits
        b.submit([5], max_new_tokens=1)


def test_drain_cut_evicts_at_current_token(cfg, params):
    eng = make_engine(cfg, params, slots=1, decode_steps=1)
    b = ContinuousBatcher(eng)
    rid = b.submit([3, 1, 4], max_new_tokens=50)
    b.step()
    b.step()
    done = b.drain(finish_in_flight=False)
    assert done[rid].finish_reason == "drained"
    assert 0 < len(done[rid].tokens) < 50           # cut, tokens kept
