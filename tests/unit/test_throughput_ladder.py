"""Throughput-ladder goldens (ISSUE 16): chunked prefill, CoW prefix
caching, speculative decoding.

The correctness bar is *exactness*: every rung is a pure throughput
transform, so each one must reproduce the vanilla engine's token
stream bit-for-bit — chunked prefill vs single-shot (tp∈{1,2} ×
vocab-parallel, including a chunk that does not divide the prompt),
a shared-prefix warm admission vs a cold cache, and speculative
decode vs plain decode for greedy AND seeded sampling (same-weights
and different-weights drafts).  Around the streams: the refcounted
allocator's ``free + used == total`` invariant after every terminal
state (including router failover and a cancelled hedge loser), the
coded ``PromptBudgetError`` both ways, the ADT116/ADT117 block-trace
lint clean on honest engine traces, and the cost-model ladder pins
both ways.
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
from autodist_tpu.serving import (ContinuousBatcher, FleetConfig,
                                  PromptBudgetError, Router,
                                  ServingEngine, ServingFleet)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

V = 33          # odd: V % 2 != 0 exercises the vocab zero-pad path
MAX_LEN = 24
PROMPT = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]   # 10 tokens: chunk=4 -> 3 chunks
MAX_NEW = 6


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


@pytest.fixture(scope="module")
def draft_params(cfg):
    """A draft with *different* weights: speculation must stay exact
    even when the draft proposes wrong tokens (acceptance < 1)."""
    return make_pipeline_lm_trainable(
        cfg, optax.sgd(0.1), jax.random.PRNGKey(3)).params


def make_engine(cfg, params, **kw):
    base = dict(num_slots=2, max_len=MAX_LEN, prefill_len=12,
                decode_steps=3, kv_layout="paged", kv_block_len=4)
    base.update(kw)
    return ServingEngine(cfg, params, **base)


def run_single(engine, prompt, n, seed=None, slot=0):
    """Drive one request through the raw engine API and return its
    first ``n`` tokens (the golden-comparison harness)."""
    B = engine.num_slots
    P = engine.max_prompt_tokens if engine.prefill_chunk \
        else engine.prefill_len
    prompts = np.zeros((B, P), np.int64)
    prompts[slot, :len(prompt)] = prompt
    p_lens = np.zeros((B,), np.int64)
    p_lens[slot] = len(prompt)
    admit = np.zeros((B,), bool)
    admit[slot] = True
    seeds = None if seed is None else np.full((B,), seed, np.int32)
    engine.reserve_slot(slot, len(prompt), n, prompt=np.asarray(prompt))
    tok = engine.prefill(prompts, p_lens, admit, seeds=seeds)
    out = [int(tok[slot])]
    active = admit.copy()
    while len(out) < n:
        w = engine.decode_window(active)
        out.extend(int(t) for t in w.tokens[:w.counts[slot], slot])
    engine.release_slot(slot)
    return out[:n]


def assert_idle_accounting(engine):
    free, used, total = engine.block_accounting()
    assert used == 0 and free == total, (free, used, total)


# --------------------------------------------------------------------- #
# rung 1: chunked prefill == single-shot, token for token
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("tp,vocab_parallel",
                         [(1, False), (2, False), (2, True)])
def test_chunked_prefill_matches_single_shot(cfg, params, tp,
                                             vocab_parallel):
    """Chunk-by-chunk prefill (chunk=4 over a 10-token prompt — the
    final chunk is partial) emits the identical stream as one
    prefill dispatch, across tp and the vocab-parallel loss head."""
    kw = dict(tensor_parallel=tp, vocab_parallel=vocab_parallel)
    base = run_single(make_engine(cfg, params, **kw), PROMPT, MAX_NEW)
    chunked = make_engine(cfg, params, prefill_chunk=4, **kw)
    got = run_single(chunked, PROMPT, MAX_NEW)
    assert got == base, (got, base)
    assert chunked.last_prefill_chunks == 3   # ceil(10 / 4)
    assert_idle_accounting(chunked)


def test_chunked_prefill_lifts_the_prompt_bucket(cfg, params):
    """Single-shot admission buckets prompts at ``prefill_len``;
    chunking lifts the bucket to the whole context window."""
    plain = make_engine(cfg, params, prefill_len=8)
    assert plain.max_prompt_tokens == 8
    chunked = make_engine(cfg, params, prefill_len=8, prefill_chunk=4)
    assert chunked.max_prompt_tokens > 8
    long_prompt = list(range(1, 15))          # 14 tokens > bucket of 8
    got = run_single(chunked, long_prompt, MAX_NEW)
    wide = make_engine(cfg, params, prefill_len=16)
    assert got == run_single(wide, long_prompt, MAX_NEW)


def test_flash_prefill_kernel_matches_composed_path(cfg, params):
    """The fused paged flash-prefill kernel is numerics-identical to
    the composed gather+attention chunk path (greedy golden)."""
    base = run_single(make_engine(cfg, params, prefill_chunk=4),
                      PROMPT, MAX_NEW)
    kern = make_engine(cfg, params, prefill_chunk=4,
                       kernel=("flash_prefill",))
    assert run_single(kern, PROMPT, MAX_NEW) == base


# --------------------------------------------------------------------- #
# rung 2: CoW prefix caching — warm == cold, bit for bit
# --------------------------------------------------------------------- #
def test_prefix_cache_shared_equals_cold(cfg, params):
    """A second request sharing a resident prefix decodes the exact
    stream a cold cache gives it, its admission charges only the
    novel suffix (2 full blocks + partial tail hit), and releasing
    both requests restores ``free == total``."""
    base = run_single(make_engine(cfg, params), PROMPT, MAX_NEW)
    e = make_engine(cfg, params, prefill_chunk=4, prefix_caching=True)
    assert run_single(e, PROMPT, MAX_NEW) == base   # cold == vanilla

    # hold slot 0 resident, then admit the same prompt into slot 1
    e.reserve_slot(0, len(PROMPT), MAX_NEW, prompt=np.asarray(PROMPT))
    prompts = np.zeros((2, e.max_prompt_tokens), np.int64)
    prompts[0, :len(PROMPT)] = PROMPT
    e.prefill(prompts, np.array([len(PROMPT), 0]),
              np.array([True, False]))
    hits = e.reserve_slot(1, len(PROMPT), MAX_NEW,
                          prompt=np.asarray(PROMPT))
    assert hits == 3        # 10-token prompt @ block 4: 2 full + tail
    prompts[1] = prompts[0]
    e.prefill(prompts, np.array([0, len(PROMPT)]),
              np.array([False, True]))
    w = e.decode_window(np.array([True, True]))
    for slot in (0, 1):
        got = [int(t) for t in w.tokens[:w.counts[slot], slot]]
        assert got == base[1:1 + len(got)], (slot, got)
    e.release_slot(0)
    e.release_slot(1)
    assert_idle_accounting(e)


def test_prefix_cache_admits_strictly_more_at_equal_pool(cfg, params):
    """The capacity claim at the heart of the rung: at the same pool,
    admitting a second shared-prefix request leaves strictly more
    free blocks with caching on than off."""
    def admit_two(prefix_caching):
        e = make_engine(cfg, params, prefill_chunk=4,
                        prefix_caching=prefix_caching)
        prompts = np.zeros((2, e.max_prompt_tokens), np.int64)
        prompts[0, :len(PROMPT)] = PROMPT
        e.reserve_slot(0, len(PROMPT), MAX_NEW,
                       prompt=np.asarray(PROMPT))
        e.prefill(prompts, np.array([len(PROMPT), 0]),
                  np.array([True, False]))
        e.reserve_slot(1, len(PROMPT), MAX_NEW,
                       prompt=np.asarray(PROMPT))
        return e.free_blocks
    assert admit_two(True) > admit_two(False)


def test_lint_block_trace_clean_on_real_engine_events(cfg, params):
    """The honest engine's own allocator trace — through sharing, CoW
    and release — replays clean under the ADT116/ADT117 rules, and a
    doctored double-free in the same trace fires ADT117."""
    from autodist_tpu.analysis import lint_block_trace

    e = make_engine(cfg, params, prefill_chunk=4, prefix_caching=True)
    run_single(e, PROMPT, MAX_NEW)
    e.reserve_slot(0, len(PROMPT), MAX_NEW, prompt=np.asarray(PROMPT))
    prompts = np.zeros((2, e.max_prompt_tokens), np.int64)
    prompts[0, :len(PROMPT)] = PROMPT
    e.prefill(prompts, np.array([len(PROMPT), 0]),
              np.array([True, False]))
    e.reserve_slot(1, len(PROMPT), MAX_NEW, prompt=np.asarray(PROMPT))
    prompts[1] = prompts[0]
    e.prefill(prompts, np.array([0, len(PROMPT)]),
              np.array([False, True]))
    e.decode_window(np.array([True, True]))
    e.release_slot(0)
    e.release_slot(1)
    trace = list(e.kv.allocator.events)
    assert any(ev[0] == "share" for ev in trace)   # sharing happened
    report = lint_block_trace(trace)
    assert not report.diagnostics, report.render()

    freed = next(b for op, b in reversed(
        [ev[:2] for ev in trace if ev[0] in ("alloc", "free")])
        if op == "free")
    doctored = trace + [("free", freed)]
    codes = {d.code for d in lint_block_trace(doctored).diagnostics}
    assert "ADT117" in codes


# --------------------------------------------------------------------- #
# rung 3: speculative decode == vanilla, greedy and sampled
# --------------------------------------------------------------------- #
def test_speculative_matches_vanilla_greedy(cfg, params, draft_params):
    """Draft-propose/verify decode reproduces plain greedy decode
    token for token — whether the draft agrees (same weights,
    acceptance ~1) or mispredicts (different weights) — and both the
    verify engine's and the nested draft's pools drain to zero."""
    base = run_single(make_engine(cfg, params), PROMPT, MAX_NEW)
    for dparams in (params, draft_params):
        e = make_engine(cfg, params, speculative=2, draft_cfg=cfg,
                        draft_params=dparams)
        got = run_single(e, PROMPT, MAX_NEW)
        assert got == base, (got, base)
        assert_idle_accounting(e)
        assert_idle_accounting(e.draft)


def test_prefill_counters_count_the_targets_program_only(cfg, params):
    """Under speculation the nested draft engine prefills the same rows
    through a program of its own; ``engine/prefill_rows`` and
    ``engine/prefill_positions`` stay the target's, so the share of
    useful positions is not halved — and a warm-up warms both."""
    e = make_engine(cfg, params, speculative=2, draft_cfg=cfg,
                    draft_params=params)
    e.warm_prefill()
    telemetry.reset()
    run_single(e, PROMPT, MAX_NEW)
    counters = {m["name"]: m["value"]
                for m in telemetry.get().registry.snapshot()
                if m["kind"] == "counter"}
    spans = [ev["args"]["rows"] for ev in
             telemetry.get().chrome_trace()["traceEvents"]
             if ev["name"] == "engine/prefill/dispatch"]
    telemetry.reset()
    assert counters["engine/prefill_rows"] == 1
    assert counters["engine/prefill_positions"] == e.prefill_len
    assert spans == [1, 1]              # the target's and the draft's


def test_sampled_parity_across_all_rungs(cfg, params, draft_params):
    """Seeded sampling (temperature 0.9) holds the same exactness:
    the position-keyed gumbel draw makes chunked prefill, the flash
    kernel, and speculative decode (same- and different-weights
    drafts) reproduce the vanilla sampled stream draw for draw."""
    kw = dict(temperature=0.9, top_k=0)
    base = run_single(make_engine(cfg, params, **kw), PROMPT, MAX_NEW,
                      seed=7)
    variants = [
        make_engine(cfg, params, prefill_chunk=4, **kw),
        make_engine(cfg, params, prefill_chunk=4,
                    kernel=("flash_prefill",), **kw),
        make_engine(cfg, params, speculative=2, draft_cfg=cfg,
                    draft_params=params, **kw),
        make_engine(cfg, params, speculative=2, draft_cfg=cfg,
                    draft_params=draft_params, **kw),
    ]
    for e in variants:
        got = run_single(e, PROMPT, MAX_NEW, seed=7)
        assert got == base, (got, base)
        assert_idle_accounting(e)


# --------------------------------------------------------------------- #
# the rungs under continuous batching, routing and failure
# --------------------------------------------------------------------- #
def make_factory(cfg, params, draft_params=None):
    def factory():
        kw = dict(prefill_chunk=4, prefix_caching=True)
        if draft_params is not None:
            kw.update(speculative=2, draft_cfg=cfg,
                      draft_params=draft_params)
        return make_engine(cfg, params, **kw)
    return factory


def test_interleaved_equals_run_alone_on_ladder_engine(cfg, params):
    """Continuous batching over the full ladder engine: interleaved
    shared-prefix requests with staggered budgets each get exactly
    their run-alone stream, completions carry the ladder facts, and
    the pool drains to zero."""
    factory = make_factory(cfg, params)
    reqs = [(PROMPT, 6), (PROMPT, 4), (PROMPT[:6] + [7, 7], 5),
            (PROMPT, 3)]
    golden = {}
    alone = ContinuousBatcher(make_factory(cfg, params)())
    for i, (p, n) in enumerate(reqs):
        rid = alone.submit(p, max_new_tokens=n)
        golden[i] = alone.run()[rid].tokens

    b = ContinuousBatcher(factory())
    rids = [b.submit(p, max_new_tokens=n) for p, n in reqs]
    done = b.run()
    hit_total = 0
    for i, rid in enumerate(rids):
        comp = done[rid]
        assert comp.tokens == golden[i], (i, comp.tokens, golden[i])
        assert comp.prefill_chunks >= 2     # every prompt was chunked
        hit_total += comp.prefix_hit_blocks
    assert hit_total > 0, "no admission ever shared a resident prefix"
    assert_idle_accounting(b.engine)


def test_router_prompt_budget_both_paths(cfg, params):
    """A prompt beyond the single-shot bucket is a *coded* rejection
    (``PromptBudgetError``, ``serve/prompt_budget``) — and the same
    prompt on a chunked fleet is a first-class admission."""
    long_prompt = list(range(1, 15))          # 14 > prefill_len=12

    def plain_factory():
        return make_engine(cfg, params)       # no chunking: bucket 12
    router = Router(ServingFleet(plain_factory, replicas=1))
    with pytest.raises(PromptBudgetError) as err:
        router.submit(long_prompt, max_new_tokens=MAX_NEW)
    assert PromptBudgetError.code == "serve/prompt_budget"
    assert PromptBudgetError.code in str(err.value)
    assert "chunk" in str(err.value)          # names the fix

    golden = run_single(make_engine(cfg, params, prefill_chunk=4),
                        long_prompt, MAX_NEW)
    fleet = ServingFleet(make_factory(cfg, params), replicas=1)
    router2 = Router(fleet)
    rid = router2.submit(long_prompt, max_new_tokens=MAX_NEW)
    done = router2.run()
    assert done[rid].tokens == golden
    for _, (free, used, total) in fleet.block_accounting().items():
        assert used == 0 and free == total


def test_failover_midstream_keeps_ladder_parity(cfg, params):
    """A replica crash mid-stream on the chunked+prefix-caching fleet:
    failover re-prefills (chunked, possibly sharing survivors'
    prefixes) and still completes every request with its run-alone
    stream — with zero block residency on every replica after."""
    factory = make_factory(cfg, params)
    reqs = [(PROMPT, 0), (PROMPT[:6] + [7, 7], 0), (PROMPT, 0)]
    golden = {}
    alone = ContinuousBatcher(factory())
    for i, (p, _) in enumerate(reqs):
        rid = alone.submit(p, max_new_tokens=MAX_NEW)
        golden[i] = alone.run()[rid].tokens

    fleet = ServingFleet(factory, replicas=2)
    router = Router(fleet)
    rids = [router.submit(p, max_new_tokens=MAX_NEW) for p, _ in reqs]
    router.step()                             # requests mid-stream
    fleet.inject("replica-0", "crash")
    done = router.run()
    failovers = 0
    for i, rid in enumerate(rids):
        assert done[rid].tokens == golden[i], (i, done[rid])
        failovers += done[rid].failovers
    assert failovers >= 1, "the crash never exercised failover"
    for _, (free, used, total) in fleet.block_accounting().items():
        assert used == 0 and free == total


def test_hedge_loser_cancellation_returns_shared_blocks(cfg, params):
    """The hedging terminal on the ladder engine: the loser's
    cancellation must unwind refcounted (possibly shared) blocks,
    not just plain ones — ``free == total`` on both replicas."""
    factory = make_factory(cfg, params)
    alone = ContinuousBatcher(factory())
    rid0 = alone.submit(PROMPT, max_new_tokens=MAX_NEW)
    golden = alone.run()[rid0].tokens

    fleet = ServingFleet(factory, replicas=2,
                         config=FleetConfig(hedge_timeout_s=0.02))
    router = Router(fleet)
    fleet.inject("replica-0", "slow", duration_s=5.0)
    rid = router.submit(PROMPT, max_new_tokens=MAX_NEW)
    done = router.run()
    comp = done[rid]
    assert comp.tokens == golden
    assert comp.hedged and comp.hedge_won
    slow = fleet.replicas[0]
    cancelled = [c for c in slow.batcher.completions.values()
                 if c.finish_reason == "cancelled"]
    assert cancelled, "the hedge loser was never cancelled"
    for _, (free, used, total) in fleet.block_accounting().items():
        assert used == 0 and free == total


# --------------------------------------------------------------------- #
# telemetry: the ladder facts are schema-gated serve fields
# --------------------------------------------------------------------- #
def test_ladder_serve_records_schema_and_report(cfg, params,
                                               draft_params, tmp_path):
    telemetry.reset()
    telemetry.configure(out_dir=str(tmp_path), enabled=True)
    try:
        b = ContinuousBatcher(make_factory(cfg, params, draft_params)())
        rids = [b.submit(PROMPT, max_new_tokens=4),
                b.submit(PROMPT, max_new_tokens=3)]
        b.run()
        telemetry.flush()
    finally:
        telemetry.reset()
    with open(os.path.join(tmp_path, "metrics.jsonl")) as f:
        recs = [json.loads(line) for line in f]
    serves = {r["request"]: r for r in recs if r.get("kind") == "serve"}
    assert set(serves) == set(rids)
    for rec in serves.values():
        assert rec["prefill_chunks"] >= 2
        assert rec["spec_proposed"] >= rec["spec_accepted"] >= 0
        assert rec["prefix_hit_blocks"] >= 0

    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    assert telemetry_report.check_schema(str(tmp_path)) == []
    md = telemetry_report.render(str(tmp_path))
    assert "throughput ladder" in md

    # the gate rejects a serve record missing the ladder facts, and
    # one claiming more acceptances than proposals
    with open(os.path.join(tmp_path, "metrics.jsonl"), "a") as f:
        f.write(json.dumps({
            "kind": "serve", "request": "x", "tokens": 1,
            "ttft_ms": 1.0, "tokens_per_sec": 1.0, "queue_wait_ms": 0.0,
            "decode_ms": 1.0, "inter_token_p50_ms": 1.0,
            "inter_token_p99_ms": 1.0, "finish_reason": "eos"}) + "\n")
    problems = telemetry_report.check_schema(str(tmp_path))
    assert any("prefix_hit_blocks" in p for p in problems)


# --------------------------------------------------------------------- #
# cost model: every rung priced both ways
# --------------------------------------------------------------------- #
def _trainable():
    return make_pipeline_lm_trainable(
        make_cfg(vocab=512, max_len=64), optax.sgd(0.1),
        jax.random.PRNGKey(0))


def _rs():
    from autodist_tpu.resource import ResourceSpec
    return ResourceSpec({"topology": {"platform": "cpu",
                                      "num_devices": 2}})


def test_decode_cost_prefix_caching_both_ways():
    from autodist_tpu.simulator import CostModel

    cm = CostModel(_rs())
    t = _trainable()
    paged = cm.decode_cost(t, {"tensor_parallel": 1,
                               "kv_layout": "paged"}, max_len=2048)
    hot = cm.decode_cost(t, {"tensor_parallel": 1, "kv_layout": "paged",
                             "prefix_caching": True},
                         max_len=2048, prefix_hit_rate=0.8)
    assert hot.request_capacity > paged.request_capacity
    assert hot.serve_score < paged.serve_score     # caching elected
    cold = cm.decode_cost(t, {"tensor_parallel": 1,
                              "kv_layout": "paged",
                              "prefix_caching": True}, max_len=2048)
    # zero hits: only the hash/refcount overhead remains -> rejected
    assert cold.serve_score > paged.serve_score
    assert cold.token_time_s > paged.token_time_s
    with pytest.raises(ValueError, match="paged"):
        cm.decode_cost(t, {"tensor_parallel": 1,
                           "prefix_caching": True}, max_len=2048)
    with pytest.raises(ValueError, match="prefix_hit_rate"):
        cm.decode_cost(t, {"tensor_parallel": 1, "kv_layout": "paged",
                           "prefix_caching": True},
                       max_len=2048, prefix_hit_rate=1.5)


def test_decode_cost_speculative_both_ways():
    from autodist_tpu.simulator import CostModel

    cm = CostModel(_rs())
    t = _trainable()
    vanilla = cm.decode_cost(t, {"tensor_parallel": 1,
                                 "kv_layout": "paged"}, max_len=2048)
    good = cm.decode_cost(t, {"tensor_parallel": 1,
                              "kv_layout": "paged", "speculative": 4},
                          max_len=2048, spec_acceptance=0.9)
    assert good.token_time_s < vanilla.token_time_s
    bad = cm.decode_cost(t, {"tensor_parallel": 1,
                             "kv_layout": "paged", "speculative": 4},
                         max_len=2048, spec_acceptance=0.1)
    assert bad.token_time_s > vanilla.token_time_s
    # the draft's residency taxes capacity regardless of acceptance
    assert good.request_capacity < vanilla.request_capacity
    with pytest.raises(ValueError, match="spec_acceptance"):
        cm.decode_cost(t, {"tensor_parallel": 1, "kv_layout": "paged",
                           "speculative": 4},
                       max_len=2048, spec_acceptance=2.0)


def test_rank_serving_ladder_is_opt_in():
    """The ladder zoo rungs appear only under ``ladder=True`` (the
    default zoo stays byte-stable), and under a hot shared-prefix
    traffic mix the capacity objective elects the caching rung."""
    from autodist_tpu.simulator import rank_serving
    from autodist_tpu.simulator.auto_strategy import \
        default_serving_candidates

    plain = default_serving_candidates(2)
    assert not any(c.get("prefix_caching") or c.get("speculative")
                   or c.get("prefill_chunk") for c in plain)
    zoo = default_serving_candidates(2, ladder=True)
    assert any(c.get("prefix_caching") for c in zoo)
    assert any(c.get("speculative") for c in zoo)
    assert any(c.get("prefill_chunk") and "flash_prefill"
               in tuple(c.get("kernel") or ()) for c in zoo)

    ranked = rank_serving(_trainable(), _rs(), objective="capacity",
                          mean_request_len=64.0, max_len=2048,
                          prefix_hit_rate=0.8, ladder=True)
    assert ranked[0][0].get("prefix_caching") is True
