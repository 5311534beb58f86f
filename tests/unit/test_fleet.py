"""Fleet goldens: fault-tolerant multi-replica serving (ISSUE 15).

The robustness bar: a request decodes the exact same token stream
whether it runs alone on one engine, routed across a 2-replica fleet,
failed over mid-stream after a replica death, or raced by a hedge —
for greedy and seeded-sampled decode, across tp and KV layouts — with
every terminal state returning its paged KV blocks
(``free + used == total``, ``free == total`` once idle), every routing
decision a schema-gated ``kind="dispatch"`` record, and the ADT085+
fleet lint firing both ways.
"""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models.pipeline_lm import make_pipeline_lm_trainable
from autodist_tpu.models.transformer import TransformerConfig
from autodist_tpu.serving import (FINISH_REASONS, ContinuousBatcher,
                                  FleetConfig, OverloadedError, Router,
                                  ServingEngine, ServingFleet)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

V = 33          # odd: V % 2 != 0 exercises the vocab zero-pad path
MAX_LEN = 24
PROMPTS = [[1, 2, 3], [4, 5], [6], [7, 8, 9, 10]]
MAX_NEW = 6


def make_cfg():
    return TransformerConfig(
        vocab_size=V, hidden_size=16, num_layers=2, num_heads=2,
        mlp_dim=32, max_len=MAX_LEN, dtype=jnp.float32,
        dropout_rate=0.0, attention_dropout_rate=0.0)


@pytest.fixture(scope="module")
def cfg():
    return make_cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return make_pipeline_lm_trainable(
        cfg, optax.sgd(0.1), jax.random.PRNGKey(0)).params


def make_factory(cfg, params, tp=1, kv_layout="dense", temperature=0.0):
    def factory():
        return ServingEngine(
            cfg, params, tensor_parallel=tp, vocab_parallel=tp > 1,
            num_slots=2, max_len=MAX_LEN, prefill_len=16,
            decode_steps=3, kv_layout=kv_layout, kv_block_len=5,
            temperature=temperature, top_k=5 if temperature else 0)
    return factory


def run_alone(factory, reqs):
    """The golden: each request alone on one engine (sequentially —
    per-slot independence makes one engine's back-to-back runs exact
    run-alone streams, and it saves a compile per request)."""
    out = {}
    b = ContinuousBatcher(factory())
    for i, (prompt, seed) in enumerate(reqs):
        rid = b.submit(prompt, max_new_tokens=MAX_NEW, seed=seed)
        out[i] = b.run()[rid].tokens
    return out


def assert_zero_residency(fleet):
    acc = fleet.block_accounting()
    for name, (free, used, total) in acc.items():
        assert used == 0 and free == total, (name, acc)


# --------------------------------------------------------------------- #
# parity goldens: run-alone == routed == failover-mid-stream
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("tp,kv_layout", [
    (1, "dense"), (1, "paged"), (2, "dense"), (2, "paged")])
def test_fleet_parity_routed_and_failover_greedy(cfg, params, tp,
                                                 kv_layout):
    """Greedy decode is token-for-token identical run-alone, routed
    across 2 replicas, and failed over mid-stream after a replica
    crash — with zero block residency at the end of each run."""
    factory = make_factory(cfg, params, tp=tp, kv_layout=kv_layout)
    reqs = [(p, 0) for p in PROMPTS]
    golden = run_alone(factory, reqs)

    fleet = ServingFleet(factory, replicas=2)
    router = Router(fleet)
    rids = [router.submit(p, max_new_tokens=MAX_NEW) for p, _ in reqs]
    done = router.run()
    for i, rid in enumerate(rids):
        assert done[rid].tokens == golden[i], (i, done[rid])
    assert_zero_residency(fleet)

    fleet2 = ServingFleet(factory, replicas=2)
    router2 = Router(fleet2)
    rids2 = [router2.submit(p, max_new_tokens=MAX_NEW) for p, _ in reqs]
    router2.step()   # requests mid-stream
    fleet2.inject("replica-0", "crash")
    done2 = router2.run()
    failovers = 0
    for i, rid in enumerate(rids2):
        assert done2[rid].tokens == golden[i], (i, done2[rid])
        failovers += done2[rid].failovers
    assert failovers >= 1, "the crash never exercised the failover path"
    assert_zero_residency(fleet2)
    states = {(r.name, r.state) for r in fleet2.replicas}
    assert ("replica-0", "replaced") in states   # lifecycle completed


@pytest.mark.parametrize("tp,kv_layout", [
    (1, "dense"), (1, "paged"), (2, "dense")])
def test_warm_replicas_compile_nothing_on_their_first_requests(
        cfg, params, tp, kv_layout):
    """A replica built with ``warm=True`` has compiled its prefill and
    decode programs before it takes traffic (a replica mid-compile looks
    hung across the heartbeat window): the first requests lower and
    compile nothing, and decode what they decode alone."""
    from tests.unit.test_serving import CompileEvents

    factory = make_factory(cfg, params, tp=tp, kv_layout=kv_layout)
    reqs = [(p, 0) for p in PROMPTS]
    golden = run_alone(factory, reqs)
    fleet = ServingFleet(factory, replicas=2, warm=True)
    router = Router(fleet)
    with CompileEvents().counting() as compiled:
        rids = [router.submit(p, max_new_tokens=MAX_NEW) for p, _ in reqs]
        done = router.run()
    assert compiled == []
    for i, rid in enumerate(rids):
        assert done[rid].tokens == golden[i], (i, done[rid])
    assert_zero_residency(fleet)


@pytest.mark.parametrize("tp,kv_layout", [(1, "paged"), (2, "dense")])
def test_fleet_parity_sampled_seeded(cfg, params, tp, kv_layout):
    """Seeded sampling keeps the same contract: the gumbel keys fold
    (request seed, context length, vocab row), so a failover
    re-prefill of prompt + emitted continues the IDENTICAL stream —
    the position-keyed draw is re-dispatch-invariant."""
    factory = make_factory(cfg, params, tp=tp, kv_layout=kv_layout,
                           temperature=0.8)
    reqs = [(p, 100 + i) for i, p in enumerate(PROMPTS[:3])]
    golden = run_alone(factory, reqs)

    fleet = ServingFleet(factory, replicas=2)
    router = Router(fleet)
    rids = [router.submit(p, max_new_tokens=MAX_NEW, seed=s)
            for p, s in reqs]
    router.step()
    fleet.inject("replica-0", "crash")
    done = router.run()
    for i, rid in enumerate(rids):
        assert done[rid].tokens == golden[i], (i, done[rid])
    assert_zero_residency(fleet)


def test_hedged_request_loser_cancelled(cfg, params):
    """A straggler replica's request is hedged onto a healthy replica;
    the first completion wins, the loser is cancelled (blocks freed
    the same round), and the stream equals run-alone."""
    factory = make_factory(cfg, params, kv_layout="paged")
    golden = run_alone(factory, [(PROMPTS[0], 0)])
    fleet = ServingFleet(factory, replicas=2,
                         config=FleetConfig(hedge_timeout_s=0.02))
    router = Router(fleet)
    fleet.inject("replica-0", "slow", duration_s=5.0)
    rid = router.submit(PROMPTS[0], max_new_tokens=MAX_NEW)
    done = router.run()
    comp = done[rid]
    assert comp.tokens == golden[0]
    assert comp.hedged and comp.hedge_won
    assert comp.replica == "replica-1"
    # the loser's dispatch was withdrawn on the slow replica
    slow = fleet.replicas[0]
    cancelled = [c for c in slow.batcher.completions.values()
                 if c.finish_reason == "cancelled"]
    assert cancelled, "the hedge loser was never cancelled"
    assert_zero_residency(fleet)


@pytest.mark.slow
def test_drain_races_in_flight_hedge_both_orderings(cfg, params):
    """Draining while a hedge is mid-flight, in both orders — drain the
    straggler that still holds the losing dispatch, and drain the
    healthy replica that holds the winning one.  Either way the client
    stream equals run-alone and every replica's pool settles back to
    ``free + used == total`` with zero residency: a drain sweep must
    not strand the hedge sibling's dispatch or its KV blocks."""
    factory = make_factory(cfg, params, kv_layout="paged")
    golden = run_alone(factory, [(PROMPTS[0], 0)])
    for victim in ("replica-0", "replica-1"):
        fleet = ServingFleet(factory, replicas=2,
                             config=FleetConfig(hedge_timeout_s=0.02))
        router = Router(fleet)
        fleet.inject("replica-0", "slow", duration_s=0.5)
        rid = router.submit(PROMPTS[0], max_new_tokens=MAX_NEW)
        deadline = time.monotonic() + 10.0
        while rid in router._open \
                and len(router._open[rid].dispatches) < 2:
            assert time.monotonic() < deadline, \
                "hedge never fired against the straggler"
            router.step()
        assert rid in router._open, \
            "request completed before the drain could race the hedge"
        router.drain_replica(victim)
        done = router.run()
        comp = done[rid]
        assert comp.tokens == golden[0], (victim, comp.tokens)
        assert comp.hedged
        assert_zero_residency(fleet)
        for name, (free, used, total) in fleet.block_accounting().items():
            assert free + used == total, (victim, name, free, used, total)


def test_hang_detected_by_heartbeat_and_failed_over(cfg, params):
    """A hung replica (no beats, no progress) is declared dead by the
    reused HeartbeatMonitor freshness check and its requests fail
    over — the training plane's detection semantics on the serving
    plane."""
    factory = make_factory(cfg, params)
    golden = run_alone(factory, [(p, 0) for p in PROMPTS])
    fleet = ServingFleet(
        factory, replicas=2,
        config=FleetConfig(heartbeat_interval_s=0.02,
                           heartbeat_timeout_s=0.25,
                           heartbeat_startup_grace_s=0.25,
                           max_replacements=1))
    router = Router(fleet)
    rids = [router.submit(p, max_new_tokens=MAX_NEW) for p in PROMPTS]
    router.step()
    fleet.inject("replica-0", "hang")
    done = router.run()
    for i, rid in enumerate(rids):
        assert done[rid].tokens == golden[i]
    dead = fleet.replicas[0]
    assert dead.declared_fault == "replica_hang"


def test_scheduler_idle_gap_is_not_a_replica_hang(cfg, params):
    """Beats only advance while the scheduler steps: a caller-side
    idle gap longer than the heartbeat timeout must reset the
    freshness windows, never mass-declare healthy replicas dead."""
    factory = make_factory(cfg, params)
    fleet = ServingFleet(
        factory, replicas=2,
        config=FleetConfig(heartbeat_interval_s=0.02,
                           heartbeat_timeout_s=0.1,
                           heartbeat_startup_grace_s=0.1))
    router = Router(fleet)
    rid = router.submit(PROMPTS[0], max_new_tokens=MAX_NEW)
    router.run()
    time.sleep(0.3)            # idle: no steps, no beats, no polls
    rid2 = router.submit(PROMPTS[1], max_new_tokens=MAX_NEW)
    done = router.run()
    assert done[rid2].finish_reason == "max_tokens"
    assert all(r.state == "admitting" for r in fleet.replicas)
    assert router.completions[rid].failovers == 0


def test_single_replica_drain_roll_keeps_drain_provenance(cfg, params):
    """A drain re-home delayed by a replica-less gap (single-replica
    rolling restart) is still recorded reason="drain" once the
    successor spawns — the drain sibling of the failover_from fix."""
    tel = telemetry.reset()
    tel.enabled = True
    try:
        factory = make_factory(cfg, params)
        golden = run_alone(factory, [(PROMPTS[0], 0)])
        fleet = ServingFleet(factory, replicas=1)
        router = Router(fleet)
        # queued behind a full slot set so the dispatch is still in
        # the replica queue when the drain lands
        rids = [router.submit(p, max_new_tokens=MAX_NEW)
                for p in PROMPTS[:3]]
        router.step()
        fleet.drain("replica-0", replace=True)
        done = router.run()
        assert done[rids[0]].tokens == golden[0]
        dispatches = [r for r in tel.step_records()
                      if r.get("kind") == "dispatch"]
        assert any(r["reason"] == "drain" for r in dispatches)
        assert not any(r["reason"] == "failover" for r in dispatches)
    finally:
        telemetry.reset()


def test_slow_replica_is_not_declared_dead(cfg, params):
    """A straggler keeps beating: the health check must never declare
    it (hedging's territory) — the slow-vs-hang distinction."""
    factory = make_factory(cfg, params)
    fleet = ServingFleet(
        factory, replicas=2,
        config=FleetConfig(heartbeat_interval_s=0.02,
                           heartbeat_timeout_s=0.15,
                           heartbeat_startup_grace_s=0.15))
    fleet.inject("replica-0", "slow", duration_s=0.4)
    t0 = time.monotonic()
    while time.monotonic() - t0 < 0.6:
        for r in fleet.live:
            r.step()
        fleet.poll_health()
        time.sleep(0.01)
    assert fleet.replicas[0].state == "admitting"
    assert fleet.replicas[0]._fault is None   # resumed


def test_replacement_budget_escalates_to_shrunk_fleet(cfg, params):
    """Beyond the replacement budget the fleet continues shrunk
    (escalated, recorded) — and still completes every request."""
    factory = make_factory(cfg, params)
    golden = run_alone(factory, [(p, 0) for p in PROMPTS])
    fleet = ServingFleet(factory, replicas=2,
                         config=FleetConfig(max_replacements=0))
    router = Router(fleet)
    rids = [router.submit(p, max_new_tokens=MAX_NEW) for p in PROMPTS]
    router.step()
    fleet.inject("replica-0", "crash")
    done = router.run()
    for i, rid in enumerate(rids):
        assert done[rid].tokens == golden[i]
    assert fleet.escalated
    assert len(fleet.live) == 1
    # an escalated (never-rebuilt) replica reports "dead", not
    # "replaced" — state printouts must show the shrunk capacity
    assert fleet.replicas[0].state == "dead"


def test_fleet_with_no_survivors_sheds_instead_of_hanging(cfg, params):
    """Every replica dead + budget spent: open requests complete
    exactly once as "shed" (coded, resubmittable) — run() terminates."""
    factory = make_factory(cfg, params)
    fleet = ServingFleet(factory, replicas=1,
                         config=FleetConfig(max_replacements=0))
    router = Router(fleet)
    rid = router.submit(PROMPTS[0], max_new_tokens=MAX_NEW)
    fleet.inject("replica-0", "crash")
    done = router.run()
    assert done[rid].finish_reason == "shed"
    assert set(router.completions) == {rid}


def test_failover_across_replicaless_gap_is_still_recorded(cfg, params):
    """A single-replica fleet whose only replica crashes: the re-home
    waits for the replacement, and the eventual dispatch is STILL a
    reason="failover" record naming the dead source — a delayed
    failover must not be relabeled a plain route."""
    tel = telemetry.reset()
    tel.enabled = True
    try:
        factory = make_factory(cfg, params)
        golden = run_alone(factory, [(PROMPTS[0], 0)])
        fleet = ServingFleet(factory, replicas=1,
                             config=FleetConfig(max_replacements=1))
        router = Router(fleet)
        rid = router.submit(PROMPTS[0], max_new_tokens=MAX_NEW)
        router.step()
        fleet.inject("replica-0", "crash")
        done = router.run()
        assert done[rid].tokens == golden[0]
        assert done[rid].failovers == 1
        dispatches = [r for r in tel.step_records()
                      if r.get("kind") == "dispatch"]
        failovers = [r for r in dispatches if r["reason"] == "failover"]
        assert len(failovers) == 1
        assert failovers[0]["from_replica"] == "replica-0"
    finally:
        telemetry.reset()


def test_drain_replace_rolls_the_replica(cfg, params):
    """drain(replace=True): the rolling-restart shape — the drained
    replica retires and a fresh incarnation takes its name, without
    touching the failure-replacement budget."""
    factory = make_factory(cfg, params)
    fleet = ServingFleet(factory, replicas=2,
                         config=FleetConfig(max_replacements=0))
    router = Router(fleet)
    rids = [router.submit(p, max_new_tokens=MAX_NEW) for p in PROMPTS]
    fleet.drain("replica-0", replace=True)
    router.run()
    states = [(r.name, r.incarnation, r.state) for r in fleet.replicas]
    assert ("replica-0", 0, "replaced") in states
    assert ("replica-0", 1, "admitting") in states
    assert not fleet.escalated
    # the fresh incarnation takes traffic again
    rid = router.submit(PROMPTS[0], max_new_tokens=MAX_NEW)
    assert router.run()[rid].tokens   # served, not shed
    assert len(fleet.admitting) == 2


def test_drain_rehomes_queued_and_finishes_in_flight(cfg, params):
    """Draining a replica: queued dispatches move (reason="drain"),
    in-flight ones finish in place, the drained replica retires dead,
    and every stream equals run-alone."""
    factory = make_factory(cfg, params)
    reqs = [(p, 0) for p in PROMPTS * 2]
    golden = run_alone(factory, reqs)
    fleet = ServingFleet(factory, replicas=2)
    router = Router(fleet)
    rids = [router.submit(p, max_new_tokens=MAX_NEW) for p, _ in reqs]
    router.drain_replica("replica-0")
    done = router.run()
    for i, rid in enumerate(rids):
        assert done[rid].tokens == golden[i]
    assert fleet.replicas[0].state == "dead"
    with pytest.raises(ValueError, match="no admitting replica"):
        fleet.drain("replica-0")


# --------------------------------------------------------------------- #
# the block-leak audit (the deadline/shed/cancel terminal states)
# --------------------------------------------------------------------- #
def _paged_engine(cfg, params, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("prefill_len", 8)
    kw.setdefault("decode_steps", 3)
    return ServingEngine(cfg, params, kv_layout="paged", kv_block_len=5,
                         **kw)


def test_deadline_expiry_of_admitted_request_returns_blocks(cfg, params):
    """The PR 14 gap: deadline expiry of an ADMITTED request must
    release its reservation like every other terminal state."""
    eng = _paged_engine(cfg, params)
    b = ContinuousBatcher(eng)
    rid = b.submit([1, 2, 3], max_new_tokens=20, deadline_s=0.05)
    b.step()   # admitted: blocks reserved
    free, used, total = eng.block_accounting()
    assert used > 0 and free + used == total
    time.sleep(0.1)
    b.run()
    assert b.completions[rid].finish_reason == "deadline_exceeded"
    assert eng.block_accounting() == (total, 0, total)


def test_every_terminal_state_restores_block_accounting(cfg, params):
    """free + used == total after queued expiry, shedding, drain (both
    modes), and cancel (queued + in-flight)."""
    eng = _paged_engine(cfg, params)
    b = ContinuousBatcher(eng, max_queue=2)
    total = eng.kv_num_blocks
    # queued deadline expiry (never admitted: no reservation to leak)
    r1 = b.submit([1], max_new_tokens=4, deadline_s=0.01)
    r2 = b.submit([2], max_new_tokens=4)
    with pytest.raises(OverloadedError):   # shed at the queue bound
        b.submit([3], max_new_tokens=4)
    time.sleep(0.05)
    b.run()
    assert b.completions[r1].finish_reason == "deadline_exceeded"
    assert b.completions[r2].finish_reason == "max_tokens"
    assert eng.block_accounting() == (total, 0, total)
    # cancel: queued and in-flight
    r3 = b.submit([1, 2], max_new_tokens=8)
    assert b.cancel(r3)                     # still queued
    assert b.completions[r3].finish_reason == "cancelled"
    assert not b.cancel(r3)                 # not live anymore
    r4 = b.submit([1, 2], max_new_tokens=8)
    b.step()                                # admitted
    assert b.cancel(r4)
    assert b.completions[r4].finish_reason == "cancelled"
    assert len(b.completions[r4].tokens) >= 1   # kept what it had
    assert eng.block_accounting() == (total, 0, total)
    # drain with an in-flight cut
    r5 = b.submit([3], max_new_tokens=8)
    b.step()
    out = b.drain(finish_in_flight=False)
    assert out[r5].finish_reason == "drained"
    assert eng.block_accounting() == (total, 0, total)
    assert "cancelled" in FINISH_REASONS


def test_prefill_failure_releases_reservations_and_requeues(cfg, params):
    """The crash-path bugfix: an engine dying mid-prefill must not
    strand the blocks reserved for the requests it was admitting —
    they are released and the requests go back to the queue head."""
    eng = _paged_engine(cfg, params)
    b = ContinuousBatcher(eng)
    total = eng.kv_num_blocks
    rid = b.submit([1, 2, 3], max_new_tokens=4)
    orig = eng.prefill
    calls = {"n": 0}

    def failing_prefill(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("injected engine death")
        return orig(*a, **kw)

    eng.prefill = failing_prefill
    with pytest.raises(RuntimeError, match="injected engine death"):
        b.step()
    assert eng.block_accounting() == (total, 0, total)
    assert b.queue_depth == 1               # back at the head
    out = b.run()                           # the engine healed: serve it
    assert out[rid].finish_reason == "max_tokens"
    assert eng.block_accounting() == (total, 0, total)


# --------------------------------------------------------------------- #
# dispatch telemetry: schema gate + fleet report section
# --------------------------------------------------------------------- #
def _report_tools():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    return telemetry_report


def test_dispatch_records_schema_and_failover_pairing(cfg, params,
                                                      tmp_path):
    telemetry.reset()
    telemetry.configure(out_dir=str(tmp_path), enabled=True)
    try:
        factory = make_factory(cfg, params, kv_layout="paged")
        fleet = ServingFleet(factory, replicas=2)
        router = Router(fleet)
        rids = [router.submit(p, max_new_tokens=MAX_NEW)
                for p in PROMPTS]
        router.step()
        fleet.inject("replica-0", "crash")
        router.run()
        telemetry.flush()
    finally:
        telemetry.reset()
    tr = _report_tools()
    assert tr.check_schema(str(tmp_path)) == []
    with open(os.path.join(tmp_path, "metrics.jsonl")) as f:
        recs = [json.loads(line) for line in f]
    dispatches = [r for r in recs if r.get("kind") == "dispatch"]
    assert {r["request"] for r in dispatches
            if r["reason"] == "route"} == set(rids)
    failovers = [r for r in dispatches if r["reason"] == "failover"]
    assert failovers and all(r["re_emitted"] == 0 for r in dispatches)
    assert all(r["from_replica"] == "replica-0" for r in failovers)
    md = tr.render(str(tmp_path))
    assert "## fleet" in md and "failover" in md
    assert "replica-0" in md   # the per-replica queue-depth rows

    # the gate fires on: a re-emitted token, an unknown reason, and a
    # failover with no paired fault record
    base = [r for r in recs]
    doctor = tmp_path / "doctored"

    def write(mods):
        doctor.mkdir(exist_ok=True)
        with open(doctor / "metrics.jsonl", "w") as f:
            for r in mods:
                f.write(json.dumps(r) + "\n")
        return tr.check_schema(str(doctor))

    bad = [dict(r) for r in base]
    for r in bad:
        if r.get("kind") == "dispatch":
            r["re_emitted"] = 2
    assert any("re_emitted" in p for p in write(bad))
    bad = [dict(r) for r in base]
    for r in bad:
        if r.get("kind") == "dispatch":
            r["reason"] = "vibes"
    assert any("unknown dispatch reason" in p for p in write(bad))
    orphan = [dict(r) for r in base if r.get("kind") != "fault"]
    assert any("unaudited failover" in p for p in write(orphan))


# --------------------------------------------------------------------- #
# the fleet objective (replicas x tp x kv_layout across ICI/DCN)
# --------------------------------------------------------------------- #
def _serving_trainable():
    return make_pipeline_lm_trainable(
        make_cfg(), optax.sgd(0.1), jax.random.PRNGKey(0))


def test_fleet_objective_elects_replicas_and_gates_tp(cfg):
    from autodist_tpu.resource import ResourceSpec
    from autodist_tpu.simulator import (CostModel,
                                        default_fleet_candidates,
                                        rank_serving)

    trainable = _serving_trainable()
    spec = ResourceSpec({"topology": {"num_devices": 8,
                                      "num_slices": 2}})
    ranked = rank_serving(trainable, spec, objective="fleet",
                          max_len=MAX_LEN, mean_request_len=8)
    assert ranked
    best_cand, best_cost = ranked[0]
    # capacity scales with replicas at equal latency: the fleet
    # objective fills the device budget with replicas
    assert best_cand.get("replicas", 1) > 1
    # tp within a slice's ICI, everywhere in the scored set
    assert all(c["tensor_parallel"] <= 4 for c, _ in ranked)
    # fleet_score monotone in replicas at fixed (tp, layout)
    cm = CostModel(spec)
    one = cm.decode_cost(trainable, {"tensor_parallel": 1},
                         max_len=MAX_LEN, mean_request_len=8)
    two = cm.decode_cost(trainable,
                         {"tensor_parallel": 1, "replicas": 2},
                         max_len=MAX_LEN, mean_request_len=8)
    assert two.fleet_score < one.fleet_score
    # replicas are PRICED across DCN: a fleet spanning slices carries
    # a dispatch term, a single-slice fleet does not
    wide = cm.decode_cost(trainable,
                          {"tensor_parallel": 2, "replicas": 4},
                          max_len=MAX_LEN, mean_request_len=8)
    assert wide.dispatch_time_s > 0
    assert two.dispatch_time_s == 0
    # ...and tp is FORBIDDEN across DCN (the ADT088 contract at
    # pricing time), as is overflowing the device budget
    with pytest.raises(ValueError, match="within a slice"):
        cm.decode_cost(trainable, {"tensor_parallel": 8},
                       max_len=MAX_LEN)
    with pytest.raises(ValueError, match="needs"):
        cm.decode_cost(trainable,
                       {"tensor_parallel": 4, "replicas": 4},
                       max_len=MAX_LEN)
    # the candidate zoo respects both bounds by construction
    for cand in default_fleet_candidates(8, num_slices=2):
        assert cand["tensor_parallel"] <= 4
        assert cand.get("replicas", 1) * cand["tensor_parallel"] <= 8
    with pytest.raises(ValueError, match="fleet"):
        rank_serving(trainable, spec, objective="warp")


def test_fleet_lint_fires_both_ways():
    from autodist_tpu.analysis import lint_fleet
    from autodist_tpu.analysis.mutations import run_mutations
    from autodist_tpu.resource import ResourceSpec

    results = run_mutations(kinds=["fleet"])
    assert {r["code"] for r in results} == {"ADT085", "ADT086",
                                            "ADT087", "ADT088"}
    bad = [r for r in results if not r["ok"]]
    assert not bad, bad
    # the shipped default config is clean, and the shared ADT081
    # heartbeat rule fires on a fleet config too
    assert lint_fleet(FleetConfig()).ok
    report = lint_fleet(FleetConfig(heartbeat_interval_s=5.0,
                                    heartbeat_timeout_s=1.0))
    assert "ADT081" in report.codes()
    spec = ResourceSpec({"topology": {"num_devices": 2}})
    report = lint_fleet({"replicas": 4, "tensor_parallel": 1},
                        resource_spec=spec)
    assert "ADT086" in report.codes()


def test_fleet_describe_lints_through_the_object(cfg, params):
    fleet = ServingFleet(make_factory(cfg, params), replicas=2,
                         warm=False)
    d = fleet.describe()
    assert d["tensor_parallel"] == 1 and d["has_engine_source"]
    from autodist_tpu.resource import ResourceSpec

    assert fleet.lint(ResourceSpec(
        {"topology": {"num_devices": 2}})).ok
    report = fleet.lint(ResourceSpec({"topology": {"num_devices": 1}}))
    assert "ADT086" in report.codes()
