"""Cross-process fleet goldens: the Router over real workers (ISSUE 17).

The bar: a request decodes the exact same token stream whether its
engine lives in this process or behind :class:`ProcessFleet`'s RPC
plane in a spawned worker — and a worker SIGKILLed mid-stream is
declared dead by the heartbeat sweep, replaced under the replacement
budget, and its in-flight requests re-dispatched to the same tokens,
with every worker pool settling to zero block residency.
"""
import json
import os
import time

import pytest

from autodist_tpu import telemetry
from autodist_tpu.serving import (ContinuousBatcher, FleetConfig,
                                  ProcessFleet, Router,
                                  tiny_engine_factory)

PROMPTS = [[1, 2, 3], [4, 5], [6], [7, 8, 9, 10]]
MAX_NEW = 6
FACTORY = "autodist_tpu.serving.remote:tiny_engine_factory"


@pytest.fixture(scope="module")
def golden():
    """Run-alone golden on the SAME factory the workers import."""
    out = {}
    b = ContinuousBatcher(tiny_engine_factory())
    for i, prompt in enumerate(PROMPTS):
        rid = b.submit(prompt, max_new_tokens=MAX_NEW)
        out[i] = b.run()[rid].tokens
    return out


@pytest.fixture()
def clean_env(monkeypatch):
    # A leaked worker identity would make THIS process think it is a
    # replica; a leaked service address would point the fleet at a
    # dead server from an earlier test.
    for var in ("AUTODIST_TPU_WORKER_REPLICA", "AUTODIST_TPU_FAULT_PLAN",
                "AUTODIST_TPU_COORD_SERVICE"):
        monkeypatch.delenv(var, raising=False)


def make_fleet(**overrides):
    kwargs = dict(replicas=2, heartbeat_interval_s=0.1,
                  heartbeat_timeout_s=2.0,
                  heartbeat_startup_grace_s=30.0)
    kwargs.update(overrides)
    return ProcessFleet({"factory": FACTORY},
                        config=FleetConfig(**kwargs))


def settle_zero_residency(fleet):
    acc = fleet.block_accounting(settle_s=5.0)
    for name, (free, used, total) in acc.items():
        assert used == 0 and free == total, (name, acc)


@pytest.mark.slow
def test_routed_across_worker_processes_matches_run_alone(clean_env,
                                                          golden):
    with make_fleet() as fleet:
        assert len(fleet.live) == 2
        assert all(r.handle.proc.pid != os.getpid()
                   for r in fleet.live)
        # each worker says what its own jax runs on: CPU-pinned, since
        # a chip belongs to one process and the chief may hold it
        assert fleet.platforms() == {"replica-0": ("cpu", "cpu"),
                                     "replica-1": ("cpu", "cpu")}
        router = Router(fleet)
        rids = [router.submit(p, max_new_tokens=MAX_NEW)
                for p in PROMPTS]
        done = router.run()
        for i, rid in enumerate(rids):
            assert done[rid].tokens == golden[i], (i, done[rid])
        # queue-depth routing spread work across both workers
        assert {done[rid].replica for rid in rids} \
            == {"replica-0", "replica-1"}
        settle_zero_residency(fleet)


@pytest.mark.slow
def test_worker_sigkill_mid_stream_fails_over_and_replaces(clean_env,
                                                           golden):
    with make_fleet(max_replacements=1) as fleet:
        router = Router(fleet)
        rids = [router.submit(p, max_new_tokens=MAX_NEW)
                for p in PROMPTS]
        router.step()   # requests dispatched, streams open
        fleet.inject("replica-0", "crash")
        done = router.run()
        for i, rid in enumerate(rids):
            assert done[rid].tokens == golden[i], (i, done[rid])
        # the dead worker was replaced by a fresh incarnation
        names = {(r.name, r.incarnation) for r in fleet.live}
        assert ("replica-0", 1) in names, names
        assert ("replica-1", 0) in names, names
        settle_zero_residency(fleet)


@pytest.mark.slow
def test_sigkill_run_stitches_one_trace_across_processes(clean_env,
                                                         golden,
                                                         tmp_path):
    """The distributed-tracing acceptance path (ISSUE 19): a 2-replica
    ProcessFleet run with a mid-stream SIGKILL stitches every process's
    telemetry shard into ONE chrome trace — spans from >= 2 real pids,
    the fault visible, the failover re-dispatch visible, and every
    completion's trace id resolvable to stitched events — while the
    token streams still match the run-alone golden."""
    telemetry.reset()
    telemetry.configure(out_dir=str(tmp_path))
    try:
        fleet = ProcessFleet(
            {"factory": FACTORY},
            config=FleetConfig(replicas=2, heartbeat_interval_s=0.1,
                               heartbeat_timeout_s=2.0,
                               heartbeat_startup_grace_s=30.0,
                               max_replacements=1),
            telemetry_dir=str(tmp_path))
        with fleet:
            router = Router(fleet)
            rids = [router.submit(p, max_new_tokens=MAX_NEW)
                    for p in PROMPTS]
            router.step()
            fleet.inject("replica-0", "crash")
            done = router.run()
            for i, rid in enumerate(rids):
                assert done[rid].tokens == golden[i], (i, done[rid])
            assert all(done[rid].trace_id for rid in rids)
            telemetry.flush()
        # close() waited for the graceful stop-op exits: every
        # surviving worker's shard is on disk before the stitch.
        trace = telemetry.stitch_trace(str(tmp_path))
        pids = trace["stitched"]["pids"]
        assert len([p for p in pids if p > 0]) >= 2, trace["stitched"]
        names = [ev["name"] for ev in trace["traceEvents"]]
        # a chief-side SIGKILL records detection + replacement (the
        # "injected" phase belongs to the chaos injector's records)
        assert "fault/detected" in names, sorted(set(names))
        assert "fault/recovered" in names, sorted(set(names))
        assert "dispatch/failover" in names, sorted(set(names))
        for rid in rids:
            tl = telemetry.request_timeline(trace, done[rid].trace_id)
            assert tl, (rid, done[rid].trace_id)
        # the stitched artifact round-trips: on-disk trace.json IS the
        # stitched trace and the schema/causal gates stay green
        with open(tmp_path / "trace.json") as f:
            assert json.load(f)["stitched"] == trace["stitched"]
        import sys
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), "tools"))
        import telemetry_report as tr
        assert tr.check_schema(str(tmp_path)) == []
    finally:
        telemetry.reset()


@pytest.mark.slow
def test_fleet_close_is_idempotent_and_restores_env(clean_env):
    fleet = make_fleet(replicas=1)
    addr = os.environ.get("AUTODIST_TPU_COORD_SERVICE")
    assert addr  # the fleet published its coordination service
    fleet.close()
    fleet.close()
    assert os.environ.get("AUTODIST_TPU_COORD_SERVICE") is None
    # the worker honors the shutdown op on its own schedule
    deadline = time.monotonic() + 15.0
    while any(r.handle.running for r in fleet.replicas):
        assert time.monotonic() < deadline, \
            "worker outlived the fleet teardown"
        time.sleep(0.05)
