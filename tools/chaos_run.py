"""Chaos harness: run a LocalCluster training job under a fault plan.

The executable proof behind every supervised-recovery path: a tiny
pipeline-LM trains on the 8-device simulated CPU mesh while a
``LocalCluster`` of real worker *processes* heartbeats through the
coordination service, and one fault from
:mod:`autodist_tpu.runtime.faults` is injected mid-run.  The run must
end in a supervised recovery (restart, degrade, or shrink-to-survivors
resume) or a clean coded teardown — never a hang, never a bare stack
trace — with a schema-valid ``kind="fault"`` record per injection and
the post-recovery loss trajectory matching the fault-free golden::

    # one fault kind
    JAX_PLATFORMS=cpu python tools/chaos_run.py --fault worker_crash

    # the full matrix: golden + every fault kind, each in its own
    # watchdogged subprocess (a hung scenario FAILS, loudly)
    JAX_PLATFORMS=cpu python tools/chaos_run.py --matrix

    # CI budget guard (remaining scenarios listed, never silently
    # dropped — the lint_strategy --max-programs pattern)
    JAX_PLATFORMS=cpu python tools/chaos_run.py --matrix --max-scenarios 3

    # the serving plane: replica_crash / replica_hang / replica_slow
    # against a 2-replica ServingFleet behind a Router — every request
    # must complete exactly once, token-for-token equal to the
    # single-replica fault-free golden, with zero leaked KV blocks
    JAX_PLATFORMS=cpu python tools/chaos_run.py --matrix --plane serving

    # the same serving faults against REAL replica processes
    # (ProcessFleet over the coordination service): the plan ships to
    # the workers and replica-0 self-injects its own death — a crash
    # is a dead process, a hang a SIGSTOP — while the golden stays
    # in-process as the token-parity anchor
    JAX_PLATFORMS=cpu python tools/chaos_run.py --matrix \
        --plane serving --processes

Per-kind expected outcome:

=================  =====================================================
worker_crash       supervisor restarts the worker (``phase=recovered``)
worker_hang        heartbeat monitor declares it dead (``detected``),
                   SIGKILL, restart (``recovered``)
slow_host          worker stalls under the heartbeat timeout; no kill,
                   run completes (``recovered`` from the worker itself)
coord_drop         server bounced; clients reconnect-and-retry
                   (``recovered``; ``coord/reconnects`` counters move)
ckpt_write_fail    Saver retries, then coded degrade on the last good
                   checkpoint (``degraded``); training never stops
preempt_signal     SIGTERM → blocking elastic checkpoint → re-search on
                   survivors → reshard → resume (``recovered``, the
                   PR 11 flow, loss within the reshard tolerance)
=================  =====================================================
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

if __name__ == "__main__":  # simulated mesh before the first jax import
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _flag = "--xla_force_host_platform_device_count=8"
    if _flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

# The one registry: a fault kind added to runtime/faults.py joins the
# matrix (and this CLI's choices) automatically.
from autodist_tpu.runtime.faults import FAULT_KINDS as FAULTS  # noqa: E402
from autodist_tpu.runtime.faults import \
    SERVING_FAULT_KINDS as SERVING_FAULTS  # noqa: E402

SCENARIOS = ("none",) + FAULTS
# The serving plane (--plane serving): the replica fault kinds against
# a two-replica ServingFleet behind a Router, fixed request mix,
# token-for-token parity vs the single-replica fault-free golden.
SERVING_SCENARIOS = ("none",) + SERVING_FAULTS

# Loss tolerance vs the fault-free golden: faults that never touch the
# chief's math must reproduce it exactly; preempt_signal reshards onto
# half the mesh (PR 11), so its trajectory is close, not bit-equal.
RTOL_EXACT, RTOL_RESHARD = 1e-6, 2e-3

_HB_INTERVAL_S = 0.2
_HB_TIMEOUT_S = 1.2


def make_plan(kind: str, steps: int):
    """The one-fault plan for ``kind`` (an empty plan for the golden).
    Worker faults trigger on wall-time (the workers don't step the
    model); chief faults trigger on the training step."""
    from autodist_tpu.runtime.faults import FaultPlan, FaultSpec

    mid = max(steps // 2, 1)
    spec = {
        "none": None,
        "worker_crash": FaultSpec("worker_crash", target="worker-1",
                                  at_s=1.0),
        "worker_hang": FaultSpec("worker_hang", target="worker-1",
                                 at_s=1.0),
        "slow_host": FaultSpec("slow_host", target="worker-1", at_s=1.0,
                               duration_s=0.6),
        "coord_drop": FaultSpec("coord_drop", target="coord",
                                at_step=mid, duration_s=0.4),
        "ckpt_write_fail": FaultSpec("ckpt_write_fail", target="chief",
                                     at_step=2, times=3),
        "preempt_signal": FaultSpec("preempt_signal", target="chief",
                                    at_step=mid),
    }[kind]
    return FaultPlan(faults=[spec] if spec else [], seed=1234)


# --------------------------------------------------------------------------- #
# Worker process (launched by the chief through the LocalCluster — the
# same re-launch-the-user-script model as a real fleet; detected via
# the AUTODIST_TPU_WORKER env marker)
# --------------------------------------------------------------------------- #
def run_worker() -> int:
    from autodist_tpu import telemetry
    from autodist_tpu.runtime import cluster, coordination, faults

    name = f"worker-{os.environ.get('AUTODIST_TPU_PROCESS_ID', '0')}"
    incarnation = int(os.environ.get("AUTODIST_TPU_WORKER_INCARNATION",
                                     "0"))
    iters = int(os.environ.get("CHAOS_WORKER_ITERS", "50"))
    base = os.environ.get("CHAOS_WORKER_TELEMETRY", "")
    if base:
        telemetry.configure(out_dir=os.path.join(
            base, f"{name}-i{incarnation}"))
    client = coordination.service_client()
    if client is not None:
        cluster.heartbeat(client, name, interval_s=_HB_INTERVAL_S)
    injector = None
    plan = faults.load_fault_plan()
    if plan is not None and incarnation == 0:
        # A restarted incarnation must not re-inject its own death.
        injector = faults.FaultInjector(plan, self_target=name)
    for i in range(iters):
        if injector is not None:
            injector.maybe_fire(i)
        time.sleep(0.1)
    if injector is not None:
        injector.drain_pending(iters)   # a late at_s trigger still fires
    if base:
        telemetry.flush()
    return 0


# --------------------------------------------------------------------------- #
# One scenario (chief): train under the plan, assert the outcome
# --------------------------------------------------------------------------- #
def _build_runner(num_devices: int = 8):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from autodist_tpu import AutoDist
    from autodist_tpu.models.pipeline_lm import make_pipeline_lm_trainable
    from autodist_tpu.models.transformer import TransformerConfig
    from autodist_tpu.strategy.parallel_builders import Pipeline

    cfg = TransformerConfig(vocab_size=64, hidden_size=16, num_layers=2,
                            num_heads=2, mlp_dim=32, max_len=8,
                            dtype=jnp.float32, dropout_rate=0.0,
                            attention_dropout_rate=0.0)
    trainable = make_pipeline_lm_trainable(cfg, optax.sgd(0.05),
                                           jax.random.PRNGKey(0))
    ad = AutoDist({"topology": {"num_devices": num_devices},
                   "mesh": {"data": num_devices // 2, "pipe": 2}},
                  Pipeline(num_microbatches=2))
    runner = ad.build(trainable)

    def make_batch(step):
        r = np.random.RandomState(1000 + step)
        x = r.randint(0, 64, (8, 8)).astype(np.int32)
        y = np.concatenate([x[:, 1:], x[:, :1]], axis=1)
        return {"x": x, "y": y}

    return trainable, runner, make_batch


def run_scenario(kind: str, steps: int, tel_dir: str,
                 out_path: str) -> int:
    import numpy as np

    from autodist_tpu import telemetry
    from autodist_tpu.analysis import lint_supervision
    from autodist_tpu.checkpoint.saver import Saver
    from autodist_tpu.elastic import ElasticController
    from autodist_tpu.runtime.cluster import LocalCluster, SupervisionConfig
    from autodist_tpu.runtime.faults import FaultInjector
    from autodist_tpu.runtime.retry import RetryPolicy

    telemetry.configure(out_dir=tel_dir)
    plan = make_plan(kind, steps)
    trainable, runner, make_batch = _build_runner()
    ckpt_dir = tempfile.mkdtemp(prefix=f"chaos_ckpt_{kind}_")
    saver = Saver(ckpt_dir,
                  retry=RetryPolicy(max_attempts=2, base_delay_s=0.05,
                                    cap_delay_s=0.1, seed=plan.seed),
                  degrade_on_failure=True)
    controller = ElasticController(trainable, saver, global_batch=8)
    controller.install(runner)
    supervision = SupervisionConfig(
        max_restarts=1,
        restart_backoff=RetryPolicy(max_attempts=2, base_delay_s=0.2,
                                    cap_delay_s=0.2, seed=plan.seed),
        heartbeat_interval_s=_HB_INTERVAL_S,
        heartbeat_timeout_s=_HB_TIMEOUT_S,
        escalate=True, saver=saver)
    sup_report = lint_supervision(supervision)
    if not sup_report.ok:
        print(sup_report.render("supervision lint"), file=sys.stderr)
        return 2
    cluster = LocalCluster(2, supervision=supervision)
    extra_env = plan.ship({
        "CHAOS_WORKER_ITERS": str(max(int(steps * 2.5), 45)),
        "CHAOS_WORKER_TELEMETRY": tel_dir,
        "PYTHONPATH": os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
        # workers need no simulated mesh and must not inherit ours
        # (LocalCluster pins their platform to the CPU itself)
        "XLA_FLAGS": "",
    })
    problems: list[str] = []
    try:
        cluster.launch_clients(None, extra_env=extra_env)
        cluster.start_heartbeat_monitor()
        from autodist_tpu.runtime.coordination import service_client

        injector = FaultInjector(plan, self_target="chief", saver=saver,
                                 coord_bounce=cluster.bounce_coord_service)
        losses = []
        for step in range(steps):
            injector.maybe_fire(step)
            if controller.preempted:
                runner = controller.resume({"num_devices": 4})
            # The chief reports its own progress through the control
            # plane every step — so the step right after a coord_drop
            # bounce hits the dead socket DETERMINISTICALLY and pins the
            # reconnect-and-retry path (worker/monitor threads also hit
            # it, but only when their poll lands inside the window).
            client = service_client()
            if client is not None:
                client.counter_add("chief/steps", 1)
            metrics = runner.step(make_batch(step))
            losses.append(float(np.asarray(metrics["loss"])))
            if step % 5 == 3:   # a cadence that never collides with the
                #                 mid-run preemption checkpoint's step
                saver.save(runner)
            time.sleep(0.15)   # stretch wall-time so worker faults and
            #                    their detection overlap the run
        # Workers run longer than the loop; join must be clean —
        # a crash beyond supervision would raise here.
        cluster.join(timeout=120)
    finally:
        cluster.terminate()
    saver.wait()
    telemetry.flush()
    _merge_worker_metrics(tel_dir)
    problems += _check_outcome(kind, tel_dir)
    record = {"kind": "chaos_scenario", "fault": kind, "steps": steps,
              "losses": losses, "problems": problems,
              "ok": not problems}
    with open(out_path, "w") as f:
        json.dump(record, f)
    print(f"chaos[{kind}]: {'OK' if not problems else problems}")
    return 0 if not problems else 1


def _merge_worker_metrics(tel_dir: str):
    """Fold every worker incarnation's fault records into the chief's
    metrics.jsonl — ONE log for the schema gate, like a real fleet's
    log aggregation."""
    main = os.path.join(tel_dir, "metrics.jsonl")
    lines = []
    for entry in sorted(os.listdir(tel_dir)):
        sub = os.path.join(tel_dir, entry, "metrics.jsonl")
        if not (entry.startswith(("worker-", "replica-"))
                and os.path.exists(sub)):
            continue
        with open(sub) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("kind") == "fault":
                    lines.append(json.dumps(rec))
    if lines:
        with open(main, "a") as f:
            f.write("\n".join(lines) + "\n")


def _check_outcome(kind: str, tel_dir: str) -> list[str]:
    """Scenario acceptance: schema-clean artifacts (including the
    injected↔outcome pairing the report gates) plus the per-kind
    recovery shape."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from telemetry_report import check_schema, load_jsonl

    problems = list(check_schema(tel_dir))
    records = load_jsonl(os.path.join(tel_dir, "metrics.jsonl"))
    faults = [r for r in records if r.get("kind") == "fault"]
    counters = {r["name"]: r["value"] for r in records
                if r.get("kind") == "counter"}

    def has(phase, fault=None, **kv):
        return any(r.get("phase") == phase
                   and (fault is None or r.get("fault") == fault)
                   and all(r.get(k) == v for k, v in kv.items())
                   for r in faults)

    if kind == "none":
        if faults:
            problems.append(f"golden run emitted fault records: {faults}")
        return problems
    if not has("injected", kind):
        problems.append(f"no injected record for {kind}")
    if kind in ("worker_crash", "worker_hang"):
        if not has("recovered", kind, action="restart"):
            problems.append(f"{kind}: no supervised restart recorded")
        if kind == "worker_hang" and not has("detected", kind):
            problems.append("worker_hang: heartbeat monitor never "
                            "declared the worker dead")
    elif kind == "slow_host":
        if not has("recovered", kind):
            problems.append("slow_host: no recovery record")
        if counters.get("runtime/worker_restarts"):
            problems.append("slow_host: a slow-but-alive worker was "
                            "restarted (heartbeat timeout too tight)")
    elif kind == "coord_drop":
        if not has("recovered", kind):
            problems.append("coord_drop: no server-restart record")
        if not counters.get("coord/reconnect_successes"):
            problems.append("coord_drop: no client ever reconnected "
                            "(chief-side); the retry path never ran")
    elif kind == "ckpt_write_fail":
        if not has("degraded", kind):
            problems.append("ckpt_write_fail: Saver never degraded "
                            "onto the last good checkpoint")
        if not counters.get("ckpt/save_failures"):
            problems.append("ckpt_write_fail: ckpt/save_failures "
                            "counter never moved")
    elif kind == "preempt_signal":
        if not has("recovered", kind, action="shrink_resume"):
            problems.append("preempt_signal: no shrink-resume recovery "
                            "record")
    return problems


# --------------------------------------------------------------------------- #
# The serving plane: replica faults against a 2-replica fleet
# --------------------------------------------------------------------------- #
# The fixed request mix every serving scenario serves (prompt,
# max_new_tokens): short ragged prompts whose decode spans the
# injection point, so a mid-stream failure always has in-flight
# requests to re-home.
SERVE_MIX = ([1, 2, 3], 8), ([4, 5], 8), ([6], 8), ([7, 8, 9], 8), \
    ([3, 1], 8), ([2, 9, 4], 8)


def _build_fleet(kind: str, *, processes: bool = False, tel_dir=None,
                 fault_plan=None):
    """The scenario fleet: 1 fault-free replica for the golden, 2 for
    every fault — hedging armed only for the straggler scenario so the
    crash/hang recoveries are unambiguously the failover path's.

    Both planes serve through :func:`tiny_engine_factory` (the
    deterministic PRNGKey(0) engine), so the in-process golden IS the
    parity anchor for the cross-process scenarios: any process that
    builds the engine from the same kwargs emits the same tokens.

    ``processes=True`` swaps in a :class:`ProcessFleet` — real replica
    processes over the coordination service, the fault plan shipped for
    worker self-injection — with the heartbeat window widened to
    cross-process scale (a replacement spawn takes seconds of worker
    boot, not microseconds of object construction)."""
    from autodist_tpu.serving import FleetConfig, ServingFleet
    from autodist_tpu.serving.remote import ProcessFleet, tiny_engine_factory

    if processes and kind != "none":
        fleet_config = FleetConfig(
            replicas=2,
            hedge_timeout_s=0.5 if kind == "replica_slow" else None,
            hedge_percentile=None,
            max_replacements=1,
            heartbeat_interval_s=0.1, heartbeat_timeout_s=2.0,
            heartbeat_startup_grace_s=30.0)
        return ProcessFleet(
            {"factory": "autodist_tpu.serving.remote:tiny_engine_factory"},
            config=fleet_config, telemetry_dir=tel_dir,
            fault_plan=fault_plan)
    fleet_config = FleetConfig(
        replicas=1 if kind == "none" else 2,
        hedge_timeout_s=0.2 if kind == "replica_slow" else None,
        hedge_percentile=None,
        max_replacements=1,
        heartbeat_interval_s=0.05, heartbeat_timeout_s=0.5,
        heartbeat_startup_grace_s=0.5)
    return ServingFleet(tiny_engine_factory, config=fleet_config)


def _await_worker_fault_records(kind: str, tel_dir: str,
                                timeout_s: float = 15.0) -> None:
    """Block until the self-injecting worker's fault records hit its
    telemetry file: the straggler flushes its injected+resumed pair
    only after its stall ends, which may be after the chief's requests
    all hedged away and completed — merging before that flush would
    fail the injected↔outcome pairing for a recovery that DID run."""
    want = {"injected"} if kind in ("replica_crash", "replica_hang") \
        else {"injected", "recovered"}
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        seen = set()
        for entry in sorted(os.listdir(tel_dir)):
            sub = os.path.join(tel_dir, entry, "metrics.jsonl")
            if not (entry.startswith("replica-") and os.path.exists(sub)):
                continue
            with open(sub) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if rec.get("kind") == "fault" \
                            and rec.get("fault") == kind:
                        seen.add(rec.get("phase"))
        if want <= seen:
            return
        time.sleep(0.2)


def run_serving_scenario(kind: str, tel_dir: str, out_path: str,
                         processes: bool = False) -> int:
    """One serving scenario: the fixed mix through a fleet under one
    injected replica fault; every request must complete exactly once
    with zero leaked KV blocks and a schema-clean dispatch/fault
    trail.  Token parity vs the golden is the matrix driver's join.

    ``processes=True`` runs the fault against REAL replica processes
    (:class:`ProcessFleet`): the plan ships to the workers and
    replica-0 self-injects its own death/stall ``at_s`` seconds after
    its first submitted request — the chief holds no injector at all,
    so the failure truly arrives from outside the scheduler loop.  The
    golden stays in-process: parity is by construction of the shared
    ``tiny_engine_factory``, and a fault-free remote run would only
    re-prove the RPC mirror, which the remote-serving unit tests own."""
    from autodist_tpu import telemetry
    from autodist_tpu.runtime.faults import (FaultInjector, FaultPlan,
                                             FaultSpec)
    from autodist_tpu.serving import Router

    telemetry.configure(out_dir=tel_dir)
    processes = processes and kind != "none"
    spec = None
    if kind != "none":
        spec = FaultSpec(kind, target="replica-0", at_s=0.5,
                         duration_s=1.5) if processes else \
            FaultSpec(kind, target="replica-0", at_step=2,
                      duration_s=1.0)
    plan = FaultPlan(faults=[spec] if spec else [], seed=1234)
    fleet = _build_fleet(kind, processes=processes, tel_dir=tel_dir,
                         fault_plan=plan)
    router = Router(fleet)
    # In-process: the chief owns the injection (it holds the fleet).
    # Cross-process: the WORKER owns it (self-injection from the
    # shipped plan) — a chief-side injector here would double-fire.
    injector = None if processes \
        else FaultInjector(plan, self_target="chief", fleet=fleet)
    rids = [router.submit(p, max_new_tokens=m) for p, m in SERVE_MIX[:4]]
    rnd = 0
    while router._open or rnd < 4:
        if injector is not None:
            injector.maybe_fire(rnd)
        if rnd == 3:   # late arrivals keep the queue live mid-fault
            rids += [router.submit(p, max_new_tokens=m)
                     for p, m in SERVE_MIX[4:]]
        router.step()
        if processes:
            time.sleep(0.01)   # remote rounds poll RPC; don't spin hot
        rnd += 1
    # A short mix can finish inside a transient fault's window (every
    # request hedged off the straggler): keep the scheduler alive until
    # the fault resolves — the injector.drain_pending analog; ending
    # early would green-light a resume record that never fired.
    if not processes:
        while any(r._fault is not None for r in fleet.live):
            router.step()
            time.sleep(0.02)
    telemetry.flush()
    if processes:
        _await_worker_fault_records(kind, tel_dir)
        _merge_worker_metrics(tel_dir)
    # One stitched chrome-trace per scenario (chief shard + any worker
    # shards): the injected fault must be VISIBLE in it — asserted in
    # the outcome check below.
    telemetry.stitch_trace(tel_dir)
    problems = _check_serving_outcome(kind, tel_dir, fleet, router, rids)
    if processes:
        fleet.close()
    record = {"kind": "chaos_scenario", "plane": "serving", "fault": kind,
              "tokens": {rid: router.completions[rid].tokens
                         for rid in rids if rid in router.completions},
              "finish": {rid: router.completions[rid].finish_reason
                         for rid in rids if rid in router.completions},
              "problems": problems, "ok": not problems}
    with open(out_path, "w") as f:
        json.dump(record, f)
    print(f"chaos[serving/{kind}]: {'OK' if not problems else problems}")
    return 0 if not problems else 1


def _check_serving_outcome(kind, tel_dir, fleet, router, rids) -> list:
    """Exactly-once + zero-leak + per-kind recovery shape (the
    schema gate covers the dispatch/fault record contracts)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from telemetry_report import check_schema, load_jsonl

    problems = list(check_schema(tel_dir))
    # exactly once: every submitted request has exactly one completion,
    # and a *decode* terminal (nothing shed/expired/stranded)
    missing = [r for r in rids if r not in router.completions]
    if missing:
        problems.append(f"requests never completed: {missing}")
    for rid in rids:
        comp = router.completions.get(rid)
        if comp is not None and comp.finish_reason not in (
                "eos", "max_tokens", "max_len"):
            problems.append(f"{rid} ended {comp.finish_reason!r}, not a "
                            "decode terminal")
    # zero leaked KV blocks on every live replica
    for name, (free, used, total) in fleet.block_accounting().items():
        if used != 0 or free != total:
            problems.append(f"{name} leaked KV blocks: free={free} "
                            f"used={used} total={total}")
    records = load_jsonl(os.path.join(tel_dir, "metrics.jsonl"))
    faults = [r for r in records if r.get("kind") == "fault"]
    dispatches = [r for r in records if r.get("kind") == "dispatch"]

    def has(phase, **kv):
        return any(r.get("phase") == phase
                   and all(r.get(k) == v for k, v in kv.items())
                   for r in faults)

    # Every injected fault must be VISIBLE in the stitched trace: the
    # scenario stitches the chief + worker span shards into one
    # chrome-trace, and an injection whose ``fault/injected`` instant
    # never landed on any process's track is a trace that cannot
    # explain its own failover.
    try:
        with open(os.path.join(tel_dir, "trace.json")) as f:
            trace_events = json.load(f).get("traceEvents", [])
    except (OSError, ValueError):
        trace_events = []
    fault_instants = {((e.get("args") or {}).get("fault"),
                      (e.get("args") or {}).get("target"))
                      for e in trace_events
                      if str(e.get("name", "")).startswith(
                          "fault/injected")}
    for rec in faults:
        if rec.get("phase") != "injected":
            continue
        if (rec.get("fault"), rec.get("target")) not in fault_instants:
            problems.append(
                f"injected fault {rec.get('fault')}@{rec.get('target')} "
                "has no fault/injected instant in the stitched "
                "trace.json — the injection is invisible to the trace")

    reasons = {r.get("reason") for r in dispatches}
    if kind == "none":
        if faults:
            problems.append(f"golden run emitted fault records: {faults}")
        if reasons - {"route"}:
            problems.append(f"golden run dispatched non-route reasons: "
                            f"{sorted(reasons - {'route'})}")
        return problems
    if not has("injected", fault=kind):
        problems.append(f"no injected record for {kind}")
    if kind in ("replica_crash", "replica_hang"):
        if not has("detected", fault=kind, target="replica-0"):
            problems.append(f"{kind}: the fleet never detected the "
                            "dead replica")
        if "failover" not in reasons:
            problems.append(f"{kind}: no failover dispatch — the "
                            "re-home path never ran")
        if not has("recovered", fault=kind, action="replace"):
            problems.append(f"{kind}: the dead replica was never "
                            "replaced")
    elif kind == "replica_slow":
        if not has("recovered", fault=kind, action="resumed"):
            problems.append("replica_slow: the straggler never "
                            "recorded its resume")
        if "hedge" not in reasons:
            problems.append("replica_slow: no hedged dispatch — the "
                            "straggler path never ran")
        if has("detected", fault="replica_hang") \
                or has("detected", fault="replica_slow"):
            problems.append("replica_slow: a slow-but-beating replica "
                            "was declared dead (hedging territory, "
                            "not the health check's)")
    return problems


def run_serving_matrix(scenario_timeout: float,
                       max_scenarios: int | None, out_dir: str,
                       processes: bool = False) -> int:
    """Golden + every serving fault kind, each subprocessed and
    watchdogged; token-for-token parity joined against the golden.
    With ``processes=True`` the fault scenarios run against real
    replica processes (the golden stays in-process — the parity
    anchor), so the join proves the RPC plane re-homes mid-stream work
    token-for-token across an actual process death."""
    results = {}
    golden_tokens = None
    todo = list(SERVING_SCENARIOS)
    skipped = []
    if max_scenarios is not None and len(todo) > max_scenarios:
        todo, skipped = todo[:max_scenarios], todo[max_scenarios:]
    for kind in todo:
        tel_dir = os.path.join(out_dir, kind)
        out_json = os.path.join(out_dir, f"{kind}.json")
        os.makedirs(tel_dir, exist_ok=True)
        argv = [sys.executable, os.path.abspath(__file__),
                "--plane", "serving", "--run-one", kind,
                "--telemetry-dir", tel_dir, "--out", out_json]
        if processes:
            argv.append("--processes")
        t0 = time.monotonic()
        try:
            proc = subprocess.run(argv, timeout=scenario_timeout,
                                  env=dict(os.environ))
            rc = proc.returncode
        except subprocess.TimeoutExpired:
            results[kind] = {"ok": False,
                             "problems": [f"scenario hung beyond "
                                          f"{scenario_timeout}s"]}
            print(f"chaos[serving/{kind}]: HUNG after "
                  f"{scenario_timeout}s")
            continue
        rec = {"ok": False, "problems": [f"scenario exited rc={rc} "
                                         "with no result record"]}
        if os.path.exists(out_json):
            with open(out_json) as f:
                rec = json.load(f)
        rec["rc"] = rc
        rec["wall_s"] = round(time.monotonic() - t0, 1)
        if kind == "none":
            golden_tokens = rec.get("tokens")
        elif golden_tokens and rec.get("tokens"):
            # Token-for-token: a failure mode may re-route, hedge, or
            # re-prefill a request, but the client stream must be the
            # golden's, byte for byte.
            for rid, want in golden_tokens.items():
                got = rec["tokens"].get(rid)
                if got != want:
                    rec["ok"] = False
                    rec.setdefault("problems", []).append(
                        f"{rid}: tokens {got} != golden {want}")
        results[kind] = rec
    print("\n== serving chaos matrix ==")
    failed = []
    for kind, rec in results.items():
        status = "OK" if rec.get("ok") and rec.get("rc", 1) == 0 \
            else f"FAIL ({rec.get('problems')})"
        print(f"  {kind:16s} {status}  [{rec.get('wall_s', '?')}s]")
        if "OK" not in status:
            failed.append(kind)
    for kind in skipped:
        print(f"  {kind:16s} SKIPPED (--max-scenarios budget)")
    with open(os.path.join(out_dir, "matrix.json"), "w") as f:
        json.dump(results, f, indent=1)
    return 1 if failed else 0


# --------------------------------------------------------------------------- #
# The matrix driver
# --------------------------------------------------------------------------- #
def run_matrix(steps: int, scenario_timeout: float,
               max_scenarios: int | None, out_dir: str) -> int:
    results = {}
    golden_losses = None
    todo = list(SCENARIOS)
    skipped = []
    if max_scenarios is not None and len(todo) > max_scenarios:
        # Loud budget guard: the golden always runs; dropped scenarios
        # are listed, never silently truncated.
        todo, skipped = todo[:max_scenarios], todo[max_scenarios:]
    for kind in todo:
        tel_dir = os.path.join(out_dir, kind)
        out_json = os.path.join(out_dir, f"{kind}.json")
        os.makedirs(tel_dir, exist_ok=True)
        argv = [sys.executable, os.path.abspath(__file__),
                "--run-one", kind, "--steps", str(steps),
                "--telemetry-dir", tel_dir, "--out", out_json]
        t0 = time.monotonic()
        try:
            proc = subprocess.run(argv, timeout=scenario_timeout,
                                  env=dict(os.environ))
            rc = proc.returncode
        except subprocess.TimeoutExpired:
            # A hang IS a failure — the whole point of the harness.
            results[kind] = {"ok": False,
                            "problems": [f"scenario hung beyond "
                                         f"{scenario_timeout}s"]}
            print(f"chaos[{kind}]: HUNG after {scenario_timeout}s")
            continue
        rec = {"ok": False, "problems": [f"scenario exited rc={rc} "
                                         "with no result record"]}
        if os.path.exists(out_json):
            with open(out_json) as f:
                rec = json.load(f)
        rec["rc"] = rc
        rec["wall_s"] = round(time.monotonic() - t0, 1)
        if kind == "none":
            golden_losses = rec.get("losses")
        elif golden_losses and rec.get("losses"):
            rtol = RTOL_RESHARD if kind == "preempt_signal" else RTOL_EXACT
            a, b = golden_losses[-1], rec["losses"][-1]
            if abs(a - b) > rtol * max(abs(a), abs(b), 1e-9):
                rec["ok"] = False
                rec.setdefault("problems", []).append(
                    f"final loss {b} drifted from golden {a} beyond "
                    f"rtol={rtol}")
        results[kind] = rec
    print("\n== chaos matrix ==")
    failed = []
    for kind, rec in results.items():
        status = "OK" if rec.get("ok") and rec.get("rc", 1) == 0 \
            else f"FAIL ({rec.get('problems')})"
        print(f"  {kind:16s} {status}  [{rec.get('wall_s', '?')}s]")
        if "OK" not in status:
            failed.append(kind)
    for kind in skipped:
        print(f"  {kind:16s} SKIPPED (--max-scenarios budget)")
    with open(os.path.join(out_dir, "matrix.json"), "w") as f:
        json.dump(results, f, indent=1)
    return 1 if failed else 0


def main(argv=None) -> int:
    from autodist_tpu import const

    if const.ENV.AUTODIST_TPU_WORKER.val:
        return run_worker()   # we ARE a launched worker
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--plane", choices=("train", "serving"),
                    default="train",
                    help="which chaos plane to sweep: the LocalCluster "
                         "training run (default) or the 2-replica "
                         "serving fleet (replica_* fault kinds)")
    ap.add_argument("--fault", choices=SCENARIOS + SERVING_FAULTS,
                    help="run one scenario inline")
    ap.add_argument("--run-one", choices=SCENARIOS + SERVING_FAULTS,
                    help="(internal) one scenario in this process")
    ap.add_argument("--matrix", action="store_true",
                    help="golden + every fault kind, each subprocessed "
                         "and watchdogged")
    ap.add_argument("--processes", action="store_true",
                    help="serving plane only: run the fault scenarios "
                         "against REAL replica processes (ProcessFleet "
                         "+ worker self-injection); the golden stays "
                         "in-process as the parity anchor")
    ap.add_argument("--steps", type=int, default=14)
    ap.add_argument("--scenario-timeout", type=float, default=600.0)
    ap.add_argument("--max-scenarios", type=int, default=None,
                    help="CI budget guard: run only the first N "
                         "scenarios, loudly listing the skipped rest")
    ap.add_argument("--telemetry-dir", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.run_one or args.fault:
        kind = args.run_one or args.fault
        plane = "serving" if kind in SERVING_FAULTS else args.plane
        valid = SERVING_SCENARIOS if plane == "serving" else SCENARIOS
        if kind not in valid:
            ap.error(f"fault {kind!r} is not a --plane {plane} "
                     f"scenario (choose from {list(valid)})")
        if args.processes and plane != "serving":
            ap.error("--processes is a serving-plane switch (the "
                     "training plane's LocalCluster is already "
                     "process-backed)")
        tel_dir = args.telemetry_dir or tempfile.mkdtemp(
            prefix=f"chaos_{kind}_")
        out = args.out or os.path.join(tel_dir, "result.json")
        if plane == "serving":
            return run_serving_scenario(kind, tel_dir, out,
                                        processes=args.processes)
        return run_scenario(kind, args.steps, tel_dir, out)
    if args.matrix:
        if args.processes and args.plane != "serving":
            ap.error("--processes is a serving-plane switch (the "
                     "training plane's LocalCluster is already "
                     "process-backed)")
        out_dir = args.telemetry_dir or tempfile.mkdtemp(prefix="chaos_")
        print(f"chaos matrix artifacts: {out_dir}")
        if args.plane == "serving":
            return run_serving_matrix(args.scenario_timeout,
                                      args.max_scenarios, out_dir,
                                      processes=args.processes)
        return run_matrix(args.steps, args.scenario_timeout,
                          args.max_scenarios, out_dir)
    ap.error("pick one of --fault/--matrix")


if __name__ == "__main__":
    sys.exit(main())
