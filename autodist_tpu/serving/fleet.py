"""Fault-tolerant multi-replica serving: the fleet behind the router.

A :class:`ServingFleet` runs N *replicas* — each a full
``ServingEngine`` + ``ContinuousBatcher`` group (one tp group; the
engine's ``tensor_parallel`` spans its own device set) — behind one
:class:`~autodist_tpu.serving.router.Router`.  The fleet owns the parts
a single engine cannot answer:

* **Lifecycle** — every replica walks ``admitting → draining → dead →
  replaced``: an admitting replica takes new dispatches; a draining one
  finishes its in-flight requests while the router re-homes its queue;
  a dead one (crash detected, or hang declared by the health check) is
  abandoned — its engine's paged blocks released wholesale, exactly as
  a crashed host's HBM dies with it — and *replaced* from the engine
  factory under a ``SupervisionConfig``-style replacement budget with
  backoff; budget exhausted escalates to a permanently shrunk fleet
  (coded, recorded — never silent).
* **Health** — per-replica heartbeats: a replica beats once per healthy
  scheduler round, and the fleet's health check runs the SAME freshness
  semantics as the training plane's
  :class:`~autodist_tpu.runtime.cluster.HeartbeatMonitor` (its
  ``poll_once`` is literally reused over an in-process beat client), so
  a hung replica is *detected* after ``heartbeat_timeout_s``, not
  never.  On real hosts the replica group runs behind
  ``runtime/cluster.py`` — the Coordinator launches one engine-loop
  process per replica host set and the same monitor polls the
  coordination-service counters; the in-process backing used here and
  in tests keeps every semantic (states, beats, detection windows,
  records) identical.
* **Fault injection** — ``runtime/faults.py``'s serving-plane kinds
  (``replica_crash``/``replica_hang``/``replica_slow``) land on
  :meth:`inject` via the ``FaultInjector(fleet=...)`` binding; every
  recovery path the router exercises is proven by an injected fault
  (``tools/chaos_run.py --matrix --plane serving``).

Every replica death/replacement emits a ``kind="fault"`` telemetry
record (``tools/telemetry_report.py --check`` pairs a router failover
with it), and fleet configs are linted by
:func:`autodist_tpu.analysis.lint_fleet` (ADT085+) before launch.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

from autodist_tpu import telemetry
from autodist_tpu.runtime.retry import RetryPolicy
from autodist_tpu.serving.batcher import ContinuousBatcher
from autodist_tpu.utils import logging

REPLICA_STATES = ("admitting", "draining", "dead", "replaced")


class ReplicaCrashedError(RuntimeError):
    """A replica's engine died mid-dispatch (the in-process rendering
    of a crashed replica host).  The fleet catches it, declares the
    replica dead, and the router fails its in-flight requests over."""

    code = "serve/replica_crashed"


class FleetDrainedError(RuntimeError):
    """No live replica remains and the replacement budget is spent —
    open requests are shed (coded) for the caller to resubmit
    elsewhere; nothing hangs."""

    code = "serve/fleet_drained"


@dataclasses.dataclass
class FleetConfig:
    """The fleet's robustness knobs (the serving-plane sibling of
    :class:`~autodist_tpu.runtime.cluster.SupervisionConfig`).  Lint
    with :func:`autodist_tpu.analysis.lint_fleet` before launch — the
    ADT085+ rules catch the configs that turn the recovery machinery
    into silent damage.

    * ``hedge_timeout_s`` — straggler deadline: a request whose primary
      dispatch is still open past it gets a duplicate dispatch on
      another replica (first completion wins, the loser is cancelled
      and its blocks freed).  ``None`` calibrates the deadline from the
      completed-request latency distribution instead:
      ``hedge_percentile`` of the last completions × ``hedge_factor``,
      armed once ``hedge_min_samples`` completions exist.  Set
      ``hedge_percentile=None`` too to disable hedging entirely.
    * ``request_deadline_s`` — default per-request deadline stamped at
      ``Router.submit`` (a request carries its remaining deadline
      through every failover re-dispatch).
    * ``max_replacements`` / ``replacement_backoff`` — the restart
      budget per replica name: a dead replica is rebuilt from the
      engine factory at most this many times, with the policy's delay
      between attempts; beyond it the fleet continues permanently
      shrunk (``escalated`` record).
    * ``heartbeat_*`` — the health-check windows (same semantics as
      ``SupervisionConfig``: interval must stay well under timeout —
      ADT081 — and a fresh replica gets the startup grace while its
      programs compile).
    """

    replicas: int = 2
    hedge_timeout_s: Optional[float] = None
    hedge_percentile: Optional[float] = 99.0
    hedge_factor: float = 3.0
    hedge_min_samples: int = 8
    request_deadline_s: Optional[float] = None
    max_replacements: int = 1
    replacement_backoff: RetryPolicy = dataclasses.field(
        default_factory=lambda: RetryPolicy(
            max_attempts=4, base_delay_s=0.0, cap_delay_s=0.0))
    heartbeat_interval_s: float = 0.5
    heartbeat_timeout_s: float = 30.0
    heartbeat_startup_grace_s: float = 120.0

    def to_dict(self) -> dict:
        return {
            "replicas": self.replicas,
            "hedge_timeout_s": self.hedge_timeout_s,
            "hedge_percentile": self.hedge_percentile,
            "hedge_factor": self.hedge_factor,
            "hedge_min_samples": self.hedge_min_samples,
            "request_deadline_s": self.request_deadline_s,
            "max_replacements": self.max_replacements,
            "heartbeat_interval_s": self.heartbeat_interval_s,
            "heartbeat_timeout_s": self.heartbeat_timeout_s,
        }


class Replica:
    """One serving replica: engine + batcher + lifecycle + health.

    Duck-typed like a :class:`~autodist_tpu.runtime.cluster
    .WorkerHandle` (``name``/``running``/``superseded``/``started_s``)
    so the training plane's ``HeartbeatMonitor.poll_once`` monitors it
    unchanged."""

    def __init__(self, name: str, engine, *, incarnation: int = 0,
                 warm: bool = True):
        self.name = name
        self.incarnation = incarnation
        self.engine = engine
        self.batcher = ContinuousBatcher(engine)
        self.state = "admitting"
        self.started_s = time.monotonic()
        self.superseded = False
        self.declared_fault: Optional[str] = None
        self.beats = 0
        self._fault: Optional[str] = None
        self._slow_until = 0.0
        self.replace_on_retire = False   # set by ServingFleet.drain
        if warm:
            self._warm_programs()

    def _warm_programs(self):
        """Compile the prefill/decode programs with dispatches that
        admit and advance nothing (no request's state touched) so the
        first real request never stalls a scheduler round across the
        heartbeat window — a replica mid-compile must look starting-up
        (grace), not hung."""
        import numpy as np

        self.engine.warm_prefill()
        self.engine.decode(np.zeros((self.engine.num_slots,), bool))

    # ------------------------------------------------------------------ #
    @property
    def running(self) -> bool:
        return self.state in ("admitting", "draining")

    @property
    def load(self) -> int:
        """Queued + in-flight requests — the dispatch signal."""
        return self.batcher.queue_depth + self.batcher.active_slots

    def step(self):
        """One scheduler round (admit/decode/evict) + one heartbeat.
        Injected faults act here: a crashed replica raises, a hung one
        neither progresses nor beats, a slow one beats (healthy!) but
        stalls its rounds until the slow window passes."""
        if not self.running:
            return
        if self._fault == "hang":
            return
        if self._fault == "crash":
            raise ReplicaCrashedError(
                f"[{ReplicaCrashedError.code}] replica {self.name} "
                "crashed")
        if self._fault == "slow":
            if time.monotonic() < self._slow_until:
                self.beats += 1
                return
            self._fault = None
            # The straggler came back: the terminal record the report's
            # injected↔outcome pairing gate expects (slow is the one
            # serving fault with no death — hedging absorbed it).
            telemetry.record_event("fault", fault="replica_slow",
                                   target=self.name, phase="recovered",
                                   action="resumed")
        self.batcher.step()
        self.beats += 1


class _FleetBeatClient:
    """The in-process stand-in for the coordination-service client the
    HeartbeatMonitor polls: ``hb/<replica>`` counters read straight off
    the live replicas' beat counts."""

    def __init__(self, fleet: "ServingFleet"):
        self._fleet = fleet

    def counter_add(self, key: str, delta: int = 0) -> int:
        name = key[len("hb/"):] if key.startswith("hb/") else key
        replica = self._fleet._by_name.get(name)
        return replica.beats if replica is not None else 0


class _FleetCoordShim:
    """Duck-types the two Coordinator touchpoints
    ``HeartbeatMonitor.poll_once`` uses (``workers`` and
    ``declare_dead``) onto the fleet's replicas."""

    def __init__(self, fleet: "ServingFleet"):
        self._fleet = fleet

    @property
    def workers(self):
        return [r for r in self._fleet.replicas if r.running]

    def declare_dead(self, replica, reason: str):
        self._fleet.declare_dead(replica, reason, fault="replica_hang")


class ServingFleet:
    """N replica serving groups + lifecycle + health + replacement.

    ``engine_factory`` builds one fresh ``ServingEngine`` per call —
    the params source replacements are rebuilt from (an exported
    artifact, a checkpoint, a params tree in memory).  Drive the fleet
    through a :class:`~autodist_tpu.serving.router.Router`; the fleet
    itself never sees requests."""

    def __init__(self, engine_factory: Callable[[], object], *,
                 replicas: Optional[int] = None,
                 config: Optional[FleetConfig] = None,
                 warm: bool = True):
        self.config = config or FleetConfig()
        if replicas is not None:
            self.config = dataclasses.replace(self.config,
                                              replicas=int(replicas))
        if self.config.replicas < 1:
            raise ValueError("a fleet needs at least one replica")
        self.engine_factory = engine_factory
        self._warm = warm
        self.replicas: list[Replica] = []
        self._by_name: dict[str, Replica] = {}
        self._replacements: dict[str, int] = {}
        self.escalated = False
        for i in range(self.config.replicas):
            self._spawn(f"replica-{i}")
        # The training plane's monitor, verbatim: poll_once over the
        # in-process beat client gives the serving plane the exact
        # detection semantics chaos already proved for workers.
        from autodist_tpu.runtime.cluster import HeartbeatMonitor

        self._beat_client = _FleetBeatClient(self)
        self._last_poll_s: Optional[float] = None
        self._monitor = HeartbeatMonitor(
            _FleetCoordShim(self), lambda: self._beat_client,
            interval_s=self.config.heartbeat_interval_s,
            timeout_s=self.config.heartbeat_timeout_s,
            startup_grace_s=self.config.heartbeat_startup_grace_s)

    # ------------------------------------------------------------------ #
    def _spawn(self, name: str, incarnation: int = 0) -> Replica:
        replica = Replica(name, self.engine_factory(),
                          incarnation=incarnation, warm=self._warm)
        self.replicas.append(replica)
        self._by_name[name] = replica
        if getattr(self, "_monitor", None) is not None:
            # A spawn blocks the whole scheduler (engine build +
            # program compile): forget every freshness window so the
            # stall cannot read as the OTHER replicas hanging — the
            # restart-grace idea, fleet-wide.
            self._monitor._last.clear()
        self._emit_live_gauge()
        return replica

    def _emit_live_gauge(self):
        telemetry.gauge("fleet/replicas_live").set(
            sum(r.running for r in self.replicas))

    @property
    def live(self) -> list:
        return [r for r in self.replicas if r.running]

    @property
    def admitting(self) -> list:
        """Routing targets: live replicas accepting new dispatches."""
        return [r for r in self.replicas if r.state == "admitting"]

    def has_replica(self, name: str) -> bool:
        """FaultInjector ownership predicate (``fleet=`` binding)."""
        replica = self._by_name.get(name)
        return replica is not None and replica.running

    def describe(self) -> dict:
        """The fleet-shape dict :func:`autodist_tpu.analysis.lint_fleet`
        checks (config knobs + the engine-derived shape keys).  A
        constructed fleet always has a factory, so
        ``has_engine_source`` is True here — ADT087 exists for the
        hand-written/serialized fleet configs that reach ``lint_fleet``
        without one."""
        d = self.config.to_dict()
        probe = self.replicas[0].engine
        d["tensor_parallel"] = int(getattr(probe, "tensor_parallel", 1))
        d["kv_layout"] = getattr(probe, "kv_layout", "dense")
        d["has_engine_source"] = self.engine_factory is not None
        return d

    def lint(self, resource_spec=None):
        from autodist_tpu.analysis import lint_fleet

        return lint_fleet(self.describe(), resource_spec=resource_spec)

    # ------------------------------------------------------------------ #
    # health + faults
    # ------------------------------------------------------------------ #
    def poll_health(self):
        """One synchronous freshness sweep (the router calls this every
        scheduler round) — ``HeartbeatMonitor.poll_once`` verbatim, so
        hang detection is the training plane's code path.

        Beats only advance while the scheduler steps, so a caller-side
        idle gap (no requests for a while, a blocking compile) would
        read as EVERY replica hanging at the next poll: when the time
        since the previous poll itself exceeds the timeout, the
        freshness windows are meaningless and are reset — a hang is a
        replica that stalls while the scheduler is actively polling,
        never a scheduler that went quiet."""
        now = time.monotonic()
        if self._last_poll_s is not None \
                and now - self._last_poll_s > \
                self.config.heartbeat_timeout_s:
            self._monitor._last.clear()
        self._last_poll_s = now
        client = self._monitor.poll_once(self._beat_client)
        if client is None:   # cannot happen in-process; keep the contract
            self._beat_client = _FleetBeatClient(self)

    def inject(self, name: str, kind: str, duration_s: float = 0.5):
        """The ``FaultInjector`` landing pad for the serving-plane
        fault kinds: ``crash`` (next dispatch raises), ``hang`` (no
        progress, no beats — only the health check ends it), ``slow``
        (beats but stalls for ``duration_s`` — a straggler, hedging's
        territory, and explicitly NOT the health check's)."""
        replica = self._by_name.get(name)
        if replica is None or not replica.running:
            raise ValueError(f"no live replica {name!r} to inject into")
        if kind == "slow":
            replica._slow_until = time.monotonic() + duration_s
        elif kind not in ("crash", "hang"):
            raise ValueError(f"unknown replica fault {kind!r}")
        replica._fault = kind

    def declare_dead(self, replica: Replica, reason: str,
                     fault: str = "replica_crash"):
        """Mark a replica dead (crash caught, or hang declared by the
        health check): emit the detection record the report pairs
        failovers with, abandon the engine (paged blocks released — a
        dead host's HBM dies with it), and let the router re-home its
        requests."""
        if not replica.running:
            return
        logging.error("fleet: declaring %s dead: %s", replica.name, reason)
        replica.declared_fault = fault
        replica.state = "dead"
        replica.engine.release_all_slots()
        telemetry.counter("fleet/replica_deaths").inc()
        telemetry.record_event("fault", fault=fault, target=replica.name,
                               phase="detected", reason=reason)
        self._emit_live_gauge()

    def maybe_replace(self, replica: Replica) -> Optional[Replica]:
        """Rebuild a dead replica from the engine factory under the
        replacement budget; beyond it, escalate to the permanently
        shrunk fleet (recorded, coded — never silent)."""
        if replica.state != "dead" or replica.superseded:
            return None
        fault = replica.declared_fault or "replica_crash"
        n = self._replacements.get(replica.name, 0)
        replica.superseded = True
        if n >= self.config.max_replacements:
            self.escalated = True
            telemetry.counter("fleet/escalations").inc()
            telemetry.record_event(
                "fault", fault=fault, target=replica.name,
                phase="escalated", action="shrink_fleet",
                survivors=[r.name for r in self.live])
            logging.error(
                "fleet: %s dead beyond its replacement budget (%d); "
                "continuing with %d replica(s)", replica.name, n,
                len(self.live))
            self._emit_live_gauge()
            return None
        delay = self.config.replacement_backoff.delay_s(n + 1)
        if delay > 0:
            time.sleep(delay)
        self._replacements[replica.name] = n + 1
        # "replaced" only once the successor actually exists — an
        # escalated (never-rebuilt) replica stays "dead", so state
        # printouts report the shrunk capacity honestly.
        replica.state = "replaced"
        fresh = self._spawn(replica.name, incarnation=n + 1)
        telemetry.counter("fleet/replacements").inc()
        telemetry.record_event(
            "fault", fault=fault, target=replica.name, phase="recovered",
            action="replace", incarnation=n + 1)
        logging.info("fleet: replaced %s (incarnation %d)", replica.name,
                     n + 1)
        return fresh

    # ------------------------------------------------------------------ #
    def grow(self, name: Optional[str] = None) -> Replica:
        """Spawn one ADDITIONAL admitting replica — the autoscaler's
        scale-out edge.  Not a replacement: no charge against the
        failure budget, no fault record (the scale event itself is the
        autoscaler's ``kind="scale"`` record).  The router's next
        ``_pick`` sees the newcomer through ``fleet.admitting``."""
        if name is None:
            i = len(self.replicas)
            while f"replica-{i}" in self._by_name:
                i += 1
            name = f"replica-{i}"
        elif name in self._by_name:
            raise ValueError(f"replica {name!r} already exists")
        return self._spawn(name)

    def drain(self, name: str, replace: bool = False):
        """Start draining a replica (rolling restart / re-election /
        preemption notice): it stops admitting, finishes its in-flight
        requests, and the router re-homes its queued ones (each move a
        ``reason="drain"`` dispatch record).  ``replace=True`` rebuilds
        a fresh replica from the engine factory once the drain
        completes — the rolling-restart shape; the default retires the
        slot for good (an intentional shrink)."""
        replica = self._by_name.get(name)
        if replica is None or replica.state != "admitting":
            raise ValueError(f"no admitting replica {name!r} to drain")
        replica.state = "draining"
        replica.replace_on_retire = bool(replace)
        telemetry.counter("fleet/drains").inc()
        self._emit_live_gauge()

    def retire_drained(self):
        """Finish the drain lifecycle: a draining replica with no work
        left becomes dead (clean teardown — its blocks were freed by
        its own evictions; ``release_all_slots`` is a no-op backstop),
        and a ``drain(replace=True)`` rolling restart spawns its
        successor — planned maintenance, so no fault record and no
        charge against the failure-replacement budget."""
        for replica in self.replicas:
            if replica.state == "draining" and replica.load == 0:
                replica.state = "dead"
                replica.superseded = True   # a drain is not a failure
                replica.engine.release_all_slots()
                if replica.replace_on_retire:
                    self._spawn(replica.name,
                                incarnation=replica.incarnation + 1)
                    replica.state = "replaced"
                    telemetry.counter("fleet/replacements").inc()
                    logging.info("fleet: rolled %s (incarnation %d)",
                                 replica.name, replica.incarnation + 1)
                self._emit_live_gauge()

    def block_accounting(self) -> dict:
        """Per-live-replica ``(free, used, total)`` pool accounting —
        the zero-leak invariant the chaos matrix asserts."""
        return {r.name: r.engine.block_accounting() for r in self.live}
