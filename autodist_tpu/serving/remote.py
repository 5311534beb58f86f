"""Cross-process serving replicas: the Router protocol over the
coordination service.

:class:`~autodist_tpu.serving.fleet.ServingFleet` runs its replicas
in-process; this module runs each replica as a REAL process — one
engine-loop worker per replica host set, launched through
:class:`~autodist_tpu.runtime.cluster.Coordinator` — while the chief
keeps driving the *unchanged*
:class:`~autodist_tpu.serving.router.Router`.  The RPC plane is the
coordination service itself (no new transport):

* **ops** travel chief → worker on the queue
  ``rpc/<name>/i<incarnation>/op`` (JSON ``submit``/``cancel``/
  ``slow``/``stop``);
* **state** travels worker → chief as one idempotent JSON snapshot per
  scheduler round on the KV key ``rpc/<name>/i<incarnation>/state``
  (queue rids, in-flight slot token streams, completions, block-pool
  accounting) — the chief-side :class:`RemoteBatcher` mirrors it into
  the exact duck-type surface the router already reads
  (``completions``/``_slots``/``_queue``/``cancel``);
* **health** is the training plane's machinery verbatim: workers bump
  ``hb/<name>`` via :func:`~autodist_tpu.runtime.cluster.heartbeat`,
  and :meth:`ProcessFleet.poll_health` runs
  ``HeartbeatMonitor.poll_once`` over a real service client — a
  SIGSTOPped replica process is *detected* after the timeout and
  SIGKILLed, exactly a hung worker;
* **faults are real**: a crashed replica is a dead process (the chief
  sees ``WorkerHandle.running`` go false and raises
  :class:`~autodist_tpu.serving.fleet.ReplicaCrashedError` into the
  router's existing declare-dead path), and chaos workers self-inject
  their own deaths from a shipped
  :class:`~autodist_tpu.runtime.faults.FaultPlan`.

Because every router contract (at-most-once emission, failover
re-dispatch of ``prompt + emitted``, hedging, drain re-homing) is
enforced CHIEF-side on the emitted stream, the process boundary adds
no new token-accounting machinery: the sub-rid
``<rid>@<replica>i<inc>.<n>`` travels token-for-token across it, and a
replacement incarnation gets fresh ``rpc/.../i<inc+1>/...`` keys so a
dead incarnation's queued ops can never replay into its successor.

Incarnation keys also scope the snapshot: a mirror ignores state blobs
whose ``inc`` differs from its own, so a stale KV value left by a
killed process cannot masquerade as its replacement's progress.

Worker entry: ``python -m autodist_tpu.serving.remote`` with the env
plane below (the chief's :meth:`ProcessFleet._spawn` ships it)::

    AUTODIST_TPU_REMOTE_REPLICA    replica name (hb/<name> counter key)
    AUTODIST_TPU_REMOTE_ENGINE     {"factory": "mod:fn", "kwargs": {...},
                                    "max_queue": null}
    AUTODIST_TPU_WORKER_INCARNATION  0, 1, ... (replacements)
    AUTODIST_TPU_REMOTE_TELEMETRY  per-worker telemetry dir base
    AUTODIST_TPU_COORD_SERVICE     host:port (+ _TOKEN) of the chief's
                                   coordination server
    AUTODIST_TPU_FAULT_PLAN        optional self-injection plan
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
import signal
import sys
import time
from collections import deque
from typing import Optional

from autodist_tpu import telemetry
from autodist_tpu.serving.batcher import OverloadedError
from autodist_tpu.serving.fleet import (FleetConfig, ReplicaCrashedError,
                                        ServingFleet)
from autodist_tpu.utils import logging

ENGINE_ENV = "AUTODIST_TPU_REMOTE_ENGINE"
REPLICA_ENV = "AUTODIST_TPU_REMOTE_REPLICA"
TELEMETRY_ENV = "AUTODIST_TPU_REMOTE_TELEMETRY"
_HB_ENV = "AUTODIST_TPU_REMOTE_HB_S"


def _rpc_keys(name: str, incarnation: int) -> tuple:
    base = f"rpc/{name}/i{incarnation}"
    return f"{base}/meta", f"{base}/op", f"{base}/state"


def _resolve_factory(path: str):
    """``"pkg.mod:fn"`` → the callable (the engine factory must be a
    module-level name — a closure cannot cross a process boundary)."""
    mod, sep, fn = path.partition(":")
    if not sep or not fn:
        raise ValueError(
            f"engine factory {path!r} must be 'module:function'")
    return getattr(importlib.import_module(mod), fn)


def tiny_engine_factory(*, vocab_size: int = 33, hidden_size: int = 16,
                        num_layers: int = 2, num_heads: int = 2,
                        mlp_dim: int = 32, max_len: int = 24,
                        num_slots: int = 2, prefill_len: int = 16,
                        decode_steps: int = 2, kv_layout: str = "paged",
                        kv_block_len: int = 5, seed: int = 0):
    """The test/chaos engine: a deterministic tiny pipeline-LM
    (``PRNGKey(seed)`` params, greedy decode), so every process that
    builds it from the same kwargs serves the SAME token streams — the
    cross-process chaos matrix's parity anchor against the in-process
    golden."""
    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu.models.pipeline_lm import make_pipeline_lm_trainable
    from autodist_tpu.models.transformer import TransformerConfig
    from autodist_tpu.serving.engine import ServingEngine

    cfg = TransformerConfig(vocab_size=vocab_size, hidden_size=hidden_size,
                            num_layers=num_layers, num_heads=num_heads,
                            mlp_dim=mlp_dim, max_len=max_len,
                            dtype=jnp.float32, dropout_rate=0.0,
                            attention_dropout_rate=0.0)
    params = make_pipeline_lm_trainable(
        cfg, optax.sgd(0.1), jax.random.PRNGKey(seed)).params
    return ServingEngine(cfg, params, num_slots=num_slots, max_len=max_len,
                         prefill_len=prefill_len, decode_steps=decode_steps,
                         kv_layout=kv_layout, kv_block_len=kv_block_len)


# --------------------------------------------------------------------------- #
# Worker side
# --------------------------------------------------------------------------- #
class _SelfFaultPlane:
    """The worker-side landing pad for
    :class:`~autodist_tpu.runtime.faults.FaultInjector`'s serving-plane
    kinds (its ``fleet=`` binding): the process IS the replica, so a
    ``replica_crash`` is a real exit, a ``replica_hang`` a real
    SIGSTOP (only the chief's SIGKILL ends it), and a ``replica_slow``
    an in-loop stall while the heartbeat thread keeps beating —
    healthy-but-straggling, hedging's territory."""

    def __init__(self, name: str):
        self.name = name

    def has_replica(self, name: str) -> bool:
        return name == self.name

    def _flush(self):
        try:
            if telemetry.get().out_dir:
                telemetry.flush()
        except OSError:
            pass

    def inject(self, name: str, kind: str, duration_s: float = 0.5):
        if kind == "crash":
            self._flush()
            os._exit(17)
        elif kind == "hang":
            self._flush()
            os.kill(os.getpid(), signal.SIGSTOP)
        elif kind == "slow":
            time.sleep(duration_s)
            # The straggler's own resume record — the terminal the
            # report's injected↔outcome pairing expects for the one
            # serving fault with no death (mirrors Replica.step).
            telemetry.record_event("fault", fault="replica_slow",
                                   target=self.name, phase="recovered",
                                   action="resumed")
            self._flush()
        else:
            raise ValueError(f"unknown replica fault {kind!r}")


def _engine_meta(engine, max_queue: Optional[int]) -> dict:
    """The scalar engine facts the chief-side proxy needs (published
    once at startup — doubling as the replica-ready handshake)."""
    import jax

    blocks = list(engine.block_accounting()) \
        if hasattr(engine, "block_accounting") else [0, 0, 0]
    device = jax.devices()[0]
    return {
        "pid": os.getpid(),
        # what this worker's jax actually runs on — the chief may hold a
        # chip while its replica processes serve from CPUs
        "platform": device.platform,
        "device_kind": device.device_kind,
        "num_slots": int(engine.num_slots),
        "prefill_len": int(engine.prefill_len),
        "max_len": int(engine.max_len),
        "decode_steps": int(engine.decode_steps),
        "kv_layout": getattr(engine, "kv_layout", "dense"),
        "tensor_parallel": int(getattr(engine, "tensor_parallel", 1)),
        "max_prompt_tokens": int(getattr(engine, "max_prompt_tokens",
                                         engine.prefill_len)),
        "prefill_chunk": getattr(engine, "prefill_chunk", None),
        "max_queue": max_queue,
        "blocks": blocks,
    }


def _snapshot(batcher, engine, incarnation: int, step: int,
              extra_done: dict) -> dict:
    """One idempotent state blob: everything the chief's mirror needs,
    written whole each round so a reader never sees a torn update."""
    done = {rid: {"tokens": list(c.tokens), "finish": c.finish_reason}
            for rid, c in batcher.completions.items()}
    done.update(extra_done)
    blocks = list(engine.block_accounting()) \
        if hasattr(engine, "block_accounting") else [0, 0, 0]
    return {
        "inc": incarnation, "step": step,
        "queue": [r.rid for r in batcher._queue],
        "slots": [[s.req.rid, list(s.tokens)]
                  for s in batcher._slots if s is not None],
        "done": done,
        "blocks": blocks,
    }


def _apply_op(batcher, op: dict, extra_done: dict) -> bool:
    """Apply one chief op; returns True on ``stop``.  A submit the
    batcher sheds (queue bound tripped, drain race) synthesizes a
    ``finish="shed"`` completion so the router re-homes the dispatch —
    the replica-local terminal crossing the process boundary."""
    kind = op.get("op")
    if kind == "submit":
        try:
            batcher.submit(op["prompt"],
                           max_new_tokens=int(op["max_new_tokens"]),
                           eos_id=op.get("eos_id"), rid=op["rid"],
                           seed=int(op.get("seed", 0)),
                           deadline_s=op.get("deadline_s"),
                           trace_id=op.get("trace_id"))
        except (OverloadedError, ValueError) as e:
            logging.warning("remote replica shed %s: %s", op["rid"], e)
            extra_done[op["rid"]] = {"tokens": [], "finish": "shed"}
    elif kind == "cancel":
        batcher.cancel(op["rid"])
    elif kind == "slow":
        # Chief-side slow injection: stall this loop while the
        # heartbeat thread keeps beating (straggler, not hang).
        time.sleep(float(op.get("duration_s", 0.5)))
        telemetry.record_event(
            "fault", fault="replica_slow",
            target=os.environ.get(REPLICA_ENV, "?"),
            phase="recovered", action="resumed")
    elif kind == "stop":
        return True
    else:
        logging.warning("remote replica: unknown op %r", kind)
    return False


def run_replica_worker() -> int:
    """The replica engine-loop process (module ``__main__``): build the
    engine from the shipped spec, heartbeat, consume ops, publish state
    snapshots — until a ``stop`` op, an orphaning (the chief died), or
    a self-injected fault ends it."""
    from autodist_tpu.runtime import cluster, coordination, faults
    from autodist_tpu.serving.batcher import ContinuousBatcher

    name = os.environ.get(REPLICA_ENV, "")
    if not name:
        print(f"remote replica worker: {REPLICA_ENV} not set",
              file=sys.stderr)
        return 2
    incarnation = int(os.environ.get("AUTODIST_TPU_WORKER_INCARNATION",
                                     "0"))
    tel_base = os.environ.get(TELEMETRY_ENV, "")
    if tel_base:
        telemetry.configure(out_dir=os.path.join(
            tel_base, f"{name}-i{incarnation}"))
    client = coordination.service_client()
    if client is None:
        print("remote replica worker: no coordination service "
              "(AUTODIST_TPU_COORD_SERVICE)", file=sys.stderr)
        return 3
    cluster.heartbeat(client, name,
                      interval_s=float(os.environ.get(_HB_ENV, "0.1")))
    spec = json.loads(os.environ[ENGINE_ENV])
    engine = _resolve_factory(spec["factory"])(**spec.get("kwargs", {}))
    max_queue = spec.get("max_queue")
    batcher = ContinuousBatcher(engine, max_queue=max_queue)
    meta_key, op_key, state_key = _rpc_keys(name, incarnation)
    client.put(meta_key,
               json.dumps(_engine_meta(engine, max_queue)).encode())
    injector = None
    # A restarted incarnation must not re-inject its own death.
    plan = faults.load_fault_plan() if incarnation == 0 else None
    ppid = os.getppid()
    extra_done: dict = {}
    step = 0
    stop = False
    while not stop:
        if injector is not None:
            injector.maybe_fire(step)
        for _ in range(64):   # bounded op drain per round
            raw = client.queue_get(op_key, timeout_ms=0)
            if raw is None:
                break
            op = json.loads(raw)
            if plan is not None and injector is None \
                    and op.get("op") == "submit":
                # Arm the self-injection clock at FIRST TRAFFIC, not at
                # boot: a shipped ``at_s`` trigger means "seconds into
                # serving", so the fault lands on in-flight requests no
                # matter how long the rest of the fleet took to boot.
                injector = faults.FaultInjector(
                    plan, self_target=name, fleet=_SelfFaultPlane(name))
            stop = _apply_op(batcher, op, extra_done) or stop
        if batcher._queue or batcher.active_slots:
            batcher.step()
        elif not stop:
            time.sleep(0.01)
        client.put(state_key, json.dumps(
            _snapshot(batcher, engine, incarnation, step,
                      extra_done)).encode())
        if os.getppid() != ppid:
            logging.warning("remote replica %s orphaned; exiting", name)
            break
        step += 1
    if tel_base:
        telemetry.flush()
    return 0


# --------------------------------------------------------------------------- #
# Chief side: the mirror the Router drives
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class _MirrorCompletion:
    rid: str
    tokens: list
    finish_reason: str


@dataclasses.dataclass
class _MirrorRequest:
    rid: str


@dataclasses.dataclass
class _MirrorSlot:
    req: _MirrorRequest
    tokens: list


class _RemoteEngineProxy:
    """The engine attributes the router/fleet read chief-side, off the
    worker's published meta.  ``release_all_slots`` is a no-op: a dead
    replica process's HBM died with it, and a drained one freed its own
    blocks through its evictions."""

    def __init__(self, meta: dict):
        self.platform = meta.get("platform")
        self.device_kind = meta.get("device_kind")
        self.num_slots = meta["num_slots"]
        self.prefill_len = meta["prefill_len"]
        self.max_len = meta["max_len"]
        self.decode_steps = meta["decode_steps"]
        self.kv_layout = meta["kv_layout"]
        self.tensor_parallel = meta["tensor_parallel"]
        self.max_prompt_tokens = meta["max_prompt_tokens"]
        if meta.get("prefill_chunk") is not None:
            self.prefill_chunk = meta["prefill_chunk"]
        self._blocks = tuple(meta.get("blocks") or (0, 0, 0))

    def release_all_slots(self):
        pass

    def block_accounting(self) -> tuple:
        return self._blocks


class RemoteBatcher:
    """The chief-side mirror of one worker's ``ContinuousBatcher``,
    duck-typing exactly the surface the Router reads:
    ``submit``/``cancel``/``completions``/``_slots``/``_queue``/
    ``queue_depth``/``active_slots``.

    Writes are ops on the worker's queue; reads are the last published
    snapshot.  Local echo keeps the mirror honest between snapshots: a
    submit appears in ``_queue`` immediately (so the router's
    least-loaded pick and drain sweep see it before the worker does),
    and a cancel hides its rid until the worker's terminal lands — an
    op in flight is part of the replica's state, not absent from it."""

    def __init__(self, client, meta: dict, *, op_key: str,
                 state_key: str, incarnation: int,
                 engine: _RemoteEngineProxy):
        self._client = client
        self._op_key = op_key
        self._state_key = state_key
        self._incarnation = incarnation
        self._engine = engine
        self.max_queue = meta.get("max_queue")
        self._max_prompt = meta["max_prompt_tokens"]
        self.completions: dict = {}
        self._slots: list = []
        self._queue: deque = deque()
        self._pending: set = set()   # submitted, not yet in a snapshot
        self._gone: set = set()      # cancelled, terminal not yet seen
        self._step = -1

    # ---- writes (ops) ------------------------------------------------- #
    def _put_op(self, op: dict):
        self._client.queue_put(self._op_key, json.dumps(op).encode())

    def submit(self, prompt, *, max_new_tokens: int = 16,
               eos_id: Optional[int] = None, rid: Optional[str] = None,
               deadline_s: Optional[float] = None, seed: int = 0,
               trace_id: Optional[str] = None) -> str:
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) > self._max_prompt:
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds the replica's "
                f"admissible {self._max_prompt}")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError("deadline_s must be > 0")
        if self.max_queue is not None \
                and self.queue_depth >= self.max_queue:
            raise OverloadedError(
                f"[{OverloadedError.code}] remote admission queue full "
                f"({self.queue_depth}/{self.max_queue})")
        if rid is None:
            raise ValueError("remote submit needs an explicit rid "
                             "(the router always provides one)")
        self._put_op({"op": "submit", "rid": rid, "prompt": prompt,
                      "max_new_tokens": int(max_new_tokens),
                      "eos_id": eos_id, "seed": int(seed),
                      "deadline_s": deadline_s, "trace_id": trace_id})
        self._pending.add(rid)
        self._queue.append(_MirrorRequest(rid))
        return rid

    def cancel(self, rid: str) -> bool:
        live = rid in self._pending \
            or any(r.rid == rid for r in self._queue) \
            or any(s.req.rid == rid for s in self._slots)
        if not live:
            return False
        self._put_op({"op": "cancel", "rid": rid})
        self._gone.add(rid)
        self._pending.discard(rid)
        self._queue = deque(r for r in self._queue if r.rid != rid)
        self._slots = [s for s in self._slots if s.req.rid != rid]
        return True

    def shutdown(self):
        try:
            self._put_op({"op": "stop"})
        except OSError:
            pass   # worker (or service) already gone

    # ---- reads (snapshot mirror) -------------------------------------- #
    def refresh(self):
        raw = self._client.get(self._state_key, timeout_ms=0)
        if raw is None:
            return
        snap = json.loads(raw)
        if snap.get("inc") != self._incarnation \
                or snap.get("step", -1) < self._step:
            return   # a stale incarnation's blob, or a re-read
        self._step = snap["step"]
        done = snap.get("done", {})
        seen = set(snap.get("queue", ())) | set(done) \
            | {rid for rid, _ in snap.get("slots", ())}
        self._pending -= seen
        self._gone &= seen - set(done)   # terminal seen: stop hiding
        self.completions = {
            rid: _MirrorCompletion(rid, d["tokens"], d["finish"])
            for rid, d in done.items()}
        self._slots = [_MirrorSlot(_MirrorRequest(rid), toks)
                       for rid, toks in snap.get("slots", ())
                       if rid not in self._gone]
        self._queue = deque(
            [_MirrorRequest(rid) for rid in snap.get("queue", ())
             if rid not in self._gone]
            + [_MirrorRequest(rid) for rid in sorted(self._pending)])
        self._engine._blocks = tuple(snap.get("blocks") or (0, 0, 0))

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def active_slots(self) -> int:
        return len(self._slots)


class RemoteReplica:
    """One process-backed replica, duck-typed like
    :class:`~autodist_tpu.serving.fleet.Replica` (lifecycle states,
    ``load``, ``step``, the WorkerHandle-ish monitor surface) so both
    the Router and ``HeartbeatMonitor.poll_once`` drive it unchanged.

    ``step()`` is the chief-side pump: refresh the mirror, and raise
    :class:`~autodist_tpu.serving.fleet.ReplicaCrashedError` when the
    process died — the router's existing catch declares the replica
    dead, exactly as an in-process engine crash."""

    def __init__(self, name: str, handle, *, client, incarnation: int = 0,
                 ready_timeout_s: float = 120.0):
        self.name = name
        self.incarnation = incarnation
        self.handle = handle
        self.state = "admitting"
        self.superseded = False
        self.declared_fault: Optional[str] = None
        self.beats = 0                  # real beats live in hb/<name>
        self.replace_on_retire = False
        self._fault = None              # in-process-injection parity
        self._slow_until = 0.0
        meta_key, op_key, state_key = _rpc_keys(name, incarnation)
        raw = client.get(meta_key, timeout_ms=int(ready_timeout_s * 1e3))
        if raw is None:
            handle.kill()
            raise RuntimeError(
                f"replica {name} (incarnation {incarnation}) never "
                f"published its engine meta within {ready_timeout_s}s")
        meta = json.loads(raw)
        self.pid = meta.get("pid")
        self.engine = _RemoteEngineProxy(meta)
        self.batcher = RemoteBatcher(client, meta, op_key=op_key,
                                     state_key=state_key,
                                     incarnation=incarnation,
                                     engine=self.engine)
        # The monitor's freshness window starts once the replica is
        # READY — the engine build/compile already happened.
        self.started_s = time.monotonic()

    @property
    def running(self) -> bool:
        return self.state in ("admitting", "draining")

    @property
    def load(self) -> int:
        return self.batcher.queue_depth + self.batcher.active_slots

    def step(self):
        if not self.running:
            return
        if not self.handle.running:
            raise ReplicaCrashedError(
                f"[{ReplicaCrashedError.code}] replica {self.name} "
                f"process died (rc={self.handle.proc.poll()})")
        self.batcher.refresh()

    def shutdown(self):
        self.batcher.shutdown()


class ProcessFleet(ServingFleet):
    """A :class:`~autodist_tpu.serving.fleet.ServingFleet` whose
    replicas are real processes.

    The lifecycle machinery is INHERITED — replacement budgets and
    escalation, drain/retire, block accounting, the fault-record
    vocabulary all run the base class's code over
    :class:`RemoteReplica` mirrors; only the edges differ:

    * ``_spawn`` launches ``python -m autodist_tpu.serving.remote``
      through a :class:`~autodist_tpu.runtime.cluster.Coordinator`
      (``fail_fast=False`` — replica deaths are THIS class's to
      absorb, through ``maybe_replace``'s budget, not the
      coordinator's fail-fast teardown) and waits for the worker's
      ready meta;
    * the beat client is a real
      :func:`~autodist_tpu.runtime.coordination.service_client`, so
      ``poll_health`` reads cross-process ``hb/<name>`` counters with
      the training plane's exact freshness semantics;
    * ``declare_dead`` SIGKILLs the process group first (the only
      signal a SIGSTOPped replica still honors), then runs the base
      bookkeeping/record path.

    ``engine_spec`` is the shippable engine recipe:
    ``{"factory": "module:function", "kwargs": {...}, "max_queue":
    None, "env": {...extra worker env...}}``.
    """

    def __init__(self, engine_spec: dict, *,
                 replicas: Optional[int] = None,
                 config: Optional[FleetConfig] = None,
                 telemetry_dir: Optional[str] = None,
                 fault_plan=None, ready_timeout_s: float = 120.0):
        from autodist_tpu.runtime.cluster import Coordinator
        from autodist_tpu.runtime.coordination import (
            CoordServer, reserve_coord_port, service_client)

        if "factory" not in engine_spec:
            raise ValueError("engine_spec needs a 'factory' "
                             "('module:function') entry")
        self.engine_spec = dict(engine_spec)
        self.telemetry_dir = telemetry_dir
        self.fault_plan = fault_plan
        self.ready_timeout_s = ready_timeout_s
        self.coordinator = Coordinator(fail_fast=False)
        self._server = CoordServer(listen_sock=reserve_coord_port())
        self._addr = f"127.0.0.1:{self._server.port}"
        self._prev_service = os.environ.get("AUTODIST_TPU_COORD_SERVICE")
        os.environ["AUTODIST_TPU_COORD_SERVICE"] = self._addr
        self._client = service_client()
        if self._client is None:   # cannot happen with a live server
            raise RuntimeError("coordination service client unavailable")
        self._closed = False
        super().__init__(self._no_local_engines, replicas=replicas,
                         config=config, warm=False)
        # Health over the REAL service counters (one client per thread;
        # the fleet is single-threaded like the router, so the op
        # client doubles as the beat client).
        self._beat_client = self._client

    @staticmethod
    def _no_local_engines():
        raise RuntimeError(
            "ProcessFleet builds engines in worker processes — the "
            "in-process factory must never be called")

    # ------------------------------------------------------------------ #
    def _spawn(self, name: str, incarnation: int = 0) -> RemoteReplica:
        import autodist_tpu

        pkg_root = os.path.dirname(os.path.dirname(
            os.path.abspath(autodist_tpu.__file__)))
        py_path = os.environ.get("PYTHONPATH", "")
        env = {
            REPLICA_ENV: name,
            "AUTODIST_TPU_WORKER_INCARNATION": str(incarnation),
            ENGINE_ENV: json.dumps({
                k: v for k, v in self.engine_spec.items()
                if k in ("factory", "kwargs", "max_queue")}),
            "AUTODIST_TPU_COORD_SERVICE": self._addr,
            "PYTHONPATH": (f"{pkg_root}:{py_path}" if py_path
                           else pkg_root),
            # A chip belongs to one process and the chief may hold it:
            # workers run on the CPU unless the engine spec's "env"
            # hands one of them a platform (see platforms()).
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "",   # replicas never inherit a simulated mesh
            _HB_ENV: str(min(self.config.heartbeat_interval_s, 0.2)),
        }
        token = os.environ.get("AUTODIST_TPU_COORD_TOKEN", "")
        if token:
            env["AUTODIST_TPU_COORD_TOKEN"] = token
        if self.telemetry_dir:
            env[TELEMETRY_ENV] = self.telemetry_dir
        env.update(self.engine_spec.get("env") or {})
        if self.fault_plan is not None:
            self.fault_plan.ship(env)
        handle = self.coordinator.launch(
            f"{name}-i{incarnation}",
            [sys.executable, "-m", "autodist_tpu.serving.remote"],
            env=env)
        replica = RemoteReplica(name, handle, client=self._client,
                                incarnation=incarnation,
                                ready_timeout_s=self.ready_timeout_s)
        self.replicas.append(replica)
        self._by_name[name] = replica
        if getattr(self, "_monitor", None) is not None:
            # The spawn stalled the whole scheduler (worker boot +
            # compile): forget every freshness window, as the base
            # class does, so the stall cannot read as the OTHER
            # replicas hanging.
            self._monitor._last.clear()
        self._emit_live_gauge()
        return replica

    # ------------------------------------------------------------------ #
    def poll_health(self):
        """The base sweep over the REAL beat client; a control-plane
        blip (poll_once returns None — blind sample) keeps the current
        client, whose own reconnect-and-retry recovers it."""
        now = time.monotonic()
        if self._last_poll_s is not None \
                and now - self._last_poll_s > \
                self.config.heartbeat_timeout_s:
            self._monitor._last.clear()
        self._last_poll_s = now
        self._monitor.poll_once(self._beat_client)

    def inject(self, name: str, kind: str, duration_s: float = 0.5):
        """Chief-side fault injection against the real process: crash
        = SIGKILL, hang = SIGSTOP (only the health check ends it),
        slow = a worker-loop stall op (the heartbeat thread keeps
        beating — a straggler, not a hang)."""
        replica = self._by_name.get(name)
        if replica is None or not replica.running:
            raise ValueError(f"no live replica {name!r} to inject into")
        if kind == "crash":
            replica.handle.kill()
        elif kind == "hang":
            try:
                os.killpg(os.getpgid(replica.handle.proc.pid),
                          signal.SIGSTOP)
            except (ProcessLookupError, PermissionError):
                replica.handle.proc.send_signal(signal.SIGSTOP)
        elif kind == "slow":
            replica.batcher._put_op({"op": "slow",
                                     "duration_s": duration_s})
        else:
            raise ValueError(f"unknown replica fault {kind!r}")

    def declare_dead(self, replica, reason: str,
                     fault: str = "replica_crash"):
        if replica.running and replica.handle.running:
            replica.handle.kill()
        replica.handle.superseded = True   # its exit is accounted HERE
        super().declare_dead(replica, reason, fault=fault)

    def retire_drained(self):
        retiring = [r for r in self.replicas
                    if r.state == "draining" and r.load == 0]
        super().retire_drained()
        for replica in retiring:
            replica.shutdown()

    def block_accounting(self, settle_s: float = 2.0) -> dict:
        """Per-live-replica ``(free, used, total)`` — refreshed from
        the workers' snapshots, polling up to ``settle_s`` for a state
        stable across two reads: a worker evicts its finished slots one
        scheduler round after the chief saw the completion, so the
        zero-leak invariant must be judged on a settled pool, not a
        mirror one round behind it."""
        deadline = time.monotonic() + settle_s
        prev = None
        while True:
            for replica in self.live:
                try:
                    replica.batcher.refresh()
                except OSError:
                    pass   # control-plane blip; judge what we have
            acct = {r.name: r.engine.block_accounting()
                    for r in self.live}
            if acct == prev or time.monotonic() >= deadline:
                return acct
            prev = acct
            time.sleep(0.1)

    # ------------------------------------------------------------------ #
    def platforms(self) -> dict:
        """``{replica name: (platform, device_kind)}`` as each worker's
        own jax reported it at start-up — what the fleet really serves
        from, whatever backend the chief process holds."""
        return {r.name: (r.engine.platform, r.engine.device_kind)
                for r in self.replicas}

    def close(self):
        """Tear the fleet down: stop ops to live workers, SIGKILL the
        rest, coordination server down, env restored."""
        if self._closed:
            return
        self._closed = True
        from autodist_tpu.runtime import coordination

        for replica in self.replicas:
            if replica.running:
                replica.shutdown()
        # Workers flush their telemetry shards at stop-op exit: give
        # the live ones a graceful window to drain the op before the
        # SIGTERM sweep, or the shards a distributed trace stitches
        # from die with their processes.
        deadline = time.monotonic() + 5.0
        while any(r.handle.running for r in self.replicas) \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        self.coordinator.terminate()
        if self._prev_service is None:
            os.environ.pop("AUTODIST_TPU_COORD_SERVICE", None)
        else:
            os.environ["AUTODIST_TPU_COORD_SERVICE"] = self._prev_service
        coordination.reset_service_client()
        self._server.stop()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):   # best-effort: never leak replica processes
        try:
            self.close()
        except Exception:   # noqa: BLE001 — interpreter teardown
            pass


if __name__ == "__main__":
    sys.exit(run_replica_worker())
