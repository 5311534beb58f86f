"""Multi-host cluster runtime: launcher, coordinator, failure watcher.

Counterpart of the reference's cluster layer
(``autodist/cluster.py`` — SSH/SFTP process control and per-node TF
servers — plus ``autodist/coordinator.py`` — chief re-launches the user
script on every worker with env-var role markers and hard-exits on any
worker failure, ``coordinator.py:98-110``).

On TPU pods there are no per-node graph servers: every host runs the same
SPMD program connected through ``jax.distributed``.  What remains of the
reference's runtime — and is built here — is:

* the chief-launches-workers process model (``Coordinator``), with the
  same env-var plane (``AUTODIST_TPU_WORKER``, ``AUTODIST_TPU_STRATEGY_ID``
  ≙ ``AUTODIST_WORKER``/``AUTODIST_STRATEGY_ID``) so heterogeneous
  strategy builders stay deterministic across hosts;
* fail-fast watchers per worker (detection only, no recovery — the
  reference's exact semantics, SURVEY.md §5.3) with clean teardown via
  ``atexit`` (≙ ``cluster.py:171-216``) — plus *opt-in* supervision
  (:class:`SupervisionConfig`): per-worker restart budgets with
  backoff, heartbeat-based hang detection through the coordination
  service, and escalation to shrink-to-survivors recovery.  With
  supervision off, behavior is byte-identical fail-fast;
* per-host data feeding (feed-split ≙ ``remapper.py:109-123``) via
  ``jax.make_array_from_process_local_data``.

Remote transport is plain ``ssh`` subprocesses (paramiko is not in this
image); ``LocalCluster`` spawns workers on localhost for testing the
process plane without hardware (its workers are CPU-pinned: on a chip
host one process drives all chips).
"""
from __future__ import annotations

import atexit
import dataclasses
import os
import random
import shlex
import signal
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Optional, Sequence

from autodist_tpu import const
from autodist_tpu.runtime.retry import RetryPolicy
from autodist_tpu.utils import logging

# Marker line the remote launch bootstrap prints before exec'ing the
# worker, so the chief knows the REMOTE pid (the local ssh client's pid
# is useless for teardown — killing it only drops the tunnel and leaves
# the remote process running).
_REMOTE_PID_MARKER = "__AUTODIST_TPU_REMOTE_PID__="


class WorkerHandle:
    """One launched worker process and its watcher thread.

    ``spec`` is the launch request (name/argv/env/host/cwd) so a
    supervising coordinator can restart the worker verbatim;
    ``superseded`` marks a handle whose failure has already been
    consumed by a restart or an escalation (its exit no longer counts
    against the job)."""

    def __init__(self, name: str, proc: subprocess.Popen,
                 on_failure: Callable[["WorkerHandle", int], None],
                 *, host: Optional[str] = None,
                 spec: Optional[dict] = None):
        self.name = name
        self.proc = proc
        self.host = host
        self.spec = spec
        self.remote_pid: Optional[int] = None
        self.superseded = False
        self.declared_fault: Optional[str] = None   # set by declare_dead
        self.started_s = time.monotonic()
        self._on_failure = on_failure
        if host and proc.stdout is not None:
            self._pid_thread = threading.Thread(
                target=self._read_remote_pid, daemon=True)
            self._pid_thread.start()
        self.thread = threading.Thread(target=self._watch, daemon=True)
        self.thread.start()

    def _watch(self):
        rc = self.proc.wait()
        if rc != 0:
            self._on_failure(self, rc)

    def _read_remote_pid(self):
        """Parse the bootstrap's pid marker off the ssh client's stdout,
        then relay the worker's remaining output to ours."""
        try:
            for raw in self.proc.stdout:
                line = raw.decode(errors="replace")
                if self.remote_pid is None \
                        and line.startswith(_REMOTE_PID_MARKER):
                    try:
                        self.remote_pid = int(
                            line[len(_REMOTE_PID_MARKER):].strip())
                    except ValueError:
                        logging.warning(
                            "worker %s: unparseable remote pid marker %r",
                            self.name, line.strip())
                    continue
                sys.stdout.write(line)
        except (OSError, ValueError):
            pass   # ssh client torn down mid-read

    @property
    def running(self) -> bool:
        return self.proc.poll() is None

    def _remote_kill(self, sig_name: str):
        """Propagate the kill to the remote process group over a second
        ssh exec (the local ssh client dying does NOT reap the remote
        side; fire-and-forget so teardown never blocks on a dead host)."""
        pid = self.remote_pid
        if pid is None:
            logging.warning(
                "worker %s on %s: no remote pid captured; killing only "
                "the local ssh client", self.name, self.host)
            return
        cmd = (f"kill -{sig_name} -- -{pid} 2>/dev/null "
               f"|| kill -{sig_name} {pid}")
        try:
            subprocess.Popen(
                ["ssh", "-o", "BatchMode=yes", self.host, cmd],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        except OSError as e:
            logging.warning("worker %s: remote kill on %s failed: %s",
                            self.name, self.host, e)

    def terminate(self):
        if not self.running:
            return
        if self.host:
            self._remote_kill("TERM")
        try:
            os.killpg(os.getpgid(self.proc.pid), signal.SIGTERM)
        except (ProcessLookupError, PermissionError):
            self.proc.terminate()

    def kill(self):
        """SIGKILL the worker's whole process group — the only signal a
        SIGSTOPped (hung) worker still honors."""
        if not self.running:
            return
        if self.host:
            self._remote_kill("KILL")
        try:
            os.killpg(os.getpgid(self.proc.pid), signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            self.proc.kill()


@dataclasses.dataclass
class SupervisionConfig:
    """Opt-in supervised recovery for a :class:`Coordinator`.

    With ``supervision=None`` (the default) the coordinator keeps the
    reference's exact fail-fast semantics.  With a config: a worker
    exiting non-zero is restarted up to ``max_restarts`` times with
    ``restart_backoff`` between attempts; a worker whose heartbeat
    counter stalls longer than ``heartbeat_timeout_s`` is declared dead
    (SIGKILL) and takes the same restart path — a hung worker is no
    longer hung forever; a worker dead beyond its restart budget
    *escalates*: the survivor set is handed to ``on_escalate`` (e.g.
    a closure around :meth:`ElasticController.resume` — shrink and
    continue) instead of tearing the job down.  ``saver`` is the
    checkpoint store escalation resumes from — the ADT080 lint rejects
    escalation without one (silent state loss).  Lint a config with
    :func:`autodist_tpu.analysis.lint_supervision` before launch.
    """

    max_restarts: int = 2
    restart_backoff: RetryPolicy = dataclasses.field(
        default_factory=lambda: RetryPolicy(
            max_attempts=8, base_delay_s=0.5, cap_delay_s=30.0))
    heartbeat_interval_s: Optional[float] = None
    heartbeat_timeout_s: Optional[float] = None
    # A worker that has not yet produced its FIRST beat since (re)start
    # is still importing/initializing — it gets this grace window, not
    # the steady-state timeout (or every restart would be declared dead
    # mid-interpreter-startup).
    heartbeat_startup_grace_s: float = 60.0
    escalate: bool = False
    saver: Any = None
    on_escalate: Optional[Callable[[list], None]] = None
    # SSP context for the ADT082 lint: staleness window =
    # staleness x step_time_estimate_s; a restart backoff that can
    # outlast it stalls every peer at the SSP gate.
    step_time_estimate_s: float = 1.0

    def to_dict(self) -> dict:
        return {
            "max_restarts": self.max_restarts,
            "restart_backoff": {
                "max_attempts": self.restart_backoff.max_attempts,
                "base_delay_s": self.restart_backoff.base_delay_s,
                "cap_delay_s": self.restart_backoff.cap_delay_s,
            },
            "heartbeat_interval_s": self.heartbeat_interval_s,
            "heartbeat_timeout_s": self.heartbeat_timeout_s,
            "heartbeat_startup_grace_s": self.heartbeat_startup_grace_s,
            "escalate": self.escalate,
            "has_saver": self.saver is not None,
            "step_time_estimate_s": self.step_time_estimate_s,
        }


class Coordinator:
    """Chief-side process manager (≙ reference ``Coordinator``).

    ``launch`` starts one copy of ``argv`` per worker with the role env
    vars set; any worker exiting non-zero triggers fail-fast (terminate
    everything, then ``on_failure`` — by default raising in ``join``;
    the reference hard-exited the chief, ``coordinator.py:108``).

    With ``supervision=``\\ :class:`SupervisionConfig`, failures are
    *supervised* instead: restart with backoff up to the budget, then
    escalate the survivor set (see :class:`SupervisionConfig`).  Every
    restart/escalation emits a ``kind="fault"`` telemetry record so
    ``tools/telemetry_report.py --check`` can pair detections with
    recoveries.
    """

    def __init__(self, fail_fast: bool = True,
                 supervision: Optional[SupervisionConfig] = None):
        self.fail_fast = fail_fast
        self.supervision = supervision
        self.workers: list[WorkerHandle] = []
        self._terminated = False
        self._first_failure: Optional[tuple[str, int]] = None
        self._restarts: dict[str, int] = {}
        self._escalated = threading.Event()
        self._lock = threading.Lock()
        atexit.register(self.terminate)

    def _worker_failed(self, worker: WorkerHandle, rc: int):
        with self._lock:
            if self._terminated or worker.superseded:
                return  # we killed it ourselves; not a failure
        if self.supervision is not None:
            self._supervise_failure(worker, rc)
            return
        with self._lock:
            if self._first_failure is None:
                self._first_failure = (worker.name, rc)
        logging.error("worker %s exited with %d", worker.name, rc)
        if self.fail_fast:
            self.terminate()

    # ------------------- supervised recovery --------------------------- #
    def _supervise_failure(self, worker: WorkerHandle, rc: int):
        """Restart-with-backoff, then escalate (runs on the dead
        worker's watcher thread)."""
        from autodist_tpu import telemetry

        sup = self.supervision
        fault = worker.declared_fault or "worker_crash"
        n = self._restarts.get(worker.name, 0)
        telemetry.counter("runtime/worker_failures").inc()
        logging.error("worker %s exited with %d (restart %d/%d used)",
                      worker.name, rc, n, sup.max_restarts)
        if n < sup.max_restarts and worker.spec is not None:
            delay = sup.restart_backoff._jittered(
                n + 1, random.Random(sup.restart_backoff.seed))
            logging.info("restarting worker %s in %.2fs", worker.name,
                         delay)
            time.sleep(delay)
            with self._lock:
                if self._terminated:
                    return
                self._restarts[worker.name] = n + 1
                worker.superseded = True
            spec = dict(worker.spec)
            env = dict(spec.get("env") or {})
            # The restarted process can tell it is an incarnation > 0
            # (e.g. a chaos-test worker must not re-inject its fault).
            env["AUTODIST_TPU_WORKER_INCARNATION"] = str(n + 1)
            spec["env"] = env
            self.launch(worker.name, spec["argv"], env=env,
                        host=spec.get("host"), cwd=spec.get("cwd"))
            telemetry.counter("runtime/worker_restarts").inc()
            telemetry.record_event(
                "fault", fault=fault, target=worker.name,
                phase="recovered", action="restart", restart=n + 1,
                rc=rc)
            return
        # Budget exhausted: escalate to shrink-to-survivors (or fall
        # back to fail-fast teardown when escalation is off).
        survivors = [w for w in self.workers
                     if w.running and not w.superseded and w is not worker]
        if sup.escalate or sup.on_escalate is not None:
            with self._lock:
                # The death is CONSUMED by the escalation: join() must
                # not re-raise a failure the shrink already recovered.
                worker.superseded = True
            self._escalated.set()
            telemetry.counter("runtime/escalations").inc()
            telemetry.record_event(
                "fault", fault=fault, target=worker.name,
                phase="escalated", action="shrink_to_survivors",
                survivors=[w.name for w in survivors], rc=rc)
            logging.error(
                "worker %s dead beyond its restart budget; escalating "
                "with %d survivor(s)", worker.name, len(survivors))
            if sup.on_escalate is not None:
                try:
                    sup.on_escalate(survivors)
                except Exception as e:  # noqa: BLE001 — watcher thread
                    logging.error("escalation callback failed: %s", e)
            return
        with self._lock:
            if self._first_failure is None:
                self._first_failure = (worker.name, rc)
        telemetry.record_event(
            "fault", fault=fault, target=worker.name,
            phase="teardown", action="fail_fast", rc=rc)
        if self.fail_fast:
            self.terminate()

    @property
    def escalated(self) -> bool:
        """True once a worker died beyond its restart budget and the
        survivor set was handed to escalation; the training loop checks
        this between steps (the elastic shrink handoff)."""
        return self._escalated.is_set()

    def declare_dead(self, worker: WorkerHandle, reason: str,
                     fault: str = "worker_hang"):
        """Declare a live-but-unresponsive worker dead (hang detection):
        SIGKILL its process group — a SIGSTOPped process honors nothing
        else — and let the watcher thread run the normal supervised
        failure path."""
        from autodist_tpu import telemetry

        if not worker.running or worker.superseded:
            return
        logging.error("declaring worker %s dead: %s", worker.name, reason)
        telemetry.counter("runtime/workers_declared_dead").inc()
        telemetry.record_event("fault", fault=fault, target=worker.name,
                               phase="detected", reason=reason)
        worker.declared_fault = fault
        worker.kill()

    def _failures(self) -> list[tuple[str, int]]:
        """Authoritative failure list: process returncodes, with
        terminated-by-us (negative rc after our own terminate) and
        superseded handles (consumed by a restart/escalation) excluded —
        except the recorded first failure, which is always reported even
        when it was a signal death (segfault/OOM-kill) that itself
        triggered the fail-fast teardown."""
        out = []
        for w in self.workers:
            rc = w.proc.poll()
            if rc is not None and rc != 0 and not w.superseded \
                    and not (self._terminated and rc < 0):
                out.append((w.name, rc))
        if self._first_failure is not None and self._first_failure not in out:
            out.insert(0, self._first_failure)
        return out

    def launch(self, name: str, argv: Sequence[str], *,
               env: Optional[dict] = None, host: Optional[str] = None,
               cwd: Optional[str] = None) -> WorkerHandle:
        """Launch one worker locally, or on ``host`` via ssh.

        Remote env vars travel on ssh *stdin* (a `/bin/sh -s` bootstrap),
        never on the command line: the set includes the coordination
        shared secret, and argv is world-readable via ``ps`` on both
        ends for the lifetime of the job.  The bootstrap also reports
        the REMOTE pid (``$$`` at exec time) back on stdout, so
        ``WorkerHandle.terminate`` can propagate the kill to the remote
        process group — killing only the local ssh client would orphan
        the actual worker on its host."""
        spec = {"argv": list(argv), "env": dict(env or {}),
                "host": host, "cwd": cwd}
        full_env = dict(os.environ)
        full_env.update(env or {})
        stdin_script = None
        if host:
            lines = [f"export {k}={shlex.quote(v)}"
                     for k, v in (env or {}).items()]
            lines.append(f'echo "{_REMOTE_PID_MARKER}$$"')
            lines.append("exec " + " ".join(shlex.quote(a) for a in argv))
            stdin_script = "\n".join(lines) + "\n"
            argv = ["ssh", "-o", "BatchMode=yes", host, "/bin/sh -s"]
        proc = subprocess.Popen(
            list(argv), env=full_env, cwd=cwd, start_new_session=True,
            stdin=subprocess.PIPE if stdin_script else None,
            stdout=subprocess.PIPE if host else None)
        if stdin_script:
            proc.stdin.write(stdin_script.encode())
            proc.stdin.close()
        handle = WorkerHandle(name, proc, self._worker_failed,
                              host=host, spec=spec)
        self.workers.append(handle)
        logging.info("launched worker %s (pid %d)%s", name, proc.pid,
                     f" on {host}" if host else "")
        return handle

    def join(self, timeout: Optional[float] = None):
        """Wait for all workers; raise if any failed.  Both the
        ``TimeoutError`` and the ``RuntimeError`` carry the FULL
        concurrent-failure list — a three-worker wreck names all three
        in the postmortem, not whichever was polled first."""
        deadline = time.time() + timeout if timeout is not None else None
        timed_out: list[str] = []
        for w in self.workers:
            remaining = None if deadline is None \
                else max(deadline - time.time(), 0.01)
            try:
                w.proc.wait(timeout=remaining)
                # Let the watcher consume the exit BEFORE judging it:
                # under supervision the restart/escalation bookkeeping
                # (and the appended replacement handle, which this loop
                # then also waits on) happens on that thread.
                w.thread.join(timeout=None if deadline is None
                              else max(deadline - time.time(), 0.01))
                if w.thread.is_alive():
                    raise subprocess.TimeoutExpired(w.name, timeout)
            except subprocess.TimeoutExpired:
                # The shared deadline has passed: every still-running
                # worker is equally timed out — report them all.  When
                # nothing is running but a watcher thread is still
                # consuming an exit (a supervised restart mid-backoff),
                # THAT is what we timed out on — say so, rather than
                # mis-reporting a failure the restart budget was about
                # to absorb.
                timed_out = [v.name for v in self.workers
                             if v.proc.poll() is None and not v.superseded]
                if not timed_out:
                    timed_out = [f"{w.name} (supervision in progress)"]
                break
        if timed_out:
            failures = self._failures()
            self.terminate()
            detail = f"; workers failed: {failures}" if failures else ""
            raise TimeoutError(
                f"worker(s) {timed_out} timed out after {timeout}s"
                f"{detail}")
        failures = self._failures()
        if failures:
            raise RuntimeError(f"workers failed: {failures}")

    def terminate(self):
        with self._lock:
            self._terminated = True
        for w in self.workers:
            w.terminate()


class HeartbeatMonitor(threading.Thread):
    """Chief-side hang detection through the coordination service.

    Workers bump a ``hb/<name>`` counter every
    ``heartbeat_interval_s`` (:func:`heartbeat`); this thread polls the
    counters with its own client (one client per thread — the
    coordination contract) and a worker whose counter has not moved for
    ``heartbeat_timeout_s`` is declared dead through
    :meth:`Coordinator.declare_dead` — a SIGSTOPped or wedged worker is
    detected after the timeout, not never.  Freshness is judged by
    *chief-side receive time* (when the counter was last seen to
    change), so remote-host clock skew cannot fake a hang.
    """

    def __init__(self, coordinator: Coordinator,
                 client_factory: Callable[[], Any],
                 interval_s: float, timeout_s: float,
                 startup_grace_s: float = 60.0):
        super().__init__(daemon=True)
        self.coordinator = coordinator
        self._client_factory = client_factory
        self.interval_s = interval_s
        self.timeout_s = timeout_s
        self.startup_grace_s = startup_grace_s
        self._stop = threading.Event()
        # handle -> [count, last_change_monotonic, beaten_since_start]:
        # keyed by the HANDLE, not the worker name — a restarted worker
        # reuses its name, and the superseded handle's cleanup must not
        # clobber the live incarnation's freshness window.
        self._last: dict[WorkerHandle, list] = {}

    def stop(self):
        self._stop.set()

    def run(self):
        client = None
        while not self._stop.wait(self.interval_s):
            if client is None:
                try:
                    client = self._client_factory()
                except OSError:
                    continue
                if client is None:
                    continue
            client = self.poll_once(client)

    def poll_once(self, client):
        """One freshness sweep over the coordinator's live workers —
        the loop body of :meth:`run`, factored out so a synchronous
        driver (the serving fleet's per-round health check) runs the
        SAME detection semantics the threaded monitor does.  Returns
        the client to use next round (``None`` after a control-plane
        error — never declare deaths on a blind sample)."""
        for w in list(self.coordinator.workers):
            if not w.running or w.superseded:
                self._last.pop(w, None)
                continue
            try:
                count = client.counter_add(f"hb/{w.name}", 0)
            except OSError:
                # Control plane briefly unreachable (coord_drop):
                # never declare deaths on a blind sample.
                return None
            now = time.monotonic()
            last = self._last.get(w)
            if last is None:
                # First sight of this handle: its window starts at
                # launch (a restarted worker is a NEW handle, so a
                # fresh incarnation never inherits stale state).
                self._last[w] = [count, max(now, w.started_s), False]
            elif count != last[0]:
                self._last[w] = [count, now, True]
            else:
                # Not-yet-first-beat gets the startup grace
                # (interpreter + backend init); a worker that HAS
                # beaten gets the steady-state timeout.
                limit = self.timeout_s if last[2] \
                    else max(self.startup_grace_s, self.timeout_s)
                if now - last[1] > limit:
                    self._last.pop(w, None)
                    self.coordinator.declare_dead(
                        w, reason=f"no heartbeat for "
                                  f"{now - last[1]:.1f}s "
                                  f"(timeout {limit}s)")
        return client


def heartbeat(client, name: str, interval_s: float,
              stop: Optional[threading.Event] = None) -> threading.Event:
    """Worker-side heartbeat loop (daemon thread): bump ``hb/<name>``
    every ``interval_s`` through ``client``.  Returns the stop event.
    A dropped coordination socket rides the client's own
    reconnect-and-retry; a fully unavailable service only logs — the
    heartbeat must never kill the worker it reports for."""
    stop = stop or threading.Event()

    def loop():
        while not stop.wait(interval_s):
            try:
                client.counter_add(f"hb/{name}", 1)
            except OSError as e:
                logging.warning("heartbeat for %s not delivered: %s",
                                name, e)

    threading.Thread(target=loop, daemon=True,
                     name=f"heartbeat-{name}").start()
    return stop


class Cluster:
    """The multi-host launch plan (≙ reference ``SSHCluster``).

    ``spec['multihost']`` lists hosts; the chief (process 0) launches the
    *same user script* on every other host with role env vars — the
    reference's exact model (``coordinator.py:66-90``) minus graph
    shipping (the strategy file is tiny JSON; SPMD ships nothing else).
    """

    def __init__(self, resource_spec, hosts: Optional[Sequence[str]] = None,
                 *, coord_service: bool = True,
                 coord_host: Optional[str] = None,
                 supervision: Optional[SupervisionConfig] = None):
        self.resource_spec = resource_spec
        self.hosts = list(hosts or [])
        self.coordinator = Coordinator(supervision=supervision)
        self._monitor: Optional[HeartbeatMonitor] = None
        # Native host-coordination service (runtime/coordination): the chief
        # runs the server; its address propagates to workers via env.
        self._use_coord_service = coord_service
        self._coord_host = coord_host or self._default_coord_host()
        self._coord_server = None
        atexit.register(self.terminate)

    def _default_coord_host(self) -> str:
        """Address remote workers can reach the chief's coordination server
        on: the jax.distributed coordinator's host when configured, the
        chief's FQDN when any worker is remote, else loopback."""
        coordinator = getattr(self.resource_spec, "coordinator", "")
        if coordinator:
            return coordinator.rpartition(":")[0] or coordinator
        if any(h not in ("localhost", "127.0.0.1") for h in self.hosts):
            import socket
            return socket.getfqdn()
        return "127.0.0.1"

    @property
    def is_chief(self) -> bool:
        return not const.ENV.AUTODIST_TPU_WORKER.val

    def _start_coord_service(self) -> str:
        """Start the native coordination server (chief only); returns its
        advertised host:port and exports it to this process's env so the
        chief's own :func:`~autodist_tpu.runtime.coordination.service_client`
        finds it.

        The port is elected by a HELD-socket reservation
        (:func:`~autodist_tpu.runtime.coordination.reserve_coord_port`):
        the exclusively-bound socket is handed straight to the native
        server, so concurrent spawns (two replica-host clusters
        starting at once) can never elect the same ephemeral port — the
        old bind-then-release probe raced in exactly that window."""
        if self._coord_server is None:
            from autodist_tpu.runtime.coordination import (
                CoordServer, reserve_coord_port)
            self._coord_server = CoordServer(
                listen_sock=reserve_coord_port())
            addr = f"{self._coord_host}:{self._coord_server.port}"
            os.environ["AUTODIST_TPU_COORD_SERVICE"] = addr
            logging.info("coordination service at %s", addr)
        return f"{self._coord_host}:{self._coord_server.port}"

    def launch_clients(self, strategy,
                       argv: Optional[Sequence[str]] = None,
                       extra_env: Optional[dict] = None):
        """Chief: start the user script on every worker host.

        ``strategy`` is the built Strategy object (published to the
        coordination service so workers without a shared filesystem can
        load it), a bare strategy-id string (env handoff only), or
        ``None`` — the strategy is decided *after* workers join (the
        AutoStrategy measured-refinement flow, where every process must
        participate in timing the candidates before a winner exists).
        """
        if not self.is_chief:
            return []
        strategy_id = ("" if strategy is None
                       else strategy if isinstance(strategy, str)
                       else strategy.id)
        coord_addr = ""
        if self._use_coord_service:
            try:
                coord_addr = self._start_coord_service()
            except (OSError, subprocess.CalledProcessError) as e:
                logging.warning(
                    "coordination service unavailable (%s); workers fall "
                    "back to the shared strategy dir", e)
        if coord_addr and strategy is not None \
                and not isinstance(strategy, str):
            from autodist_tpu.runtime.coordination import service_client
            client = service_client()
            if client is not None:
                client.put(f"strategy/{strategy_id}",
                           strategy.to_json().encode())
        argv = list(argv or [sys.executable, os.path.abspath(sys.argv[0]),
                             *sys.argv[1:]])
        handles = []
        for i, host in enumerate(self.hosts):
            env = {
                "AUTODIST_TPU_WORKER": host,
                "AUTODIST_TPU_STRATEGY_ID": strategy_id,
                "AUTODIST_TPU_PROCESS_ID": str(i + 1),
                "AUTODIST_TPU_NUM_PROCESSES": str(len(self.hosts) + 1),
                "AUTODIST_TPU_COORDINATOR": self.resource_spec.coordinator,
            }
            if coord_addr:
                env["AUTODIST_TPU_COORD_SERVICE"] = coord_addr
                token = os.environ.get("AUTODIST_TPU_COORD_TOKEN", "")
                if token:
                    env["AUTODIST_TPU_COORD_TOKEN"] = token
            env.update(extra_env or {})
            handles.append(self.coordinator.launch(
                f"worker-{i + 1}", argv, env=env,
                host=None if host in ("localhost", "127.0.0.1") else host))
        return handles

    def start_heartbeat_monitor(self) -> Optional[HeartbeatMonitor]:
        """Start chief-side hang detection (needs a
        :class:`SupervisionConfig` with heartbeat knobs and the running
        coordination service).  Workers opt in by calling
        :func:`heartbeat` against their service client."""
        sup = self.coordinator.supervision
        if sup is None or sup.heartbeat_interval_s is None \
                or sup.heartbeat_timeout_s is None:
            return None
        if self._monitor is None:
            from autodist_tpu.runtime.coordination import service_client
            self._monitor = HeartbeatMonitor(
                self.coordinator, service_client,
                interval_s=sup.heartbeat_interval_s,
                timeout_s=sup.heartbeat_timeout_s,
                startup_grace_s=sup.heartbeat_startup_grace_s)
            self._monitor.start()
        return self._monitor

    def bounce_coord_service(self, down_s: float = 0.5) -> str:
        """Stop the coordination server, wait ``down_s``, and restart it
        on the SAME port (the ``coord_drop`` chaos fault): every
        connected client's socket drops and must reconnect-and-retry.
        Volatile server state (KV, counters, barriers in flight) is
        lost, exactly like a real chief bounce.  Returns the (unchanged)
        advertised address."""
        if self._coord_server is None:
            raise RuntimeError("no coordination server running")
        from autodist_tpu.runtime.coordination import CoordServer

        port = self._coord_server.port
        self._coord_server.stop()
        time.sleep(down_s)
        # Lingering FIN-WAIT-2 sockets from clients that have not yet
        # noticed the drop can hold the port briefly; retry the rebind
        # rather than failing the whole scenario.
        deadline = time.monotonic() + 10.0
        while True:
            try:
                self._coord_server = CoordServer(port=port)
                break
            except OSError:
                if time.monotonic() >= deadline:
                    raise
                time.sleep(0.1)
        return f"{self._coord_host}:{port}"

    def join(self, timeout: Optional[float] = None):
        self.coordinator.join(timeout)

    def terminate(self):
        if self._monitor is not None:
            self._monitor.stop()
            self._monitor = None
        self.coordinator.terminate()
        if self._coord_server is not None:
            from autodist_tpu.runtime import coordination
            addr = f"{self._coord_host}:{self._coord_server.port}"
            if os.environ.get("AUTODIST_TPU_COORD_SERVICE") == addr:
                del os.environ["AUTODIST_TPU_COORD_SERVICE"]
            coordination.reset_service_client()
            self._coord_server.stop()
            self._coord_server = None


class LocalCluster(Cluster):
    """``num_workers`` workers on localhost — the process plane without
    hardware: same launcher, env handoff, coordination service,
    watchers, and (opt-in) supervision as a real fleet, every process
    on this machine.  The chaos harness (``tools/chaos_run.py``) runs
    its fault matrix against one of these.

    A CPU harness by construction: a chip belongs to one process at a
    time, so N+1 processes on one machine cannot share it — the workers
    are pinned to the CPU backend here, whatever the chief runs on.  On
    a chip host, one process drives all of the host's chips."""

    def __init__(self, num_workers: int, resource_spec=None, **kwargs):
        if num_workers < 1:
            raise ValueError("num_workers must be >= 1")
        if resource_spec is None:
            from autodist_tpu.resource import ResourceSpec
            resource_spec = ResourceSpec({})
        super().__init__(resource_spec,
                         hosts=["localhost"] * num_workers, **kwargs)

    def launch_clients(self, strategy, argv=None, extra_env=None):
        return super().launch_clients(
            strategy, argv=argv,
            extra_env={**(extra_env or {}), "JAX_PLATFORMS": "cpu"})


def make_global_batch(batch, mesh, spec=None):
    """Per-host feed: assemble a global array from this host's local shard
    (feed-split contract ≙ ``remapper.py:109-123``; on one host this is a
    plain device_put)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, spec if spec is not None else P(const.DATA_AXIS))
    if jax.process_count() == 1:
        return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(sharding, x), batch)
