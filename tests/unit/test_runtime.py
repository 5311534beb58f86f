"""Runtime layer tests: coordinator process management (fail-fast
semantics ≙ reference coordinator watcher, ``coordinator.py:98-110``),
per-host feeding, profiling meters and stage dumps."""
import os
import subprocess
import sys
import time

import jax
import numpy as np
import optax
import pytest

from autodist_tpu.runtime.cluster import (Cluster, Coordinator,
                                          make_global_batch)
from autodist_tpu.utils.profiling import (StepTimer, dump_stages, mfu,
                                          transformer_train_flops_per_token)


def test_coordinator_success_join():
    c = Coordinator()
    c.launch("ok-1", [sys.executable, "-c", "print('hi')"])
    c.launch("ok-2", [sys.executable, "-c", "import time; time.sleep(0.2)"])
    c.join(timeout=30)


def test_coordinator_fail_fast_kills_siblings():
    c = Coordinator()
    slow = c.launch("slow", [sys.executable, "-c",
                             "import time; time.sleep(60)"])
    c.launch("bad", [sys.executable, "-c", "import sys; sys.exit(3)"])
    with pytest.raises(RuntimeError, match="bad.*3"):
        c.join(timeout=30)
    # the long-running sibling must have been terminated (fail-fast)
    deadline = time.time() + 10
    while slow.running and time.time() < deadline:
        time.sleep(0.1)
    assert not slow.running


def test_coordinator_timeout():
    c = Coordinator()
    c.launch("hang", [sys.executable, "-c", "import time; time.sleep(60)"])
    with pytest.raises(TimeoutError):
        c.join(timeout=1)


def test_cluster_launch_env_plane(tmp_path):
    """Workers get the role env vars (≙ AUTODIST_WORKER/STRATEGY_ID)."""
    out = tmp_path / "env.txt"
    script = tmp_path / "w.py"
    script.write_text(
        "import os\n"
        "open(%r, 'w').write(os.environ.get('AUTODIST_TPU_WORKER','') + '|' +\n"
        "    os.environ.get('AUTODIST_TPU_STRATEGY_ID','') + '|' +\n"
        "    os.environ.get('AUTODIST_TPU_PROCESS_ID',''))\n" % str(out))
    from autodist_tpu import ResourceSpec
    cluster = Cluster(ResourceSpec({}), hosts=["localhost"])
    cluster.launch_clients("strat-42", argv=[sys.executable, str(script)])
    cluster.join(timeout=30)
    assert out.read_text() == "localhost|strat-42|1"


def test_make_global_batch_single_host():
    mesh = jax.make_mesh((8,), ("data",))
    batch = {"x": np.arange(16.0).reshape(16, 1)}
    global_b = make_global_batch(batch, mesh)
    assert global_b["x"].shape == (16, 1)
    assert global_b["x"].sharding.spec == jax.sharding.PartitionSpec("data")


def test_step_timer_and_mfu():
    t = StepTimer(batch_size=64, warmup=1)
    for _ in range(4):
        with t:
            time.sleep(0.01)
    s = t.summary()
    assert s["steps"] == 3
    assert s["examples_per_sec"] > 0
    assert 0 < mfu(1000, transformer_train_flops_per_token(1_000_000),
                   1e15) < 1


def test_dump_stages(tmp_path):
    from autodist_tpu import AllReduce, AutoDist
    from tests.unit.test_end_to_end import make_batch, make_trainable

    trainable = make_trainable()
    ad = AutoDist({}, AllReduce())
    strategy = ad.build_or_load_strategy(trainable)
    lowered = ad.lower(trainable, strategy)
    runner_batch = jax.tree.map(lambda x: jax.numpy.asarray(x), make_batch())
    out = dump_stages(lowered, trainable, strategy, str(tmp_path),
                      example_batch=runner_batch)
    names = sorted(os.listdir(out))
    assert "0-strategy.json" in names
    assert "1-plan.txt" in names
    assert "2-step.hlo.txt" in names
    hlo = open(os.path.join(out, "2-step.hlo.txt")).read()
    assert "all-reduce" in hlo or "all_reduce" in hlo.replace("-", "_")


def test_eval_step_no_update():
    from autodist_tpu import AllReduce, AutoDist, PartitionedPS
    from autodist_tpu.strategy.gspmd_builders import Sharded
    from tests.unit.test_end_to_end import make_batch, make_trainable

    for builder in (AllReduce(), PartitionedPS(), Sharded()):
        runner = AutoDist({}, builder).build(make_trainable())
        before = runner.get_params()
        m = runner.eval_step(make_batch())
        assert np.isfinite(float(m["loss"]))
        after = runner.get_params()
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), before, after)
        # evaluate() over several batches
        agg = runner.evaluate([make_batch(s) for s in range(3)])
        assert "loss" in agg and np.isfinite(agg["loss"])


def test_memory_summary_shapes():
    from autodist_tpu.utils import profiling

    # CPU backend exposes no HBM stats -> {}.
    assert profiling.memory_summary() in ({},) or isinstance(
        profiling.memory_summary(), dict)

    class FakeDev:
        def memory_stats(self):
            return {"bytes_in_use": 500, "bytes_limit": 1000,
                    "peak_bytes_in_use": 800, "label": "x"}

    out = profiling.memory_summary(FakeDev())
    assert out["bytes_in_use"] == 500 and out["utilization"] == 0.5
    assert "label" not in out


def test_native_build_falls_back_to_user_cache(monkeypatch, tmp_path):
    """Read-only installs (system site-packages, container layers) build
    the native libraries in XDG_CACHE_HOME instead of next to the
    sources."""
    import os

    from autodist_tpu.runtime import nativelib as nl

    real_access = os.access
    monkeypatch.setattr(
        nl.os, "access",
        lambda p, m: False if p == nl.NATIVE_DIR else real_access(p, m))
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path))
    nl._loaded.clear()
    lib = nl.load_native("libautodist_dataio.so", "dataio.cc")
    assert lib is not None
    cache = tmp_path / "autodist_tpu" / "native"
    assert (cache / "libautodist_dataio.so").exists()
    assert (cache / "dataio.cc").exists()   # sources copied for make
    nl._loaded.clear()                      # don't leak the cache CDLL


# --------------------------------------------------------------------------- #
# Chaos-hardened runtime: supervision, heartbeats, remote teardown,
# full-failure reporting (with supervision OFF, fail-fast is untouched —
# the tests above this line run the exact pre-supervision semantics).
# --------------------------------------------------------------------------- #
def _crash_once_script():
    """Exit 3 on the first incarnation, 0 after a supervised restart."""
    return [sys.executable, "-c",
            "import os, sys; "
            "sys.exit(0 if os.environ.get("
            "'AUTODIST_TPU_WORKER_INCARNATION') else 3)"]


def test_supervised_restart_within_budget():
    from autodist_tpu import telemetry
    from autodist_tpu.runtime.cluster import Coordinator, SupervisionConfig
    from autodist_tpu.runtime.retry import RetryPolicy

    telemetry.reset()
    sup = SupervisionConfig(
        max_restarts=1,
        restart_backoff=RetryPolicy(max_attempts=2, base_delay_s=0.05,
                                    cap_delay_s=0.05, seed=0))
    c = Coordinator(supervision=sup)
    c.launch("w1", _crash_once_script())
    c.join(timeout=30)    # restart consumed the crash: join is clean
    assert c._restarts == {"w1": 1}
    assert telemetry.get().registry.counter(
        "runtime/worker_restarts").value == 1
    recs = [r for r in telemetry.get().step_records()
            if r.get("kind") == "fault"]
    assert any(r["phase"] == "recovered" and r["action"] == "restart"
               and r["target"] == "w1" for r in recs)


def test_supervised_escalation_hands_over_survivors():
    from autodist_tpu import telemetry
    from autodist_tpu.runtime.cluster import Coordinator, SupervisionConfig

    telemetry.reset()
    seen = {}
    sup = SupervisionConfig(max_restarts=0, escalate=True, saver=object(),
                            on_escalate=lambda s: seen.update(
                                names=[w.name for w in s]))
    c = Coordinator(supervision=sup)
    c.launch("survivor", [sys.executable, "-c",
                          "import time; time.sleep(2)"])
    c.launch("doomed", [sys.executable, "-c", "import sys; sys.exit(9)"])
    deadline = time.time() + 10
    while not c.escalated and time.time() < deadline:
        time.sleep(0.05)
    assert c.escalated
    assert seen["names"] == ["survivor"]
    c.join(timeout=30)   # the escalated death is consumed, join is clean
    recs = [r for r in telemetry.get().step_records()
            if r.get("kind") == "fault"]
    assert any(r["phase"] == "escalated" and r["target"] == "doomed"
               for r in recs)


def test_supervision_off_keeps_fail_fast_teardown_records_nothing():
    """Both-ways pin: with supervision=None the fail-fast path emits no
    fault records and raises exactly as before."""
    from autodist_tpu import telemetry

    telemetry.reset()
    c = Coordinator()
    c.launch("bad", [sys.executable, "-c", "import sys; sys.exit(3)"])
    with pytest.raises(RuntimeError, match="bad.*3"):
        c.join(timeout=30)
    assert not [r for r in telemetry.get().step_records()
                if r.get("kind") == "fault"]


def test_join_reports_all_concurrent_failures():
    c = Coordinator(fail_fast=False)
    c.launch("bad-a", [sys.executable, "-c", "import sys; sys.exit(3)"])
    c.launch("bad-b", [sys.executable, "-c", "import sys; sys.exit(5)"])
    with pytest.raises(RuntimeError) as ei:
        c.join(timeout=30)
    msg = str(ei.value)
    assert "bad-a" in msg and "3" in msg
    assert "bad-b" in msg and "5" in msg


def test_join_timeout_lists_hung_and_crashed_workers():
    c = Coordinator(fail_fast=False)
    c.launch("crashed", [sys.executable, "-c", "import sys; sys.exit(7)"])
    c.launch("hung-a", [sys.executable, "-c", "import time; time.sleep(60)"])
    c.launch("hung-b", [sys.executable, "-c", "import time; time.sleep(60)"])
    time.sleep(1.0)   # let the crash land
    with pytest.raises(TimeoutError) as ei:
        c.join(timeout=2)
    msg = str(ei.value)
    assert "hung-a" in msg and "hung-b" in msg
    assert "crashed" in msg and "7" in msg


class _StallingClient:
    """Heartbeat source that beats a few times, then stalls (the
    SIGSTOPped-worker signature)."""

    def __init__(self, beats=5):
        self.n = 0
        self.beats = beats

    def counter_add(self, key, delta=0):
        self.n += 1
        return min(self.n, self.beats)


def test_heartbeat_monitor_declares_hung_worker_dead():
    from autodist_tpu import telemetry
    from autodist_tpu.runtime.cluster import (Coordinator,
                                              HeartbeatMonitor,
                                              SupervisionConfig)

    telemetry.reset()
    sup = SupervisionConfig(max_restarts=0, escalate=True, saver=object())
    c = Coordinator(supervision=sup)
    c.launch("wedged", [sys.executable, "-c",
                        "import time; time.sleep(60)"])
    mon = HeartbeatMonitor(c, lambda: _StallingClient(),
                           interval_s=0.05, timeout_s=0.4,
                           startup_grace_s=0.4)
    mon.start()
    try:
        deadline = time.time() + 10
        while not c.escalated and time.time() < deadline:
            time.sleep(0.05)
        assert c.escalated, "hang was never detected/escalated"
    finally:
        mon.stop()
        c.terminate()
    recs = [r for r in telemetry.get().step_records()
            if r.get("kind") == "fault"]
    assert any(r["phase"] == "detected" and r["fault"] == "worker_hang"
               for r in recs)
    assert any(r["phase"] == "escalated" and r["fault"] == "worker_hang"
               for r in recs)
    assert telemetry.get().registry.counter(
        "runtime/workers_declared_dead").value == 1


_FAKE_SSH = """#!%(python)s
import os, subprocess, sys
args = sys.argv[1:]
while args and args[0].startswith("-"):
    args = args[2:]                      # drop "-o BatchMode=yes" pairs
host, rest = args[0], args[1:]
if rest == ["/bin/sh -s"]:
    # launch form: the "remote" worker runs DETACHED (own session), like
    # a real remote process — killing the local ssh client must not
    # reach it.
    proc = subprocess.Popen(["/bin/sh", "-s"], stdin=sys.stdin,
                            start_new_session=True)
    sys.exit(proc.wait())
# exec form (the teardown kill): run the command locally
sys.exit(subprocess.call(["/bin/sh", "-c", " ".join(rest)]))
"""


def test_remote_worker_teardown_kills_the_remote_process(tmp_path,
                                                         monkeypatch):
    """The satellite pin: terminate() on an ssh-launched worker must kill
    the REMOTE process (via the captured remote pid + a second ssh
    exec), not just the local ssh client.  The fake ssh shim runs the
    'remote' side as a detached local process group."""
    bin_dir = tmp_path / "bin"
    bin_dir.mkdir()
    ssh = bin_dir / "ssh"
    ssh.write_text(_FAKE_SSH % {"python": sys.executable})
    ssh.chmod(0o755)
    monkeypatch.setenv("PATH", f"{bin_dir}:{os.environ['PATH']}")
    pidfile = tmp_path / "pid"
    c = Coordinator()
    h = c.launch(
        "remote-1",
        [sys.executable, "-c",
         f"import os, time; open({str(pidfile)!r}, 'w').write("
         "str(os.getpid())); time.sleep(60)"],
        host="fakehost", env={"SOME_SECRET": "s3cret"})
    deadline = time.time() + 15
    while (h.remote_pid is None or not pidfile.exists()) \
            and time.time() < deadline:
        time.sleep(0.05)
    assert h.remote_pid is not None, "remote pid never captured"
    worker_pid = int(pidfile.read_text())
    # exec in the bootstrap keeps the sh pid: the marker IS the worker
    assert h.remote_pid == worker_pid
    os.kill(worker_pid, 0)   # alive
    c.terminate()
    deadline = time.time() + 15
    while time.time() < deadline:
        try:
            os.kill(worker_pid, 0)
            time.sleep(0.05)
        except ProcessLookupError:
            break
    with pytest.raises(ProcessLookupError):
        os.kill(worker_pid, 0)   # the REMOTE side is dead, not orphaned


def test_local_cluster_launches_n_workers(tmp_path):
    from autodist_tpu.runtime.cluster import LocalCluster

    outs = [tmp_path / f"w{i}.txt" for i in (1, 2)]
    script = tmp_path / "w.py"
    script.write_text(
        "import os, sys\n"
        "pid = os.environ['AUTODIST_TPU_PROCESS_ID']\n"
        f"open(os.path.join({str(tmp_path)!r}, 'w%s.txt' % pid), "
        "'w').write(os.environ.get('AUTODIST_TPU_STRATEGY_ID', '') + ' ' "
        "+ os.environ.get('JAX_PLATFORMS', ''))\n")
    cluster = LocalCluster(2)
    try:
        cluster.launch_clients("strat-7",
                               argv=[sys.executable, str(script)],
                               extra_env={"JAX_PLATFORMS": "tpu"})
        cluster.join(timeout=60)
    finally:
        cluster.terminate()
    # N+1 processes on one machine cannot share a chip: the workers are
    # CPU-pinned whatever the caller's environment says
    assert [o.read_text() for o in outs] == ["strat-7 cpu", "strat-7 cpu"]
