"""Multihost AutoStrategy measured refinement (round-4 Weak #5).

The chief publishes top-k candidates on the coordination service,
workers launched *before* planning (``Cluster.launch_clients(None)``)
join the rendezvous, every process builds + times each candidate in
SPMD lockstep over the 2-process gloo mesh, and all adopt the chief's
measured winner.  The trained result must equal the single-process run
— proving the measured steps did not leak into training state and the
winner handoff is complete.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

pytestmark = pytest.mark.slow

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SCRIPT = """
import os, sys, json

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")

import numpy as np
import optax

from autodist_tpu import AutoDist, AllReduce, AutoStrategy, Trainable, ZeRO
from autodist_tpu.resource import ResourceSpec
from autodist_tpu.runtime.cluster import Cluster, make_global_batch

IS_CHIEF = not os.environ.get("AUTODIST_TPU_WORKER")
COORD_PORT = int(os.environ["TEST_COORD_PORT"])
OUT = os.environ["TEST_OUT"]
STEPS = 3

def make_trainable():
    rng = np.random.RandomState(0)
    params = {"w": rng.randn(6, 3).astype(np.float32),
              "b": np.zeros(3, np.float32)}
    def loss_fn(p, batch):
        import jax.numpy as jnp
        pred = batch["x"] @ p["w"] + p["b"]
        return jnp.mean((pred - batch["y"]) ** 2)
    return Trainable.from_loss_fn(loss_fn, params, optax.sgd(0.1))

def global_batch(step):
    rng = np.random.RandomState(100 + step)
    return {"x": rng.randn(16, 6).astype(np.float32),
            "y": rng.randn(16, 3).astype(np.float32)}

trainable = make_trainable()
example = global_batch(999)  # same global example batch on every process
auto = AutoStrategy(candidates=[AllReduce(chunk_size=2), ZeRO()],
                    measure_top_k=2, example_batch=example)

if IS_CHIEF:
    os.environ["AUTODIST_TPU_NUM_PROCESSES"] = "2"
    os.environ["AUTODIST_TPU_PROCESS_ID"] = "0"
    os.environ["AUTODIST_TPU_COORDINATOR"] = f"127.0.0.1:{COORD_PORT}"
    rs = ResourceSpec({"topology": {"num_devices": 4}})
    cluster = Cluster(rs, hosts=["localhost"])
    # Workers join BEFORE any strategy exists: the winner is measured.
    cluster.launch_clients(None, argv=[sys.executable,
                                       os.path.abspath(__file__)])
else:
    rs = ResourceSpec({"topology": {"num_devices": 4}})

ad = AutoDist(rs, auto)
runner = ad.build(trainable)

pid = rs.process_id
for step in range(STEPS):
    g = global_batch(step)
    half = 16 // 2
    local = {k: v[pid * half:(pid + 1) * half] for k, v in g.items()}
    batch = make_global_batch(local, runner.mesh)
    metrics = runner.step(batch)

if IS_CHIEF:
    params = jax.device_get(runner.get_params())
    np.savez(OUT, **params)
    with open(OUT + ".measured.json", "w") as f:
        json.dump({k: float(v) for k, v in auto.measured.items()}, f)
jax.distributed.shutdown()
if IS_CHIEF:
    cluster.join(timeout=60)
"""


def test_multihost_measured_refinement_matches_single_process(tmp_path):
    import socket

    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()

    script = tmp_path / "auto2.py"
    script.write_text(SCRIPT)
    out = tmp_path / "params.npz"
    env = dict(os.environ,
               PYTHONPATH=REPO_ROOT,
               TEST_COORD_PORT=str(port),
               TEST_OUT=str(out))
    env["AUTODIST_TPU_WORKING_DIR"] = str(tmp_path / "scratch")
    for k in ("AUTODIST_TPU_WORKER", "AUTODIST_TPU_NUM_PROCESSES",
              "AUTODIST_TPU_PROCESS_ID", "XLA_FLAGS", "JAX_PLATFORMS"):
        env.pop(k, None)
    proc = subprocess.run(
        [sys.executable, str(script)], env=env, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        f"chief failed\nstdout:\n{proc.stdout}\nstderr:\n{proc.stderr}")
    got = dict(np.load(out))

    # Both candidates were really measured across the 2-process job.
    import json
    measured = json.loads(open(str(out) + ".measured.json").read())
    assert len(measured) == 2, measured
    assert all(v > 0 for v in measured.values())

    # Single-process reference: same global batches, plain optax SGD
    # (both candidates are exact DP realizations, so the winner's
    # identity does not change the numbers).
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(6, 3), jnp.float32),
              "b": jnp.zeros(3, jnp.float32)}
    opt = optax.sgd(0.1)
    opt_state = opt.init(params)

    def loss_fn(p, batch):
        pred = batch["x"] @ p["w"] + p["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    for step in range(3):
        r = np.random.RandomState(100 + step)
        b = {"x": jnp.asarray(r.randn(16, 6), jnp.float32),
             "y": jnp.asarray(r.randn(16, 3), jnp.float32)}
        grads = jax.grad(loss_fn)(params, b)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)

    for k in got:
        np.testing.assert_allclose(got[k], np.asarray(params[k]),
                                   rtol=1e-5, atol=1e-6)
