"""Distributed runner: owns the compiled step and the data contract.

Counterpart of the reference's ``WrappedSession`` (``runner.py:78-132``)
and ``Remapper`` (``remapper.py``): the feed contract — a host batch with a
leading batch dimension is *split* across replicas
(``remapper.py:109-123``) — becomes placement with a
``NamedSharding(P('data'))``; the fetch contract — scalars/metrics fetched
once (``remapper.py:125-185``) — becomes replicated outputs pulled from any
shard.  Initializers-on-construction (``runner.py:97-100``) becomes
``init_state`` at construction.
"""
from __future__ import annotations

import io
import os
import struct
import threading
import time
from typing import Any, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from autodist_tpu import const, telemetry
from autodist_tpu.kernel.lowering import Lowered
from autodist_tpu.telemetry import account
from autodist_tpu.utils import logging


def stack_steps(batches):
    """Stack a list of per-step batch pytrees into the ``[k, ...]`` feed
    :meth:`DistributedRunner.run_steps` consumes (every leaf — scalars
    included — gains a leading steps axis).  The single definition of
    that stacking contract; benchmarks and tests share it."""
    return jax.tree.map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]), *batches)


class DistributedRunner:
    """Owns (mesh, compiled step fns, state); the training session."""

    def __init__(self, trainable, lowered: Lowered, *, rng: Optional[Any] = None,
                 ssp_worker: Optional[str] = None,
                 ssp_num_workers: Optional[int] = None):
        t_init = account.constructing("runner")
        self.trainable = trainable
        self.lowered = lowered
        self.mesh = lowered.mesh
        # The Strategy this runner was built from (set by AutoDist._build;
        # the checkpoint Saver binds it into the elastic sidecar).
        self.strategy = None
        self.state = lowered.init_state(trainable=trainable)
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        self._step_times: list[float] = []
        self._run_examples = 0
        self._run_steps_seen = 0
        self._run_seconds = 0.0
        self._host_step = 0
        self._scanned_fn = None   # built lazily by run_steps
        self._ssp = self._make_ssp_gate(ssp_worker, ssp_num_workers)
        account.constructed(t_init)

    def _make_ssp_gate(self, worker: Optional[str],
                       num_workers: Optional[int]):
        """Host-side stale-synchronous gate (≙ the reference's
        depth-``staleness`` token queues, ``ps_synchronizer.py:387-458``):
        active when the strategy carries ``staleness > 0`` and a
        coordination service is reachable.  Inside one SPMD process group
        the program is lockstep regardless; the gate bounds skew *between*
        processes of the job."""
        staleness = (getattr(self.lowered.plan, "ssp_staleness", 0)
                     or getattr(self.lowered, "ssp_staleness", 0))
        if staleness <= 0:
            return None
        from autodist_tpu.runtime import coordination

        client = coordination.service_client()
        if client is None:
            logging.warning(
                "strategy requests staleness=%d but no coordination service "
                "is configured (AUTODIST_TPU_COORD_SERVICE); running in "
                "lockstep", staleness)
            return None
        worker = worker or const.ENV.AUTODIST_TPU_WORKER.val or "chief"
        if num_workers is None:
            n = const.ENV.AUTODIST_TPU_NUM_PROCESSES.val
            num_workers = n if n > 1 else None
        return coordination.SSPController(client, worker, staleness,
                                          num_workers=num_workers)

    # ---------------- feed/fetch (≙ Remapper) -------------------------- #
    def _place_batch(self, batch, *, specs=None):
        """Feed contract (reference ``remapper.py:81-123``): leaves with a
        batch dimension are *split* across the data axis; scalars (the
        polymorphic-feed analog of non-batch placeholders — step counts,
        loss scales) are *duplicated* to every replica.  Already-placed
        global arrays pass through.  Placement is per-leaf, from the
        lowering's spec tree (sequence parallelism splits token leaves
        over ``data x seq``); ``specs`` overrides it (``run_steps``
        shifts every spec right by its leading steps axis)."""
        from autodist_tpu.kernel import common

        if specs is None:
            specs = self.lowered.batch_spec_tree(batch)
        shardings = common.specs_to_shardings(specs, self.mesh)

        def place(x, sharding):
            if isinstance(x, jax.Array):
                if not x.is_fully_addressable:
                    return x  # already a global array (multi-host path)
                # Already on device (e.g. a prefetching DataLoader):
                # device_put is a no-op when the sharding matches and an
                # on-device reshard otherwise — never a host round-trip.
                return jax.device_put(x, sharding)
            x = np.asarray(x)
            common.check_batch_divisibility(x, sharding.spec, self.mesh)
            return jax.device_put(x, sharding)

        return jax.tree.map(place, batch, shardings)

    # ---------------- the hot loop (≙ WrappedSession.run) --------------- #
    def step(self, batch, *, rng=None):
        """One optimizer step; returns the metrics dict (fetch contract)."""
        if self._ssp is not None and not self._ssp.start_step(self._host_step):
            # A timed-out bounded wait means a peer stalled or died;
            # free-running past it would silently void the staleness bound
            # the strategy asked for.  Fail fast (framework policy §5.3).
            raise TimeoutError(
                f"SSP wait at step {self._host_step} timed out: a worker "
                f"is more than staleness={self._ssp.staleness} steps behind")
        batch = self._place_batch(batch)
        if rng is None:
            self.rng, rng = jax.random.split(self.rng)
        self.state, metrics = self.lowered.step_fn(self.state, batch, rng)
        if self._ssp is not None:
            # Report completion only once the device work really finished —
            # the dispatch above is async.
            jax.block_until_ready(metrics)
            self._ssp.finish_step(self._host_step)
        self._host_step += 1
        telemetry.counter("runner/steps").inc()
        return metrics

    def run_steps(self, batches, *, rngs=None):
        """``k`` optimizer steps in ONE device dispatch — steps-per-loop.

        Every leaf of ``batches`` carries a leading steps dimension
        ``[k, ...]``; the lowered step runs under ``lax.scan`` on device,
        so host dispatch and feed cost are paid once per k steps instead
        of per step.  On remote/proxied backends where each dispatch is
        an RPC (and on any TPU where per-step Python dispatch shows up at
        small step times) this is the difference between measuring the
        chip and measuring the host.  The reference had no analog — its
        session ran one graph execution per ``session.run`` — but the
        capability its users actually wanted (keep the accelerator busy
        across steps) is this, expressed the XLA way.

        Returns the metrics pytree with a leading ``[k]`` axis (step
        ``i``'s metrics at index ``i``; the fetch contract of
        :meth:`step`, vectorized).  Falls back to per-step dispatch when
        an SSP gate is active — the gate's skew bound is per-step, and a
        fused k-step program would void it.
        """
        from autodist_tpu.kernel import common

        leaves = jax.tree.leaves(batches)
        if not leaves:
            raise ValueError("run_steps needs a non-empty batch pytree")
        k = None
        for leaf in leaves:
            if np.ndim(leaf) == 0 or (k is not None
                                      and np.shape(leaf)[0] != k):
                # Scalars too: step()'s duplicate-feed leaves (loss
                # scales, step counts) must arrive stacked [k] here —
                # the scan consumes one per step.
                raise ValueError(
                    "every run_steps leaf needs the same leading steps "
                    f"dimension; got shapes "
                    f"{[np.shape(l) for l in leaves]}")
            if k is None:
                k = int(np.shape(leaf)[0])
        if self._ssp is not None:
            ms = [self.step(jax.tree.map(lambda x: x[i], batches),
                            rng=None if rngs is None else rngs[i])
                  for i in range(k)]
            return jax.tree.map(lambda *xs: jnp.stack(xs), *ms)

        with telemetry.span("runner/run_steps", k=k):
            with telemetry.span("runner/place"):
                batches = self.place_steps(batches)
            if rngs is None:
                self.rng, sub = jax.random.split(self.rng)
                rngs = jax.random.split(sub, k)
            if self._scanned_fn is None:
                step_fn = self.lowered.step_fn

                def scanned(state, batches, rngs):
                    def body(s, xs):
                        b, r = xs
                        return step_fn(s, b, r)
                    return lax.scan(body, state, (batches, rngs))

                # Shape-generic: jit specializes per (k, batch shapes);
                # state donation keeps params/opt buffers in place
                # across the call.
                self._scanned_fn = jax.jit(scanned, donate_argnums=(0,))
            with telemetry.span("runner/dispatch"):
                self.state, metrics = self._scanned_fn(self.state, batches,
                                                       rngs)
        self._host_step += k
        telemetry.counter("runner/steps").inc(k)
        return metrics

    def place_steps(self, batches):
        """Place a ``run_steps`` window on device (the feed contract
        with every spec shifted right by the leading steps axis, which
        is never sharded — scan consumes it sequentially).  Idempotent:
        already-placed leaves pass through ``device_put`` as no-ops, so
        a static window (benchmark loops) can be placed once and reused
        across ``run_steps`` calls without re-transferring."""
        def slice_struct(x):
            # Shape-only step slice for the spec tree: a real x[0] on a
            # device-resident leaf would dispatch a gather per call
            # (batch_spec_tree implementations read only names + ndim).
            dtype = getattr(x, "dtype", None)
            return jax.ShapeDtypeStruct(
                np.shape(x)[1:], dtype if dtype is not None
                else np.asarray(x).dtype)

        specs = self.lowered.batch_spec_tree(
            jax.tree.map(slice_struct, batches))
        stacked = jax.tree.map(lambda s: P(None, *s), specs,
                               is_leaf=lambda s: isinstance(s, P))
        return self._place_batch(batches, specs=stacked)

    # Retained per-step timings are capped (summary percentiles come
    # from this sample; the count keeps climbing) so a long run cannot
    # grow the host with timing data — mirrors telemetry's own
    # MAX_STEP_RECORDS bound.
    MAX_STEP_TIMES = 100000

    def run(self, data: Iterable, num_steps: Optional[int] = None,
            log_every: int = 0, drift_monitor=None):
        """Drive ``num_steps`` steps from an iterable of host batches.

        Every step blocks on its metrics and its wall time is recorded
        (see :meth:`summary`) and fed to telemetry as a per-step record
        — this loop measures true device latency, at the price of
        host/device overlap.  Throughput-critical loops should use
        :meth:`run_steps` / ``fit(steps_per_loop=k)``, which keep
        dispatch fused and async.

        ``drift_monitor`` (a :class:`telemetry.DriftMonitor`) opts the
        loop into ONLINE drift detection: every step's wall time feeds
        the monitor, which gauges ``drift/<term>_ratio`` and emits a
        ``kind="drift"`` record when measured/predicted crosses its
        threshold — the live half of the post-hoc ``drift_report``.
        """
        metrics = {}
        it = iter(data)
        i = 0
        while num_steps is None or i < num_steps:
            try:
                batch = next(it)
            except StopIteration:
                break
            t0 = time.perf_counter()
            metrics = self.step(batch)
            jax.block_until_ready(metrics)
            dt = time.perf_counter() - t0
            if len(self._step_times) < self.MAX_STEP_TIMES:
                self._step_times.append(dt)
            self._run_steps_seen += 1
            self._run_seconds += dt
            bsz = next((int(np.shape(l)[0]) for l in jax.tree.leaves(batch)
                        if np.ndim(l) > 0), 0)
            self._run_examples += bsz
            telemetry.record_step(step=self._host_step - 1, duration_s=dt,
                                  examples=bsz or None)
            if drift_monitor is not None:
                drift_monitor.observe_step(self._host_step - 1, dt)
            if log_every and (i + 1) % log_every == 0:
                logging.info("step %d %s (%.1f ms/step)",
                             int(self.state["step"]),
                             {k: float(v) for k, v in metrics.items()}, dt * 1e3)
            i += 1
        return metrics

    def summary(self) -> dict:
        """Step-time percentiles over every :meth:`run` step so far —
        the same shape (and, since :meth:`run` blocks per step, the same
        semantics) as :meth:`StepTimer.summary()
        <autodist_tpu.utils.profiling.StepTimer.summary>`, so downstream
        consumers (telemetry drift report, ``tools/telemetry_report.py``)
        accept either.  Percentiles come from the retained sample
        (capped at :data:`MAX_STEP_TIMES`); ``steps`` and the rate cover
        every step."""
        ts = np.asarray(self._step_times)
        n = len(ts)
        out = {
            "steps": self._run_steps_seen,
            "mean_ms": (self._run_seconds / self._run_steps_seen * 1e3
                        if self._run_steps_seen else None),
            "p50_ms": float(np.percentile(ts, 50) * 1e3) if n else None,
            "p99_ms": float(np.percentile(ts, 99) * 1e3) if n else None,
            "examples_per_sec": (self._run_examples / self._run_seconds
                                 if self._run_seconds > 0
                                 and self._run_examples else None),
        }
        if out["examples_per_sec"] is not None:
            telemetry.gauge("runner/examples_per_sec").set(
                out["examples_per_sec"])
        return out

    def eval_step(self, batch, *, rng=None):
        """Metrics without updating state (fetch-only contract — the
        reference fetched tensors from the master replica without running
        train ops, ``remapper.py:125-185``)."""
        if self.lowered.eval_fn is None:
            raise NotImplementedError("this lowering has no eval path")
        batch = self._place_batch(batch)
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        return self.lowered.eval_fn(self.state, batch, rng)

    def evaluate(self, data: Iterable, num_batches: Optional[int] = None):
        """Mean metrics over an eval dataset."""
        sums, count = {}, 0
        for i, batch in enumerate(data):
            if num_batches is not None and i >= num_batches:
                break
            m = jax.device_get(self.eval_step(batch))
            for k, v in m.items():
                sums[k] = sums.get(k, 0.0) + np.asarray(v, dtype=float)
            count += 1
        return {k: v / max(count, 1) for k, v in sums.items()}

    # ---------------- fetches ------------------------------------------- #
    @property
    def step_count(self) -> int:
        return int(self.state["step"])

    def get_params(self):
        """Parameters at their original (unpadded) shapes — the
        'checkpoints look unpartitioned' contract
        (reference ``saver.py:50-58``)."""
        return jax.device_get(self.lowered.unpad_params(self.state["params"]))

    def get_extra(self):
        return jax.device_get(self.state["extra"])

    def close(self):
        """Release device state references (AutoStrategy's measurement
        loop closes loser runners so their HBM frees before the next
        candidate compiles; safe to call more than once)."""
        self.state = None
        self.lowered = None


# --------------------------------------------------------------------------- #
# Asynchronous PS (PS(sync=False))
# --------------------------------------------------------------------------- #
def _pack_tree(version: int, tree) -> bytes:
    leaves = [np.asarray(l) for l in jax.tree.leaves(tree)]
    buf = io.BytesIO()
    np.savez(buf, **{f"l{i}": l for i, l in enumerate(leaves)})
    return struct.pack("<q", version) + buf.getvalue()


def _unpack_tree(data: bytes, like):
    version = struct.unpack("<q", data[:8])[0]
    leaves, treedef = jax.tree_util.tree_flatten(like)
    with np.load(io.BytesIO(data[8:])) as z:
        new = [z[f"l{i}"] for i in range(len(leaves))]
    return version, jax.tree_util.tree_unflatten(treedef, new)


class AsyncPSRunner:
    """Asynchronous parameter-server training — ``PS(sync=False)``
    (reference ``synchronizers.proto:31``, ``ps_synchronizer.py:216-230``:
    workers push gradients and proceed without waiting for each other).

    SPMD lockstep cannot express this, so the data plane leaves XLA: each
    process computes gradients with a *local* SPMD program (pmean over its
    own devices ≙ in-graph replica aggregation), then pushes them to a
    host-side PS loop over the coordination service (grads queue ≙ the
    reference's conditional accumulators in their accumulate-1 async
    configuration; params KV ≙ workers' read ops).  The optimizer runs
    only on the PS; workers' parameters change only via pulls, and with a
    single worker pull-after-apply reproduces synchronous SGD exactly
    (tested).  ``staleness > 0`` adds the same SSP gate as the sync path.
    """

    GRADS_QUEUE = "asyncps/grads"
    PARAMS_KEY = "asyncps/params"
    VERSION_KEY = "asyncps/version"  # tiny: polled without moving the blob

    # Host blob exchange is O(model size); warn above this (the honest
    # scalability limit — beyond it use a synchronous ZeRO/FSDP strategy).
    BLOB_WARN_BYTES = 256 << 20

    def __init__(self, trainable, *, staleness: int = 0,
                 rng: Optional[Any] = None, ssp_worker: Optional[str] = None,
                 ssp_num_workers: Optional[int] = None,
                 is_chief: Optional[bool] = None,
                 publish_max_lag: int = 8,
                 publish_max_interval_s: float = 0.1):
        from autodist_tpu.runtime import coordination

        if trainable.extra is not None:
            raise NotImplementedError(
                "async PS does not support mutable extra state (batch "
                "stats); train those models synchronously")
        self.trainable = trainable
        # Param-publish gating: under a burst of queued gradients the PS
        # serializes the whole tree at most once per `publish_max_lag`
        # applied updates (or `publish_max_interval_s`), and always when
        # the queue drains — so host serialization stops scaling with the
        # push rate while pull-after-drain semantics stay exact.
        self._publish_max_lag = max(int(publish_max_lag), 1)
        self._publish_max_interval_s = float(publish_max_interval_s)
        blob_bytes = sum(v.byte_size for v in trainable.var_infos())
        if blob_bytes > self.BLOB_WARN_BYTES:
            logging.warning(
                "async PS exchanges whole-tree host blobs: %.0f MB per "
                "push/publish. Expect seconds per update at this size — "
                "the async path is a semantics-parity feature, not a "
                "large-model transport; use a synchronous ZeRO/FSDP "
                "strategy beyond ~%d MB",
                blob_bytes / 1e6, self.BLOB_WARN_BYTES >> 20)
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        self._host_step = 0
        self._closed = False

        self.is_chief = (is_chief if is_chief is not None
                         else not const.ENV.AUTODIST_TPU_WORKER.val)
        self._own_server = None
        client = coordination.service_client()
        if client is None:
            if not self.is_chief:
                # A private in-process server would hold no published
                # params: the worker would block forever on the first
                # pull.  Fail loudly instead.
                raise OSError(
                    "async PS worker needs a reachable coordination "
                    "service (AUTODIST_TPU_COORD_SERVICE); none configured "
                    "or connection failed")
            # Single-process convenience: the chief runs the PS service
            # in-process.
            self._own_server = coordination.CoordServer()
            os.environ["AUTODIST_TPU_COORD_SERVICE"] = \
                f"127.0.0.1:{self._own_server.port}"
            client = coordination.service_client()
        self._client = client

        worker = ssp_worker or const.ENV.AUTODIST_TPU_WORKER.val or "chief"

        # Local mesh only: async workers never run cross-process collectives.
        devs = np.array(jax.local_devices())
        self.mesh = Mesh(devs, (const.DATA_AXIS,))
        n = len(devs)
        data_axis = const.DATA_AXIS

        def local_grads(params, batch, rng_):
            local_rng = jax.random.fold_in(rng_, lax.axis_index(data_axis))

            def loss_fn(p):
                loss, _, metrics = trainable.loss(p, None, batch, local_rng)
                return loss, metrics

            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            grads = jax.tree.map(lambda g: lax.pmean(g, data_axis), grads)
            metrics = jax.tree.map(
                lambda m: lax.pmean(m, data_axis)
                if jnp.issubdtype(jnp.result_type(m), jnp.inexact) else m,
                dict(metrics))
            return grads, metrics

        def grads_step(params, batch, rng_):
            from autodist_tpu.kernel import common as kcommon
            return jax.shard_map(
                local_grads, mesh=self.mesh,
                in_specs=(P(), kcommon.batch_specs(batch, P(data_axis)), P()),
                out_specs=(P(), P()), check_vma=False)(params, batch, rng_)

        self._grads_fn = jax.jit(grads_step)
        self._batch_sharding = NamedSharding(self.mesh, P(data_axis))

        self.params = jax.tree.map(np.asarray, trainable.params)
        self._params_version = 0
        self._ps_thread = None
        self._ps_stop_event = threading.Event()
        if self.is_chief:
            self._start_ps_loop()
        else:
            self._pull(block=True, force=True)  # adopt the PS's init params

        self._ssp = None
        if staleness > 0:
            if ssp_num_workers is None:
                np_ = const.ENV.AUTODIST_TPU_NUM_PROCESSES.val
                ssp_num_workers = np_ if np_ > 1 else None
            self._ssp = coordination.SSPController(
                self._client, worker, staleness,
                num_workers=ssp_num_workers)

    # ------------------------------------------------------------------ #
    def _start_ps_loop(self):
        """The parameter server proper: one host thread owning (params,
        opt_state), applying every pushed gradient as it arrives (≙ the
        PS devices' apply ops, reference ``ps_synchronizer.py:216-230``)."""
        opt = self.trainable.optimizer
        ps_params = self.trainable.params
        ps_opt_state = opt.init(ps_params)
        apply_fn = jax.jit(lambda g, s, p: opt.update(g, s, p))
        # Blob first, version second: a reader that sees version N will
        # fetch blob ≥ N (never older).
        self._client.put(self.PARAMS_KEY, _pack_tree(0, ps_params))
        self._client.put(self.VERSION_KEY, struct.pack("<q", 0))
        coord_addr = os.environ.get("AUTODIST_TPU_COORD_SERVICE", "")

        lag = self._publish_max_lag
        interval = self._publish_max_interval_s
        self.ps_publish_count = 0  # observable for tests/diagnostics

        def loop():
            from autodist_tpu.runtime.coordination import CoordClient
            nonlocal ps_params, ps_opt_state
            host, _, port = coord_addr.rpartition(":")
            ps_client = CoordClient(host or "127.0.0.1", int(port))
            version = 0
            published = 0
            last_pub = time.time()

            def publish() -> bool:
                """False when the service is gone (exit the loop cleanly
                instead of dying on an uncaught OSError)."""
                nonlocal published, last_pub
                try:
                    ps_client.put(self.PARAMS_KEY,
                                  _pack_tree(version, ps_params))
                    ps_client.put(self.VERSION_KEY,
                                  struct.pack("<q", version))
                except OSError:
                    return False
                published = version
                last_pub = time.time()
                self.ps_publish_count += 1
                telemetry.counter("asyncps/publish").inc()
                return True

            alive = True
            while alive and not self._ps_stop_event.is_set():
                try:
                    msg = ps_client.queue_get(self.GRADS_QUEUE,
                                              timeout_ms=200)
                except OSError:
                    break  # service shut down
                if msg is None:
                    if version > published and not publish():
                        break
                    continue
                # Drain the burst, publishing at most every `lag` applied
                # updates / `interval` seconds; one publish after the
                # drain keeps pull-after-wait_applied semantics exact.
                # A popped message is ALWAYS applied (the pop is
                # destructive — dropping it on a stop-event race would
                # lose the update); the stop event only ends the drain.
                while msg is not None:
                    _, grads = _unpack_tree(msg, ps_params)
                    updates, ps_opt_state = apply_fn(grads, ps_opt_state,
                                                     ps_params)
                    ps_params = optax.apply_updates(ps_params, updates)
                    version += 1
                    telemetry.counter("asyncps/apply").inc()
                    if (version - published >= lag
                            or time.time() - last_pub > interval):
                        if not publish():
                            alive = False
                            break
                    if self._ps_stop_event.is_set():
                        break
                    try:
                        msg = ps_client.queue_get(self.GRADS_QUEUE,
                                                  timeout_ms=0)
                    except OSError:
                        alive = False
                        break
                if alive and version > published and not publish():
                    break
            ps_client.close()

        self._ps_thread = threading.Thread(target=loop, daemon=True,
                                           name="asyncps-server")
        self._ps_thread.start()

    def _pull(self, block: bool = False, force: bool = False):
        ver_raw = self._client.get(self.VERSION_KEY,
                                   timeout_ms=-1 if block else 0)
        if ver_raw is None:
            return
        if not force and struct.unpack("<q", ver_raw)[0] == self._params_version:
            # nothing new: skip moving the blob (a "dropped" pull — the
            # publish-gating elides host serialization under bursts)
            telemetry.counter("asyncps/pull_skip").inc()
            return
        data = self._client.get(self.PARAMS_KEY, timeout_ms=-1)
        self._params_version, self.params = _unpack_tree(data, self.params)
        telemetry.counter("asyncps/pull").inc()

    # ------------------------------------------------------------------ #
    def step(self, batch, *, rng=None):
        """Pull-latest → local grads → push; returns local metrics."""
        if self._closed:
            raise RuntimeError("runner is closed")
        if self._ssp is not None and not self._ssp.start_step(self._host_step):
            raise TimeoutError(
                f"SSP wait at step {self._host_step} timed out: a worker "
                f"is more than staleness={self._ssp.staleness} steps behind")
        self._pull()
        if rng is None:
            self.rng, rng = jax.random.split(self.rng)

        from autodist_tpu.kernel import common as kcommon
        batch = jax.tree.map(
            lambda x, s: jax.device_put(np.asarray(x), s), batch,
            kcommon.batch_shardings(batch, self.mesh,
                                    self._batch_sharding.spec))
        grads, metrics = self._grads_fn(self.params, batch, rng)
        self._client.queue_put(self.GRADS_QUEUE,
                               _pack_tree(self._host_step,
                                          jax.device_get(grads)))
        telemetry.counter("asyncps/push").inc()
        if self._ssp is not None:
            self._ssp.finish_step(self._host_step)
        self._host_step += 1
        return metrics

    def wait_applied(self, min_version: int, timeout_s: float = 30.0):
        """Block until the PS has applied at least ``min_version`` updates
        (deterministic hand-off for tests / epoch boundaries)."""
        deadline = time.time() + timeout_s
        while self._params_version < min_version:
            self._pull(block=False)
            if time.time() > deadline:
                raise TimeoutError(
                    f"PS applied {self._params_version} < {min_version} "
                    f"updates within {timeout_s}s")
            time.sleep(0.005)

    @property
    def step_count(self) -> int:
        return self._host_step

    def get_params(self):
        self._pull()
        return self.params

    def close(self):
        if self._closed:
            return
        self._closed = True
        self._ps_stop_event.set()
        if self._ps_thread is not None:
            self._ps_thread.join(timeout=5)
        if self._own_server is not None:
            from autodist_tpu.runtime import coordination
            addr = f"127.0.0.1:{self._own_server.port}"
            if os.environ.get("AUTODIST_TPU_COORD_SERVICE") == addr:
                del os.environ["AUTODIST_TPU_COORD_SERVICE"]
            coordination.reset_service_client()
            self._own_server.stop()
            self._own_server = None
