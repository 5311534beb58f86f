"""Strategy lowering: Strategy IR → one compiled SPMD train step.

TPU-native counterpart of the reference's whole backend stack —
``StrategyCompiler`` (device resolution, ``strategy/base.py:120-168``),
``GraphTransformer`` (pass orchestration, ``kernel/graph_transformer.py:55-92``),
``VariablePartitioner`` (``kernel/partitioner.py``), ``Replicator``
(``kernel/replicator.py``) and the synchronizers
(``kernel/synchronization/``).  There is no graph surgery: the "transform"
is a function transformation.  The per-variable synchronizer choice lowers
to explicit XLA collectives inside a single ``shard_map``-traced step:

* AllReduce synchronizer      → ``lax.pmean`` (optionally compressed /
  bucketed — bucketing ≙ ScopedAllocator merging, ``runner.py:40-46``)
* PS synchronizer (flat)      → flatten + ``psum_scatter`` (grad shard ≙
  the PS accumulator), sharded optimizer update (≙ apply op on the PS),
  ``all_gather`` of updated params (≙ proxy refresh).  ZeRO-style
  weight-update sharding (PAPERS.md 2004.13336).
* PS + partitioner (axis)     → parameters *stored* sharded along the
  partition axis (≙ PartitionedPS shards living on PS devices), gathered
  on use, gradients reduce-scattered: FSDP semantics.
* AllReduce + partitioner     → params replicated, gradient
  reduce-scatter along the partition axis + sharded update + all-gather
  (≙ PartitionedAR).

Replication (the reference Replicator's per-GPU graph copies) is the
``shard_map`` over the data axis itself; in-graph vs between-graph
synchronization both collapse into ICI collectives in one XLA program.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from autodist_tpu import const
from autodist_tpu.capture import Trainable
from autodist_tpu.kernel import common
from autodist_tpu.kernel.compressor import Compressor
from autodist_tpu.strategy.ir import (AllReduceSynchronizer, PSSynchronizer,
                                      Strategy)
from autodist_tpu.telemetry import scope
from autodist_tpu.utils import logging

# Update-space kinds: where the optimizer update for a variable runs.
U_REPLICATED = "replicated"   # full copy on every device (pure DP)
U_FLAT = "flat"               # 1/N flat chunk per device (ZeRO / PS)
U_AXIS = "axis"               # 1/N chunk along a tensor axis

# XLA's compiler-side half of communication/compute overlap: run
# collectives asynchronously (-start/-done pairs) and let the
# latency-hiding scheduler move independent compute between the halves.
# The collective-matmul decomposition (parallel/tensor.py comm_overlap)
# restructures the *program* so overlap is possible; these flags let the
# *compiler* exploit it — and they also overlap collectives this build
# doesn't decompose (grad allreduces behind backprop).  Gated behind
# AUTODIST_TPU_ASYNC_COLLECTIVES=1 because they are TPU-compiler
# scheduling flags, and silently appending to a shared flag environment
# would surprise whoever set it.  They are libtpu's flags, so they ride
# libtpu's own channel, LIBTPU_INIT_ARGS: under jax 0.9.0 / libtpu 0.0.34
# jaxlib's XLA_FLAGS parser aborts on every one of them ("Unknown flags
# in XLA_FLAGS", chip run, PR 21) while LIBTPU_INIT_ARGS takes them.
LATENCY_HIDING_FLAGS_ENV = "LIBTPU_INIT_ARGS"
LATENCY_HIDING_XLA_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_enable_async_all_gather=true",
    "--xla_enable_async_collective_permute=true",
    "--xla_tpu_enable_latency_hiding_scheduler=true",
)


def _targets_tpu(platform, env) -> bool:
    """Best-effort 'is this process going to build a TPU backend':
    explicit spec platform first, then the JAX_PLATFORMS pin, then
    libtpu presence.  Must not touch jax.devices() — deciding here is
    only legal because the backend is not up yet."""
    if platform and platform != "auto":
        return platform == "tpu"
    pin = env.get("JAX_PLATFORMS", "")
    if pin:
        return "tpu" in pin
    import importlib.util
    return importlib.util.find_spec("libtpu") is not None


def apply_latency_hiding_flags(env=None, platform=None) -> bool:
    """Append :data:`LATENCY_HIDING_XLA_FLAGS` to ``LIBTPU_INIT_ARGS``
    when the ``AUTODIST_TPU_ASYNC_COLLECTIVES`` knob is set (value
    ``1``/``True`` = the default list; a value starting with ``--``
    replaces the list verbatim — flag names drift across libtpu
    versions).

    Returns whether the flags are (now) present.  Applied only when the
    process targets a TPU backend (nothing else reads the variable).
    libtpu reads it once at backend-client init, so this must run
    before the first device
    touch — ``ResourceSpec.bootstrap()`` calls it at the right moment
    for ``AutoDist``-built runners (passing the spec's platform);
    scripts managing their own backend call it first thing.  If the
    backend is already up the append still happens (a later subprocess
    inherits it) but a warning names the miss instead of pretending the
    running client changed.
    """
    import os

    env = os.environ if env is None else env
    knob = const.ENV.AUTODIST_TPU_ASYNC_COLLECTIVES.val
    if not knob or knob.lower() in ("0", "false"):
        return False
    flags = (tuple(knob.split()) if knob.startswith("--")
             else LATENCY_HIDING_XLA_FLAGS)
    if not _targets_tpu(platform, env):
        logging.warning(
            "AUTODIST_TPU_ASYNC_COLLECTIVES is set but this process does "
            "not target a TPU backend; skipping the latency-hiding "
            "flags")
        return False
    current = env.get(LATENCY_HIDING_FLAGS_ENV, "")
    missing = [f for f in flags if f not in current]
    if not missing:
        return True
    env[LATENCY_HIDING_FLAGS_ENV] = " ".join([current] + missing).strip()
    already_up = False
    try:  # backend registry probe; private, so failure = assume not up
        from jax._src import xla_bridge
        already_up = bool(getattr(xla_bridge, "_backends", None))
    except Exception:  # pragma: no cover - jax internals moved
        pass
    if already_up:
        logging.warning(
            "AUTODIST_TPU_ASYNC_COLLECTIVES set but the XLA backend is "
            "already initialized; the latency-hiding flags apply only to "
            "future processes — set the knob before the first device use")
    else:
        logging.info("XLA latency-hiding flags enabled: %s",
                     " ".join(missing))
    return True


@dataclasses.dataclass
class VarPlan:
    """Resolved per-variable lowering decision (≙ one compiled strategy
    node after device resolution)."""

    name: str
    shape: tuple[int, ...]
    dtype: Any
    stored_sharded: bool          # params stored sharded (FSDP) vs replicated
    split_axis: int               # tensor axis for U_AXIS / storage sharding
    update: str                   # U_REPLICATED | U_FLAT | U_AXIS
    bucket: Optional[str]         # allreduce bucket key (None = unsynced path)
    compressor: str = "none"
    sparse_lookup: bool = False   # vocab-sharded: feed the loss a
                                  # ShardedEmbedding (touched-rows sync)
    # Replica axes the plan shards over: ('data',), or ('dcn', 'data') on
    # multi-slice meshes (outer axis rides DCN, inner rides ICI).
    shard_axes: tuple = (const.DATA_AXIS,)

    @property
    def _axes_entry(self):
        return common.axes_entry(self.shard_axes)

    @property
    def param_spec(self) -> P:
        if not self.stored_sharded:
            return P()
        spec = [None] * len(self.shape)
        spec[self.split_axis] = self._axes_entry
        return P(*spec)

    def stored_shape(self, n: int) -> tuple[int, ...]:
        if not self.stored_sharded:
            return self.shape
        return common.padded_shape(self.shape, self.split_axis, n)

    def update_spec(self) -> P:
        if self.update == U_REPLICATED:
            return P()
        if self.update == U_FLAT:
            return P(self._axes_entry)
        spec = [None] * len(self.shape)
        spec[self.split_axis] = self._axes_entry
        return P(*spec)

    def update_shape(self, n: int) -> tuple[int, ...]:
        if self.update == U_REPLICATED:
            return self.shape
        if self.update == U_FLAT:
            return (common.padded_flat_size(math.prod(self.shape) or 1, n),)
        return common.padded_shape(self.shape, self.split_axis, n)


@dataclasses.dataclass
class Plan:
    """The compiled strategy: per-var plans + global state layout."""

    var_plans: dict[str, VarPlan]
    num_replicas: int
    buckets: dict[str, list[str]]          # bucket key -> ordered var names
    bucket_compressor: dict[str, str]      # bucket key -> compressor name
    ssp_staleness: int = 0                 # max PSSynchronizer.staleness:
                                           # the runner's host-side SSP gate
    repl_axes: tuple = (const.DATA_AXIS,)  # ('dcn', 'data') on multi-slice

    @property
    def axes_entry(self):
        """The replica axes as a PartitionSpec entry / collective
        axis_name (see :func:`common.axes_entry`)."""
        return common.axes_entry(self.repl_axes)


def replica_axes(mesh) -> tuple:
    """The data-parallel replica axes of a mesh: ('dcn', 'data') when a
    DCN (cross-slice) axis exists, else ('data',).  Outer-major order
    matches tiled collective layout."""
    axes = tuple(a for a in (const.DCN_AXIS, const.DATA_AXIS)
                 if a in mesh.shape)
    if const.DATA_AXIS not in axes:
        raise ValueError(
            f"mesh {dict(mesh.shape)} has no '{const.DATA_AXIS}' axis")
    return axes


def make_plan(trainable: Trainable, strategy: Strategy, mesh) -> Plan:
    """Resolve a Strategy against a mesh (≙ StrategyCompiler.compile:
    device resolution + node pruning, reference ``strategy/base.py:120-168``).
    """
    repl = replica_axes(mesh)
    n = math.prod(mesh.shape[a] for a in repl)
    if strategy.graph_config.replicas not in (0, n):
        raise ValueError(
            f"strategy built for {strategy.graph_config.replicas} replicas; "
            f"mesh replica axes {repl} have {n}")
    var_plans: dict[str, VarPlan] = {}
    buckets: dict[str, list[str]] = {}
    bucket_comp: dict[str, str] = {}
    ssp_staleness = 0
    proxy_vars = [
        nc.var_name for nc in strategy.node_configs
        if isinstance(nc.synchronizer, PSSynchronizer)
        and nc.synchronizer.local_replication]
    if proxy_vars:
        # The reference's ProxyVariable cached PS values on each worker
        # (proxy_variable.py:74-114); on TPU parameters are re-gathered
        # inside the compiled step every iteration, so there is nothing
        # to cache — but a user explicitly requesting proxy caching must
        # hear that the knob is a no-op, not silently lose it.
        logging.warning(
            "local_proxy_variable=True on %d variable(s) (e.g. %s) is a "
            "no-op on TPU: parameters are re-gathered each step inside "
            "the SPMD program (no cross-step cache to manage)",
            len(proxy_vars), proxy_vars[0])
    # Dict index instead of per-variable Strategy.node_config_for linear
    # scans: plan resolution stays O(V) on 10k-leaf trees.
    node_index = {nc.var_name: nc for nc in strategy.node_configs}
    for info in trainable.var_infos():
        node = node_index.get(info.name)
        sync = node.synchronizer if node else AllReduceSynchronizer()
        part = node.partitioner if node else None
        split_axis = -1
        if part is not None and part.num_shards > 1:
            split_axis = max(part.split_axis, 0)
            if part.num_shards != n:
                # Mesh resolution overrides shard-count hints the same way
                # the reference's compiler overrode device strings
                # (strategy/base.py:120-168): shards must map 1:1 onto the
                # mesh axis.  Routine (UnevenPartitionedPS emits reference
                # counts by design), hence debug not warning.
                logging.debug(
                    "%s: partitioner requests %d shards; lowering over the "
                    "%d-way %s axis instead", info.name, part.num_shards, n,
                    const.DATA_AXIS)
        if isinstance(sync, PSSynchronizer):
            if not sync.sync:
                # Async PS is a different execution mode (host-side push/
                # pull, runner.AsyncPSRunner) — it cannot lower into one
                # SPMD program, and silently training synchronously would
                # misreport the semantics the user asked for.
                raise NotImplementedError(
                    f"PS(sync=False) on {info.name}: asynchronous training "
                    "does not lower to a synchronous SPMD program; build "
                    "through AutoDist (which dispatches to AsyncPSRunner) "
                    "or use sync=True")
            ssp_staleness = max(ssp_staleness, sync.staleness)
            if split_axis >= 0 and info.shape:
                # Sparse + vocab(axis-0)-sharded: the loss sees a
                # ShardedEmbedding and only touched rows cross the wire
                # (≙ reference sparse PS path, ps_synchronizer.py:476-535).
                plan = VarPlan(info.name, info.shape, info.dtype,
                               stored_sharded=True, split_axis=split_axis,
                               update=U_AXIS, bucket=None,
                               sparse_lookup=bool(node.is_sparse)
                               and split_axis == 0, shard_axes=repl)
            else:
                plan = VarPlan(info.name, info.shape, info.dtype,
                               stored_sharded=False, split_axis=-1,
                               update=U_FLAT, bucket=None, shard_axes=repl)
        else:  # AllReduce
            if split_axis >= 0 and info.shape:
                plan = VarPlan(info.name, info.shape, info.dtype,
                               stored_sharded=False, split_axis=split_axis,
                               update=U_AXIS, bucket=None,
                               compressor=sync.compressor, shard_axes=repl)
            else:
                key = f"g{sync.group}:{sync.compressor}"
                plan = VarPlan(info.name, info.shape, info.dtype,
                               stored_sharded=False, split_axis=-1,
                               update=U_REPLICATED, bucket=key,
                               compressor=sync.compressor, shard_axes=repl)
                buckets.setdefault(key, []).append(info.name)
                bucket_comp[key] = sync.compressor
        var_plans[info.name] = plan
    return Plan(var_plans=var_plans, num_replicas=n, buckets=buckets,
                bucket_compressor=bucket_comp, ssp_staleness=ssp_staleness,
                repl_axes=repl)


# --------------------------------------------------------------------------- #
# Spec/shape trees
# --------------------------------------------------------------------------- #
def _params_specs(plan: Plan, params):
    return common.tree_from_names(
        params, lambda name, _: plan.var_plans[name].param_spec)


def _update_space(plan: Plan, params, n):
    """Global update-space view of params (full/flat/axis, zero-padded to
    divisibility; padding lanes carry zero grads so leaf-wise optimizer
    transforms leave them at zero)."""

    def view(name, p):
        vp = plan.var_plans[name]
        if vp.update == U_REPLICATED:
            return p
        if vp.update == U_FLAT:
            flat = p.reshape(-1)
            return common.pad_axis_to(flat, 0, vp.update_shape(n)[0])
        return common.pad_axis_to(p, vp.split_axis,
                                  vp.update_shape(n)[vp.split_axis])

    return common.tree_from_names(params, view)


def _opt_state_specs(plan: Plan, trainable: Trainable, n: int):
    """PartitionSpec tree for the optimizer state.

    Optax states embed param-shaped subtrees under the same key paths
    (e.g. ``ScaleByAdamState.mu[...]``); every optimizer-state leaf whose
    path ends with a variable's path inherits that variable's update-space
    spec, scalars and unmatched leaves replicate.  (The reference instead
    re-instantiated the optimizer over rewritten variables,
    ``partitioner.py:570-573`` — declarative matching replaces graph
    rewriting.)
    """
    u_shapes = jax.eval_shape(
        lambda p: _update_space(plan, p, n),
        jax.tree.map(lambda l: jax.ShapeDtypeStruct(np.shape(l), jnp.result_type(l)),
                     trainable.params))
    opt_shapes = jax.eval_shape(trainable.optimizer.init, u_shapes)
    var_names = list(plan.var_plans)

    def spec_for(path, leaf):
        from autodist_tpu.capture import path_to_name
        name = path_to_name(path)
        var = common.match_var_by_suffix(
            name, var_names,
            shape_ok=lambda v: tuple(leaf.shape)
            == plan.var_plans[v].update_shape(n))
        return plan.var_plans[var].update_spec() if var else P()

    return jax.tree_util.tree_map_with_path(spec_for, opt_shapes), opt_shapes


def _sync_state_init(plan: Plan, trainable: Trainable):
    """Per-bucket compressor-state init rows (device axis added at init):
    the EF residual, plus whatever the compressor packs behind it
    (PowerSGD's warm-started Q)."""
    rows = {}
    by_name = {v.name: v for v in trainable.var_infos()}
    for key, names in plan.buckets.items():
        comp = Compressor.create(plan.bucket_compressor.get(key, "none"))
        if comp.stateful:
            total = sum(by_name[nm].size for nm in names)
            rows[key] = np.asarray(comp.init_state_flat(total), np.float32)
    return rows


# --------------------------------------------------------------------------- #
# The lowered program
# --------------------------------------------------------------------------- #
def _gather_full(plan: Plan, data_axis: str, stored):
    """Stored-space params → full (gather sharded vars, unpad).

    Sparse vocab-sharded tables are *not* gathered: the loss receives a
    :class:`ShardedEmbedding` whose row lookups move touched rows only
    (dense uses decay to an all_gather via ``__jax_array__``)."""
    from autodist_tpu.ops.sparse import ShardedEmbedding

    def full(name, p):
        vp = plan.var_plans[name]
        if vp.sparse_lookup:
            return ShardedEmbedding(p, vp.shape[0], data_axis,
                                    plan.num_replicas)
        if vp.stored_sharded:
            return common.all_gather_axis(
                p, data_axis, vp.split_axis, vp.shape[vp.split_axis])
        return p

    return common.tree_from_names(stored, full)


def _reduce_metrics(tree, data_axis: str):
    """Cross-replica metric reduction: floats average, integer counts
    sum, bool flags OR (each the correct global semantics)."""
    if lax.axis_size(data_axis) == 1:
        # Single replica: every reduction is an identity; skip so the
        # compiled program carries zero collectives (the same bypass
        # the gradient path takes — tools/hlo_probe.py pins this).
        return tree
    def red(x):
        dt = jnp.result_type(x)
        if jnp.issubdtype(dt, jnp.inexact):
            return lax.pmean(x, data_axis)
        if dt == jnp.bool_:
            return lax.psum(x.astype(jnp.int32), data_axis) > 0
        if jnp.issubdtype(dt, jnp.integer):
            return lax.psum(x, data_axis)
        return x
    return jax.tree.map(red, tree)


# --------------------------------------------------------------------------- #
# State-codec recipes: the declarative stored↔logical transform record.
#
# Every lowering stores training state in its own layout (padding, flat
# ZeRO shards, interleave permutations).  A *recipe* is a per-leaf list
# of invertible primitive ops mapping the stored leaf to its logical
# (strategy-free) form — plain data, so the elastic-resharding engine
# (:mod:`autodist_tpu.elastic.reshard`) can apply it traced on device,
# on host numpy, or invert it mechanically for the target layout, and a
# checkpoint sidecar can serialize it and decode the stored bytes years
# later without rebuilding the source mesh.  Ops (forward = stored →
# logical; each records its input shape so inversion is mechanical,
# padding re-inserted by the inverse is zero — the repo-wide invariant
# that padding lanes carry zeros):
#
# * ``reshape``   — to ``shape``
# * ``slice``     — leading ``[0:s]`` per dim to ``shape`` (inverse: pad)
# * ``index0``    — ``arr[indices]`` along axis 0 (inverse: argsort)
# * ``flat_slice``— ``arr.reshape(-1)[:size]`` (inverse: pad + reshape)
# --------------------------------------------------------------------------- #
def _op_reshape(in_shape, shape):
    return {"op": "reshape", "in_shape": [int(d) for d in in_shape],
            "shape": [int(d) for d in shape]}


def _op_slice(in_shape, shape):
    return {"op": "slice", "in_shape": [int(d) for d in in_shape],
            "shape": [int(d) for d in shape]}


def _op_index0(in_shape, indices):
    return {"op": "index0", "in_shape": [int(d) for d in in_shape],
            "indices": [int(i) for i in indices]}


def _op_flat_slice(in_shape, size):
    return {"op": "flat_slice", "in_shape": [int(d) for d in in_shape],
            "size": int(size)}


def leaf_record(shape, dtype, ops=()) -> dict:
    """One manifest leaf: stored shape/dtype + the stored→logical ops.
    ``logical_shape`` is derived by replaying the ops on shapes alone."""
    shape = [int(d) for d in shape]
    logical = list(shape)
    for op in ops:
        if op["op"] in ("reshape", "slice"):
            logical = list(op["shape"])
        elif op["op"] == "index0":
            logical = [len(op["indices"])] + logical[1:]
        elif op["op"] == "flat_slice":
            logical = [op["size"]]
    return {"stored_shape": shape, "logical_shape": logical,
            "dtype": str(np.dtype(jnp.result_type(dtype))
                         if not isinstance(dtype, str) else dtype),
            "ops": list(ops)}


def _shape_dtype(leaf):
    return (tuple(int(d) for d in np.shape(leaf)),
            jnp.result_type(leaf) if hasattr(leaf, "dtype")
            else np.asarray(leaf).dtype)


@dataclasses.dataclass
class Lowered:
    """Compiled artifacts: jitted init and train-step functions plus the
    state layout (≙ the transformed graph + session of the reference)."""

    plan: Plan
    mesh: Any
    init_fn: Any          # (params, extra) -> state
    step_fn: Any          # (state, batch, rng) -> (state, metrics)
    state_specs: Any      # pytree of PartitionSpec
    state_shardings: Any  # pytree of NamedSharding
    batch_spec: Any
    eval_fn: Any = None   # (state, batch, rng) -> metrics (no update)
    # Compressor error-feedback init rows (bucket key -> host row):
    # what a resharder re-seeds non-transferable sync_state from.
    sync_init: Any = None

    def init_state(self, params=None, extra=None, trainable=None):
        params = params if params is not None else trainable.params
        extra = extra if extra is not None else (
            trainable.extra if trainable else None)
        return self.init_fn(params, extra)

    def unpad_params(self, params):
        """Strip storage padding: fetch params at their original shapes
        (≙ reference checkpoints looking unpartitioned, ``saver.py:50-58``)."""

        def unpad(name, p):
            vp = self.plan.var_plans[name]
            if vp.stored_sharded and p.shape != vp.shape:
                return lax.slice_in_dim(
                    p, 0, vp.shape[vp.split_axis], axis=vp.split_axis)
            return p

        return common.tree_from_names(params, unpad)

    def batch_spec_tree(self, batch):
        """Per-leaf feed PartitionSpecs (the remapper feed contract:
        batched leaves split, scalars duplicate)."""
        return common.batch_specs(batch, self.batch_spec)

    def state_manifest(self, state) -> dict:
        """The elastic state-codec manifest: per-leaf stored↔logical
        recipes for every leaf of ``state`` (real arrays or
        ``ShapeDtypeStruct``s — only shapes/dtypes are read).  See the
        recipe-ops comment above; consumed by
        :mod:`autodist_tpu.elastic.reshard` and serialized into the
        checkpoint sidecar by :class:`~autodist_tpu.checkpoint.saver.
        Saver`."""
        plan = self.plan
        n = plan.num_replicas
        var_names = list(plan.var_plans)
        leaves: dict = {}
        sync: dict = {}
        for name, leaf in common.flatten_with_names(state):
            shape, dtype = _shape_dtype(leaf)
            ops: list = []
            if name.startswith("params/"):
                vp = plan.var_plans.get(name[len("params/"):])
                if vp is not None and vp.stored_sharded \
                        and shape != tuple(vp.shape):
                    ops = [_op_slice(shape, vp.shape)]
            elif name.startswith("opt_state/"):
                var = common.match_var_by_suffix(
                    name, var_names,
                    shape_ok=lambda v: shape
                    == tuple(plan.var_plans[v].update_shape(n)))
                if var is not None:
                    vp = plan.var_plans[var]
                    if vp.update == U_FLAT and shape != tuple(vp.shape):
                        size = math.prod(vp.shape) if vp.shape else 1
                        ops = [_op_flat_slice(shape, size),
                               _op_reshape((size,), vp.shape)]
                    elif vp.update == U_AXIS and shape != tuple(vp.shape):
                        ops = [_op_slice(shape, vp.shape)]
            elif name.startswith("sync_state/"):
                key = name[len("sync_state/"):]
                sync[name] = {
                    "rows": int(shape[0]), "width": int(shape[1]),
                    "compressor": plan.bucket_compressor.get(key, "none")}
            leaves[name] = leaf_record(shape, dtype, ops)
        return {"family": "collective", "leaves": leaves, "sync": sync}


@dataclasses.dataclass
class SimpleLowered:
    """Lowered-contract container for backends whose parameters carry no
    storage padding (gspmd / sequence / pipeline / expert lowerings).

    ``batch_spec_fn(batch) -> spec tree`` overrides the uniform feed rule
    for lowerings with per-leaf placement (sequence parallelism splits
    token leaves over ``data x seq`` and the rest over ``data`` only)."""

    mesh: Any
    init_fn: Any
    step_fn: Any
    state_specs: Any
    state_shardings: Any
    batch_spec: Any
    plan: Any = None
    eval_fn: Any = None
    batch_spec_fn: Any = None
    # SSP bound from PS(staleness>0) node configs — the runner's host
    # gate is lowering-agnostic, so parallel/gspmd lowerings carry the
    # bound here instead of a Plan.
    ssp_staleness: int = 0
    # Compressor error-feedback init rows (see Lowered.sync_init).
    sync_init: Any = None

    def init_state(self, params=None, extra=None, trainable=None):
        params = params if params is not None else trainable.params
        extra = extra if extra is not None else (
            trainable.extra if trainable else None)
        return self.init_fn(params, extra)

    def unpad_params(self, params):
        return params

    def batch_spec_tree(self, batch):
        if self.batch_spec_fn is not None:
            return self.batch_spec_fn(batch)
        return common.batch_specs(batch, self.batch_spec)

    def state_manifest(self, state) -> dict:
        """Elastic state-codec manifest (see :meth:`Lowered.
        state_manifest`): these lowerings store every leaf at its
        logical shape, so every recipe is the identity; sync_state rows
        carry their transfer metadata."""
        leaves: dict = {}
        sync: dict = {}
        for name, leaf in common.flatten_with_names(state):
            shape, dtype = _shape_dtype(leaf)
            if name.startswith("sync_state/") and len(shape) == 2:
                sync[name] = {"rows": int(shape[0]),
                              "width": int(shape[1]),
                              "compressor": "unknown"}
            leaves[name] = leaf_record(shape, dtype)
        return {"family": "simple", "leaves": leaves, "sync": sync}


def lower(trainable: Trainable, strategy: Strategy, mesh) -> Lowered:
    """Build the SPMD program for (trainable, strategy, mesh)."""
    plan = make_plan(trainable, strategy, mesh)
    n = plan.num_replicas
    data_axis = plan.axes_entry  # 'data', or ('dcn', 'data') multi-slice
    opt = trainable.optimizer

    p_specs = _params_specs(plan, trainable.params)
    o_specs, _ = _opt_state_specs(plan, trainable, n)
    sync_init = _sync_state_init(plan, trainable)
    extra_specs = jax.tree.map(lambda _: P(), trainable.extra)
    state_specs = {
        "step": P(),
        "params": p_specs,
        "opt_state": o_specs,
        "extra": extra_specs,
        "sync_state": {k: P(data_axis) for k in sync_init},
    }
    state_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), state_specs,
        is_leaf=lambda x: isinstance(x, P))
    batch_spec = P(data_axis)

    var_order = list(plan.var_plans)

    # ---------------- init ------------------------------------------------ #
    def _init(params, extra):
        def store(name, p):
            vp = plan.var_plans[name]
            if vp.stored_sharded:
                return common.pad_axis_to(
                    jnp.asarray(p), vp.split_axis, vp.stored_shape(n)[vp.split_axis])
            return jnp.asarray(p)

        params_store = common.tree_from_names(params, store)
        u_params = _update_space(plan, jax.tree.map(jnp.asarray, params), n)
        opt_state = opt.init(u_params)
        sync_state = {k: jnp.tile(jnp.asarray(row)[None], (n, 1))
                      for k, row in sync_init.items()}
        return {
            "step": jnp.zeros((), jnp.int32),
            "params": params_store,
            "opt_state": opt_state,
            "extra": extra,
            "sync_state": sync_state,
        }

    init_fn = jax.jit(_init, out_shardings=state_shardings)

    accum = max(getattr(strategy.graph_config, "accum_steps", 1), 1)

    # The kernel slot's word on the kernels a model's call sites elect
    # (models.transformer.attend), opened where the model is traced.
    from autodist_tpu.parallel.tensor import kernel_scope
    kernel = strategy.graph_config.kernel

    # ---------------- train step ------------------------------------------ #
    @kernel_scope(kernel)
    def _local_step(state, batch, rng):
        params_store = state["params"]
        local_rng = jax.random.fold_in(rng, lax.axis_index(data_axis))

        def micro_grads(mb, rng_, extra_in):
            def stored_loss(stored):
                loss, new_extra, metrics = trainable.loss(
                    _gather_full(plan, data_axis, stored), extra_in,
                    mb, rng_)
                return loss, (new_extra, metrics)

            return jax.value_and_grad(stored_loss, has_aux=True)(
                params_store)

        if accum == 1:
            (loss, (new_extra, metrics)), grads_stored = micro_grads(
                batch, local_rng, state["extra"])
        else:
            grads_stored, new_extra, metrics = \
                common.accumulate_microbatches(
                    micro_grads, params_store, batch, local_rng,
                    state["extra"], accum)

        g_by_name = dict(common.flatten_with_names(grads_stored))
        p_by_name = dict(common.flatten_with_names(params_store))

        # --- per-bucket compressed allreduce (≙ AllReduceSynchronizer +
        # ScopedAllocator merging) ---------------------------------------- #
        synced: dict[str, Any] = {}
        new_sync_state: dict[str, Any] = {}
        with scope("grad_sync"):
            for key, names in plan.buckets.items():
                comp_name = plan.bucket_compressor.get(key, "none")
                if n == 1 and comp_name in ("", "none", None):  # ≙ Compressor.create's no-op aliases
                    # Single replica: the allreduce is an identity and
                    # bucketing exists only to amortize collectives — skip
                    # the flatten/concat/slice round trip (a full extra
                    # pass over every gradient through HBM per step).
                    for nm in names:
                        synced[nm] = g_by_name[nm]
                    continue
                comp = Compressor.create(comp_name)
                flats = [g_by_name[nm].reshape(-1).astype(jnp.float32)
                         for nm in names]
                concat = jnp.concatenate(flats) if len(flats) > 1 else flats[0]
                comp_state = (state["sync_state"][key][0]
                              if comp.stateful else None)
                reduced, comp_state = comp.allreduce(concat, comp_state, data_axis)
                if comp.stateful:
                    new_sync_state[key] = comp_state[None]
                offset = 0
                for nm in names:
                    vp = plan.var_plans[nm]
                    sz = math.prod(vp.shape) or 1
                    synced[nm] = lax.slice_in_dim(reduced, offset, offset + sz)\
                        .reshape(vp.shape).astype(g_by_name[nm].dtype)
                    offset += sz

        # --- update-space grads and param views --------------------------- #
        def u_grad(name, _p):
            vp = plan.var_plans[name]
            g = g_by_name[name]
            if vp.update == U_REPLICATED:
                return synced[name]
            if vp.update == U_FLAT:
                return common.reduce_scatter_flat(g, data_axis, n, mean=True)
            if vp.stored_sharded:
                # AD through all_gather already psum_scatter'ed (summed);
                # convert to mean to match the DP objective.
                return g / n
            return common.reduce_scatter_axis(
                g, data_axis, n, vp.split_axis, mean=True)

        def u_param(name, p):
            vp = plan.var_plans[name]
            if vp.update == U_REPLICATED or vp.stored_sharded:
                return p
            if vp.update == U_FLAT:
                return common.local_flat_shard(p, data_axis, n)
            return common.local_axis_shard(p, data_axis, n, vp.split_axis)

        with scope("grad_sync"):
            u_grads = common.tree_from_names(params_store, u_grad)
        u_params = common.tree_from_names(params_store, u_param)

        with scope("optimizer"):
            updates, new_opt_state = opt.update(
                u_grads, state["opt_state"], u_params)
            u_new = optax.apply_updates(u_params, updates)

        # --- back to storage space ---------------------------------------- #
        def to_store(name, un):
            vp = plan.var_plans[name]
            if vp.update == U_REPLICATED or vp.stored_sharded:
                return un
            if vp.update == U_FLAT:
                return common.all_gather_flat(un, data_axis, vp.shape)
            return common.all_gather_axis(
                un, data_axis, vp.split_axis, vp.shape[vp.split_axis])

        with scope("optimizer"):
            new_params = common.tree_from_names(u_new, to_store)

        metrics = _reduce_metrics(dict(metrics), data_axis)
        # extra state (e.g. batch stats) must be SPMD-invariant: average
        # float leaves defensively even if the model forgot axis_name.
        new_extra = jax.tree.map(
            lambda x: lax.pmean(x, data_axis)
            if jnp.issubdtype(jnp.result_type(x), jnp.inexact) else x,
            new_extra)

        full_sync_state = dict(state["sync_state"])
        full_sync_state.update(new_sync_state)
        new_state = {
            "step": state["step"] + 1,
            "params": new_params,
            "opt_state": new_opt_state,
            "extra": new_extra,
            "sync_state": full_sync_state,
        }
        return new_state, metrics

    def _step(state, batch, rng):
        sm = jax.shard_map(
            _local_step, mesh=mesh,
            in_specs=(state_specs, common.batch_specs(batch, batch_spec), P()),
            out_specs=(state_specs, P()),
            check_vma=False,
        )
        return sm(state, batch, rng)

    step_fn = jax.jit(_step, donate_argnums=(0,))

    # ---------------- eval step (no update; fetch contract) --------------- #
    @kernel_scope(kernel)
    def _local_eval(state, batch, rng):
        params_full = _gather_full(plan, data_axis, state["params"])
        loss, _, metrics = trainable.eval_loss(
            params_full, state["extra"], batch,
            jax.random.fold_in(rng, lax.axis_index(data_axis)))
        return _reduce_metrics(dict(metrics), data_axis)

    def _eval(state, batch, rng):
        return jax.shard_map(
            _local_eval, mesh=mesh,
            in_specs=(state_specs, common.batch_specs(batch, batch_spec), P()),
            out_specs=P(), check_vma=False)(state, batch, rng)

    eval_fn = jax.jit(_eval)

    return Lowered(plan=plan, mesh=mesh, init_fn=init_fn, step_fn=step_fn,
                   state_specs=state_specs, state_shardings=state_shardings,
                   batch_spec=batch_spec, eval_fn=eval_fn,
                   sync_init=dict(sync_init))
