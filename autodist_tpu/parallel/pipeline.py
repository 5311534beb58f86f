"""Pipeline parallelism: microbatch schedules over the ``pipe`` mesh axis.

Absent from the reference (``architecture.rst:49-51``, SURVEY.md §2.10
lists pipeline parallelism as not implemented) — built TPU-first: all
pipeline stages run the *same* SPMD program (identical stage structure,
stacked parameters sharded on the ``pipe`` axis); activations hop stage to
stage via ``lax.ppermute`` inside a ``lax.scan`` over schedule ticks.
The backward pass is the transposed ring (AD through ppermute).

Two schedules, one implementation:

* ``virtual_stages=1`` — GPipe fill-drain: microbatch ``m``'s stage ``c``
  runs at tick ``m + c``; bubble fraction ``(n-1)/(M+n-1)``.
* ``virtual_stages=V>1`` — Megatron-style interleaved: each device owns
  ``V`` *chunks* (chunk ``c`` on device ``c mod n``), and chunk ``c`` of
  microbatch ``m`` runs at tick

      start(m, c) = n·V·⌊m/n⌋ + (m mod n) + c

  which is provably conflict-free (for a device's chunks ``c ≡ d mod n``
  the tick decomposes uniquely into ``(⌊m/n⌋, v, m mod n)`` base-V/base-n
  digits) and keeps the one-hop property ``start(m, c+1) = start(m, c)+1``
  — so the same single-carry ppermute ring serves both schedules.  Total
  ticks drop from ``V·(M + n - 1)`` chunk-times (GPipe with V-chunk
  fused stages) to ``M·V + n - 1`` for ``n | M`` (exactly
  ``num_ticks`` below in general), shrinking the bubble ~``V``-fold:
  ``(n-1)/(M·V + n - 1)``.

Activations are pytrees; stages may emit auxiliary scalar losses
(``stage_aux=True``) which accumulate across every chunk — the
"non-last-stage loss" path (e.g. MoE balance terms inside pipeline
stages).

Per-device memory: O(V·chunk params + activations · ticks); use
``jax.checkpoint`` in ``stage_fn`` for long pipelines.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from autodist_tpu import const
from autodist_tpu import fetches as _fetches
from autodist_tpu.kernel import common
from autodist_tpu.kernel.lowering import SimpleLowered


# --------------------------------------------------------------------------- #
# The schedule (shared by the kernel and by tests/diagnostics)
# --------------------------------------------------------------------------- #
def start_tick(m: int, c: int, *, num_devices: int, virtual_stages: int):
    """Tick at which chunk ``c`` of microbatch ``m`` runs (host math)."""
    n, V = num_devices, virtual_stages
    return n * V * (m // n) + m % n + c


def num_ticks(num_microbatches: int, num_devices: int,
              virtual_stages: int) -> int:
    """Total schedule ticks = start of the last (microbatch, chunk) + 1."""
    n, V, M = num_devices, virtual_stages, num_microbatches
    return start_tick(M - 1, n * V - 1, num_devices=n,
                      virtual_stages=V) + 1


def bubble_fraction(num_microbatches: int, num_devices: int,
                    virtual_stages: int) -> float:
    """Idle fraction of the schedule: (ticks - useful) / ticks, where a
    device's useful ticks are its M·V chunk computations."""
    T = num_ticks(num_microbatches, num_devices, virtual_stages)
    useful = num_microbatches * virtual_stages
    return (T - useful) / T


def _tick_assignment(t, device, *, n: int, V: int, M: int):
    """(valid, m, v) processed by ``device`` at tick ``t`` (traced math).

    Inverts ``start(m, c)``: with ``c = v·n + device``,
    ``t - device = (m mod n) + n·(v + V·⌊m/n⌋)``.
    """
    rel = t - device
    nonneg = rel >= 0
    rel_safe = jnp.maximum(rel, 0)
    r = rel_safe % n
    v = (rel_safe // n) % V
    q = rel_safe // (n * V)
    m = q * n + r
    valid = nonneg & (m < M)
    return valid, jnp.clip(m, 0, M - 1), v


# --------------------------------------------------------------------------- #
# The kernel
# --------------------------------------------------------------------------- #
def pipeline_apply(stage_fn: Callable, stage_params, x, *,
                   axis_name: str = const.PIPE_AXIS,
                   num_microbatches: int, virtual_stages: int = 1,
                   stage_aux: bool = False, stage_rng: bool = False,
                   rng=None, row_offset=0):
    """Run the pipeline schedule (call inside ``shard_map``).

    Args:
      stage_fn: ``(chunk_params, activation) -> activation`` (or
        ``-> (activation, aux_scalar)`` with ``stage_aux=True``) — one
        pipeline chunk.  Activations are pytrees; chunk 0 consumes a
        microbatch of ``x``, so the activation structure/shapes must
        match the microbatch's.  With ``stage_rng=True`` the signature
        is ``(chunk_params, activation, chunk_rng, rows)``: ``chunk_rng``
        is ``fold_in(rng, global_chunk)`` (``None`` when ``rng`` is
        ``None`` — eval), ``rows`` the *global* sample indices of the
        microbatch —
        keying stochasticity (dropout) per (chunk, sample) makes the
        masks microbatching- and data-sharding-invariant, so the
        pipelined run reproduces the sequential reference exactly for
        any M (see ``models/pipeline_lm.py``).
      stage_params: this device's chunk parameters — the local shard.
        ``virtual_stages == 1``: the chunk's params directly;
        ``virtual_stages == V > 1``: leaves carry a leading ``[V]`` dim
        (local chunk ``v`` is global chunk ``v·n + device``).
      x: local batch pytree ``[B, ...]``; split into ``num_microbatches``
        along dim 0.  Only chunk 0's value is consumed; pass the same
        batch on all devices.
      num_microbatches: M; B must be divisible by M.
      virtual_stages: V — chunks per device (Megatron interleaving).
      stage_aux: stage_fn also returns a scalar accumulated over every
        (microbatch, chunk) — per-stage auxiliary losses.
      stage_rng / rng / row_offset: per-chunk rng threading (above);
        ``row_offset`` is this data-shard's first global sample index.

    Returns the last chunk's outputs ``[B, ...]`` (zeros on other
    devices — use :func:`last_stage_value` or a psum to extract), plus
    this device's accumulated aux scalar when ``stage_aux``.
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    M, V = num_microbatches, virtual_stages
    leaves = jax.tree.leaves(x)
    if not leaves:
        raise ValueError("pipeline_apply needs a non-empty batch pytree")
    B = leaves[0].shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    mb = jax.tree.map(lambda a: a.reshape(M, B // M, *a.shape[1:]), x)

    vparams = stage_params if V > 1 else \
        jax.tree.map(lambda p: p[None], stage_params)
    for leaf in jax.tree.leaves(vparams):
        if leaf.shape[0] != V:
            raise ValueError(
                f"virtual_stages={V} but a chunk-param leaf has leading "
                f"dim {leaf.shape[0]} (expected [V, ...] per-device "
                "layout)")

    mb_size = B // M

    def call_stage(pv, act, m, v):
        if not stage_rng:
            return stage_fn(pv, act)
        c_global = v * n + lax.axis_index(axis_name)
        rng_c = (jax.random.fold_in(rng, c_global)
                 if rng is not None else None)
        rows = row_offset + m * mb_size + jnp.arange(mb_size)
        return stage_fn(pv, act, rng_c, rows)

    mb0 = jax.tree.map(lambda a: a[0], mb)
    pv0 = jax.tree.map(lambda p: p[0], vparams)
    if stage_rng:
        probe = jax.eval_shape(
            lambda pv, act: call_stage(pv, act, jnp.zeros((), jnp.int32),
                                       jnp.zeros((), jnp.int32)),
            pv0, mb0)
    else:
        probe = jax.eval_shape(stage_fn, pv0, mb0)
    act_probe = probe[0] if stage_aux else probe
    in_probe = jax.eval_shape(lambda t: t, mb0)
    if (jax.tree.structure(act_probe) != jax.tree.structure(in_probe)
            or [(a.shape, a.dtype) for a in jax.tree.leaves(act_probe)]
            != [(a.shape, a.dtype) for a in jax.tree.leaves(in_probe)]):
        raise ValueError(
            "stage activations must match the microbatch structure/"
            f"shapes (chunk 0 consumes the batch): got {act_probe} vs "
            f"{in_probe}")

    T = num_ticks(M, n, V)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def tick(carry, t):
        prev_out, outputs, aux_acc = carry
        recv = jax.tree.map(lambda a: lax.ppermute(a, axis_name, perm),
                            prev_out)
        valid, m, v = _tick_assignment(t, idx, n=n, V=V, M=M)
        first = (v == 0) & (idx == 0)   # global chunk 0: inject the batch
        inj = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, m, keepdims=False), mb)
        my_in = jax.tree.map(lambda i, rcv: jnp.where(first, i, rcv),
                             inj, recv)
        pv = jax.tree.map(
            lambda p: lax.dynamic_index_in_dim(p, v, keepdims=False),
            vparams)
        res = call_stage(pv, my_in, m, v)
        out, aux = res if stage_aux else (res, None)
        if stage_aux:
            aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
        last = valid & (v == V - 1) & (idx == n - 1)

        def store(o_acc, o):
            cur = lax.dynamic_index_in_dim(o_acc, m, keepdims=False)
            return lax.dynamic_update_index_in_dim(
                o_acc, jnp.where(last, o, cur), m, 0)

        outputs = jax.tree.map(store, outputs, out)
        return (out, outputs, aux_acc), None

    act0 = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), in_probe)
    out0 = jax.tree.map(
        lambda a: jnp.zeros((M,) + tuple(a.shape), a.dtype), in_probe)
    carry0 = (act0, out0, jnp.zeros((), jnp.float32))
    (_, outputs, aux_acc), _ = lax.scan(tick, carry0, jnp.arange(T))
    outputs = jax.tree.map(
        lambda a: a.reshape(B, *a.shape[2:]), outputs)
    return (outputs, aux_acc) if stage_aux else outputs


def last_stage_value(value, axis_name: str = const.PIPE_AXIS):
    """psum-select the last pipeline stage's value (zeros elsewhere)."""
    S = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    return jax.tree.map(
        # pipe-axis last-stage broadcast (role select), not a policied
        # data boundary:        # lint: allow-raw-collective
        lambda x: lax.psum(
            jnp.where(idx == S - 1, x, jnp.zeros_like(x)), axis_name),
        value)


# --------------------------------------------------------------------------- #
# Chunk <-> storage permutations (interleaving strides chunks over devices)
# --------------------------------------------------------------------------- #
def chunk_permutation(n: int, V: int) -> np.ndarray:
    """``perm`` with storage row ``d·V + v`` = logical chunk ``v·n + d``:
    applying ``logical[perm]`` yields the storage order whose
    ``P('pipe')`` shard on device ``d`` holds that device's V chunks."""
    return np.array([(r % V) * n + r // V for r in range(n * V)])


def chunk_permutation_inv(n: int, V: int) -> np.ndarray:
    """Inverse: ``storage[perm_inv]`` restores logical chunk order."""
    return np.array([(c % n) * V + c // n for c in range(n * V)])


@dataclasses.dataclass
class _PipelineLowered(SimpleLowered):
    """SimpleLowered + the storage→logical chunk permutation, so
    ``get_params`` / portable checkpoints expose stage order the user
    declared (the 'looks unpartitioned' contract)."""

    perm_inv: Any = None
    has_shared: bool = False
    # Original (pre-padding) shapes of model-sharded shared leaves
    # (vocab parallelism zero-pads non-divisible vocab dims in storage);
    # fetch paths slice the padding back off.
    shared_orig_shapes: Any = None
    # Logical shapes of ZeRO-3 flat-stored leaves (full variable name ->
    # pre-flattening shape): fetch paths restore the declared layout.
    zero3_shapes: Any = None
    # name -> reason for every ZeRO request this lowering degraded
    # (tp-sharded stage vars, stage-3 on the vocab-sharded table): the
    # plan record that replaced the old warn-and-degrade logging.
    zero_degraded: Any = None
    # The resolved per-collective precision policy this program lowered
    # with (normalized boundary -> precision dict; {} = fp32
    # everywhere) — the plan record a caller can audit without
    # re-deriving the graph/per-variable adoption rules.
    precision: Any = None
    # The fused-kernel election this program lowered with (normalized
    # name -> True dict; {} = composed everywhere) — same audit record
    # as ``precision``.
    kernel: Any = None
    # Elastic state-codec builder (closure over _build_pipeline's layout
    # bookkeeping): state tree -> per-leaf stored↔logical recipes.
    state_manifest_fn: Any = None

    def state_manifest(self, state) -> dict:
        if self.state_manifest_fn is None:
            return super().state_manifest(state)
        return self.state_manifest_fn(state)

    def unpad_params(self, params):
        if self.perm_inv is None:
            return params
        # Host-side permutation: a device gather on the pipe-sharded dim
        # would need a reshard; fetch callers (get_params, portable save)
        # device_get immediately anyway.
        inv = np.asarray(self.perm_inv)
        z3 = self.zero3_shapes or {}

        def unstage(nm, p):
            arr = np.asarray(jax.device_get(p))
            shape = z3.get(nm)
            if shape is not None:
                elems = max(int(np.prod(shape[1:])), 1)
                arr = arr[:, :elems].reshape(shape)
            return arr[inv]

        def unperm(tree, prefix=""):
            return common.tree_from_names(
                tree, lambda nm, p: unstage(prefix + nm, p))

        if self.has_shared:
            orig = self.shared_orig_shapes or {}

            def unpad_shared(nm, p):
                arr = np.asarray(jax.device_get(p))
                shape = z3.get(f"shared/{nm}")
                if shape is not None:
                    size = max(int(np.prod(shape)), 1)
                    return arr.reshape(-1)[:size].reshape(shape)
                shape = orig.get(nm)
                if shape is not None and tuple(arr.shape) != tuple(shape):
                    arr = arr[tuple(slice(0, s) for s in shape)]
                return arr

            return {"stages": unperm(params["stages"], "stages/"),
                    "shared": common.tree_from_names(params["shared"],
                                                     unpad_shared)}
        return unperm(params)


# --------------------------------------------------------------------------- #
# Lowering
# --------------------------------------------------------------------------- #
def _build_pipeline(stage_fn: Callable, stacked_params, loss_head: Callable,
                    optimizer, mesh, *, num_microbatches: int,
                    data_axis: str = const.DATA_AXIS,
                    pipe_axis: str = const.PIPE_AXIS,
                    accum: int = 1, batch_key: str = "x",
                    virtual_stages: int = 1, stage_aux: bool = False,
                    shared_params=None, prologue: Callable = None,
                    policies=None, stage_rng: bool = False,
                    remat: bool = False, tp_specs=None,
                    model_axis: str = const.MODEL_AXIS,
                    comm_overlap=None, shared_specs=None,
                    zero_degraded=None, precision=None, kernel=None):
    """Shared construction for the direct API and the Strategy-IR entry;
    returns a Lowered-contract container.

    ``stacked_params``: pytree whose leaves carry the *logical* leading
    chunk dimension ``C = n·virtual_stages``; stored internally in the
    interleaved device order (``chunk_permutation``), restored on fetch.

    ``shared_params`` (optional): replicated parameters outside the
    stage stack — a pipelined transformer's embedding/unembedding.
    ``prologue(shared, batch) -> activation`` produces chunk 0's input
    on every device (only device 0's value enters the ring) and
    ``loss_head(outputs, batch, shared)`` closes the model on the last
    stage; shared grads psum over the pipe axis (each device contributes
    a different role) then average over data.

    ``accum > 1`` composes gradient accumulation *around* the pipeline:
    each accumulation slice runs the full microbatched schedule, so one
    optimizer step consumes ``accum x num_microbatches`` microbatches
    (the reconciliation of ``GraphConfig.accum_steps`` with pipeline
    microbatching).

    ``policies`` (per-variable :class:`~autodist_tpu.parallel._spmd.VarPolicy`,
    resolved from the Strategy's node configs by :func:`lower_pipeline_ir`)
    composes ZeRO-1 and gradient compression with the pipeline:

    * a *stage* variable with ``zero_axes`` (the data axes) keeps its
      pipe-sharded storage, but its optimizer state lives flat-sharded
      over the data axes *within* each pipe shard — grads reduce-scatter
      over data, the update runs on the local 1/n_d flat shard, updated
      values all-gather back (opt-state spec ``P((pipe, data))``);
    * a *shared* variable with ``zero_axes`` shards its optimizer state
      over ``pipe x data`` jointly: one ``psum_scatter`` realizes the
      sum-over-pipe (each device contributes a different role) and the
      shard split, divided by the data-replica count for the mean;
    * ``zero_stage == 2`` lowers identically (the U_FLAT scheme above
      already reduce-scatters the gradient sync); the stage is the
      record the cost model prices the 1/n gradient term from;
    * ``zero_stage == 3`` additionally *stores* the parameter sharded:
      a stage variable lives as ``[C, padded_chunk]`` flat rows sharded
      ``P(pipe, data)`` and each chunk is all-gathered on demand inside
      the step — one gather per (layer, leaf), chained through
      ``optimization_barrier`` sentinels (``common.chain_gathers``) so
      XLA can neither merge them into a bulk up-front materialization
      nor hoist them, and the next layer's gather can prefetch under
      the current layer's compute with the async-collective flags.  The
      gather's custom VJP (``common.zero3_gather``) reduce-scatters the
      cotangent, so gradients are born sharded, the update runs on the
      stored shard, and nothing full-sized survives the step boundary
      (``tools/hlo_probe.py probe_zero3`` asserts both properties);
    * a ``compressor`` runs the compressed allreduce over the data axes
      (stage grads differ across pipe; shared grads psum over pipe at
      full precision first).

    ``tp_specs`` (tensor parallelism inside stages — the dp×pp×tp
    composition): per-stage-variable tuples of mesh axes, one entry per
    *non-stacked* dim, naming which dims shard over ``model_axis``
    (resolved from the Strategy's ``Pipeline(tensor_parallel=...)``
    partitioner specs by :func:`lower_pipeline_ir`).  Matched stage
    leaves are stored sharded ``P(pipe, ..., model, ...)``, so inside
    the shard_map each device holds only its Megatron slice of each
    chunk; ``stage_fn`` must be TP-aware — accept a ``model_axis=``
    keyword and mark its column/row-parallel boundaries with the
    :mod:`autodist_tpu.parallel.tensor` primitives (identity/psum
    custom-VJP pairs), which insert exactly one activation all-reduce
    per Megatron block in forward and one in backward.  Grad sync is
    unchanged: each (pipe, model) coordinate owns its slice, replicas
    differ along the data axes only; model-replicated stage variables
    (layer norms, row-parallel biases) compute bitwise-identical
    gradients on every model member because every boundary activation
    and cotangent is model-replicated by the psum placement.  ZeRO on a
    tp-sharded variable is rejected here (its optimizer state already
    shards with the parameter; ``lower_pipeline_ir`` degrades such
    requests, recording the reason on the lowered plan, before calling).

    ``comm_overlap`` (with tensor parallelism): how the model-axis
    activation collectives lower — ``None`` blocking psum, ``"rsag"``
    reduce-scatter + all-gather, ``"matmul"`` the chunked
    collective-matmul ring (see :mod:`autodist_tpu.parallel.tensor`).
    The stage_fn must additionally accept a ``comm_overlap=`` keyword;
    with ``tp == 1`` the knob is a no-op (no collectives either way).

    ``shared_specs`` (vocab parallelism — ``Pipeline(vocab_parallel=
    True)``): per-*shared*-variable tuples of mesh axes, one entry per
    dim, naming which dims shard over ``model_axis`` (resolved from the
    shared variables' partitioner specs by :func:`lower_pipeline_ir`).
    Matched shared leaves are stored sharded (e.g. the tied embedding
    ``P(model, None)``) with non-divisible dims zero-padded; replicated
    ``P()`` remains the default for every other shared leaf.  The
    ``prologue`` and ``loss_head`` then receive local shards and must be
    vocab-parallel aware — accept ``model_axis=`` and use the
    :mod:`autodist_tpu.parallel.tensor` vocab primitives (masked-lookup
    psum; streaming fused cross-entropy).  Shared-grad sync is
    unchanged: the psum over ``pipe`` composes with model-axis sharding
    because each (pipe, model) coordinate owns its vocab slice's
    contribution and the sum runs per model coordinate.  ZeRO on a
    model-sharded shared variable shards its optimizer state
    *additionally* over ``pipe x data`` — the local ``[V_pad/tp, H]``
    shard's flat update space lives ``P((model, pipe, data))``, state
    at ``1/(tp·pipe·data)`` — the grad reduce-scatter and update
    all-gather running entirely within each model coordinate (a stage-3
    request on it degrades to this state-sharding form, recorded on the
    lowered plan: the parameter is already 1/tp-sharded)."""
    from autodist_tpu.parallel.tensor import normalize_comm_overlap

    n = mesh.shape[pipe_axis]
    V = virtual_stages
    C = n * V
    policies = policies or {}
    tp_specs = dict(tp_specs or {})
    shared_specs = dict(shared_specs or {})
    comm_overlap = normalize_comm_overlap(comm_overlap)
    # Per-collective precision policy (Strategy IR, normalized dict):
    # tp_psum / vocab_stats apply through a trace-time scope around the
    # step body (stage code keeps its signature); zero3_gather binds
    # into the gather chain; the grad slot was already resolved into
    # compressor configs by the builder / lower_pipeline_ir.
    from autodist_tpu.strategy.ir import (normalize_kernel,
                                          normalize_precision)
    precision = normalize_precision(precision)
    zero3_precision = precision.get("zero3_gather", "fp32")
    # Fused-kernel tier election (Strategy IR kernel slot): applied
    # through the same trace-time scope discipline as the precision
    # policy — flash_decode is serving-side and ignored here.
    kernel = {k: True for k in normalize_kernel(kernel)
              if k in ("quant_ring", "collective_matmul")}
    tp = mesh.shape.get(model_axis, 1) if tp_specs else 1
    if (tp_specs or shared_specs) and model_axis not in mesh.shape:
        raise ValueError(
            f"tp_specs/shared_specs given but the mesh has no "
            f"{model_axis!r} axis: {dict(mesh.shape)}")
    if shared_specs and shared_params is None:
        raise ValueError(
            "shared_specs shard shared variables but this pipeline has "
            "no shared_params")
    vp = mesh.shape.get(model_axis, 1) if shared_specs else 1
    if vp > 1:
        import inspect
        for role, fn in (("prologue", prologue), ("loss_head", loss_head)):
            if fn is None:
                continue
            try:
                role_sig = inspect.signature(fn).parameters
            except (TypeError, ValueError):  # partials: trust the caller
                role_sig = {"model_axis": None, "comm_overlap": None}
            if "model_axis" not in role_sig:
                raise ValueError(
                    f"vocab parallelism needs a vocab-parallel-aware "
                    f"{role}: it must accept model_axis= and use the "
                    "autodist_tpu.parallel.tensor vocab primitives")
            if comm_overlap is not None and "comm_overlap" not in role_sig:
                raise ValueError(
                    f"comm_overlap={comm_overlap!r} with vocab "
                    f"parallelism needs the {role} to accept "
                    "comm_overlap= and route it to the epilogue psums")
        import functools
        vp_kwargs = {"model_axis": model_axis}
        if comm_overlap is not None:
            vp_kwargs["comm_overlap"] = comm_overlap
        if prologue is not None:
            prologue = functools.partial(prologue, **vp_kwargs)
        loss_head = functools.partial(loss_head, **vp_kwargs)
    if tp > 1:
        import inspect
        try:
            params_sig = inspect.signature(stage_fn).parameters
        except (TypeError, ValueError):  # builtins/partials: trust the caller
            params_sig = {"model_axis": None, "comm_overlap": None}
        if "model_axis" not in params_sig:
            raise ValueError(
                "tensor_parallel > 1 needs a TP-aware stage_fn: it must "
                "accept model_axis= and psum its row-parallel outputs "
                "(see autodist_tpu.parallel.tensor)")
        import functools
        tp_kwargs = {"model_axis": model_axis}
        if comm_overlap is not None:
            if "comm_overlap" not in params_sig:
                raise ValueError(
                    f"comm_overlap={comm_overlap!r} needs an overlap-aware "
                    "stage_fn: it must accept comm_overlap= and route it to "
                    "its row/column-parallel boundaries "
                    "(autodist_tpu.parallel.tensor primitives)")
            tp_kwargs["comm_overlap"] = comm_overlap
        stage_fn = functools.partial(stage_fn, **tp_kwargs)
    if remat:
        # Each chunk recomputes its forward in the backward pass: live
        # residuals shrink from every chunk intermediate to the chunk
        # boundary activations (the Pipeline(remat=True) strategy knob;
        # the cost model prices both envelopes).
        stage_fn = jax.checkpoint(stage_fn)
    # Replica axes include dcn on multi-slice meshes (data-only sync
    # would skip cross-slice gradient exchange).
    d_axes = tuple(a for a in (const.DCN_AXIS, data_axis)
                   if a in mesh.shape)
    has_data = bool(d_axes)
    d_entry = common.axes_entry(d_axes) if has_data else None
    n_d = math.prod(mesh.shape[a] for a in d_axes) if d_axes else 1
    has_shared = shared_params is not None
    for leaf in jax.tree.leaves(stacked_params):
        if leaf.shape[0] != C:
            raise ValueError(
                f"stacked param leading dim {leaf.shape[0]} != "
                f"{n} pipe devices x {V} virtual stages = {C}")
    perm = jnp.asarray(chunk_permutation(n, V))
    perm_inv = jnp.asarray(chunk_permutation_inv(n, V))

    # --- tensor-parallel storage bookkeeping ------------------------------- #
    def full_stage_name(rel: str) -> str:
        return f"stages/{rel}" if has_shared else rel

    stage_leaf_names = {full_stage_name(nm) for nm, _ in
                        common.flatten_with_names(stacked_params)}
    unknown = set(tp_specs) - stage_leaf_names
    if unknown:
        raise ValueError(
            f"tp_specs name non-stage variables {sorted(unknown)} "
            f"(stage variables: {sorted(stage_leaf_names)})")
    if shared_specs:
        shared_leaf_names = {f"shared/{nm}" for nm, _ in
                             common.flatten_with_names(shared_params)}
        unknown = set(shared_specs) - shared_leaf_names
        if unknown:
            raise ValueError(
                f"shared_specs name non-shared variables {sorted(unknown)} "
                f"(shared variables: {sorted(shared_leaf_names)})")

    def tp_shards(name: str) -> int:
        """Device count the model axis splits one stage leaf over."""
        return math.prod(mesh.shape[a] for a in tp_specs.get(name, ())
                         if a is not None)

    def stage_param_spec(name: str) -> P:
        if zero3(name):   # ZeRO-3 storage: [C, padded_chunk] flat rows
            return u_spec(name)
        tail = tp_specs.get(name)
        return P(pipe_axis, *tail) if tail else P(pipe_axis)

    def shared_shards(name: str) -> int:
        """Device count a shared leaf's spec shards it over."""
        return math.prod(mesh.shape[a] for a in shared_specs.get(name, ())
                         if a is not None)

    def shared_param_spec(name: str) -> P:
        if zero3(name):   # ZeRO-3 storage: the flat padded shard
            return u_spec(name)
        spec = shared_specs.get(name)
        return P(*spec) if spec else P()

    def shared_padded_shape(name: str, shape: tuple) -> tuple:
        """Stored shape of a shared leaf: each model-sharded dim
        zero-padded to divide its axis size (vocab % tp != 0)."""
        spec = shared_specs.get(name)
        if not spec:
            return tuple(shape)
        return tuple(
            common.padded_flat_size(d, mesh.shape[a]) if a is not None
            else d for d, a in zip(shape, spec))

    if has_shared:
        full_params = {"stages": stacked_params, "shared": shared_params}
    else:
        full_params = stacked_params

    # --- per-variable policy bookkeeping (ZeRO / compressors) ------------- #
    zero_degraded = dict(zero_degraded or {})

    def is_stage_var(name: str) -> bool:
        return name.startswith("stages/") if has_shared else True

    def zero_pol(name):
        pol = policies.get(name)
        return pol if (pol is not None and pol.zero_axes) else None

    def zero_count(pol) -> int:
        return math.prod(mesh.shape[a] for a in pol.zero_axes)

    def zero3(name) -> bool:
        """Stage 3: the variable's parameter is *stored* as its ZeRO
        shard and gathered on demand per layer inside the step.  Never
        true for model-sharded variables — their stage-3 requests
        degrade to the state-sharding form (recorded below)."""
        pol = zero_pol(name)
        return (pol is not None and pol.zero_stage >= 3
                and name not in tp_specs and name not in shared_specs)

    for name, pol in policies.items():
        if pol.zero_axes and is_stage_var(name) \
                and pipe_axis in pol.zero_axes:
            raise ValueError(
                f"{name}: a stage variable is already pipe-sharded; its "
                f"ZeRO axes must not include {pipe_axis!r}")
        if pol.zero_axes and name in tp_specs:
            raise ValueError(
                f"{name}: a tensor-parallel sharded variable's optimizer "
                "state already shards with the parameter; ZeRO on it "
                "is a no-op request (lower_pipeline_ir degrades it)")
        if pol.zero_axes and name in shared_specs:
            # The model-sharded (vocab-parallel) table: its *parameter*
            # already lives 1/tp, so ZeRO here shards the optimizer
            # state additionally over pipe x data (update space
            # P((model, pipe, data)), state at 1/(tp * pipe * data)).
            # Only a dim-0 model shard is supported — the vocab-rule
            # form; anything fancier degrades to plain sync.
            spec = shared_specs[name]
            if not (spec and spec[0] == model_axis
                    and all(a is None for a in spec[1:])):
                zero_degraded[name] = (
                    "ZeRO on a shared variable model-sharded beyond "
                    f"dim 0 (spec {list(spec)}) is unsupported; state "
                    "shards with the parameter only")
                policies = {k: p for k, p in policies.items() if k != name}
            elif pol.zero_stage >= 3:
                zero_degraded[name] = (
                    "zero_stage=3 on the model-sharded table degrades "
                    "to optimizer-state sharding: the parameter is "
                    "already 1/tp-sharded over the model axis; state "
                    "shards over (model, pipe, data)")

    leaves_by_name = dict(common.flatten_with_names(full_params))
    # Per-device sizes: stage leaves hold this device's V chunks (1/n of
    # the stack, further 1/tp for model-axis-sharded leaves); shared
    # leaves replicate in full — except vocab-sharded ones, which hold
    # their (padded) 1/tp slice.
    local_sizes = {
        name: (max(int(np.prod(np.shape(leaf))), 1)
               // (n * tp_shards(name))
               if is_stage_var(name)
               else max(int(np.prod(shared_padded_shape(
                   name, np.shape(leaf)))), 1) // shared_shards(name)
               if name in shared_specs
               else max(int(np.prod(np.shape(leaf))), 1))
        for name, leaf in leaves_by_name.items()}

    def chunk_elems(name) -> int:
        """Elements of ONE chunk of a stage leaf (the stacked shape
        minus its leading chunk dim)."""
        return max(local_sizes[name] // V, 1)

    def padded_chunk(name) -> int:
        """ZeRO-3 stage storage row width: one chunk's elements padded
        to divide the ZeRO shard count (per-chunk padding keeps every
        layer's shard contiguous, so each layer gathers independently)."""
        return common.padded_flat_size(chunk_elems(name),
                                       zero_count(zero_pol(name)))

    def u_shape(name) -> tuple:
        pol = zero_pol(name)
        if pol is None:
            shape = tuple(np.shape(leaves_by_name[name]))
            if name in shared_specs:
                # opt state is initialized from (and shards like) the
                # padded stored leaf
                shape = shared_padded_shape(name, shape)
            return shape
        if zero3(name):
            # Stage 3: update space IS the storage — [C, padded_chunk]
            # rows for stage leaves, the flat padded shard for shared.
            if is_stage_var(name):
                return (C, padded_chunk(name))
            return (common.padded_flat_size(local_sizes[name],
                                            zero_count(pol)),)
        if name in shared_specs:
            # Model-sharded table + ZeRO: the local 1/tp shard's flat
            # update space, model-major over the full group.
            tp_n = shared_shards(name)
            return (tp_n * common.padded_flat_size(local_sizes[name],
                                                   zero_count(pol)),)
        padded = common.padded_flat_size(local_sizes[name], zero_count(pol))
        return (n * padded,) if is_stage_var(name) else (padded,)

    def u_spec(name):
        pol = zero_pol(name)
        if is_stage_var(name):
            if zero3(name):
                return P(pipe_axis, common.axes_entry(pol.zero_axes))
            return P((pipe_axis, *pol.zero_axes))
        if name in shared_specs:
            return P((model_axis, *pol.zero_axes))
        return P(common.axes_entry(pol.zero_axes))

    def u_view(name, leaf):
        """Global update-space view (runs in plain jit on the *stored*,
        i.e. interleave-permuted, layout): ZeRO leaves flatten pipe-major
        so the jit sharding matches what ``local_flat_shard`` /
        ``reduce_scatter_flat`` produce inside shard_map (model-major
        for the vocab-sharded table's state — its shards live within
        each model coordinate).  ZeRO-3 leaves are stored in update
        space already."""
        pol = zero_pol(name)
        if pol is None:
            return leaf
        if zero3(name):
            return leaf
        nz = zero_count(pol)
        if is_stage_var(name):
            flat = jnp.reshape(leaf, (n, local_sizes[name]))
            flat = common.pad_axis_to(
                flat, 1, common.padded_flat_size(local_sizes[name], nz))
            return flat.reshape(-1)
        if name in shared_specs:
            tp_n = shared_shards(name)
            flat = jnp.reshape(leaf, (tp_n, local_sizes[name]))
            flat = common.pad_axis_to(
                flat, 1, common.padded_flat_size(local_sizes[name], nz))
            return flat.reshape(-1)
        flat = jnp.reshape(leaf, (-1,))
        return common.pad_axis_to(
            flat, 0, common.padded_flat_size(flat.size, nz))

    stage_specs = common.tree_from_names(
        stacked_params, lambda nm, _: stage_param_spec(full_stage_name(nm)))
    if has_shared:
        # Per-leaf shared specs from the Strategy IR (vocab parallelism
        # shards the tied embedding P(model, None)); replicated P()
        # remains the default; ZeRO-3 leaves store their flat shard.
        p_specs = {"stages": stage_specs,
                   "shared": common.tree_from_names(
                       shared_params,
                       lambda nm, _: shared_param_spec(f"shared/{nm}"))}
    else:
        p_specs = stage_specs
    state_specs = {"step": P(), "params": p_specs, "opt_state": p_specs,
                   "extra": None, "sync_state": {}}

    def opt_specs_tree(opt_state_shapes):
        # ZeRO leaves resolve by path-suffix + u-shape match; otherwise
        # 'leading dim == C means stacked' — which holds only for the
        # stages subtree (every stage leaf is validated to carry it); a
        # shared leaf whose leading dim coincidentally equals C (a
        # size-C ln scale, say) must stay replicated.
        u_by_name = {k: u_shape(k) for k in leaves_by_name}

        def spec_for(path, leaf):
            from autodist_tpu.capture import path_to_name
            name = path_to_name(path)
            var = common.match_var_by_suffix(
                name, u_by_name,
                shape_ok=lambda v: tuple(leaf.shape) == u_by_name[v])
            if var is not None and zero_pol(var) is not None:
                return u_spec(var)
            if var is not None and var in tp_specs:
                # Optimizer state of a tensor-parallel sharded stage
                # variable shards exactly like the parameter.
                return stage_param_spec(var)
            if var is not None and var in shared_specs:
                # Same rule for a vocab-sharded shared variable.
                return shared_param_spec(var)
            in_shared = has_shared and any(
                isinstance(k, jax.tree_util.DictKey) and k.key == "shared"
                for k in path)
            if in_shared:
                return P()
            return P(pipe_axis) if getattr(leaf, "ndim", 0) > 0 \
                and leaf.shape and leaf.shape[0] == C else P()
        return jax.tree_util.tree_map_with_path(spec_for, opt_state_shapes)

    opt_shapes = jax.eval_shape(
        optimizer.init,
        common.tree_from_names(
            full_params,
            lambda nm, l: jax.ShapeDtypeStruct(u_shape(nm),
                                               jnp.result_type(l))))
    o_specs = opt_specs_tree(opt_shapes)
    state_specs["opt_state"] = o_specs

    # Compressor EF state: one row per device (residuals are per-device;
    # stage grads genuinely differ across pipe shards).  Shared plumbing
    # with the replicated-SPMD builder (_spmd.py) so the subtle EF
    # bookkeeping has one implementation.
    from autodist_tpu.parallel._spmd import (apply_compressed,
                                             init_sync_rows,
                                             sync_state_layout,
                                             tile_sync_rows)

    comp_policies = {k: p for k, p in policies.items() if has_data}
    sync_rows = init_sync_rows(comp_policies, lambda nm: local_sizes[nm])
    state_specs["sync_state"], n_total = sync_state_layout(mesh, sync_rows)

    state_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                   state_specs,
                                   is_leaf=lambda x: isinstance(x, P))

    def _pad_shared(name: str, leaf):
        """Storage form of one shared leaf: model-sharded dims zero-
        padded to divisibility (padded rows carry zero params and zero
        grads, so the optimizer keeps them at zero; ``unpad_params``
        slices them back off)."""
        arr = jnp.asarray(leaf)
        target = shared_padded_shape(name, arr.shape)
        for dim, t in enumerate(target):
            arr = common.pad_axis_to(arr, dim, t)
        return arr

    def _store_stage(name, p):
        """Storage form of one stage leaf: interleave-permuted; ZeRO-3
        leaves additionally flatten per chunk into [C, padded_chunk]
        rows (update space — no separate re-layout at optimizer time)."""
        arr = jnp.asarray(p)[perm]
        if zero3(name):
            flat = arr.reshape(C, chunk_elems(name))
            return common.pad_axis_to(flat, 1, padded_chunk(name))
        return arr

    def _store_shared(name, p):
        if zero3(name):
            flat = jnp.asarray(p).reshape(-1)
            return common.pad_axis_to(flat, 0, u_shape(name)[0])
        return _pad_shared(name, p)

    def _permute(params):
        if has_shared:
            return {"stages": common.tree_from_names(
                params["stages"],
                lambda nm, p: _store_stage(f"stages/{nm}", p)),
                "shared": common.tree_from_names(
                    params["shared"],
                    lambda nm, p: _store_shared(f"shared/{nm}", p))}
        return common.tree_from_names(params, _store_stage)

    def _init(params, extra=None):
        stored = _permute(params)
        return {"step": jnp.zeros((), jnp.int32),
                "params": stored,
                "opt_state": optimizer.init(
                    common.tree_from_names(stored, u_view)),
                "extra": None,
                "sync_state": tile_sync_rows(sync_rows, n_total)}

    init_fn = jax.jit(_init, out_shardings=state_shardings)

    any_zero3 = any(zero3(nm) for nm in leaves_by_name)

    def _materialize_zero3(vp):
        """Gather ZeRO-3 stored shards back into logical parameters for
        this forward: shared leaves first (the prologue consumes them
        first), then stage chunks in layer order — one independent
        all-gather per (layer, leaf), chained through barrier sentinels
        (``common.chain_gathers``) so XLA neither merges them into a
        bulk up-front materialization nor reorders them; the next
        layer's gather can prefetch under the current layer's compute.
        Gradients flow back *sharded* through the gathers' custom VJP
        (``common.zero3_gather``), so no full gradient ever joins the
        differentiated state."""
        if not any_zero3:
            return vp
        chained = common.make_chained_gather(zero3_precision)

        def gather(shard, pol, shape):
            return chained(shard, common.axes_entry(pol.zero_axes),
                           zero_count(pol), shape)

        stages = vp["stages"] if has_shared else vp
        shared = vp.get("shared") if has_shared else None
        if shared is not None:
            def one_shared(nm, leaf):
                name = f"shared/{nm}"
                if not zero3(name):
                    return leaf
                return gather(leaf, zero_pol(name),
                              np.shape(leaves_by_name[name]))

            shared = common.tree_from_names(shared, one_shared)
        named = common.flatten_with_names(stages)
        chunks: dict = {}
        for v in range(V):
            for rel, leaf in named:
                name = full_stage_name(rel)
                if not zero3(name):
                    continue
                shape1 = tuple(np.shape(leaves_by_name[name]))[1:]
                chunks.setdefault(rel, []).append(
                    gather(leaf[v], zero_pol(name), shape1))
        if chunks:
            stages = common.tree_from_names(
                stages, lambda rel, leaf: jnp.stack(chunks[rel])
                if rel in chunks else leaf)
        return {"stages": stages, "shared": shared} if has_shared \
            else stages

    def _forward_loss(vp, batch, rng=None, slice_idx=0, slices=1):
        """Masked local loss+metrics of one batch slice (the head loss is
        nonzero on the last device only; per-stage aux losses are local
        to every device.  Gradients reach earlier chunks through the
        transposed ppermute ring; a psum before the grad would double-
        scale cotangents under check_vma=False, so values are broadcast
        after)."""
        vp = _materialize_zero3(vp)
        stages = vp["stages"] if has_shared else vp
        shared = vp.get("shared") if has_shared else None
        # local shard of the [C]-stacked params is [V, ...]; the V == 1
        # public contract of pipeline_apply takes the chunk params bare
        local = stages if V > 1 else jax.tree.map(lambda p: p[0], stages)
        x_in = prologue(shared, batch) if prologue is not None \
            else batch[batch_key]
        if stage_rng:
            # Global sample index of this (data shard, accum slice)'s
            # first row keys per-row stochasticity (dropout) shard- and
            # slice-invariantly: global row = shard*full_shard_rows +
            # slice*slice_rows + i (shards split the batch before
            # accumulation slices it).
            b_local = jax.tree.leaves(x_in)[0].shape[0]
            offset = slice_idx * b_local
            if has_data:
                offset = offset + lax.axis_index(d_axes) * (slices * b_local)
        else:
            offset = 0
        # The loss head runs outside the tick scan, so fetch tags inside
        # it can surface; head fetch values get the same last-stage
        # masking as other head metrics.  The collector also spans
        # pipeline_apply so a tag inside stage_fn — which CANNOT escape
        # the tick scan — is caught as a dead tracer by the merge guard
        # (loud error naming the tag) instead of silently vanishing
        # while the sequential reference loss reports it.
        with _fetches.collecting() as fd:
            res = pipeline_apply(stage_fn, local, x_in,
                                 axis_name=pipe_axis,
                                 num_microbatches=num_microbatches,
                                 virtual_stages=V, stage_aux=stage_aux,
                                 stage_rng=stage_rng, rng=rng,
                                 row_offset=offset)
            outputs, aux = res if stage_aux else (res, None)
            loss, metrics = loss_head(outputs, batch, shared) \
                if has_shared else loss_head(outputs, batch)
        metrics = _fetches.merge_into_metrics(metrics, fd)
        idx = lax.axis_index(pipe_axis)
        masked = jnp.where(idx == n - 1, loss, 0.0)
        metrics = dict(metrics, loss=loss)
        if stage_aux:
            # aux is per-device-local; its grads flow where they arose.
            masked = masked + aux / num_microbatches
            metrics["aux_loss"] = aux / num_microbatches
        return masked, metrics

    def _broadcast_metrics(metrics):
        """Head metrics: last-stage-masked psum over pipe (value
        broadcast); the stage-aux scalar: plain psum (every device
        contributed its own chunks' aux); then mean over the data axis
        when one exists.  The ``aux_loss`` key is special-cased only
        under ``stage_aux`` — a user metric of that name in a non-aux
        pipeline gets the normal last-stage treatment."""
        idx = lax.axis_index(pipe_axis)

        def bc_last(m):
            # lint: allow-raw-collective — pipe-axis metric broadcast
            return lax.psum(
                jnp.where(idx == n - 1, m, jnp.zeros_like(m)), pipe_axis)

        out = {}
        for k, m in metrics.items():
            if stage_aux and k == "aux_loss":
                # lint: allow-raw-collective — scalar pipe-axis metric
                out[k] = lax.psum(m, pipe_axis)
            else:
                out[k] = jax.tree.map(bc_last, m)
        if stage_aux:
            out["loss"] = out["loss"] + out["aux_loss"]
        if has_data:
            out = jax.tree.map(lambda m: lax.pmean(m, d_axes), out)
        return out

    def _local_step(state, batch, rng):
        # The precision scope is opened INSIDE the traced function (jit
        # traces at first call, not at build), so every tp/vocab
        # boundary primitive — including the custom-VJP backwards
        # linearized within value_and_grad below — resolves the policy
        # at trace time.
        from autodist_tpu.parallel.tensor import (kernel_scope,
                                                  precision_scope)
        with precision_scope(precision), kernel_scope(kernel):
            return _local_step_impl(state, batch, rng)

    def _local_step_impl(state, batch, rng):
        vparams = state["params"]  # local [V, ...] chunks

        def micro_grads(mb, rng_, extra_in, idx=0):
            def loss_of(vp):
                masked, metrics = _forward_loss(vp, mb, rng_, idx, accum)
                return masked, (extra_in, metrics)

            return jax.value_and_grad(loss_of, has_aux=True)(vparams)

        if accum == 1:
            (_, (_, metrics)), grads = micro_grads(batch, rng, None)
        else:
            # stage_rng keys draws on global (chunk, row): slices share
            # the step rng so the accumulated step reproduces the single
            # full-batch draw exactly (common.accumulate_microbatches).
            grads, _, metrics = common.accumulate_microbatches(
                micro_grads, vparams, batch, rng, None, accum,
                with_index=True, split_rng=not stage_rng)

        metrics = _broadcast_metrics(metrics)
        new_sync: dict = {}

        def compressed(name, g, comp_name):
            return apply_compressed(name, g, comp_name, d_entry,
                                    state["sync_state"], new_sync)

        def sync_one(name, g):
            pol = policies.get(name)
            if is_stage_var(name):
                # Stage grads: each pipe shard owns its chunks; replicas
                # differ along the data axes only.
                if pol is not None and pol.zero_axes:
                    if zero3(name):
                        # The gathers' custom VJP already reduce-
                        # scattered (sum) the cotangent into storage
                        # form; the data mean just divides.
                        return g / zero_count(pol)
                    return common.reduce_scatter_flat(
                        g, common.axes_entry(pol.zero_axes),
                        zero_count(pol), mean=True)
                if pol is not None and pol.compressor != "none" \
                        and has_data:
                    return compressed(name, g, pol.compressor)
                return lax.pmean(g, d_axes) if has_data else g
            # Shared grads: each device holds a different piece
            # (injection on device 0, the head on device n-1, zeros in
            # between): sum, don't average, over the pipe axis.
            if pol is not None and pol.zero_axes:
                if zero3(name):
                    # vjp reduce-scattered the (pipe x data) sum; /n_d
                    # restores the data mean, keeping the pipe sum.
                    return g / n_d
                # One psum_scatter over (pipe x data) realizes the
                # pipe-sum and the ZeRO shard split; /n_d restores the
                # data mean.  For the model-sharded (vocab-parallel)
                # table the same code runs on the local 1/tp shard —
                # each model coordinate owns its slice's state shards.
                rs = common.reduce_scatter_flat(
                    g, common.axes_entry(pol.zero_axes),
                    zero_count(pol), mean=False)
                return rs / n_d
            # pipe-axis role sum (each device holds a DIFFERENT shared-
            # grad piece); the policied dp grad boundary is the pmean/
            # compressor below:  # lint: allow-raw-collective
            gp = lax.psum(g, pipe_axis)
            if pol is not None and pol.compressor != "none" and has_data:
                return compressed(name, gp, pol.compressor)
            return lax.pmean(gp, d_axes) if has_data else gp

        u_grads = common.tree_from_names(grads, sync_one)

        def u_param(name, p):
            pol = zero_pol(name)
            if pol is None or zero3(name):
                return p  # ZeRO-3 storage IS the update-space shard
            return common.local_flat_shard(
                p, common.axes_entry(pol.zero_axes), zero_count(pol))

        u_params = common.tree_from_names(vparams, u_param)
        updates, new_opt = optimizer.update(u_grads, state["opt_state"],
                                            u_params)
        u_new = optax.apply_updates(u_params, updates)

        from autodist_tpu.capture import path_to_name

        def to_store(path, un, p_local):
            name = path_to_name(path)
            pol = zero_pol(name)
            if pol is None or zero3(name):
                return un  # ZeRO-3: the shard persists; no re-gather
            return common.all_gather_flat(
                un, common.axes_entry(pol.zero_axes), p_local.shape)

        new_params = jax.tree_util.tree_map_with_path(
            to_store, u_new, vparams)
        full_sync = dict(state["sync_state"])
        full_sync.update(new_sync)
        return ({"step": state["step"] + 1, "params": new_params,
                 "opt_state": new_opt, "extra": None,
                 "sync_state": full_sync}, metrics)

    batch_spec = P(d_entry) if has_data else P()

    def _step(state, batch, rng):
        return jax.shard_map(
            _local_step, mesh=mesh,
            in_specs=(state_specs, common.batch_specs(batch, batch_spec), P()),
            out_specs=(state_specs, P()),
            check_vma=False)(state, batch, rng)

    step_fn = jax.jit(_step, donate_argnums=(0,))

    def _local_eval(state, batch, rng):
        # Eval is deterministic: no rng reaches the stages (dropout off).
        from autodist_tpu.parallel.tensor import (kernel_scope,
                                                  precision_scope)
        with precision_scope(precision), kernel_scope(kernel):
            _, metrics = _forward_loss(state["params"], batch, None)
            return _broadcast_metrics(metrics)

    def _eval(state, batch, rng):
        return jax.shard_map(
            _local_eval, mesh=mesh,
            in_specs=(state_specs, common.batch_specs(batch, batch_spec), P()),
            out_specs=P(), check_vma=False)(state, batch, rng)

    eval_fn = jax.jit(_eval)

    shared_orig_shapes = None
    if has_shared and shared_specs:
        shared_orig_shapes = {
            nm: tuple(np.shape(leaf)) for nm, leaf in
            common.flatten_with_names(shared_params)
            if f"shared/{nm}" in shared_specs}
    zero3_shapes = {name: tuple(np.shape(leaf))
                    for name, leaf in leaves_by_name.items()
                    if zero3(name)}

    # --- elastic state-codec manifest (kernel.lowering recipe ops) --------- #
    # One int-listified inverse chunk permutation, shared by every leaf
    # recipe (state_manifest runs per save/reshard over every leaf).
    _inv_chunks = [int(i) for i in np.asarray(perm_inv)]

    def _param_ops(name, shape):
        """Stored→logical ops for one params leaf (``name`` is the full
        variable name; ``shape`` its stored shape)."""
        from autodist_tpu.kernel.lowering import (_op_index0, _op_reshape,
                                                  _op_slice, _op_flat_slice)
        inv = _inv_chunks
        logical = tuple(np.shape(leaves_by_name[name]))
        if is_stage_var(name):
            if zero3(name):
                elems = chunk_elems(name)
                return [_op_slice(shape, (C, elems)),
                        _op_reshape((C, elems), logical),
                        _op_index0(logical, inv)]
            return [_op_index0(shape, inv)]
        if zero3(name):
            size = max(int(np.prod(logical)), 1)
            return [_op_flat_slice(shape, size),
                    _op_reshape((size,), logical)]
        if shape != logical:   # vocab-padded shared storage
            return [_op_slice(shape, logical)]
        return []

    def _opt_ops(name, shape):
        """Stored→logical ops for one optimizer-state leaf matched to
        variable ``name`` (``shape`` = the leaf's stored/u-space
        shape)."""
        from autodist_tpu.kernel.lowering import (_op_index0, _op_reshape,
                                                  _op_slice, _op_flat_slice)
        pol = zero_pol(name)
        if pol is None or zero3(name):
            # Shards-with-the-parameter state (tp/vocab-sharded vars and
            # plain stacked leaves) and ZeRO-3 storage transform exactly
            # like the parameter.
            return _param_ops(name, shape)
        nz = zero_count(pol)
        padded = common.padded_flat_size(local_sizes[name], nz)
        local = local_sizes[name]
        inv = _inv_chunks
        logical = tuple(np.shape(leaves_by_name[name]))
        if is_stage_var(name):
            stacked = tuple(np.shape(leaves_by_name[name]))
            return [_op_reshape(shape, (n, padded)),
                    _op_slice((n, padded), (n, local)),
                    _op_reshape((n, local), stacked),
                    _op_index0(stacked, inv)]
        if name in shared_specs:
            tp_n = shared_shards(name)
            padded_shape = shared_padded_shape(name, logical)
            ops = [_op_reshape(shape, (tp_n, padded)),
                   _op_slice((tp_n, padded), (tp_n, local)),
                   _op_reshape((tp_n, local), padded_shape)]
            if tuple(padded_shape) != logical:
                ops.append(_op_slice(padded_shape, logical))
            return ops
        size = max(int(np.prod(logical)), 1)
        return [_op_flat_slice(shape, size), _op_reshape((size,), logical)]

    def _state_manifest(state):
        from autodist_tpu.kernel.lowering import (_op_index0, _shape_dtype,
                                                  leaf_record)
        u_by_name = {k: u_shape(k) for k in leaves_by_name}
        inv = _inv_chunks
        leaves: dict = {}
        sync: dict = {}
        for path_name, leaf in common.flatten_with_names(state):
            shape, dtype = _shape_dtype(leaf)
            ops: list = []
            if path_name.startswith("params/"):
                ops = _param_ops(path_name[len("params/"):], shape)
            elif path_name.startswith("opt_state/"):
                var = common.match_var_by_suffix(
                    path_name, u_by_name,
                    shape_ok=lambda v: shape == tuple(u_by_name[v]))
                if var is not None:
                    ops = _opt_ops(var, shape)
                elif len(shape) > 0 and shape and shape[0] == C:
                    # the opt_specs_tree stacked-leaf heuristic: a
                    # [C, ...] leaf is pipe-stacked in storage order
                    ops = [_op_index0(shape, inv)]
            elif path_name.startswith("sync_state/"):
                key = path_name[len("sync_state/"):]
                pol = comp_policies.get(key)
                sync[path_name] = {
                    "rows": int(shape[0]), "width": int(shape[1]),
                    "compressor": pol.compressor if pol else "none"}
            leaves[path_name] = leaf_record(shape, dtype, ops)
        return {"family": "pipeline", "leaves": leaves, "sync": sync}
    return _PipelineLowered(mesh=mesh, init_fn=init_fn, step_fn=step_fn,
                            state_specs=state_specs,
                            state_shardings=state_shardings,
                            batch_spec=batch_spec, eval_fn=eval_fn,
                            perm_inv=perm_inv, has_shared=has_shared,
                            shared_orig_shapes=shared_orig_shapes,
                            zero3_shapes=zero3_shapes,
                            zero_degraded=zero_degraded,
                            precision=dict(precision),
                            kernel=dict(kernel),
                            state_manifest_fn=_state_manifest,
                            sync_init=dict(sync_rows))


def lower_pipeline(stage_fn: Callable, stacked_params, loss_head: Callable,
                   optimizer, mesh, *, num_microbatches: int,
                   data_axis: str = const.DATA_AXIS,
                   pipe_axis: str = const.PIPE_AXIS,
                   virtual_stages: int = 1):
    """Build a complete pipelined SPMD train step.

    ``stacked_params``: pytree whose leaves have a leading logical-chunk
    dimension ``C == mesh.shape[pipe_axis] * virtual_stages``.
    ``loss_head(outputs, batch) -> (loss, metrics)`` runs on the last
    chunk's outputs.

    Returns ``(init_fn, step_fn, state_shardings)`` with the same state
    dict layout as the other lowerings.
    """
    built = _build_pipeline(stage_fn, stacked_params, loss_head, optimizer,
                            mesh, num_microbatches=num_microbatches,
                            data_axis=data_axis, pipe_axis=pipe_axis,
                            virtual_stages=virtual_stages)
    return built.init_fn, built.step_fn, built.state_shardings


def lower_pipeline_ir(trainable, strategy, mesh):
    """Strategy-IR entry: lower a ``lowering == "pipeline"`` strategy
    (built by :class:`~autodist_tpu.strategy.parallel_builders.Pipeline`)
    for a :class:`~autodist_tpu.capture.PipelineTrainable`."""
    from autodist_tpu.capture import PipelineTrainable

    if not isinstance(trainable, PipelineTrainable):
        raise TypeError(
            "the pipeline strategy lowers stage-structured trainables; "
            "declare one with PipelineTrainable(stage_fn, stacked_params, "
            "loss_head, optimizer, num_stages=S)")
    cfg = strategy.graph_config
    V = max(int(cfg.parallel.get("virtual_stages", 1)), 1)
    S = mesh.shape.get(const.PIPE_AXIS)
    if S is None or S * V != trainable.num_stages:
        raise ValueError(
            f"trainable declares {trainable.num_stages} stages; mesh pipe "
            f"axis has {S} devices x {V} virtual stages")
    stacked = (trainable.params["stages"] if trainable.has_shared
               else trainable.params)

    # Tensor parallelism inside stages: a Pipeline(tensor_parallel=t)
    # strategy records the model-axis dims in each stage variable's
    # partitioner spec ([pipe, ..., model, ...]); resolve them back into
    # the lowering's per-variable tp_specs (the spec minus its leading
    # pipe entry).
    tp_cfg = max(int(cfg.parallel.get("tensor_parallel", 1)), 1)
    tp_mesh = mesh.shape.get(const.MODEL_AXIS, 1)
    if tp_cfg > 1 and tp_mesh != tp_cfg:
        raise ValueError(
            f"strategy declares tensor_parallel={tp_cfg}; mesh "
            f"{const.MODEL_AXIS!r} axis has {tp_mesh} devices")
    tp_specs = {}
    shared_specs = {}
    for nc in strategy.node_configs:
        part = nc.partitioner
        is_stage = not trainable.has_shared \
            or nc.var_name.startswith("stages/")
        if is_stage and part is not None and part.spec \
                and const.MODEL_AXIS in part.spec[1:]:
            tp_specs[nc.var_name] = tuple(part.spec[1:])
        elif not is_stage and part is not None and part.spec \
                and const.MODEL_AXIS in part.spec:
            # Vocab parallelism: a *shared* variable (the tied
            # embedding/unembedding) sharded over the model axis.
            shared_specs[nc.var_name] = tuple(part.spec)
    if (tp_specs or shared_specs) and tp_mesh == 1:
        raise ValueError(
            "strategy shards variables over the model axis but the "
            f"mesh has none: {dict(mesh.shape)}")
    # Latency-hiding collectives: the graph-level knob drives the stage_fn
    # (one mode for the whole stage body); the per-variable partitioner
    # field is the IR record the cost model prices from.  A hand-edited
    # strategy that sets per-variable overlap without the graph knob gets
    # the mode from the variables (all set modes must agree — the stage
    # body is one function).
    overlap = cfg.parallel.get("comm_overlap") or None
    var_overlaps = {nc.partitioner.comm_overlap
                    for nc in strategy.node_configs
                    if nc.partitioner is not None
                    and getattr(nc.partitioner, "comm_overlap", None)}
    if overlap is None and var_overlaps:
        if len(var_overlaps) > 1:
            raise ValueError(
                "per-variable comm_overlap modes disagree "
                f"({sorted(var_overlaps)}); the stage body lowers with one "
                "mode — set graph_config.parallel['comm_overlap']")
        overlap = var_overlaps.pop()

    # Per-collective precision: the graph-level policy is canonical
    # (normalize rejects hand-edited unknown boundaries/values with the
    # named UnknownPrecisionError); per-variable partitioner fields are
    # the cost model's record and may fill in a hand-edited strategy's
    # missing tp_psum slot — the stage body lowers with ONE precision,
    # so disagreeing per-variable values are rejected like comm_overlap.
    from autodist_tpu.strategy.ir import normalize_precision
    precision = dict(normalize_precision(cfg.precision))

    def _var_precisions(stage_vars: bool) -> set:
        """Per-variable partitioner precision records, split by slot:
        tp-sharded STAGE variables carry the tp_psum slot, the
        vocab-sharded SHARED table the vocab_stats slot — adopting one
        into the other would silently narrow boundaries the policy
        left at fp32."""
        out = set()
        for nc in strategy.node_configs:
            part = nc.partitioner
            if part is None or getattr(part, "precision", None) \
                    in (None, "fp32"):
                continue
            is_stage = not trainable.has_shared \
                or nc.var_name.startswith("stages/")
            if is_stage == stage_vars:
                out.add(part.precision)
        return out

    for slot, vps in (("tp_psum", _var_precisions(True)),
                      ("vocab_stats", _var_precisions(False))):
        if slot not in precision and vps:
            if len(vps) > 1:
                raise ValueError(
                    f"per-variable collective precisions for the {slot} "
                    f"boundary disagree ({sorted(vps)}); the stage body "
                    "lowers with one policy — set graph_config.precision")
            precision[slot] = vps.pop()
    precision = normalize_precision(precision)

    # Fused-kernel tier (Strategy IR kernel slot, PR 13).  Each training
    # kernel needs its enabling knob — electing it without one would be
    # a silent no-op the user believes is active (mirrors the
    # comm_overlap/precision reject-don't-drift discipline; plan lint
    # ADT090 reports the same contradictions on hand-edited JSON):
    # quant_ring replaces the monolithic int8 tp_psum (so it needs the
    # int8 slot and the blocking form — a decomposed boundary never
    # takes the psum path), collective_matmul fuses the ppermute ring
    # (so it needs comm_overlap == "matmul").  flash_decode is the
    # serving engine's kernel: recorded here, applied there.
    from autodist_tpu.strategy.ir import normalize_kernel
    kernel = normalize_kernel(cfg.kernel)
    if "quant_ring" in kernel:
        if precision.get("tp_psum") != "int8":
            raise ValueError(
                "kernel 'quant_ring' fuses q/dq into the int8 tp_psum "
                "ring; set collective_precision's tp_psum slot to "
                "'int8' (or drop the kernel election)")
        if overlap is not None:
            raise ValueError(
                "kernel 'quant_ring' replaces the monolithic tp_psum; "
                f"comm_overlap={overlap!r} routes the boundary through "
                "the decomposed rs+ag/matmul forms instead — pick one")
    if "collective_matmul" in kernel and overlap != "matmul":
        raise ValueError(
            "kernel 'collective_matmul' fuses the chunked ppermute "
            "ring; it requires comm_overlap='matmul' "
            f"(got {overlap!r})")

    # Per-variable synchronizer configs (PS -> ZeRO stages, compressors)
    # compose with the pipeline: stage variables zero/compress over the
    # data axes (they are pipe-sharded already), shared variables zero
    # over pipe x data jointly.  tp-sharded stage variables degrade
    # (their state shards with the parameter), the reason recorded on
    # the lowered plan; the model-sharded (vocab-parallel) table keeps
    # its ZeRO request — _build_pipeline shards its optimizer state
    # additionally over pipe x data (state at 1/(tp·pipe·data)).
    from autodist_tpu.parallel._spmd import policies_from_node_configs
    from autodist_tpu.utils import logging

    d_axes = tuple(a for a in (const.DCN_AXIS, const.DATA_AXIS)
                   if a in mesh.shape)
    shared_axes = (const.PIPE_AXIS, *d_axes)

    def axes_for(name):
        if not trainable.has_shared or name.startswith("stages/"):
            return d_axes
        return shared_axes

    degraded: dict = {}
    policies = policies_from_node_configs(
        strategy, mesh, replicated_axes=shared_axes, axes_for=axes_for,
        sharded_vars=set(tp_specs), degraded=degraded)
    # The grad slot resolves onto the compressor machinery (the one
    # boundary whose reduction semantics need error-feedback state): a
    # bf16/int8 grad policy elects the EF compressor on every AllReduce-
    # synced variable that doesn't already carry an explicit compressor
    # or a ZeRO policy — so a hand-edited strategy JSON with only
    # graph_config.precision narrows its gradient sync too.
    grad_prec = precision.get("grad", "fp32")
    if grad_prec != "fp32":
        from autodist_tpu.parallel._spmd import VarPolicy
        from autodist_tpu.strategy.ir import AllReduceSynchronizer
        comp = {"bf16": "bf16_ef", "int8": "int8_ef"}[grad_prec]
        for nc in strategy.node_configs:
            if (isinstance(nc.synchronizer, AllReduceSynchronizer)
                    and (nc.synchronizer.compressor or "none") == "none"
                    and nc.var_name not in policies):
                policies[nc.var_name] = VarPolicy(compressor=comp)
    # Per-boundary precision gauges: a lowering that silently dropped
    # the policy would miss these, and `tools/telemetry_report.py
    # --check` schema-gates them against the run's annotation.
    from autodist_tpu.kernel.pallas import OBSERVED_KERNELS
    from autodist_tpu.parallel._spmd import (emit_kernel_gauges,
                                             emit_precision_gauges)
    emit_precision_gauges(precision)
    # kernel/<name>_elected gauges for the kernels THIS lowering honors
    # (flash_decode's gauge is the serving engine's to emit) — the
    # schema gate `tools/telemetry_report.py --check` matches them
    # against the run's declared kernel annotation.
    emit_kernel_gauges({k: True for k in kernel
                        if k not in ("flash_decode", *OBSERVED_KERNELS)})
    if not d_axes:
        dropped = sorted(nm for nm, p in policies.items()
                         if p.compressor != "none")
        if dropped:
            logging.warning(
                "pipe-only mesh: compressor configs on %d variable(s) "
                "(e.g. %s) have no data axis to compress over; syncing "
                "uncompressed", len(dropped), dropped[0])
    return _build_pipeline(
        trainable.stage_fn, stacked, trainable.loss_head,
        trainable.optimizer, mesh,
        num_microbatches=int(cfg.parallel.get("num_microbatches", 1)),
        accum=max(cfg.accum_steps, 1), batch_key=trainable.batch_key,
        shared_params=(trainable.params["shared"] if trainable.has_shared
                       else None),
        prologue=trainable.prologue,
        virtual_stages=V, stage_aux=trainable.stage_aux,
        policies=policies, stage_rng=trainable.stage_rng,
        remat=bool(cfg.parallel.get("remat", False)),
        tp_specs=tp_specs, comm_overlap=overlap,
        shared_specs=shared_specs, zero_degraded=degraded,
        precision=precision, kernel=kernel)
