"""GSPMD lowering path: jit + NamedSharding, XLA inserts collectives.

The second backend beside :mod:`autodist_tpu.kernel.lowering`'s explicit
shard_map collectives.  Where the reference's synchronizers hand-rewired
the graph per variable, GSPMD (PAPERS.md 2105.04663) lets XLA derive the
communication from sharding annotations — the idiomatic TPU path for
tensor/model parallelism and mixed-axis layouts the reference never had
(``docs/design/architecture.rst:49-51`` lists op-level model parallelism
as unimplemented future work).

Chosen when ``Strategy.graph_config.lowering == "gspmd"`` (e.g. the
``Sharded``/``TensorParallel`` builders).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from autodist_tpu import const
from autodist_tpu.capture import Trainable, path_to_name
from autodist_tpu.kernel import common
from autodist_tpu.kernel import lowering as lowering_mod
from autodist_tpu.strategy.ir import Strategy
from autodist_tpu.utils import logging


def _node_spec(node, ndim: int) -> P:
    """PartitionSpec for one variable from its node config."""
    part = node.partitioner if node else None
    if part is None:
        return P()
    if part.spec is not None:
        if len(part.spec) != ndim:
            raise ValueError(
                f"{node.var_name}: sharding spec {part.spec} has "
                f"{len(part.spec)} entries for a rank-{ndim} tensor")
        return P(*[tuple(a) if isinstance(a, list) else a
                   for a in part.spec])
    if part.num_shards > 1 and ndim > 0:
        spec = [None] * ndim
        spec[max(part.split_axis, 0)] = part.mesh_axis
        return P(*spec)
    return P()


class GspmdLowered(lowering_mod.SimpleLowered):
    """Same contract as :class:`autodist_tpu.kernel.lowering.Lowered`
    (GSPMD shards unevenly without padding, so ``unpad_params`` is the
    identity)."""


def lower_gspmd(trainable: Trainable, strategy: Strategy, mesh) -> GspmdLowered:
    opt = trainable.optimizer
    nodes = {n.var_name: n for n in strategy.node_configs}

    # The gspmd path delegates communication to XLA.  PS(sync=True) node
    # configs ARE honored — as GSPMD-style ZeRO-1: the variable's
    # optimizer state shards its leading dim over the data axes (XLA
    # derives the reduce-scatter into the update and the all-gather out
    # of it).  Compressors have no GSPMD realization (custom wire
    # arithmetic needs explicit collectives): warn, don't silently
    # reprice — the cost model skips compressor factors for gspmd
    # strategies (`simulator/cost_model.py`).
    from autodist_tpu.strategy.ir import PSSynchronizer

    for n in strategy.node_configs:
        if isinstance(n.synchronizer, PSSynchronizer) \
                and not n.synchronizer.sync:
            raise NotImplementedError(
                f"PS(sync=False) on {n.var_name}: asynchronous "
                "training does not lower to one SPMD program; build "
                "through AutoDist (AsyncPSRunner) or use sync=True")
    ps_vars = {n.var_name for n in strategy.node_configs
               if isinstance(n.synchronizer, PSSynchronizer)}
    ignored = sorted({
        n.var_name for n in strategy.node_configs
        if getattr(n.synchronizer, "compressor", "none")
        not in ("", "none")})
    if ignored:
        logging.warning(
            "gspmd lowering ignores compressor config on %d variable(s), "
            "e.g. %s — use the collective lowering for compressed "
            "gradients", len(ignored), ignored[0])
    # ZeRO stages beyond 1 have no gspmd realization here (stage 3's
    # sharded-parameter layout under gspmd is the FSDPSharded builder;
    # stages 2/3 with explicit per-layer gathers are the pipeline
    # lowering's knob).  The Sharded builder rejects stage > 1 at build
    # time; a hand-edited or deserialized strategy reaching this
    # lowering must not silently train stage-1 semantics — warn, like
    # the compressor path above.
    staged = sorted({
        n.var_name for n in strategy.node_configs
        if isinstance(n.synchronizer, PSSynchronizer)
        and int(getattr(n.synchronizer, "zero_stage", 1) or 1) > 1})
    if staged:
        logging.warning(
            "gspmd lowering realizes PS as ZeRO-1 state sharding only; "
            "zero_stage>1 on %d variable(s), e.g. %s, lowers with "
            "stage-1 semantics (params/grads stay unsharded) — use "
            "FSDPSharded for the GSPMD sharded-parameter layout or the "
            "pipeline lowering's zero_stage", len(staged), staged[0])

    def axis_size(axis) -> int:
        axes = axis if isinstance(axis, tuple) else (axis,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        return size

    def param_spec(name, leaf):
        spec = _node_spec(nodes.get(name), getattr(leaf, "ndim", 0))
        # jit out_shardings require even divisibility; drop assignments
        # that don't divide (≙ compiler overriding strategy hints).
        shape = getattr(leaf, "shape", ())
        fixed = []
        for d, axis in enumerate(spec):
            if axis is not None and shape[d] % axis_size(axis):
                logging.warning(
                    "%s: dim %d (size %d) not divisible by mesh axis %r "
                    "(size %d); replicating that dim", name, d, shape[d],
                    axis, axis_size(axis))
                axis = None
            fixed.append(axis)
        return P(*fixed) if fixed else P()

    p_specs = common.tree_from_names(trainable.params, param_spec)

    # Optimizer-state specs: path-suffix matching against param specs (same
    # scheme as the collective path, lowering.py _opt_state_specs).
    p_spec_list = list(zip([v.name for v in trainable.var_infos()],
                           jax.tree.leaves(p_specs,
                                           is_leaf=lambda x: isinstance(x, P))))
    by_name = dict(p_spec_list)
    shapes_by_name = {v.name: v.shape for v in trainable.var_infos()}

    opt_shapes = jax.eval_shape(
        opt.init,
        jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(
                tuple(np.shape(l)), jnp.result_type(l)),
            trainable.params))

    from autodist_tpu.kernel.lowering import replica_axes
    repl = replica_axes(mesh)
    repl_entry = common.axes_entry(repl)
    n_repl = int(np.prod([mesh.shape[a] for a in repl]))

    def opt_spec_for(path, leaf):
        from autodist_tpu.kernel import common
        name = path_to_name(path)
        var = common.match_var_by_suffix(
            name, by_name,
            shape_ok=lambda v: tuple(leaf.shape)
            == tuple(shapes_by_name[v]))
        if var is None:
            return P()
        spec = by_name[var]
        if var in ps_vars and leaf.ndim > 0:
            # GSPMD ZeRO-1: additionally shard the state over the data
            # axes — extending dim 0 (joining a model axis already there
            # when divisible), else the first free divisible dim.
            entries = list(spec) + [None] * (leaf.ndim - len(list(spec)))
            e0 = entries[0]
            axes0 = tuple(e0) if isinstance(e0, tuple) else (
                (e0,) if e0 else ())
            if any(a in repl for a in axes0):
                # dim 0 already shards over a data axis (FSDP-style
                # rule): the inherited spec IS the ZeRO layout.
                return P(*entries)
            shard0 = int(np.prod([mesh.shape[a] for a in axes0])) \
                if axes0 else 1
            if leaf.shape[0] % (shard0 * n_repl) == 0:
                entries[0] = (*axes0, *repl) if axes0 else repl_entry
                return P(*entries)
            for d in range(1, leaf.ndim):
                if entries[d] is None and leaf.shape[d] % n_repl == 0:
                    entries[d] = repl_entry
                    return P(*entries)
            logging.warning(
                "%s: PS (ZeRO-1) requested but no dim of %s (spec %s) "
                "can shard over the %d-way data axes; state stays %s",
                var, tuple(leaf.shape), spec, n_repl, spec)
        return spec

    o_specs = jax.tree_util.tree_map_with_path(opt_spec_for, opt_shapes)
    extra_specs = jax.tree.map(lambda _: P(), trainable.extra)
    state_specs = {"step": P(), "params": p_specs, "opt_state": o_specs,
                   "extra": extra_specs, "sync_state": {}}
    state_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), state_specs,
        is_leaf=lambda x: isinstance(x, P))
    from autodist_tpu.kernel.lowering import replica_axes
    batch_spec = P(common.axes_entry(replica_axes(mesh)))


    def _init(params, extra):
        return {"step": jnp.zeros((), jnp.int32),
                "params": jax.tree.map(jnp.asarray, params),
                "opt_state": opt.init(jax.tree.map(jnp.asarray, params)),
                "extra": extra, "sync_state": {}}

    init_fn = jax.jit(_init, out_shardings=state_shardings)

    def constrain(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, s)),
            tree, specs, is_leaf=lambda x: isinstance(x, P))

    accum = max(getattr(strategy.graph_config, "accum_steps", 1), 1)

    # The kernel slot's word on the kernels a model's call sites elect
    # (models.transformer.attend).  On more than one device they keep
    # the composed path here whatever the word: XLA cannot partition a
    # bare pallas_call, and the call site sees that it is not inside a
    # shard_map.
    from autodist_tpu.parallel.tensor import kernel_scope
    kernel = strategy.graph_config.kernel

    @kernel_scope(kernel)
    def _step(state, batch, rng):
        def micro(mb, rng_, extra_in):
            def loss_of(params):
                loss, new_extra, metrics = trainable.loss(
                    params, extra_in, mb, rng_)
                return loss, (new_extra, metrics)

            return jax.value_and_grad(loss_of, has_aux=True)(
                state["params"])

        if accum == 1:
            (loss, (new_extra, metrics)), grads = micro(
                batch, rng, state["extra"])
        else:
            grads, new_extra, metrics = common.accumulate_microbatches(
                micro, state["params"], batch, rng, state["extra"], accum)
        grads = constrain(grads, p_specs)
        updates, new_opt = opt.update(grads, state["opt_state"],
                                      state["params"])
        new_params = optax.apply_updates(state["params"], updates)
        return ({"step": state["step"] + 1,
                 "params": new_params,
                 "opt_state": new_opt,
                 "extra": new_extra,
                 "sync_state": {}},
                dict(metrics))

    def _constrain_batch(batch):
        # Per-leaf feed rule (scalars duplicate) resolved at trace time —
        # a fixed in_shardings entry cannot express mixed batch trees.
        from autodist_tpu.kernel import common
        return jax.tree.map(
            jax.lax.with_sharding_constraint, batch,
            common.batch_shardings(batch, mesh, batch_spec))

    def _step_outer(state, batch, rng):
        return _step(state, _constrain_batch(batch), rng)

    step_fn = jax.jit(
        _step_outer, donate_argnums=(0,),
        in_shardings=(state_shardings, None, None),
        out_shardings=(state_shardings, None))

    @kernel_scope(kernel)
    def _eval(state, batch, rng):
        _, _, metrics = trainable.eval_loss(state["params"], state["extra"],
                                            _constrain_batch(batch), rng)
        return dict(metrics)

    eval_fn = jax.jit(
        _eval, in_shardings=(state_shardings, None, None))

    return GspmdLowered(mesh=mesh, init_fn=init_fn, step_fn=step_fn,
                        state_specs=state_specs,
                        state_shardings=state_shardings,
                        batch_spec=batch_spec, eval_fn=eval_fn)
