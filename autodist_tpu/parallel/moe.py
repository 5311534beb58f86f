"""Mixture-of-Experts with expert parallelism over the ``expert`` mesh axis.

Beyond reference parity (SURVEY.md §2.10 lists expert parallelism as
absent): top-2 gated MoE FFN where experts are sharded across devices and
tokens travel by ``lax.all_to_all`` — the TPU-idiomatic dispatch
(einsum-based one-hot dispatch/combine, capacity-bounded static shapes;
the Mesh-TensorFlow / GShard formulation) — for the training lowering
(:func:`top2_gating`, :func:`expert_parallel_ffn`).

The served routed layer (:func:`route_top_k`, :func:`routed_experts`)
has no capacity and drops nothing: the router scores every expert, a
device is told which experts it holds and computes their part of the
result over the (row, expert) pairs that land on them, sorted by expert
into one grouped matmul whose bytes grow with the experts hit.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from autodist_tpu import const
from autodist_tpu.kernel import quantize as qz


def top2_gating(gate_logits, capacity: int):
    """GShard-style top-2 gating with capacity.

    gate_logits: [G, E] (per local token, all experts).
    Returns (dispatch [G, E, C] bool, combine [G, E, C] float, aux_loss).
    """
    G, E = gate_logits.shape
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)

    top1 = probs.argmax(-1)                             # [G]
    mask1 = jax.nn.one_hot(top1, E, dtype=jnp.float32)
    probs_wo1 = probs * (1.0 - mask1)
    top2 = probs_wo1.argmax(-1)
    mask2 = jax.nn.one_hot(top2, E, dtype=jnp.float32)

    # load-balancing auxiliary loss (GShard eq. (4))
    density = mask1.mean(0)                             # fraction routed
    density_proxy = probs.mean(0)
    aux_loss = (density * density_proxy).sum() * E

    # positions within each expert's capacity, first-come order
    pos1 = (jnp.cumsum(mask1, axis=0) - 1.0) * mask1    # [G, E]
    mask1 = mask1 * (pos1 < capacity)
    pos2 = (jnp.cumsum(mask2, axis=0) - 1.0 + mask1.sum(0)[None]) * mask2
    mask2 = mask2 * (pos2 < capacity)

    w1 = (probs * mask1).sum(-1)                        # [G]
    w2 = (probs * mask2).sum(-1)
    denom = jnp.maximum(w1 + w2, 1e-9)
    w1, w2 = w1 / denom, w2 / denom

    def onehot_pos(mask, pos, w):
        # [G, E, C]: token g → (expert e, slot c) with weight w
        slot = jax.nn.one_hot((pos * mask).sum(-1).astype(jnp.int32),
                              capacity, dtype=jnp.float32)  # [G, C]
        return mask[:, :, None] * slot[:, None, :] * w[:, None, None]

    combine = onehot_pos(mask1, pos1, w1) + onehot_pos(mask2, pos2, w2)
    dispatch = combine > 0.0
    return dispatch, combine, aux_loss


def _route(x, router_w, top_k: int, renormalise: bool = True, *,
           scores: str = "softmax", groups: int = 1, groups_kept: int = 1,
           scale: float = 1.0, correction=None):
    """:func:`route_top_k`, and the groups each row kept (``[R, groups]``
    bool; ``None`` where the router has one group)."""
    from autodist_tpu.telemetry import scope

    with scope("moe_route"):
        logits = jnp.matmul(x.astype(jnp.float32),
                            router_w.astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        if scores == "softmax" and groups == 1 and correction is None:
            weights, experts = lax.top_k(jax.nn.softmax(logits, axis=-1),
                                         top_k)
            kept = None
        else:
            s = jax.nn.softmax(logits, axis=-1) if scores == "softmax" \
                else jax.nn.sigmoid(logits)
            # what chooses: the scores with the correction; what weighs:
            # the scores without it
            choose, kept = s, None
            if correction is not None:
                choose = s + correction.astype(jnp.float32)
            if groups > 1:
                R, E = s.shape
                grouped = choose.reshape(R, groups, E // groups)
                # a group's two largest, summed: the largest, and the
                # largest of the rest (a sort of every group, which is
                # what top_k is on the TPU, took 3.5% of a decode step)
                at = jnp.argmax(grouped, -1, keepdims=True)
                rest = jnp.where(jnp.arange(E // groups) == at, -jnp.inf,
                                 grouped)
                _, which = lax.top_k(grouped.max(-1) + rest.max(-1),
                                     groups_kept)
                kept = (which[..., None] == jnp.arange(groups)).any(-2)
                choose = jnp.where(jnp.repeat(kept, E // groups, axis=-1),
                                   choose, -jnp.inf)
            _, experts = lax.top_k(choose, top_k)
            weights = jnp.take_along_axis(s, experts, axis=-1)
        if renormalise:
            weights = weights / weights.sum(-1, keepdims=True)
        if scale != 1.0:
            weights = weights * scale
        return experts.astype(jnp.int32), weights, kept


def route_top_k(x, router_w, top_k: int, renormalise: bool = True, **rule):
    """``(experts [R, top_k] int32, weights [R, top_k] float32)`` of
    rows ``x`` ``[R, H]``: softmax over ALL the router's outputs in
    float32, the ``top_k`` largest — renormalised to sum 1 over the
    chosen experts wherever they live, or (``renormalise`` false) as the
    softmax left them, summing to less than 1.

    ``rule`` — a router of another kind.  ``scores="sigmoid"``: each
    output's sigmoid in place of the softmax.  ``correction`` ``[E]``: a
    term an expert added to the scores that choose, never to a weight.
    ``groups``, ``groups_kept``: the experts lie in ``groups`` equal
    groups in order, a group's score is the sum of its two best
    (corrected) scores, a row keeps the ``groups_kept`` best groups and
    its ``top_k`` are the largest inside them.  ``scale``: what the
    (renormalised) weights are multiplied by."""
    return _route(x, router_w, top_k, renormalise, **rule)[:2]


def _pairs_bound(pairs: int, held: int, experts: int) -> int:
    """How many sorted pairs the routed layer works through when the
    pairs that landed on held experts fit: twice what even routing sends
    here, in ``64 x odd`` rows — the TPU's grouped matmul tiles its rows
    by the largest of 512, 256, 128, 64 that divides their count, and a
    group of a few rows costs a whole tile (at 512 a tile the 64 experts
    a 1,024-row prompt touches cost 0.58 ms a matmul, 18% of a prefill:
    PERF.md section 6, PR 32).  ``pairs`` itself where that is no
    saving."""
    expected = -(-pairs * held // experts)
    bound = 64 * (-(-2 * expected // 64) | 1)
    return bound if bound <= pairs // 2 else pairs


def ragged_products(x, expert_wi, expert_wo, sizes):
    """``wo_e(silu(gate_e x) * up_e x)`` of sorted rows ``x`` ``[P, H]``,
    expert ``e``'s the ``sizes[e]`` after its predecessors', through two
    :func:`jax.lax.ragged_dot`: ``[P, H]`` float32, whatever the matmul
    leaves in the rows past the groups."""
    h = lax.ragged_dot(x, expert_wi.astype(x.dtype), sizes,
                       preferred_element_type=jnp.float32)
    gate, up = jnp.split(h, 2, axis=-1)
    h = (jax.nn.silu(gate) * up).astype(x.dtype)
    return lax.ragged_dot(h, expert_wo.astype(x.dtype), sizes,
                          preferred_element_type=jnp.float32)


def routed_experts(x, router_w, expert_wi, expert_wo, *, top_k: int,
                   first_expert: int = 0, valid=None,
                   renormalise: bool = True, kernel=None, **rule):
    """The held experts' part of a routed FFN, without capacity.

    ``x``: ``[R, H]`` rows; ``router_w``: ``[H, E]`` over all ``E``
    experts; ``expert_wi``: ``[E_held, H, 2 * M]`` (gate, then up) and
    ``expert_wo``: ``[E_held, M, H]`` — experts ``first_expert ..
    first_expert + E_held``, the ones this device holds.  Returns ``(y
    [R, H] float32, stats)``: ``y = sum_e w_e wo_e(silu(gate_e x) *
    up_e x)`` over the row's chosen experts that are held (a row none of
    whose choices is held gets 0: the rest of its sum is another
    device's), and ``stats`` int32 ``[rows_held, experts_hit]`` — the
    pairs that landed here and the held experts with at least one row.

    The ``R * top_k`` (row, expert) pairs are sorted by held expert, the
    ones that are not held last, and the experts run over their groups:
    a grouped matmul that visits the groups that have rows, so an expert
    nobody chose is not read.  Which one follows what the call observes
    (:func:`~autodist_tpu.kernel.pallas.grouped_matmul
    .grouped_matmul_elected`): a decode step's few pairs of bf16 rows on
    a TPU go through this repo's kernel, gate/up, SiLU and down in one
    call that reads each hit expert once; anything else — a prefill's
    thousands of pairs, another type or width, the CPU — through two
    :func:`jax.lax.ragged_dot` (:func:`ragged_products`).  ``kernel`` is
    the kernel slot's word on ``grouped_matmul``: ``True`` takes the
    kernel wherever it can run, ``False`` forbids it.  Each row's terms
    come back through one matmul with the pairs' weights laid out by row
    (a scatter-add is a serial loop on the TPU).  Nothing is dropped:
    the kernel works from the groups' sizes and takes every pair; the
    composed products work through :func:`_pairs_bound` sorted pairs
    where the held pairs fit, and through all of them where they do not
    (``lax.cond``: skewed routing costs time, never a row).  ``valid``
    (``[R]`` bool): rows that are padding or belong to no request choose
    nothing.  ``renormalise`` and ``rule`` are :func:`route_top_k`'s; a
    router that keeps groups adds a third number to ``stats``,
    ``groups_hit``: the rows that kept a group some held expert lies
    in."""
    from autodist_tpu import telemetry
    from autodist_tpu.kernel.pallas.grouped_matmul import (
        grouped_matmul, grouped_matmul_elected)

    R, H = x.shape
    E_held = expert_wi.shape[0]
    experts, weights, kept = _route(x, router_w, top_k, renormalise, **rule)
    local = experts - first_expert                       # [R, k]
    held = (local >= 0) & (local < E_held)
    if valid is not None:
        held = held & valid[:, None]
    key = jnp.where(held, local, E_held).reshape(-1)     # not held: last
    order = jnp.argsort(key, stable=True)
    sizes = (key[:, None] == jnp.arange(E_held)[None, :]).sum(
        0, dtype=jnp.int32)                              # [E_held]
    weight = jnp.where(held, weights, 0.0).reshape(-1)

    def experts_over(pairs: int, products=ragged_products):
        """The first ``pairs`` sorted pairs through the held experts."""
        first = order[:pairs]
        rows = first // top_k                            # pair -> its row
        y = products(x[rows], expert_wi, expert_wo, sizes)
        # rows past the groups are nobody's, whatever the matmul left
        w = weight[first]
        y = jnp.where((w > 0)[:, None], y * w[:, None], 0.0)
        by_row = (rows[None, :] == jnp.arange(R)[:, None]) \
            .astype(jnp.float32)                         # [R, pairs]
        return jnp.matmul(by_row, y, precision=lax.Precision.HIGH)

    total = R * top_k
    bound = _pairs_bound(total, E_held, router_w.shape[-1])
    n_held = sizes.sum()
    fused = expert_wi.dtype == expert_wo.dtype == x.dtype \
        and grouped_matmul_elected(kernel, total, H, expert_wo.shape[1],
                                   x.dtype)
    if fused:
        if isinstance(x, jax.core.Tracer):  # count programs, not init
            telemetry.counter("kernel/grouped_matmul_calls").inc()
        out = experts_over(total, grouped_matmul)
    elif bound < total:
        out = lax.cond(n_held <= bound, lambda: experts_over(bound),
                       lambda: experts_over(total))
    else:
        out = experts_over(total)
    stats = [n_held, (sizes > 0).sum(dtype=jnp.int32)]
    if kept is not None:
        size = router_w.shape[-1] // kept.shape[-1]
        here = kept[:, first_expert // size:
                    (first_expert + E_held - 1) // size + 1].any(-1)
        if valid is not None:
            here = here & valid
        stats.append(here.sum(dtype=jnp.int32))
    return out, jnp.stack(stats)


def _qa2a_impl(x, axis_name, split_axis, concat_axis, precision):
    """One narrowed tiled all_to_all: the convert *sandwich* around a
    single monolithic collective (vs. the fused ring that moves q/dq
    inside the hops).  ``bf16``: cast → a2a → cast.  ``int8``: quantize
    the whole local payload against ONE abs-max scale, ship true ``s8``,
    all_gather the n scales alongside and dequantize per source block of
    the concat dim."""
    n = lax.axis_size(axis_name)
    if precision == "bf16":
        y = lax.all_to_all(x.astype(jnp.bfloat16), axis_name,
                           split_axis=split_axis, concat_axis=concat_axis,
                           tiled=True)
        return y.astype(x.dtype)
    if precision != "int8":
        raise ValueError(f"moe_a2a precision {precision!r}; expected one "
                         f"of {list(qz.PRECISIONS)}")
    xf = x.astype(jnp.float32)
    scale = qz.abs_max_scale(xf)
    q = qz.quantize_levels(xf, scale).astype(jnp.int8)
    q = lax.all_to_all(q, axis_name, split_axis=split_axis,
                       concat_axis=concat_axis, tiled=True)
    # lint: allow-raw-collective — fp32 scale side-channel OF the policied s8 a2a
    scales = lax.all_gather(scale, axis_name)            # [n], source order
    # The output concat dim is n source-ordered blocks of the input's
    # concat length; each block dequantizes with its source's scale.
    c = x.shape[concat_axis]
    moved = jnp.moveaxis(q.astype(jnp.float32), concat_axis, 0)
    rest = moved.shape[1:]
    blocks = moved.reshape((n, c) + rest)
    blocks = blocks * scales.reshape((n,) + (1,) * (blocks.ndim - 1))
    out = jnp.moveaxis(blocks.reshape((n * c,) + rest), 0, concat_axis)
    return out.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _qa2a(x, axis_name, split_axis, concat_axis, precision):
    return _qa2a_impl(x, axis_name, split_axis, concat_axis, precision)


def _qa2a_fwd(x, axis_name, split_axis, concat_axis, precision):
    return _qa2a_impl(x, axis_name, split_axis, concat_axis, precision), None


def _qa2a_bwd(axis_name, split_axis, concat_axis, precision, _, ct):
    # The cotangent of an all_to_all is the all_to_all with split/concat
    # swapped; the backward wire narrows like the forward (the moe_a2a
    # policy covers BOTH directions — tolerance contract, not a detail).
    return (_qa2a_impl(ct, axis_name, concat_axis, split_axis, precision),)


_qa2a.defvjp(_qa2a_fwd, _qa2a_bwd)


def quantized_all_to_all(x, axis_name, *, split_axis: int,
                         concat_axis: int, precision: Optional[str] = None):
    """Tiled ``lax.all_to_all`` under a ``moe_a2a`` wire precision.

    ``None``/``"fp32"`` is the exact collective; ``"bf16"``/``"int8"``
    narrow the wire as a composed convert sandwich (one whole-payload
    scale — contrast the per-chunk scales of the elected
    ``a2a_ring`` kernel), with the transposed all_to_all at the same
    precision as backward."""
    if precision in (None, "fp32"):
        return lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)
    return _qa2a(x, axis_name, split_axis, concat_axis, precision)


def expert_parallel_ffn(tokens, gate_w, expert_wi, expert_wo, *,
                        axis_name: str = const.EXPERT_AXIS,
                        capacity_factor: float = 2.0,
                        a2a_precision: Optional[str] = None,
                        a2a_kernel: bool = False):
    """MoE FFN (call inside ``shard_map``).

    tokens: [G, M] local tokens;  gate_w: [M, E] replicated;
    expert_wi: [E_local, M, H], expert_wo: [E_local, H, M] — this device's
    experts.  Returns ([G, M], aux_loss).

    ``a2a_precision`` narrows the dispatch/combine wire (the
    ``GraphConfig.precision["moe_a2a"]`` policy); ``a2a_kernel`` swaps
    both all_to_alls for the fused s8 ``ppermute`` ring
    (:func:`autodist_tpu.kernel.pallas.a2a_ring.ring_dispatch` — the
    elected ``a2a_ring`` kernel; implies the int8 wire).
    """
    P = lax.axis_size(axis_name)
    G, M = tokens.shape
    E_local = expert_wi.shape[0]
    E = E_local * P
    capacity = max(int(np.ceil(2 * G * capacity_factor / E)), 4)

    if a2a_kernel:
        from autodist_tpu.kernel.pallas.a2a_ring import ring_dispatch

        def route(x, split_axis, concat_axis):
            return ring_dispatch(x, axis_name, split_axis, concat_axis)
    else:
        def route(x, split_axis, concat_axis):
            return quantized_all_to_all(
                x, axis_name, split_axis=split_axis,
                concat_axis=concat_axis, precision=a2a_precision)

    gate_logits = tokens @ gate_w                        # [G, E]
    dispatch, combine, aux = top2_gating(gate_logits, capacity)

    # local dispatch: [E, C, M]
    xs = jnp.einsum("gm,gec->ecm", tokens.astype(jnp.float32),
                    dispatch.astype(jnp.float32))
    # all_to_all (tiled): every device keeps its E_local experts, gathering
    # those experts' slots from all P devices → [E_local, P*C, M]
    xs = route(xs, 0, 1)
    h = jnp.einsum("ecm,emh->ech", xs, expert_wi.astype(jnp.float32))
    h = jax.nn.gelu(h)
    ys = jnp.einsum("ech,ehm->ecm", h, expert_wo.astype(jnp.float32))
    # route back: [E, C, M] on every source device
    ys = route(ys, 1, 0)
    out = jnp.einsum("ecm,gec->gm", ys, combine)
    return out.astype(tokens.dtype), aux


def lower_expert_ir(trainable, strategy, mesh):
    """Strategy-IR entry: lower a ``lowering == "expert"`` strategy
    (built by :class:`~autodist_tpu.strategy.parallel_builders.ExpertParallel`).

    Expert-annotated variables (node configs with a partitioner spec on
    the ``expert`` axis) are stored sharded along their leading
    expert dimension; every device trains its own experts (their
    gradients synchronize over the data axis only).  All other variables
    replicate and synchronize over (data x expert) — the expert axis
    doubles as a batch axis for the non-MoE parts of the model, which is
    the GShard/Mesh-TensorFlow arrangement.  The trainable's loss runs
    inside ``shard_map`` and must route tokens with
    :func:`expert_parallel_ffn` (``axis_name="expert"``).
    """
    from jax.sharding import PartitionSpec as P

    from autodist_tpu.kernel import common
    from autodist_tpu.parallel._spmd import build_replicated_spmd

    expert_axis = const.EXPERT_AXIS
    if expert_axis not in mesh.shape:
        raise ValueError(
            f"mesh {dict(mesh.shape)} has no {expert_axis!r} axis")
    # Replica axes include dcn on multi-slice meshes (data-only sync
    # would skip cross-slice gradient exchange).
    d_axes = tuple(a for a in (const.DCN_AXIS, const.DATA_AXIS)
                   if a in mesh.shape)
    has_data = bool(d_axes)
    batch_axes = (*d_axes, expert_axis)
    E_shards = mesh.shape[expert_axis]

    expert_vars = set()
    for nc in strategy.node_configs:
        part = nc.partitioner
        if part is not None and part.spec is not None \
                and expert_axis in part.spec:
            expert_vars.add(nc.var_name)
        elif part is not None and part.mesh_axis == expert_axis \
                and part.num_shards > 1:
            expert_vars.add(nc.var_name)

    infos = {v.name: v for v in trainable.var_infos()}
    for name in sorted(expert_vars):
        shape = infos[name].shape
        if not shape or shape[0] % E_shards:
            raise ValueError(
                f"expert variable {name} leading dim {shape} must divide "
                f"the {E_shards}-way expert axis")

    # Bind the dispatch/combine wire election into the loss: the
    # trainable publishes a mutable ``moe_a2a`` slot (its loss reads the
    # slot at trace time — `make_moe_lm_trainable` threads it down to
    # ``expert_parallel_ffn``), and the lowering writes the strategy's
    # ``precision["moe_a2a"]`` + ``kernel["a2a_ring"]`` election into it.
    # A strategy that elects either without a slot to bind would silently
    # train at fp32 — fail loudly instead.
    from autodist_tpu.kernel.pallas import OBSERVED_KERNELS
    from autodist_tpu.parallel._spmd import emit_kernel_gauges
    a2a_prec = strategy.graph_config.precision.get("moe_a2a")
    a2a_kern = bool(strategy.graph_config.kernel.get("a2a_ring"))
    slot = getattr(trainable, "moe_a2a", None)
    if slot is not None:
        slot["precision"] = a2a_prec
        slot["kernel"] = a2a_kern
    elif a2a_prec or a2a_kern:
        raise ValueError(
            "strategy elects a moe_a2a wire policy "
            f"(precision={a2a_prec!r}, a2a_ring={a2a_kern}) but trainable "
            f"{trainable.name!r} has no moe_a2a binding slot (see "
            "make_moe_lm_trainable)")
    emit_kernel_gauges({k: True for k, v in
                        strategy.graph_config.kernel.items()
                        if v and k not in OBSERVED_KERNELS})

    def param_spec(name, leaf):
        if name in expert_vars:
            return P(*([expert_axis] + [None] * (leaf.ndim - 1)))
        return P()

    def sync_grad(name, g):
        if name in expert_vars:
            # Each device owns its experts; only replicas along the data
            # axis hold the same shard.  The global objective is the
            # mean over ALL token groups — (1/E) x the mean of this
            # device's local-mean loss — so the local grad must be
            # scaled by 1/E_shards to match what replicated params get
            # from their pmean over (data x expert).  (Without this,
            # expert tables train at an E_shards-scaled learning rate;
            # adam's scale invariance masked it.)
            g = g / E_shards
            return lax.pmean(g, d_axes) if has_data else g
        return lax.pmean(g, batch_axes)

    # Per-variable synchronizer configs (PS -> ZeRO-1, compressors):
    # replicated variables sync over (data x expert) — both are batch
    # axes for them; expert-sharded variables over data only, scaled
    # 1/E_shards (same objective as sync_grad above).  ZeRO on an
    # expert-sharded variable degrades — its optimizer state is already
    # E-way sharded with the parameter — with the reason recorded on the
    # lowered plan (``ZeroLowered.zero_degraded``).
    from autodist_tpu.parallel._spmd import policies_from_node_configs
    degraded: dict = {}
    policies = policies_from_node_configs(
        strategy, mesh, replicated_axes=batch_axes,
        axes_for=lambda n: d_axes if n in expert_vars else batch_axes,
        scale_for=lambda n: 1.0 / E_shards if n in expert_vars else 1.0,
        sharded_vars=expert_vars, degraded=degraded)

    batch_spec = P(common.axes_entry(batch_axes))
    return build_replicated_spmd(
        trainable, mesh, sync_axes=batch_axes,
        batch_spec_fn=lambda batch: common.batch_specs(batch, batch_spec),
        batch_spec=batch_spec, param_spec_fn=param_spec,
        grad_sync=sync_grad,
        accum=max(strategy.graph_config.accum_steps, 1),
        policies=policies, zero_degraded=degraded,
        precision=strategy.graph_config.precision)


def dense_moe_reference(tokens, gate_w, expert_wi, expert_wo,
                        capacity: int):
    """Single-device reference: same gating + experts, no all_to_all."""
    G, M = tokens.shape
    E = expert_wi.shape[0]
    gate_logits = tokens @ gate_w
    dispatch, combine, aux = top2_gating(gate_logits, capacity)
    xs = jnp.einsum("gm,gec->ecm", tokens.astype(jnp.float32),
                    dispatch.astype(jnp.float32))
    h = jax.nn.gelu(jnp.einsum("ecm,emh->ech", xs,
                               expert_wi.astype(jnp.float32)))
    ys = jnp.einsum("ech,ehm->ecm", h, expert_wo.astype(jnp.float32))
    return jnp.einsum("ecm,gec->gm", ys, combine).astype(tokens.dtype), aux
