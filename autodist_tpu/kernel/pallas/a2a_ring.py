"""Fused quantize-into-all-to-all: the EQuARX ring, reduce -> permute.

The MoE dispatch/combine boundary is an ``lax.all_to_all`` — permute-
shaped, never summing — so the composed int8 lowering
(``parallel/moe.py quantized_all_to_all``) is a convert *sandwich*:
quantize the whole payload once, run ONE monolithic ``s8`` collective,
gather the per-source scales alongside, dequantize once.  The PR 13
``quant_ring`` observation generalizes: put the quantize/dequantize
*inside* the exchange's hops and every hop's wire carries a TRUE ``s8``
chunk with its own fresh fp32 scale — no whole-payload scale agreement
(one outlier token no longer flattens every other chunk's levels), and
a form one monolithic collective cannot express.

This module is that ring.  The all-to-all is decomposed into ``n - 1``
shift-``h`` ``lax.ppermute`` hops (hop ``h``: device ``i`` sends the
chunk destined for device ``(i + h) % n`` and receives from
``(i - h) % n``); per hop, ONE fused kernel pass —
:func:`_dq_and_q_kernel` — dequantizes the arrived chunk and quantizes
the next outgoing chunk in VMEM.  The device's own chunk never touches
the wire and stays exact.  A permute never sums, so unlike the reduce
ring there is NO per-hop requantization chain: each chunk is quantized
exactly once, giving the same single-rounding error bound as the
composed ``s8`` sandwich — with per-chunk (not per-payload) scales,
usually tighter.

On the simulated CPU mesh the kernels run under the Pallas interpreter
and the structure is provable from HLO: ``n - 1`` ``s8``
collective-permutes per all-to-all — ``2(n-1)`` per MoE layer's
dispatch + combine pair — and zero payload-carrying all-to-alls: the
ADT120 signature.

Numerics: :func:`reference_ring_all_to_all` mirrors the arithmetic op
for op (the exactness golden); vs the exact fp32 all_to_all the error
is one int8 rounding per off-device chunk.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.kernel import quantize as qz
from autodist_tpu.kernel.pallas import default_interpret, kernel_marker
from autodist_tpu.kernel.pallas.quant_ring import (LANES, hop_grid,
                                                   reduce_abs_max, to_tiles)


def _dq_and_q_kernel(scale_in_ref, q_in_ref, next_ref, out_ref,
                     q_out_ref, scale_out_ref, amax_ref):
    """One fused hop pass: dequantize the arrived chunk
    (``out = q_in * scale_in``) and quantize the next outgoing chunk
    against its own abs-max scale — the work a composed lowering would
    spread over HBM-shaped converts, block by block in VMEM (the
    two-phase walk of :func:`~autodist_tpu.kernel.pallas.quant_ring
    .hop_grid`).  ``scale_in == 0`` (the warm-up, nothing arrived yet)
    makes the dequantized block vanish to exact zeros; an all-zero
    ``next`` quantizes to exact zeros through the scale floor."""
    nxt = next_ref[...].astype(jnp.float32)
    reduce_abs_max(amax_ref, nxt)

    @pl.when(pl.program_id(0) == 1)
    def _write():
        out_ref[...] = q_in_ref[...].astype(jnp.float32) \
            * scale_in_ref[0, 0]
        scale = qz.scale_of_abs_max(amax_ref[0])
        q_out_ref[...] = qz.quantize_levels(nxt, scale).astype(jnp.int8)
        scale_out_ref[0, 0] = scale


def _fused_hop(q_in, scale_in, nxt, *, interpret: bool):
    """Run the fused pass; ``q_in`` s8 ``[rows, LANES]``, ``scale_in``
    f32 scalar, ``nxt`` f32 ``[rows, LANES]`` -> ``(arrived f32 [rows,
    LANES], q_out s8 [rows, LANES], scale_out f32 scalar)``."""
    rows = nxt.shape[0]
    grid, br, in_map, out_map = hop_grid(rows)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out, q_out, scale_out = pl.pallas_call(
        _dq_and_q_kernel,
        grid=grid,
        in_specs=[smem,
                  # only phase 1 reads the arrived chunk
                  pl.BlockSpec((br, LANES), out_map),
                  pl.BlockSpec((br, LANES), in_map)],
        out_specs=(pl.BlockSpec((br, LANES), out_map),
                   pl.BlockSpec((br, LANES), out_map), smem),
        out_shape=(jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((rows, LANES), jnp.int8),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)),
        scratch_shapes=[pltpu.SMEM((1,), jnp.float32)],
        interpret=interpret,
    )(scale_in.reshape(1, 1), q_in, nxt)
    return out, q_out, scale_out[0, 0]


def quantized_ring_all_to_all(x, axis_name, *, split_axis: int,
                              concat_axis: int,
                              interpret: Optional[bool] = None):
    """All-to-all ``x`` over ``axis_name`` (tiled ``lax.all_to_all``
    semantics) as the fused-q/dq shift ring; result cast back to
    ``x.dtype``.  Drop-in for the composed
    ``quantized_all_to_all(..., precision="int8")`` — same contract,
    per-chunk scales, ``n - 1`` ``s8`` collective-permutes on the wire.

    ``x.shape[split_axis]`` must divide the ring size (the tiled
    all_to_all contract)."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    if x.shape[split_axis] % n:
        raise ValueError(
            f"all_to_all split dim {x.shape[split_axis]} (axis "
            f"{split_axis}) must divide the {n}-way {axis_name!r} ring")
    interp = default_interpret() if interpret is None else bool(interpret)
    me = lax.axis_index(axis_name)

    # Canonicalize: parts[j] = the chunk destined for device j, each
    # flattened and zero-padded to whole VMEM tiles for the kernel
    # passes (the form it keeps on the wire).
    moved = jnp.moveaxis(x, split_axis, 0).astype(jnp.float32)
    part_shape = (moved.shape[0] // n,) + moved.shape[1:]
    L = int(np.prod(part_shape)) if part_shape else 1
    flat = to_tiles(moved.reshape(n, L))             # [n, rows, LANES]
    tile = flat.shape[1:]

    def part(shift):
        # The chunk destined for device (me + shift) % n.
        return lax.dynamic_index_in_dim(flat, (me + shift) % n, axis=0,
                                        keepdims=False)

    out = jnp.zeros((n,) + tile, jnp.float32)
    with jax.named_scope(kernel_marker("a2a_ring")):
        # Warm-up: quantize hop 1's outgoing chunk (nothing arrived).
        _, q, s = _fused_hop(jnp.zeros(tile, jnp.int8),
                             jnp.float32(0.0), part(1),
                             interpret=interp)
        # Own chunk stays local and exact (it never rides the wire).
        out = lax.dynamic_update_index_in_dim(out, part(0), me, axis=0)
        # Hops unrolled (n is static and small): every hop's s8
        # ppermute is its own HLO op — the n-1 narrowed transfers per
        # all-to-all (2(n-1) per dispatch+combine pair) ADT120 counts
        # as the ring's wire signature.
        for h in range(1, n):
            perm = [(i, (i + h) % n) for i in range(n)]
            q = lax.ppermute(q, axis_name, perm)
            s = lax.ppermute(s, axis_name, perm)
            nxt = part(h + 1) if h + 1 < n else jnp.zeros(tile,
                                                          jnp.float32)
            arrived, q, s = _fused_hop(q, s, nxt, interpret=interp)
            # Hop h delivered device (me - h)'s chunk for me -> slot
            # (me - h) % n (output parts are source-ordered).
            out = lax.dynamic_update_index_in_dim(
                out, arrived, (me - h) % n, axis=0)

    out = out.reshape(n, -1)[:, :L]
    gathered = out.reshape((n,) + part_shape)        # source-major
    # Reassemble tiled-concat semantics: received parts concatenate
    # along concat_axis in source order.
    out_parts = [jnp.moveaxis(gathered[i], 0, split_axis)
                 for i in range(n)]
    result = jnp.concatenate(out_parts, axis=concat_axis)
    return result.astype(x.dtype)


def reference_ring_all_to_all(shards, *, split_axis: int,
                              concat_axis: int):
    """Host-side mirror of the ring arithmetic over a list of per-device
    payloads (identical shapes): the exactness golden — the
    interpreter-mode ring must reproduce this bit for bit.  Every
    off-device chunk is quantized once against its own abs-max scale and
    dequantized on arrival; the own chunk stays exact."""
    n = len(shards)
    mats = [jnp.asarray(s).astype(jnp.float32) for s in shards]
    if n == 1:
        return [mats[0].astype(jnp.asarray(shards[0]).dtype)]

    def parts_of(m):
        moved = jnp.moveaxis(m, split_axis, 0)
        return moved.reshape((n, moved.shape[0] // n) + moved.shape[1:])

    split_parts = [parts_of(m) for m in mats]
    outs = []
    for me in range(n):
        received = []
        for src in range(n):
            chunk = split_parts[src][me]
            if src != me:
                scale = qz.abs_max_scale(chunk)
                q = qz.quantize_levels(chunk, scale).astype(jnp.int8)
                chunk = q.astype(jnp.float32) * scale
            received.append(jnp.moveaxis(chunk, 0, split_axis))
        outs.append(jnp.concatenate(received, axis=concat_axis)
                    .astype(jnp.asarray(shards[0]).dtype))
    return outs


# --------------------------------------------------------------------------- #
# The boundary-layer entries (parallel/moe.py dispatches here)
# --------------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def ring_dispatch(x, axis_name, split_axis, concat_axis):
    """Fused-ring all-to-all with the transposed ring as its backward —
    the fused-kernel form of the MoE dispatch/combine boundary under an
    int8 ``moe_a2a`` policy with the ``a2a_ring`` kernel elected.  The
    cotangent of an all-to-all is the all-to-all with split/concat axes
    swapped, so the backward rides the same s8 ring."""
    return quantized_ring_all_to_all(x, axis_name, split_axis=split_axis,
                                     concat_axis=concat_axis)


def _ring_a2a_fwd(x, axis_name, split_axis, concat_axis):
    return quantized_ring_all_to_all(
        x, axis_name, split_axis=split_axis, concat_axis=concat_axis), None


def _ring_a2a_bwd(axis_name, split_axis, concat_axis, _, ct):
    return (quantized_ring_all_to_all(
        ct, axis_name, split_axis=concat_axis, concat_axis=split_axis),)


ring_dispatch.defvjp(_ring_a2a_fwd, _ring_a2a_bwd)
