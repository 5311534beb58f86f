"""Fused collective-matmul ring step.

``parallel/tensor.py collective_matmul_row`` chunks a row-parallel
matmul around a ``lax.ppermute`` ring so hop *k*'s transfer overlaps
chunk *k+1*'s matmul.  Composed, each hop is still two HBM-shaped ops:
the chunk matmul writes its partial product, then the add reads it
back to fold it into the carry that just arrived.  The fused ring step
does both in one kernel pass — ``carry + x @ kernel_chunk`` accumulated
in VMEM while the MXU streams the chunk — which on real silicon also
gives the scheduler a single op to overlap the next hop's RDMA against
(the per-hop launch overhead the cost model's ``fused_hop_alpha_s``
constant prices).

Same math, same custom-VJP contract (local tensordot transpose, zero
model-axis collectives in the row layer's own backward), same
zero-padding of non-divisible output widths as the composed ring; the
CPU golden pins it against ``collective_matmul_row`` within float
summation-order tolerance.
"""
from __future__ import annotations

from typing import Optional

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from autodist_tpu.kernel.pallas import default_interpret, kernel_marker


# Tile sizes of the fused step's (M, C, K) grid.  A dimension no larger
# than its tile is one full-extent block; a larger one is walked in
# tiles (zero-padded to a whole number of them), so VMEM use is bounded
# by the tiles — about 1.5 MiB double-buffered in bf16 — whatever the
# activation's row count or the contraction width.
TILE_M, TILE_C, TILE_K = 256, 256, 512


def _matmul_acc_kernel(carry_ref, x_ref, k_ref, o_ref, acc_ref):
    """``o = carry + x @ k``, accumulated in fp32 over the K grid axis:
    the accumulator opens on the carry tile and closes into the output
    tile at the last K step."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _open():
        acc_ref[...] = carry_ref[...].astype(jnp.float32)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], k_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _close():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _padded(a, tiles):
    """Zero-pad each dim of ``a`` past its tile up to whole tiles."""
    pads = [(0, (-d) % t if d > t else 0) for d, t in zip(a.shape, tiles)]
    return jnp.pad(a, pads) if any(p for _, p in pads) else a


def _fused_matmul_add(carry, x2d, kc2d, *, interpret: bool):
    """Pallas-fused ``carry + x2d @ kc2d``; shapes ``[M, C] + [M, K] @
    [K, C]``."""
    from jax.experimental.pallas import tpu as pltpu

    M, C = carry.shape
    carry_p = _padded(carry, (TILE_M, TILE_C))
    x_p = _padded(x2d, (TILE_M, TILE_K))
    k_p = _padded(kc2d, (TILE_K, TILE_C))
    Mp, Cp = carry_p.shape
    Kp = x_p.shape[1]
    tm, tc, tk = min(Mp, TILE_M), min(Cp, TILE_C), min(Kp, TILE_K)
    out = pl.pallas_call(
        _matmul_acc_kernel,
        grid=(Mp // tm, Cp // tc, Kp // tk),
        in_specs=[pl.BlockSpec((tm, tc), lambda i, j, k: (i, j)),
                  pl.BlockSpec((tm, tk), lambda i, j, k: (i, k)),
                  pl.BlockSpec((tk, tc), lambda i, j, k: (k, j))],
        out_specs=pl.BlockSpec((tm, tc), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Cp), carry.dtype),
        scratch_shapes=[pltpu.VMEM((tm, tc), jnp.float32)],
        interpret=interpret,
    )(carry_p, x_p, k_p)
    return out[:M, :C] if (Mp, Cp) != (M, C) else out


def _fused_ring_fwd(x, kernel, model_axis, axes: int,
                    interpret: Optional[bool]):
    """The ``_ring_matmul_fwd_impl`` schedule with the hop accumulate +
    chunk matmul as ONE fused kernel pass.  Chunk assignment matches
    the composed ring exactly: the carry a device starts with is chunk
    ``me - 1``; after ``tp - 1`` hops it owns chunk ``me``, and the
    closing tiled all-gather concatenates chunks in position order."""
    if kernel.ndim != axes + 1:
        raise ValueError(
            "collective_matmul_row_fused expects a kernel with exactly "
            f"one output dim after {axes} contraction dim(s); got shape "
            f"{kernel.shape} — use the composed collective_matmul_row")
    interp = default_interpret() if interpret is None \
        else bool(interpret)
    tp = lax.axis_size(model_axis)
    me = lax.axis_index(model_axis)
    width = kernel.shape[-1]
    pad = (-width) % tp
    if pad:
        kernel = jnp.pad(
            kernel, [(0, 0)] * (kernel.ndim - 1) + [(0, pad)])
    chunk_w = (width + pad) // tp
    perm = [(i, (i + 1) % tp) for i in range(tp)]

    lead_shape = x.shape[:x.ndim - axes]
    M = int(math.prod(lead_shape)) or 1
    K = int(math.prod(x.shape[x.ndim - axes:])) or 1
    x2d = x.reshape(M, K)
    kflat = kernel.reshape(K, chunk_w * tp)
    out_dtype = jnp.result_type(x.dtype, kernel.dtype)

    def part(carry, c):
        kc = lax.dynamic_slice_in_dim(kflat, c * chunk_w, chunk_w,
                                      axis=1)
        return _fused_matmul_add(carry, x2d, kc, interpret=interp)

    with jax.named_scope(kernel_marker("collective_matmul")):
        zero = jnp.zeros((M, chunk_w), out_dtype)
        owned = part(zero, (me - 1) % tp)
        # Hops unrolled (tp is static and small): each ppermute is its
        # own HLO op, so the scheduler can overlap hop k's transfer
        # against hop k+1's fused matmul, and ADT120 can count the
        # tp-1 ring transfers in the compiled program.
        for h in range(1, tp):
            carry = lax.ppermute(owned, model_axis, perm)
            owned = part(carry, (me - h - 1) % tp)
        y2d = lax.all_gather(owned, model_axis, axis=1, tiled=True)
    y = y2d.reshape(*lead_shape, chunk_w * tp)
    if pad:
        y = lax.slice_in_dim(y, 0, width, axis=y.ndim - 1)
    return y


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def collective_matmul_row_fused(x, kernel, model_axis, axes: int = 1,
                                interpret: Optional[bool] = None):
    """Row-parallel matmul on the fused ``ppermute`` ring — the
    kernel-tier form of :func:`autodist_tpu.parallel.tensor
    .collective_matmul_row` (elected via the Strategy IR's
    ``collective_matmul`` kernel choice).

    Equals ``sum_partials(tensordot(x, kernel, axes), model_axis)`` up
    to float summation order; the backward is the local tensordot
    transpose with zero model-axis collectives of its own.
    """
    return _fused_ring_fwd(x, kernel, model_axis, axes, interpret)


def _fused_fwd(x, kernel, model_axis, axes, interpret):
    return _fused_ring_fwd(x, kernel, model_axis, axes, interpret), \
        (x, kernel)


def _fused_bwd(model_axis, axes, interpret, res, ct):
    x, kernel = res
    _, pullback = jax.vjp(
        lambda a, b: jnp.tensordot(a, b, axes=axes), x, kernel)
    return pullback(ct)


collective_matmul_row_fused.defvjp(_fused_fwd, _fused_bwd)
