"""Fused quantize-into-all-reduce: the EQuARX ring (PAPERS.md 2506.17615).

The composed int8 lowering (``kernel/quantize.py quantized_psum``) is a
convert *sandwich*: agree a shared scale (scalar pmax), quantize the
whole payload once, run ONE monolithic collective on an fp16 wire
(int8 levels must survive summation), dequantize once.  EQuARX's
observation is that the real win needs the quantize/dequantize *inside*
the all-reduce's ring steps — then every hop's wire carries TRUE ``s8``
chunks (4x narrower than fp32, 2x narrower than the fp16-levels wire)
because each hop re-quantizes its own partial sum against a fresh
per-hop scale.  Composed HLO cannot express that: XLA's all-reduce is
one op with one wire dtype.

This module is that ring.  Per hop, ONE fused kernel pass does
dequantize-incoming + add-local + requantize-outgoing (abs-max scale
included) in VMEM — :func:`_dq_add_q_kernel` — and the hop transfer
rides a ``lax.ppermute`` of the ``s8`` chunk plus its fp32 scale
scalar.  Reduce-scatter phase: ``n - 1`` hops of partial chunk sums
(re-quantized per hop — the bounded per-hop rounding EQuARX trades for
the narrow wire); all-gather phase: ``n - 1`` hops of the final chunks
(quantized once, no further error).  On the simulated CPU mesh the
kernels run under the Pallas interpreter and the structure is provable
from HLO: ``2(n-1)`` ``s8`` collective-permutes per boundary and zero
payload-carrying all-reduces — the ADT120 signature.

Numerics: every arithmetic step is the reference ring arithmetic
(:func:`reference_ring_all_reduce` mirrors it op for op — the exactness
golden); vs the exact fp32 psum the error is the int8 quantization
bound the composed-int8 goldens already tolerate, plus the per-hop
requantization term (``<= (n-2)`` extra roundings on the partial-sum
path).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.kernel import quantize as qz
from autodist_tpu.kernel.pallas import default_interpret, kernel_marker


# VMEM tile geometry of the hop kernels.  A hop's payload is viewed as
# ``[rows, LANES]`` with ``rows`` a multiple of the int8 sublane tile,
# and the kernel walks it ``BLOCK_ROWS`` rows per grid step (an fp32
# block of 512 KiB), so VMEM use does not grow with the payload.
LANES = 128
BLOCK_ROWS = 1024
_INT8_SUBLANES = 32


def tile_rows(length: int) -> int:
    """Rows of the ``[rows, LANES]`` view that holds ``length``
    elements: whole int8 tiles, and whole blocks once past one block."""
    rows = -(-length // LANES)
    unit = _INT8_SUBLANES if rows <= BLOCK_ROWS else BLOCK_ROWS
    return -(-rows // unit) * unit


def to_tiles(mat):
    """``[n, C]`` -> ``[n, rows, LANES]``, each row of ``mat`` zero-
    padded on its own (zeros change neither an abs-max nor a level)."""
    n, length = mat.shape
    rows = tile_rows(length)
    mat = jnp.pad(mat, ((0, 0), (0, rows * LANES - length)))
    return mat.reshape(n, rows, LANES)


def hop_grid(rows: int):
    """``(grid, block_rows, in_map, out_map)`` of a two-phase hop call.
    Phase 0 walks the payload to reduce its abs-max into SMEM scratch;
    phase 1 walks it again and writes.  The output maps pin block 0
    through phase 0 (``p * i``), so no unwritten block is ever flushed:
    block 0's buffer is first written back after phase 1 filled it."""
    br = min(rows, BLOCK_ROWS)
    return ((2, rows // br), br,
            lambda p, i: (i, 0), lambda p, i: (p * i, 0))


def reduce_abs_max(amax_ref, x):
    """Phase 0 of a hop kernel: fold this block's ``max|x|`` into the
    running max in SMEM scratch (reset at the walk's first block)."""
    phase, i = pl.program_id(0), pl.program_id(1)

    @pl.when((phase == 0) & (i == 0))
    def _reset():
        amax_ref[0] = jnp.float32(0.0)

    @pl.when(phase == 0)
    def _reduce():
        amax_ref[0] = jnp.maximum(amax_ref[0], jnp.max(jnp.abs(x)))


def _dq_add_q_kernel(scale_in_ref, q_in_ref, local_ref, q_out_ref,
                     scale_out_ref, amax_ref):
    """One fused ring-step pass: ``acc = dq(incoming) + local`` then
    requantize ``acc`` against its own abs-max scale — the arithmetic a
    composed lowering would spread over four HBM-shaped ops (convert,
    add, reduce, convert), block by block in VMEM.  ``scale_in == 0``
    (the ring's first send) makes the incoming term vanish, so the same
    kernel is the plain quantizer too."""
    acc = q_in_ref[...].astype(jnp.float32) * scale_in_ref[0, 0] \
        + local_ref[...].astype(jnp.float32)
    reduce_abs_max(amax_ref, acc)

    @pl.when(pl.program_id(0) == 1)
    def _write():
        scale = qz.scale_of_abs_max(amax_ref[0])
        q_out_ref[...] = qz.quantize_levels(acc, scale).astype(jnp.int8)
        scale_out_ref[0, 0] = scale


def _fused_hop(q_in, scale_in, local, *, interpret: bool):
    """Run the fused pass; ``q_in`` s8 ``[rows, LANES]``, ``scale_in``
    f32 scalar, ``local`` f32 ``[rows, LANES]`` -> ``(q_out s8 [rows,
    LANES], scale_out f32 scalar)``."""
    rows = local.shape[0]
    grid, br, in_map, out_map = hop_grid(rows)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    q_out, scale_out = pl.pallas_call(
        _dq_add_q_kernel,
        grid=grid,
        in_specs=[smem,
                  pl.BlockSpec((br, LANES), in_map),
                  pl.BlockSpec((br, LANES), in_map)],
        out_specs=(pl.BlockSpec((br, LANES), out_map), smem),
        out_shape=(jax.ShapeDtypeStruct((rows, LANES), jnp.int8),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)),
        scratch_shapes=[pltpu.SMEM((1,), jnp.float32)],
        interpret=interpret,
    )(scale_in.reshape(1, 1), q_in, local)
    return q_out, scale_out[0, 0]


def quantized_ring_all_reduce(x, axis_name, *,
                              interpret: Optional[bool] = None):
    """All-reduce ``x`` over ``axis_name`` as the EQuARX fused-q/dq
    ring; result cast back to ``x.dtype``.  Drop-in for
    :func:`autodist_tpu.kernel.quantize.quantized_psum` at
    ``precision="int8"`` — same contract, TRUE ``s8`` wire.

    Any payload shape is legal: the flattened payload zero-pads to
    ``n`` equal chunks (zero columns quantize to exact zeros), and each
    chunk zero-pads to whole VMEM tiles (:func:`to_tiles`) — the form
    it keeps on the wire."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    interp = default_interpret() if interpret is None else bool(interpret)
    me = lax.axis_index(axis_name)
    flat = x.reshape(-1).astype(jnp.float32)
    size = flat.shape[0]
    pad = (-size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunk = (size + pad) // n
    chunks = to_tiles(flat.reshape(n, chunk))       # [n, rows, LANES]
    tile = chunks.shape[1:]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def local(c):
        return lax.dynamic_index_in_dim(chunks, c, axis=0, keepdims=False)

    with jax.named_scope(kernel_marker("quant_ring")):
        # --- reduce-scatter phase: n-1 hops of re-quantized partials --- #
        # Device me opens by quantizing chunk me (destined to travel the
        # ring); after hop h it holds the partial sum of chunk
        # (me - h) % n; after n-1 hops it owns the full sum of chunk
        # (me - (n-1)) % n == (me + 1) % n.
        q, s = _fused_hop(jnp.zeros(tile, jnp.int8),
                          jnp.float32(0.0), local(me % n),
                          interpret=interp)
        # Hops unrolled (n is static and small): every hop's s8
        # ppermute is its own HLO op — the 2(n-1) narrowed transfers
        # ADT120 counts as the ring's wire signature.
        for h in range(1, n):
            q = lax.ppermute(q, axis_name, perm)
            s = lax.ppermute(s, axis_name, perm)
            q, s = _fused_hop(q, s, local((me - h) % n),
                              interpret=interp)
        q_own, s_own = q, s
        own_idx = (me + 1) % n

        # --- all-gather phase: n-1 hops of the final owned chunks ------ #
        out = jnp.zeros((n,) + tile, jnp.float32)
        out = lax.dynamic_update_index_in_dim(
            out, q_own.astype(jnp.float32) * s_own, own_idx, axis=0)
        for j in range(n - 1):
            q = lax.ppermute(q, axis_name, perm)
            s = lax.ppermute(s, axis_name, perm)
            # After j+1 hops the arriving chunk was owned by device
            # me - (j+1), i.e. chunk index (me - j) % n.
            out = lax.dynamic_update_index_in_dim(
                out, q.astype(jnp.float32) * s, (me - j) % n, axis=0)

    full = out.reshape(n, -1)[:, :chunk].reshape(-1)
    if pad:
        full = lax.slice_in_dim(full, 0, size)
    return full.reshape(x.shape).astype(x.dtype)


def reference_ring_all_reduce(shards):
    """Host-side mirror of the ring arithmetic over a list of per-device
    payloads (numpy/jnp arrays, identical shapes): the exactness golden
    — the interpreter-mode ring must reproduce this bit for bit, and
    the tolerance goldens bound it against the exact fp32 sum."""
    n = len(shards)
    if n == 1:
        return [jnp.asarray(shards[0])]
    flats = [jnp.asarray(s).reshape(-1).astype(jnp.float32)
             for s in shards]
    size = flats[0].shape[0]
    pad = (-size) % n
    flats = [jnp.pad(f, (0, pad)) for f in flats]
    chunk = (size + pad) // n
    mats = [f.reshape(n, chunk) for f in flats]

    def qz_pair(acc):
        scale = qz.abs_max_scale(acc)
        return qz.quantize_levels(acc, scale).astype(jnp.int8), scale

    # rs phase
    carry = {}
    for me in range(n):
        carry[me] = qz_pair(mats[me][me % n])
    for h in range(1, n):
        nxt = {}
        for me in range(n):
            q, s = carry[(me - 1) % n]
            acc = q.astype(jnp.float32) * s + mats[me][(me - h) % n]
            nxt[me] = qz_pair(acc)
        carry = nxt
    owned = {me: carry[me] for me in range(n)}
    # ag phase: every device assembles all n chunks
    outs = []
    for me in range(n):
        out = jnp.zeros((n, chunk), jnp.float32)
        for src in range(n):
            q, s = owned[src]
            out = out.at[(src + 1) % n].set(q.astype(jnp.float32) * s)
        full = out.reshape(-1)
        if pad:
            full = full[:size]
        outs.append(full.reshape(jnp.asarray(shards[0]).shape))
    return outs


# --------------------------------------------------------------------------- #
# The boundary-layer entry (parallel/tensor.py dispatches here)
# --------------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def ring_sum_partials(x, model_axis):
    """Ring all-reduce forward / identity backward — the fused-kernel
    form of ``sum_partials`` under an int8 ``tp_psum`` policy with the
    ``quant_ring`` kernel elected."""
    return quantized_ring_all_reduce(x, model_axis)


def _ring_sp_fwd(x, model_axis):
    return quantized_ring_all_reduce(x, model_axis), None


def _ring_sp_bwd(model_axis, _, ct):
    return (ct,)


ring_sum_partials.defvjp(_ring_sp_fwd, _ring_sp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def ring_gather_grads(x, model_axis):
    """Identity forward / ring all-reduce backward — the fused-kernel
    form of ``gather_grads`` (the column-parallel input boundary's
    backward cotangent reduction rides the same s8 ring)."""
    return x


def _ring_gg_fwd(x, model_axis):
    return x, None


def _ring_gg_bwd(model_axis, _, ct):
    return (quantized_ring_all_reduce(ct, model_axis),)


ring_gather_grads.defvjp(_ring_gg_fwd, _ring_gg_bwd)
