"""One position of the gated delta rule over the cache manager's state,
in place: each ``(slot, value head)`` tile is read once and written once.

The composed step (``models.pipeline_lm.gated_delta_step``) reads the
recurrent state twice — a two-sum reduce, then the update — and, handed
a layer's slice of the stacked array, has the slice put back by a
``dynamic_update_slice``.  The kernel here takes the stacked array
``[linear layers, slots, value heads, dk, dv]`` whole, the layer a
prefetched scalar in the block index maps (as the dense decode kernel
takes the key/value cache, ``flash_decode.flash_decode_layer``), brings
``heads_per_step`` tiles of one slot into VMEM a grid step, computes
both sums and the update from the one resident copy and writes the new
tiles back where they came from (``input_output_aliases``): no other
layer's tile is touched.

Same mathematics as the composed step, float32 throughout, and
elementwise: a float32 product on the MXU at default precision would
round the state to bf16.  ``g == 0`` and ``beta == 0`` leave a tile bit
for bit (``1 * S + k * 0``).

The decay is a column: row ``i`` of a tile is multiplied by its own
``exp(g_i)`` (Kimi Delta Attention's gate a key channel).  A head's one
decay (gated DeltaNet) is the same column broadcast, so there is one
kernel, and the decay rides in VMEM beside the key as ``[hb, dk]``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.kernel.pallas import default_interpret, kernel_marker

# Heads a grid step takes, [128, 128] tiles each way: read on a v5e
# from the kernel alone at the benchmark's shape (32 slots x 32 value
# heads, 12 layers; ``chip_smoke.mixed_block_phase``, PERF.md section 6,
# PR 33): 232 us a layer at 8 heads a step, 228 at 16, 227 at 32, where
# a plain copy of the same tiles takes 219 (614 GB/s of 819: the chip's
# rate of reading and writing HBM at once, through these blocks or
# through hand-issued DMAs alike).  The count only has to keep the
# ~0.35 us a grid step costs small beside its bytes.
HEADS_PER_STEP = 16
VMEM_LIMIT_BYTES = 32 << 20    # the tiles in and out, double-buffered


def delta_step_fits(state_shape, state_dtype) -> bool:
    """Whether the kernel can advance a state of this shape and type:
    float32 (a narrower state is refused, never cast), ``dk`` and ``dv``
    whole lanes of 128, the value heads whole sublane tiles of 8."""
    heads, dk, dv = state_shape[-3:]
    return (jnp.dtype(state_dtype) == jnp.float32
            and dk % 128 == 0 and dv % 128 == 0 and heads % 8 == 0)


def delta_step_elected(word, state_shape, state_dtype,
                       backend: Optional[str] = None) -> bool:
    """The election for a decode step (one position), from what the call
    can observe.  ``word`` is the kernel slot's on ``delta_step``:
    ``False`` forbids the kernel, ``True`` takes it wherever it can run
    (the interpreter off the TPU), ``None`` leaves it to the backend — a
    TPU takes it, anything else the composed step."""
    if word is False or not delta_step_fits(state_shape, state_dtype):
        return False
    return bool(word) or (backend or jax.default_backend()) == "tpu"


def _heads_per_step(heads: int) -> int:
    """The most heads a grid step takes: a divisor of ``heads`` in whole
    sublane tiles of 8, :data:`HEADS_PER_STEP` at most."""
    return max(hb for hb in range(8, HEADS_PER_STEP + 1, 8)
               if heads % hb == 0)


def _delta_step_kernel(layer_ref, scal_ref, k_ref, q_ref, d_ref, v_ref,
                       s_ref, o_ref, s_out_ref, *, hb: int, heads: int):
    """``hb`` heads of one slot.  ``k_ref``, ``q_ref``, ``d_ref`` (the
    decay of each row of the tile, ``exp(g)``): ``[hb, dk]`` and
    ``v_ref``: ``[hb, dv]``, a head a row; ``scal_ref`` (SMEM): every
    (slot, head)'s write strength and ``k . q``, two scalars each;
    ``s_ref`` / ``s_out_ref``: the tiles ``[hb, dk, dv]``, one array.  A
    key, query or decay multiplies its tile along ``dk``, the sublane
    axis: turned to a column it broadcasts along the lanes.  The decayed
    tile's sums come from the tile as it stands, the decay folded into
    the key and the query (``S_d^T k = S^T (d k)``)."""
    del layer_ref                       # the index maps read it
    first = (pl.program_id(0) * heads + pl.program_id(1) * hb) * 2
    d_cols = d_ref[...].T                                   # [dk, hb]
    k_cols, q_cols = k_ref[...].T, q_ref[...].T
    for j in range(hb):
        beta, kq = (scal_ref[first + 2 * j + i] for i in range(2))
        S = s_ref[j]                                        # [dk, dv]
        dc, kc = d_cols[:, j:j + 1], k_cols[:, j:j + 1]
        s_k = jnp.sum(S * (dc * kc), axis=0, keepdims=True)
        s_q = jnp.sum(S * (dc * q_cols[:, j:j + 1]), axis=0, keepdims=True)
        delta = (v_ref[j:j + 1, :] - s_k) * beta            # [1, dv]
        o_ref[j:j + 1, :] = s_q + delta * kq
        s_out_ref[j] = S * dc + kc * delta


@functools.partial(jax.jit, static_argnames=("heads_per_step", "interpret"))
def delta_step_layer(layer, scal, k, q, d, v, ssm, *, heads_per_step: int,
                     interpret: bool):
    """The one inner function every layer's call goes through (``layer``
    an operand: a decode body of any depth lowers the kernel once).
    ``scal``: ``[B * H * 2]``; ``k``, ``q``, ``d``: ``[B, H, dk]``;
    ``v``: ``[B, H, dv]``; ``ssm``: the stacked state.  Returns ``(o [B,
    H, dv], ssm)``."""
    _, B, H, dk, dv = ssm.shape
    hb = heads_per_step
    rows = lambda width: pl.BlockSpec((None, hb, width),
                                      lambda b, h, *_: (b, h, 0))
    tiles = pl.BlockSpec((None, None, hb, dk, dv),
                         lambda b, h, layer, _: (layer[0], b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # layer, scal (SMEM)
        grid=(B, H // hb),
        in_specs=[rows(dk), rows(dk), rows(dk), rows(dv), tiles],
        out_specs=[rows(dv), tiles],
    )
    return pl.pallas_call(
        functools.partial(_delta_step_kernel, hb=hb, heads=H),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)],
        # operands: layer, scal, k, q, d, v, the state
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(layer, scal, k, q, d, v, ssm)


def gated_delta_step_fused(q, k, v, g, beta, ssm, layer, *,
                           heads_per_step: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """``models.pipeline_lm.gated_delta_step`` on linear layer ``layer``
    of the stacked state, in place.  ``q``, ``k``: ``[B, heads, dk]``
    (normalised, ``q`` scaled); ``v``: ``[B, heads, dv]``; ``g`` (log
    decay): ``[B, heads]``, or ``[B, heads, dk]`` a row of the tile its
    own; ``beta``: ``[B, heads]``; ``ssm``: ``[linear layers, B,
    heads, dk, dv]`` float32 — the cache manager's array itself, no
    slice; ``layer``: int or int32 scalar.  Returns ``(o [B, heads, dv],
    ssm)``, the array updated in place under ``jit`` with donation.
    Every slot's row is advanced."""
    if not delta_step_fits(ssm.shape, ssm.dtype):
        raise ValueError(
            f"the delta-step kernel takes a float32 state of whole "
            f"[128, 128] tiles, 8 heads at a time; got "
            f"{ssm.dtype}{list(ssm.shape)} (the composed gated_delta_step "
            "serves it)")
    H = ssm.shape[2]
    hb = int(heads_per_step or _heads_per_step(H))
    if H % hb or hb % 8:
        raise ValueError(f"heads_per_step={hb} must divide heads={H} "
                         "into whole sublane tiles of 8")
    f32 = lambda t: t.astype(jnp.float32)
    q, k, v, g, beta = map(f32, (q, k, v, g, beta))
    scal = jnp.stack([beta, (k * q).sum(-1)], -1).reshape(-1)
    decay = jnp.exp(g)
    if decay.ndim < k.ndim:             # a head's one decay: every row's
        decay = jnp.broadcast_to(decay[..., None], k.shape)
    interp = default_interpret() if interpret is None else bool(interpret)
    with jax.named_scope(kernel_marker("delta_step")):
        o, ssm = delta_step_layer(
            jnp.asarray(layer, jnp.int32).reshape(1), scal, k, q, decay, v,
            ssm,
            heads_per_step=hb, interpret=interp)
    return o, ssm
