"""One position of power retention over the cache manager's state, in
place: each ``(slot, key/value head)`` tile is read once and written
once, and every query head of the group reads it while it is resident.

The state of a key/value head is ``[offsets, dv, d]`` float32 —
``d / 2 + 1`` tiles of ``[dv, d]``, 4.3 MB at heads of 128 — beside a
normaliser ``[offsets, d]``, which the manager keeps for all heads of a
slot as ``[offsets, kv, d]``
(:class:`~autodist_tpu.models.transformer.LinearMixerSpec`: row ``(o,
i)`` belongs to the product ``k_i k_{(i + o) mod d}``).  A tile that
size does not sit in VMEM twice over, so the grid walks ``(slot,
key/value head, slab of offsets)``: a step brings ``offsets_per_step``
tiles in, builds its slice of ``phi(k)`` and of each query head's
``phi(q)`` from the ``[d]`` vectors themselves — an offset's row is one
lane rotation and two multiplies, for the key and every query head of
the group in ONE ``[8, d]`` register tile — decays the tiles, adds ``v
phi(k)^T``, writes them back where they came from
(``input_output_aliases``) and adds their part of the group's read-outs
``S^T phi(q)`` and normalisers ``z . phi(q)`` to output blocks that stay
resident across the slabs: the read-out is a reduction ACROSS grid
steps.  The stacked array goes in whole, the layer a prefetched scalar
in the block index maps (as ``delta_step`` takes its state): no other
layer's or slot's tile is touched.

Same mathematics as the composed step
(``models.pipeline_lm.retention_step``), float32 throughout and
elementwise: nothing here goes through the MXU, which would round a
float32 product's operands to bf16.  ``g == 0`` and ``k == 0`` leave a
tile bit for bit (``1 * S + v * 0``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.kernel.pallas import default_interpret, kernel_marker

# Offsets a grid step takes, a [dv, d] tile each: 13 of 65 at heads of
# 128 (832 KB in, 832 KB out, double-buffered).  The count has to divide
# the offsets and keep the ~0.35 us a grid step costs small beside its
# bytes; PERF.md section 6 (PR 43) has the chip's readings.
OFFSETS_PER_STEP = 13
VMEM_LIMIT_BYTES = 48 << 20
_ROWS = 8              # a register tile's sublanes: k, the group's q, v


def retention_step_fits(state_shape, state_dtype, group: int = 1) -> bool:
    """Whether the kernel can advance a state of this shape and type:
    float32 (a narrower state is refused, never cast), tiles of whole
    lanes ``dv == d == 128``, and a group of query heads that fits one
    register tile beside the key and the value (at most 6)."""
    if len(state_shape) < 3:
        return False
    offsets, dv, d = state_shape[-3:]
    return (jnp.dtype(state_dtype) == jnp.float32 and dv == d == 128
            and offsets == d // 2 + 1 and 1 <= group <= _ROWS - 2)


def retention_step_elected(word, state_shape, state_dtype, group: int = 1,
                           backend: Optional[str] = None) -> bool:
    """The election for a decode step (one position), from what the call
    can observe.  ``word`` is the kernel slot's on ``retention_step``:
    ``False`` forbids the kernel, ``True`` takes it wherever it can run
    (the interpreter off the TPU), ``None`` leaves it to the backend — a
    TPU takes it, anything else the composed step."""
    if word is False or not retention_step_fits(state_shape, state_dtype,
                                                group):
        return False
    return bool(word) or (backend or jax.default_backend()) == "tpu"


def _offsets_per_step(offsets: int) -> int:
    """The most offsets a grid step takes: a divisor of ``offsets``,
    :data:`OFFSETS_PER_STEP` at most."""
    return max(n for n in range(1, OFFSETS_PER_STEP + 1) if offsets % n == 0)


def _retention_step_kernel(layer_ref, dec_ref, rows_ref, s_ref, z_ref,
                           num_ref, den_ref, s_out_ref, z_out_ref,
                           phi_ref, yt_ref, *, ob: int, group: int, kv: int):
    """``ob`` offsets of one (slot, key/value head).  ``rows_ref`` ``[8,
    d]``: row 0 the key, rows ``1 .. group`` the group's queries
    (scaled), row ``group + 1`` the value; ``dec_ref`` (SMEM): every
    (slot, head)'s ``exp(g)``; ``s_ref`` / ``s_out_ref``: the slab's
    tiles ``[ob, dv, d]``, one array; ``z_ref`` / ``z_out_ref``: the
    slot's whole normaliser ``[offsets, kv, d]``, resident across its
    heads and slabs, of which a step moves its head's ``ob`` rows;
    ``num_ref`` ``[8, dv]`` and ``den_ref`` ``[8, d]``: rows ``h`` and
    ``1 + h`` gather query head ``h``'s read-out and (lane by lane) its
    normaliser over the slabs.  ``phi_ref`` ``[ob, group + 1, 8, d]``
    holds the slab's rows of ``phi`` spread over a register tile's
    sublanes, ``yt_ref`` ``[dv, d]`` a slab's read-outs before they are
    turned to rows."""
    del layer_ref                       # the index maps read it
    b, g, s = (pl.program_id(i) for i in range(3))
    dec = dec_ref[b * kv + g]
    rows = rows_ref[...]                                    # [8, d]
    d, dv = rows.shape[-1], s_ref.shape[-2]
    half = d // 2

    @pl.when(s == 0)
    def _():
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    # the slab's rows of phi: k's and every query head's at once
    den = jnp.zeros((_ROWS, d), jnp.float32)
    for j in range(ob):
        o = s * ob + j
        c = jnp.where((o == 0) | (o == half), 1.0, 2.0 ** 0.5)
        # x_{(i + o) mod d}: rotate left by o
        phi = rows * pltpu.roll(rows, (d - o) % d, 1) * c
        z_new = z_ref[o, pl.ds(g, 1), :] * dec + phi[0:1, :]
        z_out_ref[o, pl.ds(g, 1), :] = z_new
        den = den + z_new * phi
        for r in range(group + 1):
            phi_ref[j, r] = jnp.broadcast_to(phi[r:r + 1, :], (_ROWS, d))
    den_ref[...] += den
    # v down the sublanes, the same on every lane
    v_t = jnp.broadcast_to(rows[group + 1:group + 2, :], (d, dv)).T
    lane = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, d), 1)
    for c in range(dv // _ROWS):
        at = slice(c * _ROWS, (c + 1) * _ROWS)
        v_c = v_t[at, :]
        acc = [jnp.zeros((_ROWS, d), jnp.float32)] * group
        for j in range(ob):
            new = s_ref[j, at, :] * dec + v_c * phi_ref[j, 0]
            s_out_ref[j, at, :] = new
            acc = [a + new * phi_ref[j, 1 + h] for h, a in enumerate(acc)]
        tile = jnp.zeros((_ROWS, d), jnp.float32)
        for h, a in enumerate(acc):         # head h's sums on lane h
            tile = jnp.where(lane == h, jnp.sum(a, axis=1, keepdims=True),
                             tile)
        yt_ref[at, :] = tile
    num_ref[...] += yt_ref[...].T[0:_ROWS, :]


@functools.partial(jax.jit, static_argnames=("group", "offsets_per_step",
                                             "interpret"))
def retention_step_layer(layer, dec, rows, ssm, nrm, *, group: int,
                         offsets_per_step: int, interpret: bool):
    """The one inner function every layer's call goes through (``layer``
    an operand: a decode body of any depth lowers the kernel once).
    ``dec``: ``[B * kv]``; ``rows``: ``[B, kv, 8, d]``; ``ssm``, ``nrm``:
    the stacked state and normaliser.  Returns ``(num [B, kv, 8, dv], den
    [B, kv, 8, d], ssm, nrm)``."""
    _, B, kv, O, dv, d = ssm.shape
    ob = offsets_per_step
    per_head = lambda width: pl.BlockSpec(
        (None, None, _ROWS, width), lambda b, g, s, *_: (b, g, 0, 0))
    tiles = pl.BlockSpec(
        (None, None, None, ob, dv, d),
        lambda b, g, s, layer, _: (layer[0], b, g, s, 0, 0))
    normaliser = pl.BlockSpec(
        (None, None, O, kv, d),
        lambda b, g, s, layer, _: (layer[0], b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # layer, dec (SMEM)
        grid=(B, kv, O // ob),
        in_specs=[per_head(d), tiles, normaliser],
        out_specs=[per_head(dv), per_head(d), tiles, normaliser],
        scratch_shapes=[pltpu.VMEM((ob, group + 1, _ROWS, d), jnp.float32),
                        pltpu.VMEM((dv, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_retention_step_kernel, ob=ob, group=group, kv=kv),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, kv, _ROWS, dv), jnp.float32),
                   jax.ShapeDtypeStruct((B, kv, _ROWS, d), jnp.float32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
                   jax.ShapeDtypeStruct(nrm.shape, nrm.dtype)],
        # operands: layer, dec, rows, the state, the normaliser
        input_output_aliases={3: 2, 4: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(layer, dec, rows, ssm, nrm)


def retention_step_fused(q, k, v, g, state, layer, *, eps: float,
                         offsets_per_step: Optional[int] = None,
                         interpret: Optional[bool] = None):
    """``models.pipeline_lm.retention_step`` on linear layer ``layer`` of
    the stacked state, in place.  ``q``: ``[B, heads, d]`` (scaled);
    ``k``, ``v``: ``[B, kv, d]``; ``g`` (log gate): ``[B, kv]``;
    ``state``: ``(ssm [linear layers, B, kv, offsets, dv, d], nrm [linear
    layers, B, offsets, kv, d])`` float32 — the cache manager's arrays
    themselves, no slice; ``layer``: int or int32 scalar.  Returns ``(y
    [B, heads, dv], (ssm, nrm))``, the arrays updated in place under
    ``jit`` with donation.  Every slot's row is advanced."""
    ssm, nrm = state
    B, kv = k.shape[:2]
    group = q.shape[1] // kv
    if not retention_step_fits(ssm.shape, ssm.dtype, group) \
            or nrm.dtype != jnp.float32:
        raise ValueError(
            "the retention-step kernel takes a float32 state of "
            "[d / 2 + 1, 128, 128] tiles a key/value head and at most "
            f"{_ROWS - 2} query heads a group; got {ssm.dtype}"
            f"{list(ssm.shape)} with {group} (the composed retention_step "
            "serves it)")
    O = ssm.shape[3]
    ob = int(offsets_per_step or _offsets_per_step(O))
    if O % ob:
        raise ValueError(f"offsets_per_step={ob} must divide the "
                         f"{O} offsets")
    f32 = lambda t: t.astype(jnp.float32)
    d = k.shape[-1]
    rows = jnp.concatenate(
        [f32(k)[:, :, None], f32(q).reshape(B, kv, group, d),
         f32(v)[:, :, None],
         jnp.zeros((B, kv, _ROWS - group - 2, d), jnp.float32)], axis=2)
    interp = default_interpret() if interpret is None else bool(interpret)
    with jax.named_scope(kernel_marker("retention_step")):
        num, den, ssm, nrm = retention_step_layer(
            jnp.asarray(layer, jnp.int32).reshape(1),
            jnp.exp(f32(g)).reshape(-1), rows, ssm, nrm, group=group,
            offsets_per_step=ob, interpret=interp)
    y = num[:, :, :group] / (den[:, :, 1:group + 1].sum(-1, keepdims=True)
                             + eps)
    return y.reshape(B, kv * group, -1), (ssm, nrm)
