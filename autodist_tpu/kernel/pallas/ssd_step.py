"""One position of a Mamba-2 state-space layer over the cache manager's
state, in place: each ``(slot, group)`` matrix is read once and written
once.

It is the delta step (``delta_step.py``) with the read-before-write
taken out — ``S' = S a + B (Delta x)``, ``y = S' C`` — but over a state
no other rule has: the key ``B`` and the query ``C`` are shared by every
head of a group and the decay is one scalar a head, so a group's heads
are ONE ``[N, heads a group * P]`` float32 matrix (``LinearMixerSpec
.state_shape``): ``B`` and ``C`` down the sublanes, the heads' values
side by side along the lanes (a head of 64 is half a lane tile, which
``delta_step_fits`` refuses; side by side they fill whole ones), the
decay and the write ``Delta x`` two ROWS of the width.  The update
broadcasts a column along the lanes and a row down the sublanes; the
read-out is a sum down the sublanes, vector adds and one sublane reduce a
lane tile, and comes out as a row.

The kernel takes the stacked array ``[linear layers, slots, groups, N,
W]`` whole, the layer a prefetched scalar in the block index maps, one
matrix a grid step, :data:`LANES_PER_PASS` lanes of it at a time (what
keeps the temporaries in registers), and writes the new matrix back where
it came from (``input_output_aliases``): no other layer's is touched.
float32 throughout and elementwise: a float32 product on the MXU at
default precision would round the state to bf16.  A decay of 1 and a
write of 0 leave a matrix bit for bit.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.kernel.pallas import default_interpret, kernel_marker

# Lanes of a matrix multiplied, summed and stored at a time: [128, 512]
# float32 is 64 vector registers, the whole file.
LANES_PER_PASS = 512
VMEM_LIMIT_BYTES = 32 << 20    # a matrix in and out, double-buffered
_COLUMNS = 8                   # B, C and six rows of nothing: a sublane tile


def ssd_step_fits(state_shape, state_dtype) -> bool:
    """Whether the kernel can advance a state of this shape and type:
    float32 (a narrower state is refused, never cast), ``N`` whole
    sublane tiles of 8 that transpose as one lane tile at most, the
    group's width whole lanes of 128."""
    N, W = state_shape[-2:]
    return (jnp.dtype(state_dtype) == jnp.float32
            and N % 8 == 0 and N <= 128 and W % 128 == 0)


def ssd_step_elected(word, state_shape, state_dtype,
                     backend: Optional[str] = None) -> bool:
    """The election for a decode step (one position), from what the call
    can observe.  ``word`` is the kernel slot's on ``ssd_step``:
    ``False`` forbids the kernel, ``True`` takes it wherever it can run
    (the interpreter off the TPU), ``None`` leaves it to the backend — a
    TPU takes it, anything else the composed step."""
    if word is False or not ssd_step_fits(state_shape, state_dtype):
        return False
    return bool(word) or (backend or jax.default_backend()) == "tpu"


def _ssd_step_kernel(layer_ref, bc_ref, rows_ref, s_ref, y_ref, s_out_ref,
                     *, width: int, lanes: int):
    """One group's matrix of one slot.  ``bc_ref`` ``[8, N]``: ``B``,
    ``C`` and six rows of nothing, turned to columns here; ``rows_ref``
    ``[2, W]``: the decay of each lane and the write ``Delta x``;
    ``s_ref`` / ``s_out_ref``: the matrix ``[N, W]``, one array;
    ``y_ref`` ``[1, W]``: the new matrix read through ``C``."""
    del layer_ref                       # the index maps read it
    cols = bc_ref[...].T                                    # [N, 8]
    b_col, c_col = cols[:, 0:1], cols[:, 1:2]
    for lo in range(0, width, lanes):
        at = slice(lo, min(lo + lanes, width))
        new = s_ref[:, at] * rows_ref[0:1, at] + b_col * rows_ref[1:2, at]
        s_out_ref[:, at] = new
        y_ref[:, at] = jnp.sum(new * c_col, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_step_layer(layer, bc, rows, ssm, *, interpret: bool):
    """The one inner function every layer's call goes through (``layer``
    an operand: a decode body of any depth lowers the kernel once).
    ``bc``: ``[B, G, 8, N]``; ``rows``: ``[B, G, 2, W]``; ``ssm``: the
    stacked state.  Returns ``(y [B, G, 1, W], ssm)``."""
    _, B, G, N, W = ssm.shape
    per = lambda n, width: pl.BlockSpec(
        (None, None, n, width), lambda b, g, *_: (b, g, 0, 0))
    matrix = pl.BlockSpec((None, None, None, N, W),
                          lambda b, g, layer: (layer[0], b, g, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,          # layer
        grid=(B, G),
        in_specs=[per(_COLUMNS, N), per(2, W), matrix],
        out_specs=[per(1, W), matrix],
    )
    return pl.pallas_call(
        functools.partial(_ssd_step_kernel, width=W,
                          lanes=min(W, LANES_PER_PASS)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, G, 1, W), jnp.float32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)],
        # operands: layer, bc, rows, the state
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(layer, bc, rows, ssm)


def ssd_step_fused(x, Bm, Cm, g, dt, ssm, layer, *,
                   interpret: Optional[bool] = None):
    """``models.pipeline_lm.ssd_step`` on linear layer ``layer`` of the
    stacked state, in place.  ``x`` ``[B, heads, P]``; ``Bm``, ``Cm``
    ``[B, groups, N]``; ``g`` (log decay) and ``dt`` ``[B, heads]``;
    ``ssm``: ``[linear layers, B, groups, N, heads a group * P]`` float32
    — the cache manager's array itself, no slice; ``layer``: int or int32
    scalar.  Returns ``(y [B, heads, P], ssm)``, the array updated in
    place under ``jit`` with donation.  Every slot's row is advanced."""
    if not ssd_step_fits(ssm.shape, ssm.dtype):
        raise ValueError(
            "the ssd-step kernel takes a float32 state of [N, width] "
            "matrices, N whole sublane tiles of 8 up to 128 and the "
            f"width whole lanes of 128; got {ssm.dtype}{list(ssm.shape)} "
            "(the composed ssd_step serves it)")
    B, heads, P = x.shape
    G = ssm.shape[2]
    f32 = lambda t: t.astype(jnp.float32)
    x, Bm, Cm, g, dt = map(f32, (x, Bm, Cm, g, dt))
    flat = lambda t: t.reshape(B, G, 1, -1)
    rows = jnp.concatenate(
        [flat(jnp.repeat(jnp.exp(g), P, axis=-1)), flat(dt[..., None] * x)],
        axis=2)                                          # [B, G, 2, W]
    bc = jnp.stack([Bm, Cm], 2)                          # [B, G, 2, N]
    bc = jnp.pad(bc, [(0, 0), (0, 0), (0, _COLUMNS - 2), (0, 0)])
    interp = default_interpret() if interpret is None else bool(interpret)
    with jax.named_scope(kernel_marker("ssd_step")):
        y, ssm = ssd_step_layer(
            jnp.asarray(layer, jnp.int32).reshape(1), bc, rows, ssm,
            interpret=interp)
    return y.reshape(B, heads, P), ssm
