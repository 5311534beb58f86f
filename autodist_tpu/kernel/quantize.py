"""Boundary-agnostic quantize/dequantize layer for collectives.

One home for the int8 pack/unpack and error-feedback arithmetic that was
previously private to :mod:`autodist_tpu.kernel.compressor` (the dp-grad
path), now shared with the per-boundary precision policy of the Strategy
IR (PR 8): the TP activation psums, the decomposed rs+ag halves, the
vocab-epilogue stat psums, and the ZeRO-3 on-demand gathers all narrow
through the helpers below (EQuARX-style — quantize *inside* the
collective, PAPERS.md 2506.17615).

Two wire disciplines, chosen by collective semantics:

* **Summing collectives** (psum / psum-scatter) carry int8 *levels* on an
  fp16 wire: integer levels in [-127, 127] are exact in fp16, and the
  running sum stays exact while its magnitude is <= 2048 — i.e. >= 16
  full-scale summands; beyond that fp16 rounds integers to multiples of
  2 (then 4, ...), a bounded ~2^-11 relative error on the sum that the
  goldens' tolerance covers.  Half the fp32 width either way.  A shared
  scale (``pmax`` over the group — a scalar-sized side collective) makes
  independently-quantized payloads summable.
* **Gathering collectives** (all-gather) never sum, so the payload rides
  a TRUE ``int8`` wire (4x) with one fp32 scale per source shard
  gathered alongside.

Error feedback is a *gradient* concern (the residual persists across
steps in optimizer-adjacent state); activation boundaries are stateless
by construction — each step's activations are fresh, so there is nothing
to feed an error back into.  The EF helpers here serve the compressor
path and any future stateful boundary.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

# The per-boundary precision vocabulary of the Strategy IR policy
# (strategy/ir.py re-exports these; kernel code stays IR-agnostic).
PRECISIONS = ("fp32", "bf16", "int8")

# Wire dtype of a *summing* quantized collective per precision: int8
# levels ride fp16 (exact while the running sum is <= 2048, ~16
# full-scale summands; bounded ~2^-11 relative rounding past that).
SUM_WIRE_DTYPE = {"bf16": jnp.bfloat16, "int8": jnp.float16}


class UnknownPrecisionError(ValueError):
    """A precision value outside :data:`PRECISIONS` — the named error a
    hand-edited strategy JSON gets instead of a silent fp32 fallback."""


def check_precision(value, *, where: str = "precision") -> str:
    """Canonicalize one precision value (``None`` -> ``"fp32"``);
    anything outside :data:`PRECISIONS` raises
    :class:`UnknownPrecisionError`."""
    if value is None:
        return "fp32"
    if value not in PRECISIONS:
        raise UnknownPrecisionError(
            f"{where}: unknown precision {value!r}; expected one of "
            f"{list(PRECISIONS)}")
    return value


# --------------------------------------------------------------------------- #
# int8 pack/unpack (shared by the compressors and the boundary layer)
# --------------------------------------------------------------------------- #
# Scale floor: an all-zero block would otherwise divide by zero; any
# positive floor maps it to all-zero levels exactly.
_SCALE_FLOOR = 1e-20


def scale_of_abs_max(amax):
    """The int8 scale of a payload whose ``max|x|`` is ``amax`` (a
    kernel that walks a payload in tiles reduces the max itself)."""
    return jnp.maximum(amax / 127.0, _SCALE_FLOOR)


def abs_max_scale(x):
    """Symmetric per-tensor int8 scale: ``max|x| / 127``, floored so an
    all-zero (or single-element zero) block quantizes to exact zeros."""
    return scale_of_abs_max(jnp.max(jnp.abs(x)))


def quantize_levels(x, scale):
    """Quantize to integer *levels* in [-127, 127], kept in the input's
    float dtype (the summable wire form — cast to the fp16 wire at the
    collective)."""
    return jnp.clip(jnp.round(x / scale), -127, 127)


def quantize_int8(x):
    """``(q, scale)`` with ``q`` a true ``int8`` payload (the gather-wire
    form) and ``scale`` its fp32 per-tensor scale."""
    scale = abs_max_scale(x)
    return quantize_levels(x, scale).astype(jnp.int8), scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def shared_scale(x, axis_name):
    """Group-wide int8 scale: every device proposes ``max|x|/127`` and a
    ``pmax`` makes them agree, so quantized payloads are summable (the
    Int8EF discipline).  One scalar-sized collective per boundary."""
    return jnp.maximum(
        lax.pmax(jnp.max(jnp.abs(x)), axis_name) / 127.0, _SCALE_FLOOR)


# --------------------------------------------------------------------------- #
# Error feedback (gradient boundaries only — see module docstring)
# --------------------------------------------------------------------------- #
def ef_correct(grad, residual):
    """Apply the carried quantization error before compressing:
    ``grad + residual`` in fp32 (the CompressorEF step)."""
    return grad.astype(jnp.float32) + residual


def ef_residual(corrected, wire):
    """Next step's residual: what this step's wire form lost."""
    return corrected - wire.astype(jnp.float32)


# --------------------------------------------------------------------------- #
# Quantized collectives (the boundary layer proper)
# --------------------------------------------------------------------------- #
def quantized_psum(x, axis_name, precision: str):
    """All-reduce ``x`` over ``axis_name`` at the requested wire
    precision; the result is cast back to ``x.dtype``.

    ``fp32`` is today's exact psum; ``bf16`` casts the payload; ``int8``
    agrees a shared scale (scalar pmax), sums integer levels on an fp16
    wire, and rescales.  Stateless — activation-grade (no error
    feedback; see module docstring).
    """
    precision = check_precision(precision)
    if precision == "fp32":
        return lax.psum(x, axis_name)
    if precision == "bf16":
        return lax.psum(x.astype(jnp.bfloat16), axis_name).astype(x.dtype)
    scale = shared_scale(x, axis_name)
    q = quantize_levels(x.astype(jnp.float32), scale)
    summed = lax.psum(q.astype(jnp.float16), axis_name)
    return (summed.astype(jnp.float32) * scale).astype(x.dtype)


def quantized_pmax(x, axis_name, precision: str):
    """Group max at the wire precision.  A max is order-free, so any
    narrowing only rounds the result (no summation error); ``int8``
    takes the bf16 wire — 8-bit levels would waste the max's role as a
    softmax stabilizer for no extra byte savings on token-shaped
    stats."""
    precision = check_precision(precision)
    if precision == "fp32":
        return lax.pmax(x, axis_name)
    return lax.pmax(x.astype(jnp.bfloat16), axis_name).astype(x.dtype)


def quantized_psum_scatter_flat(flat, axis_name, precision: str):
    """Reduce-scatter of an already padded-flat payload at the wire
    precision (the rs half of a decomposed pair).  Returns the fp32
    shard."""
    precision = check_precision(precision)
    if precision == "fp32":
        return lax.psum_scatter(flat, axis_name, scatter_dimension=0,
                                tiled=True)
    if precision == "bf16":
        return lax.psum_scatter(flat.astype(jnp.bfloat16), axis_name,
                                scatter_dimension=0,
                                tiled=True).astype(jnp.float32)
    scale = shared_scale(flat, axis_name)
    q = quantize_levels(flat.astype(jnp.float32), scale)
    shard = lax.psum_scatter(q.astype(jnp.float16), axis_name,
                             scatter_dimension=0, tiled=True)
    return shard.astype(jnp.float32) * scale


def quantized_all_gather_flat(shard, axis_name, precision: str):
    """All-gather of equal flat shards at the wire precision (the ag
    half of a decomposed pair, and the ZeRO-3 on-demand gather).  A
    gather never sums, so ``int8`` rides a TRUE ``s8`` wire — each
    source shard's fp32 scale (one scalar) is gathered alongside and
    the rows dequantize independently.  Returns the gathered fp32 flat
    payload."""
    precision = check_precision(precision)
    if precision == "fp32":
        return lax.all_gather(shard, axis_name, tiled=True)
    if precision == "bf16":
        return lax.all_gather(shard.astype(jnp.bfloat16), axis_name,
                              tiled=True).astype(jnp.float32)
    q, scale = quantize_int8(shard.astype(jnp.float32))
    rows = lax.all_gather(q, axis_name)            # [n, shard] s8 wire
    scales = lax.all_gather(scale, axis_name)      # [n] fp32 sidecar
    return (rows.astype(jnp.float32)
            * scales[:, None]).reshape(-1)
