"""A decode step's routed FFN over the held experts, one call a layer.

``parallel.moe.routed_experts`` sorts a step's (row, expert) pairs by
held expert.  At a decode step's few rows the experts' weights are the
whole cost: the kernel here walks the experts that have rows, in order,
and streams each one's ``wi`` then ``wo`` through VMEM once, as slabs of
whole rows (``[tk, 2M]`` and ``[tm, H]``: contiguous in HBM), its own
DMAs :data:`IN_FLIGHT` slabs ahead of the products over ONE flat
sequence of slabs — the next expert's first slabs are on their way
while the current one's last are computed.  A group's rows meet a slab
in aligned windows of :data:`ROW_TILE` sorted pairs (a dynamic sublane
offset has to be a multiple of the tile; the rows of the window that are
another expert's are masked out of the result), a group of more rows
looping over its windows with the slab resident: skewed routing costs
MXU passes, never a second read and never a row.  Gate and up
accumulate in float32, ``silu(gate) * up`` is rounded to the operands'
type once, as the composed path rounds it, and never leaves VMEM; the
down product accumulates in float32 into the output's rows.

The composed path it replaces is two ``jax.lax.ragged_dot`` (the TPU
compiler's own grouped matmul), which stays for a prefill's thousands of
pairs: :func:`grouped_matmul_elected`.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.kernel.pallas import default_interpret, kernel_marker

# The most sorted pairs the kernel takes: the pairs, their float32
# results and a group's activations all stay in VMEM.  A decode step of
# the benchmark's routed cells has 384 and 320; a prefill row's 6,144
# and 10,240 are the compiler's grouped matmul's.
MAX_GROUPED_PAIRS = 1024
# Sorted pairs a window: two bf16 sublane tiles, so that a group of up to
# 17 rows is one MXU pass wherever it starts.
ROW_TILE = 32
# A slab is the most whole rows of 128 that divide the expert's and stay
# under this, and IN_FLIGHT slabs are requested ahead of the one computed
# (tools/grouped_matmul_crossover.py read them on a v5e: PERF.md
# section 6, PR 38).
SLAB_BYTES = 1536 << 10
IN_FLIGHT = 3
VMEM_LIMIT_BYTES = 64 << 20


def grouped_matmul_fits(pairs: int, hidden: int, width: int, dtype) -> bool:
    """Whether the kernel can run ``pairs`` sorted pairs through experts
    ``[hidden, 2 * width]`` / ``[width, hidden]`` of ``dtype``: bf16 as
    held (a wider type is refused, never cast), widths in whole lanes of
    128, and few enough pairs to stay in VMEM."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and hidden % 128 == 0
            and width % 128 == 0 and 0 < pairs <= MAX_GROUPED_PAIRS)


def grouped_matmul_elected(word, pairs: int, hidden: int, width: int,
                           dtype, backend: Optional[str] = None) -> bool:
    """The election for a routed layer's call, from what it can observe.
    ``word`` is the kernel slot's on ``grouped_matmul``: ``False``
    forbids the kernel, ``True`` takes it wherever it can run (the
    interpreter off the TPU), ``None`` leaves it to the backend — a TPU
    takes it, anything else the composed products."""
    if word is False or not grouped_matmul_fits(pairs, hidden, width, dtype):
        return False
    return bool(word) or (backend or jax.default_backend()) == "tpu"


def slab_rows(rows: int, row_bytes: int, slab_bytes: int = SLAB_BYTES):
    """Rows of a slab: the largest multiple of 128 that divides ``rows``
    under ``slab_bytes`` (128 where none is)."""
    fit = [t for t in range(128, rows + 1, 128)
           if rows % t == 0 and t * row_bytes <= slab_bytes]
    return max(fit, default=128)


def slab_walk(visit_ref, count, wi_hbm, wo_hbm, wi_buf, wo_buf, sem, *,
              tk: int, tm: int):
    """The walk of the visited experts' slabs: ``(slab, request, nk,
    slabs)``.  Slab ``r`` of a visited expert is ``wi``'s ``r``-th
    ``[tk, 2M]`` or, from ``nk`` on, ``wo``'s ``[tm, H]``, ``slabs`` of
    them an expert; ``slab(idx, r)`` is ``(copy, buffer)`` of slab ``r``
    of the ``idx``-th visited expert, the buffers going round by the
    slab's count among its kind; ``request(idx, r)`` starts the copy of
    the slab ``r`` after the ``idx``-th expert's first — the following
    experts' where ``r`` passes ``slabs`` — if there is one."""
    nk, nm = wi_hbm.shape[1] // tk, wo_hbm.shape[1] // tm
    slabs, depth = nk + nm, wi_buf.shape[0]

    def slab(idx, r):
        e = visit_ref[idx]
        if r < nk:
            at = (idx * nk + r) % depth
            return pltpu.make_async_copy(
                wi_hbm.at[e, pl.ds(r * tk, tk), :], wi_buf.at[at],
                sem.at[0, at]), at
        at = (idx * nm + r - nk) % depth
        return pltpu.make_async_copy(
            wo_hbm.at[e, pl.ds((r - nk) * tm, tm), :], wo_buf.at[at],
            sem.at[1, at]), at

    def request(idx, r):
        idx, r = idx + r // slabs, r % slabs

        @pl.when(idx < count)
        def _():
            slab(idx, r)[0].start()

    return slab, request, nk, slabs


def visited(sizes):
    """``(visit, count)`` of the groups' ``sizes``: the experts that have
    rows, in order (the others after them), and ``[1]`` how many."""
    hit = sizes > 0
    return (jnp.argsort(~hit, stable=True).astype(jnp.int32),
            hit.sum(dtype=jnp.int32).reshape(1))


def _grouped_matmul_kernel(visit_ref, offs_ref, sizes_ref, count_ref, x_ref,
                           wi_hbm, wo_hbm, y_ref, wi_buf, wo_buf, sem,
                           h_acc, h_act, *, tk: int, tm: int, ahead: int):
    """``x_ref``: the sorted pairs' rows ``[P, H]``; ``wi_hbm`` ``[E, H,
    2M]`` and ``wo_hbm`` ``[E, M, H]``: the layer's experts where they
    are held; ``y_ref``: ``[P, H]`` float32.  SMEM: ``visit_ref`` the
    experts that have rows, in order, ``count_ref[0]`` of them;
    ``offs_ref`` / ``sizes_ref``: where each expert's group starts among
    the pairs and its rows."""
    P = x_ref.shape[0]
    M = wo_hbm.shape[1]
    count = count_ref[0]
    slab, request, nk, slabs = slab_walk(
        visit_ref, count, wi_hbm, wo_hbm, wi_buf, wo_buf, sem, tk=tk, tm=tm)

    for r in range(ahead):
        request(0, r)
    y_ref[...] = jnp.zeros_like(y_ref)

    def expert(idx, carry):
        e = visit_ref[idx]
        off, n = offs_ref[e], sizes_ref[e]
        first = off // 16 * 16          # the group's first aligned window
        passes = (off - first + n + ROW_TILE - 1) // ROW_TILE

        def window(j):          # its rows among the pairs: inside them
            return pl.multiple_of(
                jnp.minimum(first + j * ROW_TILE, P - ROW_TILE), 16)

        def held(j):                    # the window's rows in h_acc / h_act
            return pl.ds(pl.multiple_of(j * ROW_TILE, ROW_TILE), ROW_TILE)

        for r in range(slabs):
            request(idx, r + ahead)
            copy, at = slab(idx, r)
            copy.wait()
            if r < nk:
                def gate_up(j, c, r=r, at=at):
                    part = jnp.dot(
                        x_ref[pl.ds(window(j), ROW_TILE),
                              r * tk:(r + 1) * tk], wi_buf[at],
                        preferred_element_type=jnp.float32)
                    if r:
                        part = part + h_acc[held(j), :]
                    if r + 1 < nk:
                        h_acc[held(j), :] = part
                    else:               # the one rounding of h
                        h_act[held(j), :] = (
                            jax.nn.silu(part[:, :M]) * part[:, M:]
                        ).astype(h_act.dtype)
                    return c

                jax.lax.fori_loop(0, passes, gate_up, 0)
            else:
                def down(j, c, m=r - nk, at=at):
                    part = jnp.dot(
                        h_act[held(j), m * tm:(m + 1) * tm], wo_buf[at],
                        preferred_element_type=jnp.float32)
                    start = window(j)
                    row = start + jax.lax.broadcasted_iota(
                        jnp.int32, (ROW_TILE, 1), 0)
                    # this pass's rows of this group, and no other's
                    lo = jnp.maximum(off, first + j * ROW_TILE)
                    hi = jnp.minimum(off + n, first + (j + 1) * ROW_TILE)
                    rows = pl.ds(start, ROW_TILE)
                    y_ref[rows, :] = y_ref[rows, :] + jnp.where(
                        (row >= lo) & (row < hi), part, 0.0)
                    return c

                jax.lax.fori_loop(0, passes, down, 0)
        return carry

    jax.lax.fori_loop(0, count, expert, 0)


@functools.partial(jax.jit, static_argnames=(
    "slab_bytes", "in_flight", "interpret"))
def grouped_matmul_layer(visit, offs, sizes, count, x, wi, wo, *,
                         slab_bytes: int, in_flight: int, interpret: bool):
    """The one inner function every layer's call goes through (a decode
    body of any depth lowers the kernel once).  ``x``: ``[P, H]``, ``P``
    a multiple of 16 and at least :data:`ROW_TILE`; returns ``[P, H]``
    float32."""
    P, H = x.shape
    M = wo.shape[1]
    tk = slab_rows(H, wi.shape[2] * wi.dtype.itemsize, slab_bytes)
    tm = slab_rows(M, H * wo.dtype.itemsize, slab_bytes)
    depth = in_flight + 1
    # a group's windows: its rows and the start's distance to a window's
    held = (P // ROW_TILE + 1) * ROW_TILE
    whole = pl.BlockSpec(memory_space=pl.ANY)
    rows = pl.BlockSpec((P, H), lambda i, *_: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,          # visit, offs, sizes, count (SMEM)
        grid=(1,),
        in_specs=[rows, whole, whole],
        out_specs=rows,
        scratch_shapes=[pltpu.VMEM((depth, tk, wi.shape[2]), wi.dtype),
                        pltpu.VMEM((depth, tm, H), wo.dtype),
                        pltpu.SemaphoreType.DMA((2, depth)),
                        pltpu.VMEM((held, wi.shape[2]), jnp.float32),
                        pltpu.VMEM((held, M), x.dtype)],
    )
    return pl.pallas_call(
        functools.partial(_grouped_matmul_kernel, tk=tk, tm=tm,
                          ahead=in_flight),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, H), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(visit, offs, sizes, count, x, wi, wo)


def grouped_matmul(x, wi, wo, sizes, *, slab_bytes: int = SLAB_BYTES,
                   in_flight: int = IN_FLIGHT,
                   interpret: Optional[bool] = None):
    """``wo_e(silu(gate_e x) * up_e x)`` of sorted rows ``x`` ``[P, H]``
    whose first ``sizes[0]`` rows are expert 0's, the next ``sizes[1]``
    expert 1's, and so on, through ``wi`` ``[E, H, 2M]`` (gate, then up)
    and ``wo`` ``[E, M, H]`` as they are held.  Returns ``[P, H]``
    float32, the rows past the groups zero.  An expert without a row is
    not read."""
    P, H = x.shape
    if not grouped_matmul_fits(P, H, wo.shape[1], x.dtype) \
            or wi.dtype != x.dtype or wo.dtype != x.dtype:
        raise ValueError(
            f"the grouped-matmul kernel takes up to {MAX_GROUPED_PAIRS} "
            f"bf16 rows through bf16 experts of whole lanes of 128; got "
            f"{x.dtype}{list(x.shape)} through {wi.dtype}{list(wi.shape)} "
            "(jax.lax.ragged_dot serves it)")
    sizes = sizes.astype(jnp.int32)
    offs = jnp.cumsum(sizes) - sizes
    visit, count = visited(sizes)
    padded = max(-(-P // 16) * 16, ROW_TILE)
    if padded != P:
        x = jnp.pad(x, ((0, padded - P), (0, 0)))
    interp = default_interpret() if interpret is None else bool(interpret)
    with jax.named_scope(kernel_marker("grouped_matmul")):
        y = grouped_matmul_layer(
            visit, offs, sizes, count, x, wi, wo,
            slab_bytes=int(slab_bytes), in_flight=int(in_flight),
            interpret=interp)
    return y[:P]
