"""Tensor-parallel collective primitives for ``shard_map`` stage code.

Megatron-style tensor parallelism splits a transformer block into a
*column*-parallel matmul (output features sharded over the ``model``
axis) followed by a *row*-parallel matmul (input features sharded), with
exactly one all-reduce of the activations at the row matmul's output per
block (arxiv 1909.08053; GSPMD reaches the same program from annotations,
arxiv 2105.04663).  Inside ``shard_map`` with ``check_vma=False`` the
replication of values is *not* tracked, so ``lax.psum``'s transpose —
another psum — would double-count cotangents that are already replicated
across the model group.  The classic fix is the pair of custom-VJP
identities (Megatron's ``f``/``g`` operators):

* :func:`gather_grads` — identity forward, psum backward.  Wrap the
  *input* of a column-parallel matmul: the forward input is replicated,
  but each model shard produces only its slice's contribution to the
  input cotangent, which must be summed across the group.
* :func:`sum_partials` — psum forward, identity backward.  Wrap the
  *output* of a row-parallel matmul: each shard holds a partial sum over
  its slice of the contraction dim; the backward cotangent is already
  replicated, so every shard just keeps its copy.

``model_axis=None`` turns both into exact no-ops, so one stage function
serves the sequential single-device reference (full parameters, no
collectives) and the tp>1 lowering (local shards) — the property the
bit-parity goldens rely on.

Latency-hiding variants (``comm_overlap``)
------------------------------------------

A monolithic ``psum`` serializes the model-axis transfer behind the
matmul that feeds it.  Both classic decompositions (GSPMD, arxiv
2105.04663; portable redistribution, arxiv 2112.01075) are available
per boundary via ``comm_overlap``:

* ``"rsag"`` — the all-reduce splits into a ``psum_scatter`` +
  ``all_gather`` pair (ring-equivalent volume, two launches).  XLA's
  async-collective passes can then start the gather while unrelated
  compute proceeds (enable them with the runner knob
  ``AUTODIST_TPU_ASYNC_COLLECTIVES=1``); an ``optimization_barrier``
  between the halves keeps the combiner pass from re-fusing them back
  into the monolithic all-reduce.
* ``"matmul"`` (alias ``True``) — the chunked *collective matmul*: the
  row-parallel matmul splits into ``tp`` output chunks driven around a
  ``lax.ppermute`` ring, so hop *k*'s transfer overlaps chunk *k+1*'s
  matmul (:func:`collective_matmul_row`).  The column-parallel
  *backward* cotangent reduction has no matmul of its own to hide
  behind and takes the ``"rsag"`` form.

Every variant carries the same custom-VJP contract as the blocking
pair, so cotangents stay exact under ``check_vma=False``; numerics
differ from the ``psum`` path only by float summation order
(``tools/hlo_probe.py`` pins the structure, the pipeline-TP goldens pin
parity within tolerance).
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax import lax

from autodist_tpu.kernel import quantize as qz


# --------------------------------------------------------------------------- #
# Per-collective precision scope (the Strategy IR policy, PR 8)
# --------------------------------------------------------------------------- #
# The active wire precision per boundary slot, read by the primitives
# below at TRACE time.  A scope (not a per-call kwarg) so the policy
# reaches every boundary inside an arbitrary stage_fn/prologue/loss_head
# without changing their signatures: the lowering opens the scope inside
# its traced step body (tracing is single-threaded), stage code keeps
# calling the primitives unchanged, and code outside any scope — the
# sequential reference, the parity goldens — stays exactly fp32.
_FP32_SLOTS = {"tp_psum": "fp32", "vocab_stats": "fp32"}
_active_slots = dict(_FP32_SLOTS)


@contextlib.contextmanager
def precision_scope(policy):
    """Activate a per-boundary precision policy (``{"tp_psum": ...,
    "vocab_stats": ...}``; missing slots stay fp32) for the primitives
    traced inside the ``with`` body."""
    global _active_slots
    prev = _active_slots
    slots = dict(_FP32_SLOTS)
    for k, v in (policy or {}).items():
        if k in slots:
            slots[k] = qz.check_precision(v, where=k)
    _active_slots = slots
    try:
        yield
    finally:
        _active_slots = prev


def active_precision(slot: str) -> str:
    return _active_slots.get(slot, "fp32")


# --------------------------------------------------------------------------- #
# Fused-kernel scope (the Strategy IR kernel slot, PR 13)
# --------------------------------------------------------------------------- #
# The kernels elected for the program being traced, read by the
# primitives below at TRACE time — same discipline as the precision
# scope: the lowering opens the scope inside its traced step body,
# stage code keeps calling the primitives unchanged, and code outside
# any scope (the sequential reference, every pre-PR-13 program) lowers
# composed exactly as before.
_active_kernels: dict = {}


@contextlib.contextmanager
def kernel_scope(kernel):
    """Activate a fused-kernel election (a ``normalize_kernel`` dict or
    an iterable of kernel names) for the primitives traced inside the
    ``with`` body (or, as a decorator, inside the function)."""
    global _active_kernels
    prev = _active_kernels
    _active_kernels = (dict(kernel) if isinstance(kernel, dict)
                       else dict.fromkeys(kernel or (), True))
    try:
        yield
    finally:
        _active_kernels = prev


def active_kernel(name: str) -> bool:
    return bool(_active_kernels.get(name))


def kernel_word(name: str):
    """The scope's word on a kernel its call site elects from what it
    observes (``kernel.pallas.OBSERVED_KERNELS``): ``True`` forces it,
    ``False`` forbids it, ``None`` leaves the call site to it."""
    return _active_kernels.get(name)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _gather_grads_fp32(x, model_axis):
    return x


def _gather_grads_fwd(x, model_axis):
    return x, None


def _gather_grads_bwd(model_axis, _, ct):
    return (lax.psum(ct, model_axis),)


_gather_grads_fp32.defvjp(_gather_grads_fwd, _gather_grads_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _gather_grads_q(x, model_axis, precision):
    return x


def _gather_grads_q_fwd(x, model_axis, precision):
    return x, None


def _gather_grads_q_bwd(model_axis, precision, _, ct):
    return (qz.quantized_psum(ct, model_axis, precision),)


_gather_grads_q.defvjp(_gather_grads_q_fwd, _gather_grads_q_bwd)


def gather_grads(x, model_axis):
    """Identity forward / psum-over-``model_axis`` backward (Megatron f).

    Under a non-fp32 ``tp_psum`` precision scope the backward cotangent
    reduction narrows (:func:`~autodist_tpu.kernel.quantize
    .quantized_psum`) — the custom-VJP wrapper is what lets a *backward*
    boundary carry the policy too.  With the ``quant_ring`` kernel
    elected (and the slot at int8), the reduction runs the fused-q/dq
    EQuARX ring instead of the composed convert sandwich."""
    prec = active_precision("tp_psum")
    if prec == "fp32":
        return _gather_grads_fp32(x, model_axis)
    if prec == "int8" and active_kernel("quant_ring"):
        from autodist_tpu.kernel.pallas.quant_ring import ring_gather_grads
        return ring_gather_grads(x, model_axis)
    return _gather_grads_q(x, model_axis, prec)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _sum_partials_fp32(x, model_axis):
    return lax.psum(x, model_axis)


def _sum_partials_fwd(x, model_axis):
    return lax.psum(x, model_axis), None


def _sum_partials_bwd(model_axis, _, ct):
    return (ct,)


_sum_partials_fp32.defvjp(_sum_partials_fwd, _sum_partials_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _sum_partials_q(x, model_axis, precision):
    return qz.quantized_psum(x, model_axis, precision)


def _sum_partials_q_fwd(x, model_axis, precision):
    return qz.quantized_psum(x, model_axis, precision), None


def _sum_partials_q_bwd(model_axis, precision, _, ct):
    return (ct,)


_sum_partials_q.defvjp(_sum_partials_q_fwd, _sum_partials_q_bwd)


def sum_partials(x, model_axis):
    """psum-over-``model_axis`` forward / identity backward (Megatron g).

    The forward reduction narrows to the active ``tp_psum`` precision
    (fp32 outside any scope — the exact psum); int8 under the
    ``quant_ring`` kernel election takes the fused-q/dq ring."""
    prec = active_precision("tp_psum")
    if prec == "fp32":
        return _sum_partials_fp32(x, model_axis)
    if prec == "int8" and active_kernel("quant_ring"):
        from autodist_tpu.kernel.pallas.quant_ring import ring_sum_partials
        return ring_sum_partials(x, model_axis)
    return _sum_partials_q(x, model_axis, prec)


# --------------------------------------------------------------------------- #
# Latency-hiding decompositions
# --------------------------------------------------------------------------- #
def normalize_comm_overlap(mode):
    """Canonicalize a ``comm_overlap`` request: ``None``/``False``/"" →
    ``None`` (blocking psum), ``True`` → ``"matmul"``; otherwise one of
    ``"rsag"`` / ``"matmul"``."""
    if mode in (None, False, ""):
        return None
    if mode is True:
        return "matmul"
    if mode in ("rsag", "matmul"):
        return mode
    raise ValueError(
        f"comm_overlap must be one of None/False, True, 'rsag', 'matmul'; "
        f"got {mode!r}")


def psum_decomposed(x, axis_name, precision: str = "fp32"):
    """All-reduce as an explicit reduce-scatter + all-gather pair.

    Mathematically ``lax.psum(x, axis_name)`` at ring-equivalent wire
    volume, but emitted as two ops so XLA's latency-hiding scheduler can
    start each half asynchronously.  The ``optimization_barrier``
    between the halves pins the decomposition: without it the
    all-reduce-reassociation pass is free to fuse the pair back into
    the monolithic collective this exists to avoid (the HLO probe
    asserts it stays split).  Shapes need not divide the axis size —
    the flattened payload is zero-padded to divisibility.

    ``precision`` narrows each half independently: the rs half sums
    int8 levels on an fp16 wire, the ag half re-quantizes the fp32
    shard onto a TRUE s8 wire (a gather never sums) — the per-hop
    requantization trade of the EQuARX ring, bounded by the goldens'
    tolerance.  The barrier stays between the halves, so the narrowed
    pair is exactly as re-fusion-proof as the fp32 one.
    """
    precision = qz.check_precision(precision)
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    flat = x.reshape(-1)
    size = flat.shape[0]
    pad = (-size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    if precision == "fp32":
        shard = lax.psum_scatter(flat, axis_name, scatter_dimension=0,
                                 tiled=True)
        shard = lax.optimization_barrier(shard)
        full = lax.all_gather(shard, axis_name, axis=0, tiled=True)
    else:
        shard = qz.quantized_psum_scatter_flat(flat, axis_name, precision)
        shard = lax.optimization_barrier(shard)
        full = qz.quantized_all_gather_flat(shard, axis_name, precision)
        full = full.astype(x.dtype)
    if pad:
        full = lax.slice_in_dim(full, 0, size)
    return full.reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _gather_grads_dec(x, model_axis, precision):
    return x


def _gather_grads_dec_fwd(x, model_axis, precision):
    return x, None


def _gather_grads_dec_bwd(model_axis, precision, _, ct):
    return (psum_decomposed(ct, model_axis, precision),)


_gather_grads_dec.defvjp(_gather_grads_dec_fwd, _gather_grads_dec_bwd)


def gather_grads_decomposed(x, model_axis):
    """Identity forward / decomposed (rs+ag) psum backward — the
    ``comm_overlap`` form of :func:`gather_grads` for column-parallel
    inputs: the backward cotangent reduction stops being a monolithic
    all-reduce (and narrows to the active ``tp_psum`` precision)."""
    return _gather_grads_dec(x, model_axis, active_precision("tp_psum"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _sum_partials_dec(x, model_axis, precision):
    return psum_decomposed(x, model_axis, precision)


def _sum_partials_dec_fwd(x, model_axis, precision):
    return psum_decomposed(x, model_axis, precision), None


def _sum_partials_dec_bwd(model_axis, precision, _, ct):
    return (ct,)


_sum_partials_dec.defvjp(_sum_partials_dec_fwd,
                         _sum_partials_dec_bwd)


def sum_partials_decomposed(x, model_axis):
    """Decomposed (rs+ag) psum forward / identity backward — the
    ``comm_overlap="rsag"`` form of :func:`sum_partials` for
    row-parallel outputs (narrowed to the active ``tp_psum``
    precision)."""
    return _sum_partials_dec(x, model_axis, active_precision("tp_psum"))


def _ring_matmul_fwd_impl(x, kernel, model_axis, axes):
    """``psum(tensordot(x, kernel, axes))`` as a chunked ppermute ring.

    The kernel's last (output) dim splits into ``tp`` chunks; a partial
    chunk sum travels the ring for ``tp - 1`` hops, and each device adds
    its local contribution to whatever chunk just arrived — so hop *k*'s
    transfer overlaps chunk *k+1*'s matmul (the "collective matmul" of
    GSPMD/Wang et al.).  Chunk assignment: the carry a device starts
    with is chunk ``me - 1``; after ``tp - 1`` hops it owns the full sum
    of chunk ``me``, so the closing tiled ``all_gather`` concatenates
    chunks already in position order.  Output widths that don't divide
    ``tp`` are zero-padded (zero columns compute nothing real and are
    sliced off).
    """
    tp = lax.axis_size(model_axis)
    me = lax.axis_index(model_axis)
    width = kernel.shape[-1]
    pad = (-width) % tp
    if pad:
        kernel = jnp.pad(
            kernel, [(0, 0)] * (kernel.ndim - 1) + [(0, pad)])
    chunk_w = (width + pad) // tp
    perm = [(i, (i + 1) % tp) for i in range(tp)]

    def part(c):
        kc = lax.dynamic_slice_in_dim(kernel, c * chunk_w, chunk_w,
                                      axis=kernel.ndim - 1)
        return jnp.tensordot(x, kc, axes=axes)

    def hop(carry, h):
        carry = lax.ppermute(carry, model_axis, perm)
        return carry + part((me - h - 1) % tp), None

    owned, _ = lax.scan(hop, part((me - 1) % tp), jnp.arange(1, tp))
    y = lax.all_gather(owned, model_axis, axis=owned.ndim - 1, tiled=True)
    if pad:
        y = lax.slice_in_dim(y, 0, width, axis=y.ndim - 1)
    return y


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def collective_matmul_row(x, kernel, model_axis, axes: int = 1):
    """Row-parallel matmul with the output all-reduce decomposed into a
    chunked ``ppermute`` ring (``comm_overlap="matmul"``).

    Equals ``sum_partials(tensordot(x, kernel, axes), model_axis)`` up
    to float summation order.  The backward is the *local* tensordot
    transpose — identical math to the blocking pair (``sum_partials``'s
    backward is the identity), with zero model-axis collectives in the
    row layer's own backward.
    """
    return _ring_matmul_fwd_impl(x, kernel, model_axis, axes)


def _collective_matmul_fwd(x, kernel, model_axis, axes):
    return _ring_matmul_fwd_impl(x, kernel, model_axis, axes), (x, kernel)


def _collective_matmul_bwd(model_axis, axes, res, ct):
    x, kernel = res
    _, pullback = jax.vjp(
        lambda a, b: jnp.tensordot(a, b, axes=axes), x, kernel)
    return pullback(ct)


collective_matmul_row.defvjp(_collective_matmul_fwd, _collective_matmul_bwd)


# --------------------------------------------------------------------------- #
# Vocab parallelism: sharded embedding lookup + the streaming fused
# cross-entropy epilogue
# --------------------------------------------------------------------------- #
def vocab_pad(vocab_size: int, tp: int) -> int:
    """Rows of zero-padding that make ``vocab_size`` divide ``tp``."""
    return (-vocab_size) % max(tp, 1)


def vocab_parallel_embedding(tokens, embedding, *, model_axis=None,
                             comm_overlap=None):
    """Token lookup on a vocab-sharded (dim 0) embedding table.

    With ``model_axis`` set, ``embedding`` is the *local* ``[V_pad/tp, H]``
    shard (zero-padded rows at the tail of the last shard when the true
    vocab doesn't divide).  Each shard contributes its rows' vectors
    (zeros for out-of-shard tokens) and one psum over the model group
    assembles the full lookup — the Megatron/GSPMD vocab-parallel input
    embedding (arxiv 1909.08053 §3, 2105.04663).  The psum wears the
    :func:`sum_partials` custom-VJP contract (identity backward), so the
    backward is the purely local masked scatter into this shard's rows —
    no model-axis collective and never a full-vocab buffer.
    ``comm_overlap`` (any mode) decomposes the forward psum into the
    rs+ag pair.  ``model_axis=None`` is the exact unsharded lookup.
    """
    if model_axis is None:
        return embedding[tokens]
    rows = embedding.shape[0]
    start = lax.axis_index(model_axis) * rows
    local = tokens - start
    in_shard = (local >= 0) & (local < rows)
    safe = jnp.clip(local, 0, rows - 1)
    out = embedding[safe] * in_shard[..., None].astype(embedding.dtype)
    overlap = normalize_comm_overlap(comm_overlap)
    return (sum_partials_decomposed(out, model_axis) if overlap
            else sum_partials(out, model_axis))


def _resolve_seq_chunk(length: int, seq_chunk) -> int:
    """Largest divisor of ``length`` that is <= the requested chunk
    (default 128): ``lax.scan`` needs equal chunks, and a divisor keeps
    the streaming epilogue padding-free along the sequence."""
    want = max(min(length, seq_chunk or 128), 1)
    for c in range(want, 0, -1):
        if length % c == 0:
            return c
    return length


def vocab_parallel_cross_entropy(x, embedding, targets, *, vocab_size: int,
                                 model_axis=None, seq_chunk=None,
                                 comm_overlap=None):
    """Streaming fused softmax cross-entropy against a vocab-sharded
    (tied) unembedding — the GSPMD-style epilogue (arxiv 2105.04663).

    ``x``: ``[B, L, H]`` final hidden states (fp32 math recommended);
    ``embedding``: the local ``[V_pad/tp, H]`` shard (full ``[V, H]``
    table when ``model_axis`` is ``None``); ``targets``: ``[B, L]`` int
    ids ``< vocab_size``.  Returns ``(nll, pred)``: per-token negative
    log-likelihood ``[B, L]`` fp32 and the argmax token id ``[B, L]``
    int32 (ties resolve to the smallest id, matching ``argmax``).

    Neither forward nor backward ever materializes the full-vocab
    logits: per sequence chunk the local ``[B, chunk, V/tp]`` logits are
    reduced to three token-shaped statistics — shard max → ``pmax``,
    shard sum-exp → psum, target-logit extraction by in-shard mask →
    psum — and the backward *recomputes* the chunk logits from the saved
    ``(x, shard, logsumexp)`` residuals, so the live buffer is bounded
    by ``chunk × V/tp`` in both directions.  Zero-padded vocab rows are
    masked to ``-inf`` so they never enter max/sum-exp/argmax.  The
    backward's hidden-state cotangent (each shard holds only its slice's
    contribution) psums over the model group; ``comm_overlap`` (any
    mode) lowers that psum — and the forward's two scalar-sized sum
    psums — as the rs+ag pair with the re-fusion barrier
    (:func:`psum_decomposed`).  ``model_axis=None`` runs the same
    streaming math on the full table with zero collectives (the
    sequential-reference path the parity goldens compare against).
    """
    overlap = normalize_comm_overlap(comm_overlap)
    B, L = targets.shape[0], targets.shape[1]
    chunk = _resolve_seq_chunk(L, seq_chunk)
    n_chunks = L // chunk
    rows = embedding.shape[0]
    neg_inf = jnp.finfo(jnp.float32).min
    # The epilogue's statistics boundaries (sum-exp / target-logit /
    # backward hidden-cotangent psums, the stabilizing pmax) narrow to
    # the active vocab_stats precision; fp32 outside any scope.
    stats_prec = active_precision("vocab_stats")

    def _psum(v):
        if model_axis is None:
            return v
        return (psum_decomposed(v, model_axis, stats_prec) if overlap
                else qz.quantized_psum(v, model_axis, stats_prec))

    def shard_start():
        if model_axis is None:
            return 0
        return lax.axis_index(model_axis) * rows

    def chunk_logits(xc, emb):
        """Local ``[B, chunk, V/tp]`` logits, padded rows at -inf."""
        logits = jnp.tensordot(xc.astype(jnp.float32),
                               emb.astype(jnp.float32).T, axes=1)
        valid = (shard_start() + jnp.arange(rows)) < vocab_size
        return jnp.where(valid, logits, neg_inf)

    def to_chunks(a):
        # [B, L, ...] -> [n_chunks, B, chunk, ...] for the scan
        a = a.reshape(B, n_chunks, chunk, *a.shape[2:])
        return jnp.moveaxis(a, 1, 0)

    def from_chunks(a):
        return jnp.moveaxis(a, 0, 1).reshape(B, L, *a.shape[3:])

    def fwd_impl(x, emb):
        start = shard_start()

        def body(_, args):
            xc, tc = args
            logits = chunk_logits(xc, emb)
            m_loc = jnp.max(logits, axis=-1)
            # Under a narrowed policy the argmax election must compare in
            # the *rounded* domain: the winner's bf16-rounded max equals
            # the pmax result exactly, while its fp32 value might sit
            # below a rounded-up group max (every shard would then
            # propose vocab_size — an invalid prediction).
            if model_axis is not None and stats_prec != "fp32":
                m_loc = m_loc.astype(jnp.bfloat16).astype(jnp.float32)
            m = m_loc if model_axis is None \
                else qz.quantized_pmax(m_loc, model_axis, stats_prec)
            s = _psum(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))
            loc = tc - start
            in_shard = (loc >= 0) & (loc < rows)
            safe = jnp.clip(loc, 0, rows - 1)
            tgt_loc = jnp.take_along_axis(logits, safe[..., None],
                                          axis=-1)[..., 0]
            tgt = _psum(jnp.where(in_shard, tgt_loc, 0.0))
            lse = m + jnp.log(s)
            # argmax: the shard holding the global max proposes its id;
            # losers propose vocab_size, pmin keeps the smallest winner.
            am = start + jnp.argmax(logits, axis=-1).astype(jnp.int32)
            cand = jnp.where(m_loc >= m, am, jnp.int32(vocab_size))
            pred = cand if model_axis is None else lax.pmin(cand, model_axis)
            return None, (lse - tgt, pred, lse)

        _, (nll, pred, lse) = lax.scan(
            body, None, (to_chunks(x), to_chunks(targets)))
        return from_chunks(nll), from_chunks(pred), from_chunks(lse)

    @jax.custom_vjp
    def xent(x, emb):
        nll, pred, _ = fwd_impl(x, emb)
        return nll, pred

    def xent_fwd(x, emb):
        nll, pred, lse = fwd_impl(x, emb)
        return (nll, pred), (x, emb, lse)

    def xent_bwd(res, cts):
        x, emb, lse = res
        ct_nll = cts[0].astype(jnp.float32)  # ct for pred is symbolic zero
        start = shard_start()

        def body(dW, args):
            xc, tc, lse_c, ct_c = args
            logits = chunk_logits(xc, emb)
            p = jnp.exp(logits - lse_c[..., None])   # padded rows -> 0
            loc = tc - start
            in_shard = (loc >= 0) & (loc < rows)
            safe = jnp.clip(loc, 0, rows - 1)
            onehot = (jnp.arange(rows) == safe[..., None]) \
                & in_shard[..., None]
            g = (p - onehot.astype(jnp.float32)) * ct_c[..., None]
            dx_c = jnp.tensordot(g, emb.astype(jnp.float32), axes=1)
            dW = dW + jnp.tensordot(
                g.reshape(-1, rows).T,
                xc.astype(jnp.float32).reshape(-1, xc.shape[-1]), axes=1)
            return dW, dx_c

        dW0 = jnp.zeros((rows, emb.shape[1]), jnp.float32)
        dW, dx = lax.scan(
            body, dW0, (to_chunks(x), to_chunks(targets), to_chunks(lse),
                        to_chunks(ct_nll)))
        dx = _psum(from_chunks(dx))
        return dx.astype(x.dtype), dW.astype(emb.dtype)

    xent.defvjp(xent_fwd, xent_bwd)
    return xent(x, embedding)


def vocab_parallel_greedy_token(x, embedding, *, vocab_size: int,
                                model_axis=None):
    """Greedy next-token ids from *last-position* hidden states against a
    (possibly vocab-sharded) tied unembedding — the decode-time epilogue.

    ``x``: ``[B, H]`` final hidden states (one position per sequence —
    a decode step never materializes full-sequence logits);
    ``embedding``: the local ``[V_pad/tp, H]`` shard (full ``[V, H]``
    table when ``model_axis`` is ``None``).  Returns ``(token, logit)``:
    the argmax token id ``[B]`` int32 and its logit ``[B]`` fp32.

    The live logits buffer is bounded at ``[B, V/tp]``: each shard
    proposes its local argmax, a ``pmax`` finds the global max logit and
    a ``pmin`` over candidate ids resolves ties to the smallest id —
    exactly :func:`vocab_parallel_cross_entropy`'s prediction semantics,
    so greedy decode agrees token-for-token with the training-time
    ``pred`` metric.  Zero-padded vocab rows (``V % tp != 0``) are
    masked to ``-inf`` and can never be sampled.  ``model_axis=None``
    runs the same math on the full table (the sequential-reference path
    the decode goldens compare against).
    """
    rows = embedding.shape[0]
    logits = jnp.tensordot(x.astype(jnp.float32),
                           embedding.astype(jnp.float32).T, axes=1)
    start = 0 if model_axis is None else lax.axis_index(model_axis) * rows
    valid = (start + jnp.arange(rows)) < vocab_size
    logits = jnp.where(valid, logits, jnp.finfo(jnp.float32).min)
    return _resolve_global_argmax(logits, start, vocab_size, model_axis)


def _resolve_global_argmax(scores, start, vocab_size: int, model_axis):
    """The shard-invariant argmax election the greedy AND sampling
    epilogues share: each shard proposes its local argmax's global id,
    a ``pmax`` finds the global max score, losers propose
    ``vocab_size`` and a ``pmin`` keeps the smallest winning id (the
    tie-break :func:`vocab_parallel_cross_entropy`'s ``pred`` also
    uses).  ONE copy so the ``temperature=0 == greedy`` and
    ``top_k=1 == greedy`` parity contracts are structural, not
    coincidental.  Returns ``(token [B] int32, max score [B] f32)``."""
    m_loc = jnp.max(scores, axis=-1)
    m = m_loc if model_axis is None else lax.pmax(m_loc, model_axis)
    am = (start + jnp.argmax(scores, axis=-1)).astype(jnp.int32)
    cand = jnp.where(m_loc >= m, am, jnp.int32(vocab_size))
    tok = cand if model_axis is None else lax.pmin(cand, model_axis)
    return tok, m


def _rowwise_gumbel(seed, position, row_ids):
    """Gumbel noise per *global* vocab row for one slot, deterministic
    in ``(seed, position, row_id)`` alone — each shard folds its own
    global row ids, so the draw is **shard-invariant**: the same
    virtual ``[V]`` gumbel vector materializes only as each shard's
    ``[rows_local]`` slice (never a full-vocab buffer), and tp=1,
    tp=2, and the sequential reference all see identical noise."""
    base = jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(0), seed), position)
    keys = jax.vmap(lambda r: jax.random.fold_in(base, r))(row_ids)
    u = jax.vmap(lambda k: jax.random.uniform(
        k, (), jnp.float32, minval=1e-7, maxval=1.0))(keys)
    return -jnp.log(-jnp.log(u))


def vocab_parallel_sample_token(x, embedding, *, vocab_size: int,
                                seeds, positions, temperature: float,
                                top_k: int = 0, model_axis=None):
    """Temperature/top-k sampling from *last-position* hidden states —
    the sampling rung of :func:`vocab_parallel_greedy_token`, same
    ``[B, V/tp]``-bounded live logits.

    Sampling is the **Gumbel-max trick**: ``argmax(logits/T + g)``
    where ``g`` is per-(slot, position, global-row) gumbel noise from
    :func:`_rowwise_gumbel`.  Because the noise is keyed by the global
    row id (not the shard), the perturbed scores agree across any tp
    sharding and the argmax resolves through the exact pmax/pmin
    machinery of the greedy path — so a sampled stream keeps the
    interleave-parity contract: interleaved == run-alone == the
    sequential reference at the same per-slot ``(seed, position)``
    keys.

    ``seeds``/``positions``: ``[B]`` int32 (the request's sampling seed
    and the emitted token's context length — the fold keys).
    ``top_k > 0`` restricts sampling to the global top-k rows: each
    shard proposes its local top-k, an ``all_gather`` of the ``k·tp``
    candidate *values* (scalars, never rows) finds the global
    threshold.  ``temperature`` must be > 0 — the engine routes
    ``temperature == 0`` to the greedy path so it stays bit-identical.
    """
    if temperature <= 0.0:
        raise ValueError("temperature must be > 0 (temperature == 0 is "
                         "the greedy path)")
    rows = embedding.shape[0]
    logits = jnp.tensordot(x.astype(jnp.float32),
                           embedding.astype(jnp.float32).T, axes=1)
    start = 0 if model_axis is None else lax.axis_index(model_axis) * rows
    valid = (start + jnp.arange(rows)) < vocab_size
    neg = jnp.finfo(jnp.float32).min
    logits = jnp.where(valid, logits, neg)
    if top_k and top_k > 0:
        k = min(int(top_k), vocab_size)
        loc = lax.top_k(logits, min(k, rows))[0]         # [B, k_loc]
        if model_axis is not None:
            loc = lax.all_gather(loc, model_axis, axis=1,
                                 tiled=True)             # [B, k_loc*tp]
        kth = lax.top_k(loc, k)[0][:, -1]                # [B]
        logits = jnp.where(logits >= kth[:, None], logits, neg)
    row_ids = start + jnp.arange(rows, dtype=jnp.int32)
    g = jax.vmap(_rowwise_gumbel, in_axes=(0, 0, None))(
        seeds.astype(jnp.int32), positions.astype(jnp.int32), row_ids)
    z = jnp.where(logits > neg, logits / temperature + g, neg)
    return _resolve_global_argmax(z, start, vocab_size, model_axis)


def column_parallel(x, kernel, bias=None, *, model_axis=None, axes: int = 1,
                    comm_overlap=None):
    """``x @ kernel (+ bias)`` with the kernel's *output* dims sharded.

    ``axes`` contraction dims are taken from the end of ``x`` and the
    front of ``kernel`` (``jax.lax.dot_general`` semantics via
    tensordot).  With ``model_axis`` set, ``kernel``/``bias`` are the
    local output-shard; the result is the sharded activation slice.
    ``comm_overlap`` (any non-None mode) decomposes the *backward*
    cotangent all-reduce into the rs+ag pair.
    """
    overlap = normalize_comm_overlap(comm_overlap)
    if model_axis is not None:
        x = (gather_grads_decomposed(x, model_axis) if overlap
             else gather_grads(x, model_axis))
    y = jnp.tensordot(x, kernel, axes=axes)
    if bias is not None:
        y = y + bias
    return y


def row_parallel(x, kernel, bias=None, *, model_axis=None, axes: int = 1,
                 comm_overlap=None):
    """``x @ kernel (+ bias)`` with the kernel's *input* dims sharded.

    With ``model_axis`` set, ``x``/``kernel`` are local input-shards; the
    partial products are summed over the model group (one activation
    all-reduce — THE Megatron block boundary) and the replicated ``bias``
    is added after the sum, matching the unsharded math exactly.

    ``comm_overlap`` selects how that sum lowers: ``None`` — the
    blocking monolithic ``psum``; ``"rsag"`` — reduce-scatter +
    all-gather; ``"matmul"``/``True`` — the chunked collective-matmul
    ring (:func:`collective_matmul_row`), whose per-hop transfers hide
    behind per-chunk compute.
    """
    overlap = normalize_comm_overlap(comm_overlap)
    if model_axis is not None and overlap == "matmul":
        if active_kernel("collective_matmul") and kernel.ndim == axes + 1:
            from autodist_tpu.kernel.pallas.collective_matmul import \
                collective_matmul_row_fused
            y = collective_matmul_row_fused(x, kernel, model_axis, axes)
        else:
            y = collective_matmul_row(x, kernel, model_axis, axes)
    else:
        y = jnp.tensordot(x, kernel, axes=axes)
        if model_axis is not None:
            y = (sum_partials_decomposed(y, model_axis) if overlap
                 else sum_partials(y, model_axis))
    if bias is not None:
        y = y + bias
    return y
